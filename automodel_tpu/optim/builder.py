"""Optimizer construction (reference recipes/llm/train_ft.py:275 build_optimizer).

Params stay fp32 (the master copy); the model casts to bf16 at use. optax keeps
moments in fp32 alongside — the same mixed-precision contract as the reference's
FSDP2 mp_policy (bf16 compute / fp32 params+grads, distributed/config.py:74-81) with
none of the wrapping ceremony.

Weight decay is masked off 1-D params (norm scales, biases) matching standard HF
finetune behavior.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import optax

__all__ = ["build_optimizer", "first_moment_tree", "no_decay_mask"]


def first_moment_tree(opt_state: Any) -> Any:
    """First first-moment accumulator in an optax state tree, or None.

    The dynamics pillar (observability/dynamics.py) reports a per-subtree
    ``moment_norm``, which needs the optimizer's own view of the gradient
    trend: walk the chain's state tuples breadth-first for a pytree-valued
    field named ``mu`` (the adam families, including
    :func:`low_mem_scale_by_adam`'s bf16 state) or ``trace`` (momentum SGD,
    :func:`int8_trace`). Optimizers without a moment (adafactor, plain sgd)
    return None and the telemetry row simply omits the metric. Works inside
    jit — it only rearranges tree references, no value ops.
    """
    stack = [opt_state]
    while stack:
        node = stack.pop(0)
        for field in ("mu", "trace"):
            sub = getattr(node, field, None)
            if sub is not None and not hasattr(sub, "dtype"):
                return sub
        if isinstance(node, (tuple, list)):
            stack.extend(node)
    return None


def no_decay_mask(params: Any) -> Any:
    """True where weight decay applies (rank >= 2 tensors only).

    Layer-stacked params have a leading L dim, so the cutoff is rank >= 3 for
    stacked leaves; top-level embed/lm_head are rank 2; norms/biases stacked are
    rank 2 or 1 — decide by trailing dims instead: decay iff the *per-layer* rank
    (total rank minus the stack dim for leaves under "layers") is >= 2.
    """

    def mask_tree(tree, under_layers=False):
        out = {}
        for k, v in tree.items():
            if isinstance(v, dict):
                out[k] = mask_tree(v, under_layers or k == "layers")
            else:
                # getattr: robust under optax multi_transform MaskedNode leaves
                rank = getattr(v, "ndim", 0) - (1 if under_layers else 0)
                out[k] = rank >= 2
        return out

    return mask_tree(params)


def low_mem_scale_by_adam(
    b1: float, b2: float, eps: float,
    mu_dtype=jax.numpy.bfloat16, nu_dtype=jax.numpy.bfloat16,
) -> optax.GradientTransformation:
    """Adam moment tracking with reduced-precision state (bf16 mu AND nu).

    optax.scale_by_adam only casts mu; the fp32 nu is the single largest
    optimizer tensor (4 bytes/param). Storing both moments bf16 halves+ the
    optimizer footprint; the update math runs in fp32 (moments are decayed
    running averages — bf16's ~3 significant digits cost far less than the
    gradient noise they smooth). The freed HBM buys lighter remat policies,
    which is where the throughput actually comes from."""
    import jax.numpy as jnp

    def init(params):
        return optax.ScaleByAdamState(
            count=jnp.zeros([], jnp.int32),
            mu=jax.tree.map(lambda p: jnp.zeros_like(p, dtype=mu_dtype), params),
            nu=jax.tree.map(lambda p: jnp.zeros_like(p, dtype=nu_dtype), params),
        )

    def update(grads, state, params=None):
        del params
        count = state.count + 1
        bc1 = 1 - b1 ** count.astype(jnp.float32)
        bc2 = 1 - b2 ** count.astype(jnp.float32)

        def moments(g, mu, nu):
            g32 = g.astype(jnp.float32)
            mu32 = b1 * mu.astype(jnp.float32) + (1 - b1) * g32
            nu32 = b2 * nu.astype(jnp.float32) + (1 - b2) * g32 * g32
            upd = (mu32 / bc1) / (jnp.sqrt(nu32 / bc2) + eps)
            return {"u": upd.astype(g.dtype), "mu": mu32.astype(mu_dtype), "nu": nu32.astype(nu_dtype)}

        out = jax.tree.map(moments, grads, state.mu, state.nu)
        is_res = lambda x: isinstance(x, dict) and set(x) == {"u", "mu", "nu"}
        pick = lambda k: jax.tree.map(lambda o: o[k], out, is_leaf=is_res)
        return pick("u"), optax.ScaleByAdamState(count=count, mu=pick("mu"), nu=pick("nu"))

    return optax.GradientTransformation(init, update)


def int8_trace(decay: float, block: int = 256) -> optax.GradientTransformation:
    """Momentum with an int8 blockwise-quantized accumulator (the 8-bit-optimizer
    recipe: per-``block`` absmax scales keep quantization error local, reference
    gets the same from bitsandbytes-backed torch optimizers).

    Halves the bf16 ``optax.trace`` footprint to ~1 byte/param; on a 16GB chip
    that is the difference between remat policies — worth far more throughput
    than the momentum LSBs (the accumulator already smooths gradient noise much
    larger than the ~0.4% blockwise rounding)."""
    import jax.numpy as jnp

    def _quant(x):
        flat = x.reshape(-1).astype(jnp.float32)
        pad = (-flat.size) % block
        blocks = jnp.pad(flat, (0, pad)).reshape(-1, block)
        scale = jnp.max(jnp.abs(blocks), axis=1, keepdims=True) / 127.0
        q = jnp.round(blocks / jnp.maximum(scale, 1e-20)).astype(jnp.int8)
        return {"q": q, "scale": scale}

    def _dequant(s, shape):
        flat = (s["q"].astype(jnp.float32) * s["scale"]).reshape(-1)
        size = 1
        for d in shape:
            size *= d
        return flat[:size].reshape(shape)

    def init(params):
        return jax.tree.map(lambda p: _quant(jnp.zeros_like(p, jnp.float32)), params)

    def update(updates, state, params=None):
        del params
        # state slots are {"q","scale"} dicts (a deeper structure than updates),
        # so pair them via flatten_up_to rather than tree.map
        flat_u, treedef = jax.tree.flatten(updates)
        flat_s = treedef.flatten_up_to(state)
        mom = [decay * _dequant(s, u.shape) + u.astype(jnp.float32)
               for u, s in zip(flat_u, flat_s)]
        new_state = treedef.unflatten([_quant(m) for m in mom])
        out = treedef.unflatten([m.astype(u.dtype) for m, u in zip(mom, flat_u)])
        return out, new_state

    return optax.GradientTransformation(init, update)


def build_optimizer(
    lr: float | Callable[[int], float],
    weight_decay: float = 0.0,
    betas: tuple[float, float] = (0.9, 0.95),
    eps: float = 1e-8,
    max_grad_norm: float | None = None,
    optimizer: str = "adamw",
    **optimizer_kwargs,
) -> optax.GradientTransformation:
    """AdamW (or SGD/adafactor/low-mem AdamW) with decay masking and global-norm clip.

    Note: when grads are pre-normalized by global num_label_tokens (the recipe's
    contract), clipping here operates on that normalized gradient, matching the
    reference's scale-then-clip order (training/utils.py:276).
    """
    chain = []
    if max_grad_norm is not None and max_grad_norm > 0:
        chain.append(optax.clip_by_global_norm(max_grad_norm))
    if optimizer == "adamw_lowmem":
        chain.append(low_mem_scale_by_adam(b1=betas[0], b2=betas[1], eps=eps))
        if weight_decay:
            chain.append(optax.add_decayed_weights(weight_decay, mask=no_decay_mask))
        chain.append(optax.scale_by_learning_rate(lr))
    elif optimizer == "adafactor_momentum":
        # factored second moment (rows+cols instead of a full tensor: ~zero HBM)
        # + bf16 momentum — the lightest stateful optimizer here. The ~2.5GB it
        # frees vs even bf16-nu adam buys remat_policy "mlp_dots" on memory-tight
        # configs, which is worth far more throughput than the moment precision.
        # betas -> (momentum decay, second-moment decay); eps is NOT wired: the
        # factored-rms epsilon (1e-30 inside the rms) has different semantics
        # than adam's denominator eps and its default is the right one
        chain.append(optax.scale_by_factored_rms(decay_rate=betas[1]))
        chain.append(optax.trace(decay=betas[0], accumulator_dtype=jax.numpy.bfloat16))
        if weight_decay:
            chain.append(optax.add_decayed_weights(weight_decay, mask=no_decay_mask))
        chain.append(optax.scale_by_learning_rate(lr))
    elif optimizer == "adafactor_nomom":
        # momentum-free factored rms — pure Adafactor a la T5/PaLM. ~Zero
        # optimizer state: on a 16GB chip this affords remat "mlp_attn_dots"
        chain.append(optax.scale_by_factored_rms(decay_rate=betas[1]))
        if weight_decay:
            chain.append(optax.add_decayed_weights(weight_decay, mask=no_decay_mask))
        chain.append(optax.scale_by_learning_rate(lr))
    elif optimizer == "adafactor_momentum8":
        # adafactor_momentum with the momentum itself int8-blockwise quantized:
        # the lightest optimizer state here (~1 byte/param total)
        chain.append(optax.scale_by_factored_rms(decay_rate=betas[1]))
        chain.append(int8_trace(decay=betas[0]))
        if weight_decay:
            chain.append(optax.add_decayed_weights(weight_decay, mask=no_decay_mask))
        chain.append(optax.scale_by_learning_rate(lr))
    elif optimizer == "adamw":
        chain.append(
            optax.adamw(
                learning_rate=lr, b1=betas[0], b2=betas[1], eps=eps,
                weight_decay=weight_decay,
                mask=no_decay_mask if weight_decay else None,
            )
        )
    elif optimizer == "adam":
        chain.append(optax.adam(learning_rate=lr, b1=betas[0], b2=betas[1], eps=eps))
    elif optimizer == "sgd":
        chain.append(optax.sgd(learning_rate=lr, momentum=betas[0]))
    elif optimizer == "adafactor":
        chain.append(optax.adafactor(learning_rate=lr))
    elif optimizer == "dion":
        from automodel_tpu.optim.dion import build_dion_optimizer

        # clipping is handled inside (before the split transform); extra YAML keys
        # (mu, rank_fraction, adamw_lr_scale) pass straight through
        return build_dion_optimizer(
            lr, weight_decay=weight_decay, b1=betas[0], b2=betas[1], eps=eps,
            max_grad_norm=max_grad_norm, **optimizer_kwargs,
        )
    else:
        raise ValueError(f"unknown optimizer {optimizer!r}")
    if optimizer_kwargs:
        raise ValueError(f"unknown optimizer kwargs for {optimizer!r}: {sorted(optimizer_kwargs)}")
    return optax.chain(*chain)
