"""The jitted training step (reference _run_train_optim_step, recipes/llm/train_ft.py:1284).

One compiled function does what the reference's python loop + FSDP hooks do:

- gradient accumulation is a ``lax.scan`` over stacked microbatches — no "defer grad
  sync until last microbatch" ceremony (distributed/utils.py:216): grads live sharded
  and XLA inserts exactly one reduce-scatter/all-reduce where the sharding demands it;
- loss normalization by *global* label-token count happens inside, so summed microbatch
  grads equal the true global-mean gradient (training/utils.py:276 contract);
- params/optimizer state are donated — updates happen in place in HBM.

The returned step fn is pure: (params, opt_state, batch_stack, step) -> (params,
opt_state, metrics). Shard once with jit's in_shardings/out_shardings and every
collective is derived, not written.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax

from automodel_tpu.ops.losses import IGNORE_INDEX

__all__ = ["make_train_step", "make_eval_step", "count_label_tokens", "jit_train_step"]


def count_label_tokens(labels: jnp.ndarray, ignore_index: int = IGNORE_INDEX) -> jnp.ndarray:
    return (labels != ignore_index).sum()


def _guard_nonfinite_update(new_updates, new_opt_state, opt_state, grad_norm, loss):
    """reference check_for_nan_in_grad: skip the whole update when the training
    signal is non-finite so params/opt_state never corrupt; the host reads
    metrics["nonfinite"] and raises (recipe contract). Returns
    (updates, opt_state, nonfinite_flag)."""
    ok = jnp.isfinite(grad_norm) & jnp.isfinite(loss)
    new_updates = jax.tree.map(lambda u: jnp.where(ok, u, jnp.zeros_like(u)), new_updates)
    new_opt_state = jax.tree.map(
        lambda new, old: jnp.where(ok, new, old) if hasattr(new, "dtype") else new,
        new_opt_state, opt_state,
    )
    return new_updates, new_opt_state, ~ok


def _dynamics_metrics(metrics, grads, params, new_updates, new_opt_state,
                      loss, guard_nonfinite):
    """Shared dense/pp dynamics assembly so both step builders emit an identical
    metric contract (key-set parity is unit-tested). Reductions only — every
    value is a replicated scalar, no tensor leaves the device sharded."""
    from automodel_tpu.observability.dynamics import (
        dynamics_tree, nonfinite_provenance)

    metrics["dynamics"] = dynamics_tree(grads, params, new_updates, new_opt_state)
    if guard_nonfinite:
        metrics["nonfinite_map"] = nonfinite_provenance(grads, loss)
    return metrics


def make_train_step(
    forward_loss: Callable[..., jnp.ndarray],
    optimizer: optax.GradientTransformation,
    post_update: Callable[[dict, dict], dict] | None = None,
    with_frozen: bool = False,
    guard_nonfinite: bool = False,
    pass_rng: bool = False,
    dynamics: bool = False,
):
    """Build the accumulating train step.

    ``forward_loss(params, batch, num_label_tokens)`` must return either the scalar
    *sum* CE over the microbatch divided by ``num_label_tokens`` (the global count) —
    i.e. microbatch losses are additive — or ``(loss, aux_dict)`` where aux arrays
    (e.g. MoE expert_load) accumulate by summation across microbatches.

    ``post_update(params, aux_acc)`` runs after the optimizer step — the hook for
    non-gradient param updates like the MoE gate-bias loss-free balancing (reference
    update_moe_gate_bias, train_ft.py:1341).

    ``with_frozen=True`` is the PEFT shape: ``params`` is the small trainable tree
    (LoRA factors), and a second ``frozen`` pytree (the base model) is passed through
    untouched and undifferentiated — `forward_loss(trainable, frozen, batch, n)`.
    Freezing-by-argument replaces the reference's requires_grad ceremony
    (_peft/lora.py:335) and keeps optimizer state rank-r sized.

    ``pass_rng=True``: the step takes a trailing ``rng`` key, split per microbatch
    and appended to ``forward_loss``'s arguments (LoRA dropout etc.).
    """

    def _call(params, microbatch, num_label_tokens, frozen, rng=None):
        args = (params, frozen, microbatch, num_label_tokens) if with_frozen else (
            params, microbatch, num_label_tokens)
        if pass_rng:
            args = (*args, rng)
        out = forward_loss(*args)
        return out if isinstance(out, tuple) else (out, {})

    def train_step(params, opt_state, batch_stack, frozen=None, rng=None):
        """batch_stack: pytree whose leaves are stacked (n_micro, ...) arrays."""
        # global label-token count: computed inside jit on the sharded labels, so the
        # sum is automatically global across data axes (reference allreduces by hand,
        # train_ft.py:1284)
        num_label_tokens = count_label_tokens(batch_stack["labels"])
        n_micro = jax.tree.leaves(batch_stack)[0].shape[0]
        keys = jax.random.split(rng, n_micro) if pass_rng else jnp.zeros((n_micro, 1))

        def micro_step(carry, scanned):
            microbatch, key = scanned
            grads_acc, loss_acc, aux_acc = carry
            (loss, aux), grads = jax.value_and_grad(_call, has_aux=True)(
                params, microbatch, num_label_tokens, frozen,
                key if pass_rng else None,
            )
            grads_acc = jax.tree.map(jnp.add, grads_acc, grads)
            aux_acc = jax.tree.map(jnp.add, aux_acc, aux)
            return (grads_acc, loss_acc + loss, aux_acc), None

        zero_grads = jax.tree.map(jnp.zeros_like, params)
        micro0 = jax.tree.map(lambda x: x[0], batch_stack)
        aux_shapes = jax.eval_shape(
            _call, params, micro0, num_label_tokens, frozen,
            keys[0] if pass_rng else None,
        )[1]
        zero_aux = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), aux_shapes)
        (grads, loss, aux), _ = jax.lax.scan(
            micro_step, (zero_grads, jnp.float32(0.0), zero_aux), (batch_stack, keys)
        )
        with jax.named_scope("optimizer"):
            grad_norm = optax.global_norm(grads)
            new_updates, new_opt_state = optimizer.update(grads, opt_state, params)
            if guard_nonfinite:
                new_updates, new_opt_state, nonfinite = _guard_nonfinite_update(
                    new_updates, new_opt_state, opt_state, grad_norm, loss
                )
        dyn = None
        if dynamics:
            # pre-update params: upd_ratio compares this step's update against
            # the weights it is about to move
            dyn = dict(grads=grads, params=params, updates=new_updates,
                       opt_state=new_opt_state)
        with jax.named_scope("optimizer"):
            params = optax.apply_updates(params, new_updates)
        opt_state = new_opt_state
        if post_update is not None:
            params = post_update(params, aux)
        metrics = {
            "loss": loss,
            "grad_norm": grad_norm,
            "num_label_tokens": num_label_tokens,
            **aux,
        }
        if guard_nonfinite:
            metrics["nonfinite"] = nonfinite
        if dynamics:
            metrics = _dynamics_metrics(
                metrics, dyn["grads"], dyn["params"], dyn["updates"],
                dyn["opt_state"], loss, guard_nonfinite)
        return params, opt_state, metrics

    return train_step


def make_pp_train_step(
    forward_loss: Callable[..., jnp.ndarray],
    optimizer: optax.GradientTransformation,
    post_update: Callable[[dict, dict], dict] | None = None,
    guard_nonfinite: bool = False,
    with_frozen: bool = False,
    pass_rng: bool = False,
    dynamics: bool = False,
):
    """Train step for pipeline parallelism: ``forward_loss`` consumes the WHOLE
    (n_micro, ...) batch stack at once — microbatching happens inside the pipeline
    schedule (parallel/pipeline.py), not an outer grad-accum scan (the reference's
    PP path does the same: the schedule owns the microbatch loop,
    recipes/llm/train_ft.py:1234). ``forward_loss`` may return ``(loss, aux)``
    (MoE expert-load stats); ``post_update`` then runs after the optimizer step.
    ``with_frozen``: PEFT shape — ``forward_loss(trainable, frozen, batch, n)``
    with the frozen base undifferentiated.

    ``pass_rng=True``: the step takes a trailing ``rng`` and appends ONE derived
    key to ``forward_loss``'s arguments. Under pp the LoRA merge happens once
    outside the manual region, so dropout samples one mask per optimizer step
    (shared by the schedule's microbatches — still unbiased dropout, the mask
    just refreshes per step instead of per microbatch). The key is derived as
    ``split(rng, n_micro)[0]`` so the n_micro=1 case is bit-exact with
    ``make_train_step``'s per-microbatch keys."""

    def _call(params, batch_stack, num_label_tokens, frozen=None, rng=None):
        args = (params, frozen, batch_stack, num_label_tokens) if with_frozen else (
            params, batch_stack, num_label_tokens)
        if pass_rng:
            args = (*args, rng)
        out = forward_loss(*args)
        return out if isinstance(out, tuple) else (out, {})

    def train_step(params, opt_state, batch_stack, frozen=None, rng=None):
        num_label_tokens = count_label_tokens(batch_stack["labels"])
        if pass_rng:
            n_micro = jax.tree.leaves(batch_stack)[0].shape[0]
            rng = jax.random.split(rng, n_micro)[0]
        (loss, aux), grads = jax.value_and_grad(_call, has_aux=True)(
            params, batch_stack, num_label_tokens, frozen, rng
        )
        with jax.named_scope("optimizer"):
            grad_norm = optax.global_norm(grads)
            new_updates, new_opt_state = optimizer.update(grads, opt_state, params)
            if guard_nonfinite:
                new_updates, new_opt_state, nonfinite = _guard_nonfinite_update(
                    new_updates, new_opt_state, opt_state, grad_norm, loss
                )
        dyn = None
        if dynamics:
            dyn = dict(grads=grads, params=params, updates=new_updates,
                       opt_state=new_opt_state)
        with jax.named_scope("optimizer"):
            params = optax.apply_updates(params, new_updates)
        opt_state = new_opt_state
        if post_update is not None:
            params = post_update(params, aux)
        metrics = {
            "loss": loss,
            "grad_norm": grad_norm,
            "num_label_tokens": num_label_tokens,
            **aux,
        }
        if guard_nonfinite:
            metrics["nonfinite"] = nonfinite
        if dynamics:
            metrics = _dynamics_metrics(
                metrics, dyn["grads"], dyn["params"], dyn["updates"],
                dyn["opt_state"], loss, guard_nonfinite)
        return params, opt_state, metrics

    return train_step


def jit_train_step(step: Callable, params: Any, opt_state: Any):
    """``jax.jit`` a ``(params, opt_state, ...) -> (params, opt_state, metrics)``
    step with both states donated and handed back in the shardings they came
    in with. The loop feeds a step its own outputs, and an AOT-compiled step
    accepts only the input shardings it was lowered for: left to the compiler,
    an output can come back laid out differently (adapter factors under
    dp x tp, pipeline stages) and step 2 is refused."""
    def same(tree):
        return jax.tree.map(lambda x: x.sharding, tree)

    return jax.jit(step, donate_argnums=(0, 1),
                   out_shardings=(same(params), same(opt_state), None))


def make_eval_step(forward_loss: Callable[..., jnp.ndarray], with_frozen: bool = False):
    def eval_step(params, batch, num_label_tokens, frozen=None):
        if with_frozen:
            out = forward_loss(params, frozen, batch, num_label_tokens)
        else:
            out = forward_loss(params, batch, num_label_tokens)
        return out[0] if isinstance(out, tuple) else out

    return eval_step
