"""The reference's first optimizer steps: float32 weights from the seed, the
configuration's plain model (``benchmarks/reference/<name>.py``, handed in), the plain
optimizer rule of ``benchmarks/optimizers/<name>.py``.

Held on the device: the float32 weights, one block's gradient, what one block's
backward pass needs and, for an optimizer with history, the earlier steps' gradients.
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import weights


def _collect(per_block: dict, groups: dict[str, list[int]]) -> dict[str, np.ndarray]:
    """{block: {leaf: scalar}} -> {"embed": x, "<group>.wq": (layers of the group,), ...}
    as float64: a layer's leaf lands at the layer's place within its group."""
    place = {f"layer_{i}": (group, at) for group, indices in groups.items()
             for at, i in enumerate(indices)}
    out: dict[str, np.ndarray] = {}
    for block, leaves in per_block.items():
        for leaf, value in leaves.items():
            if block in place:
                group, at = place[block]
                out.setdefault(f"{group}.{leaf}", np.zeros(len(groups[group])))[at] = float(value)
            else:
                out[leaf] = np.float64(value)
    return out


def follow(model, m: dict, seed: int, batches: list, optimizer: str, recipe_opt: dict,
           params_dtype: str = "bfloat16") -> dict:
    """Follow ``len(batches)`` optimizer steps of ``model`` (the cell's reference module).
    Returns ``losses`` (one a step), ``grad_sq`` (per leaf and layer, the first gradient
    as the optimizer gets it: after the clip) and ``change_sq`` (parameters now minus
    parameters at the start)."""
    opt = importlib.import_module("benchmarks.optimizers." + optimizer)
    hp = opt.hyper(recipe_opt)
    groups = model.layer_groups(m)
    start = weights.make_blocks(model, m, seed, params_dtype)
    blocks = jax.tree.map(lambda x: x.astype(jnp.float32), start)
    del start
    sq_sum = jax.jit(lambda g: jax.tree.map(lambda x: jnp.sum(x * x), g))
    scale_tree = jax.jit(lambda g, s: jax.tree.map(lambda x: x * s, g), donate_argnums=0)
    if opt.needs_history:
        update = jax.jit(lambda w, gs: opt.reference_update(w, gs, hp), donate_argnums=0)
    else:
        update = jax.jit(lambda w, g, st, step: opt.reference_update(w, g, st, step, hp),
                         static_argnames="step", donate_argnums=(0, 2))
    history: dict = {}  # (block, leaf) -> [gradients of earlier steps], on the device
    state: dict = {}    # (block, leaf) -> optimizer state without history
    losses, grad_sq = [], None

    def apply(block: str, grads: dict, step: int, last: bool) -> None:
        for leaf, g in grads.items():
            key = (block, leaf)
            if opt.needs_history:
                blocks[block][leaf] = update(blocks[block][leaf], [*history.get(key, []), g])
                if not last:
                    history.setdefault(key, []).append(g)
            else:
                blocks[block][leaf], state[key] = update(
                    blocks[block][leaf], g, state.get(key), step=step)

    def sweep(ids, labels, on_grad):
        return model.loss_and_grads(blocks, jnp.asarray(ids), jnp.asarray(labels), m=m,
                                      on_grad=on_grad)

    for step, (ids, labels) in enumerate(batches, 1):
        last = step == len(batches)
        squares: dict = {}

        def measure(block, grads):
            squares[block] = sq_sum(grads)

        factor = 1.0
        if hp["clip"] is not None:
            # the clip needs the whole gradient's norm before any leaf can be updated:
            # one sweep for the norms alone, a second to update block by block, so that
            # two whole gradients are never held beside the weights and the history
            sweep(ids, labels, measure)
            norm = float(np.sqrt(sum(np.sum(v) for v in
                                     _collect(jax.device_get(squares), groups).values())))
            factor = 1.0 if norm < hp["clip"] else hp["clip"] / norm

        def on_grad(block, grads, step=step, last=last, factor=factor):
            measure(block, grads)
            apply(block, scale_tree(grads, factor) if factor != 1.0 else grads, step, last)

        losses.append(float(sweep(ids, labels, on_grad)))
        if step == 1:
            grad_sq = {k: v * factor**2
                       for k, v in _collect(jax.device_get(squares), groups).items()}

    start = weights.make_blocks(model, m, seed, params_dtype)
    diff_sq = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: jnp.sum(jnp.square(x - y.astype(jnp.float32))), a, b))
    change = {block: diff_sq(blocks[block], start[block]) for block in blocks}
    return {"losses": losses, "grad_sq": grad_sq,
            "change_sq": _collect(jax.device_get(change), groups)}
