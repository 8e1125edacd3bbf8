"""Adafactor without momentum (Shazeer & Stern 2018, section 4), no update clipping,
no relative step: ``u = g / sqrt(v)``, with ``v`` factored into row and column means
over a leaf's two largest axes when the smaller of them has 128 entries or more
(the rule of ``optax.scale_by_factored_rms``, which the paper leaves to the
implementation), decay ``1 - t ** -decay`` and ``eps = 1e-30`` added to ``g * g``.
No clip of the global norm: every leaf can be updated as soon as its gradient exists.

``first_grad_squares`` reads the first gradient's per-leaf sum of squares back from
the program's state after one step, where the decay is 0: ``v_row`` is then the mean
of ``g * g`` over the leaf's largest axis, so its sum times that axis' length is the
sum of squares.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

needs_history = False
_EPS = 1e-30
_MIN_FACTOR = 128


def hyper(recipe_opt: dict) -> dict:
    if recipe_opt.get("weight_decay") or recipe_opt.get("max_grad_norm"):
        raise ValueError("the reference Adafactor has neither weight decay nor a clip")
    return dict(lr=float(recipe_opt["lr"]), decay=float(recipe_opt.get("betas", (0.9, 0.95))[1]),
                clip=None)


def factored_axes(shape) -> tuple[int, int] | None:
    """(second largest axis, largest axis), or None where the leaf is not factored."""
    if len(shape) < 2:
        return None
    order = np.argsort(shape)
    if shape[order[-2]] < _MIN_FACTOR:
        return None
    return int(order[-2]), int(order[-1])


def reference_update(w, g, state: dict | None, step: int, hp: dict):
    """One leaf, float32. Returns ``(new_w, new_state)``."""
    decay = 1.0 - float(step) ** -hp["decay"]
    g2 = g * g + _EPS
    axes = factored_axes(g.shape)
    if axes is None:
        v = (1 - decay) * g2 + (decay * state["v"] if state else 0.0)
        return w - hp["lr"] * g * jax.lax.rsqrt(v), {"v": v}
    d1, d0 = axes
    row = (1 - decay) * g2.mean(axis=d0) + (decay * state["row"] if state else 0.0)
    col = (1 - decay) * g2.mean(axis=d1) + (decay * state["col"] if state else 0.0)
    reduced_d1 = d1 - 1 if d1 > d0 else d1
    row_factor = (row / row.mean(axis=reduced_d1, keepdims=True)) ** -0.5
    col_factor = col ** -0.5
    u = g * jnp.expand_dims(row_factor, d0) * jnp.expand_dims(col_factor, d1)
    return w - hp["lr"] * u, {"row": row, "col": col}


def first_grad_squares(opt_state, params, hp: dict):
    """A tree like ``params`` whose leaves sum, layer by layer, to the sums of squares
    of the first gradient."""
    from benchmarks.harness.optstate import find_field

    v_row, v = find_field(opt_state, "v_row"), find_field(opt_state, "v")

    def leaf(p, row, full):
        axes = factored_axes(p.shape)
        if axes is None:
            return full.astype(jnp.float32)
        return row.astype(jnp.float32) * p.shape[axes[1]]

    return jax.tree.map(leaf, params, v_row, v)
