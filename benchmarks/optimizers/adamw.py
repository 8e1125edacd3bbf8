"""AdamW (Loshchilov & Hutter 2019) after a clip of the gradient's global norm.

``reference_update``: the plain float32 rule for one leaf, with the moments written
as their defining sums over the gradients seen so far (the reference follows two or
three steps, and a list of gradients is smaller than two moment tensors).
``first_grad_squares``: the first gradient as the program's optimizer got it, read
back from the program's state after one step: ``mu_1 = (1 - b1) g``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

needs_history = True


def hyper(recipe_opt: dict) -> dict:
    if recipe_opt.get("weight_decay"):
        raise ValueError("the reference AdamW has no weight decay: add it with its test")
    b1, b2 = recipe_opt.get("betas", (0.9, 0.95))
    return dict(lr=float(recipe_opt["lr"]), b1=float(b1), b2=float(b2),
                eps=float(recipe_opt.get("eps", 1e-8)), clip=recipe_opt.get("max_grad_norm"))


def reference_update(w, grads: list, hp: dict):
    """``grads``: this leaf's clipped gradients of steps 1..t, oldest first."""
    t = len(grads)
    b1, b2 = hp["b1"], hp["b2"]
    m = sum((1 - b1) * b1 ** (t - 1 - i) * g for i, g in enumerate(grads))
    v = sum((1 - b2) * b2 ** (t - 1 - i) * g * g for i, g in enumerate(grads))
    m_hat, v_hat = m / (1 - b1 ** t), v / (1 - b2 ** t)
    return w - hp["lr"] * m_hat / (jnp.sqrt(v_hat) + hp["eps"])


def first_grad_squares(opt_state, params, hp: dict):
    """A tree like ``params`` whose leaves sum, layer by layer, to the sums of squares
    of the first gradient."""
    from benchmarks.harness.optstate import find_field

    del params
    mu = find_field(opt_state, "mu")
    return jax.tree.map(lambda x: jnp.square(x.astype(jnp.float32) / (1 - hp["b1"])), mu)
