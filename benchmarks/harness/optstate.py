"""Reading a program's optimizer state and parameters leaf by leaf."""

from __future__ import annotations

import jax.numpy as jnp


def find_field(opt_state, name: str):
    """The first pytree-valued field called ``name`` in an optax state, breadth first."""
    queue = [opt_state]
    while queue:
        node = queue.pop(0)
        sub = getattr(node, name, None)
        if sub is not None and not hasattr(sub, "dtype"):
            return sub
        if isinstance(node, (tuple, list)):
            queue.extend(node)
    raise KeyError(f"no field {name!r} in the optimizer state")


def layer_sums(flat: dict) -> dict:
    """``{leaf: sum}``: ``layers.*`` leaves keep their leading (layer) axis. Traceable."""
    out = {}
    for name, x in flat.items():
        x = x.astype(jnp.float32)
        out[name] = x.sum(axis=tuple(range(1, x.ndim))) if name.startswith("layers.") else x.sum()
    return out
