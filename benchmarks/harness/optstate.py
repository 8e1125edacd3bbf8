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


def layer_sums(flat: dict, groups) -> dict:
    """``{leaf: sum}``: a ``<group>.<leaf>`` of one of the reference's layer ``groups``
    keeps its leading axis (the layer within the group). Traceable."""
    out = {}
    for name, x in flat.items():
        x = x.astype(jnp.float32)
        group, dot, _ = name.partition(".")
        stacked = bool(dot) and group in groups
        out[name] = x.sum(axis=tuple(range(1, x.ndim))) if stacked else x.sum()
    return out
