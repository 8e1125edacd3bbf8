"""Reference layout <-> the tree of ``models/falcon_h1/model.py``: one stack, ``layers``,
leaf for leaf under the program's own names.

One leaf changes type on the way: the program keeps ``a_log`` in float32 whatever the
parameters' type (an exponent; its values, made in the cell's type, are exact in float32),
and the harness refuses a tree whose types are not the program's. ``to_reference`` hands
back what it is given (sums of squares, already float32)."""

from __future__ import annotations

import jax.numpy as jnp


def from_reference(flat: dict) -> dict:
    tree = {k: v for k, v in flat.items() if not k.startswith("layers.")}
    tree["layers"] = {k.split(".", 1)[1]: v for k, v in flat.items() if k.startswith("layers.")}
    tree["layers"]["a_log"] = tree["layers"]["a_log"].astype(jnp.float32)
    return tree


def to_reference(tree: dict) -> dict:
    flat = {k: v for k, v in tree.items() if k != "layers"}
    flat.update({"layers." + k: v for k, v in tree["layers"].items()})
    return flat
