"""Reference layout <-> the tree of ``models/qwen3_moe/model.py`` (every layer sparse)."""

from __future__ import annotations

_MOE = {"router": ("gate", "weight"), "experts_gate_up": ("experts", "gate_up_proj"),
        "experts_down": ("experts", "down_proj")}


def from_reference(flat: dict) -> dict:
    tree = {k: v for k, v in flat.items() if not k.startswith("layers.")}
    layers: dict = {"moe": {"gate": {}, "experts": {}}}
    for key, value in flat.items():
        if not key.startswith("layers."):
            continue
        name = key.split(".", 1)[1]
        if name in _MOE:
            group, leaf = _MOE[name]
            layers["moe"][group][leaf] = value
        else:
            layers[name] = value
    tree["moe_layers"] = layers
    return tree


def to_reference(tree: dict) -> dict:
    flat = {k: v for k, v in tree.items() if k != "moe_layers"}
    layers = tree["moe_layers"]
    flat.update({"layers." + k: v for k, v in layers.items() if k != "moe"})
    for name, (group, leaf) in _MOE.items():
        flat["layers." + name] = layers["moe"][group][leaf]
    return flat
