"""Reference layout <-> the tree of ``models/llama/model.py`` (Llama lineage: Mistral)."""

from __future__ import annotations


def from_reference(flat: dict) -> dict:
    tree = {k: v for k, v in flat.items() if not k.startswith("layers.")}
    tree["layers"] = {k.split(".", 1)[1]: v for k, v in flat.items() if k.startswith("layers.")}
    return tree


def to_reference(tree: dict) -> dict:
    flat = {k: v for k, v in tree.items() if k != "layers"}
    flat.update({"layers." + k: v for k, v in tree["layers"].items()})
    return flat
