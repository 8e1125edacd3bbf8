"""Reference layout <-> the tree of ``models/nemotron_v3/model.py``: three stacks, one a
layer kind (``mamba_layers``, ``attn_layers``, ``moe_layers``; the names are the reference's
``layer_groups``), the MoE's leaves under ``moe.{gate,latent,experts,shared_experts}``.

Two leaves change type on the way: the program keeps ``a_log`` and the router's
score-correction buffer in float32 whatever the parameters' type (an exponent and a
selection bias; their values, made in the cell's type, are exact in float32), and the
harness refuses a tree whose types are not the program's. ``to_reference`` hands back what
it is given (sums of squares, already float32)."""

from __future__ import annotations

import jax.numpy as jnp

_RENAMED = {"mamba_layers": {"conv_b": "b_conv"}}
_MOE = {"router": ("gate", "weight"), "router_bias": ("gate", "score_correction_bias"),
        "latent_down": ("latent", "w_down"), "latent_up": ("latent", "w_up"),
        "experts_up": ("experts", "gate_up_proj"), "experts_down": ("experts", "down_proj"),
        "shared_up": ("shared_experts", "w_up"), "shared_down": ("shared_experts", "w_down")}
_FLOAT32 = {("mamba_layers", "a_log"), ("moe_layers", "router_bias")}
_STACKS = ("mamba_layers", "attn_layers", "moe_layers")


def from_reference(flat: dict) -> dict:
    tree: dict = {}
    for key, value in flat.items():
        stack, dot, leaf = key.partition(".")
        if not dot or stack not in _STACKS:
            tree[key] = value
            continue
        if (stack, leaf) in _FLOAT32:
            value = value.astype(jnp.float32)
        layers = tree.setdefault(stack, {})
        if stack == "moe_layers" and leaf in _MOE:
            group, name = _MOE[leaf]
            layers.setdefault("moe", {}).setdefault(group, {})[name] = value
        else:
            layers[_RENAMED.get(stack, {}).get(leaf, leaf)] = value
    return tree


def to_reference(tree: dict) -> dict:
    flat = {k: v for k, v in tree.items() if k not in _STACKS}
    for stack in _STACKS:
        if stack not in tree:
            continue
        back = {v: k for k, v in _RENAMED.get(stack, {}).items()}
        for leaf, value in tree[stack].items():
            if leaf != "moe":
                flat[f"{stack}.{back.get(leaf, leaf)}"] = value
        if stack == "moe_layers":
            for leaf, (group, name) in _MOE.items():
                flat[f"{stack}.{leaf}"] = tree[stack]["moe"][group][name]
    return flat
