"""Reference layout <-> the tree of ``models/qwen3_next/model.py``: two stacks, one a layer
kind (``linear_layers``, ``full_layers``; the names are the reference's ``layer_groups``),
every layer's MoE leaves under ``moe.{gate,experts,shared_experts}`` and
``moe.shared_expert_gate``.

The program keeps the published FUSED projections: ``wqkvz (D, Hk, 2 dk + 2 r dv)`` holds,
a key head, ``[q | k | v of its r value heads | z of its r value heads]`` and
``wba (D, Hk, 2 r)`` holds ``[b | a]`` of them (value head j belongs to key head ``j // r``);
the full mixer's ``wq (D, n, 2 h)`` holds ``[q | gate]`` a head. The reference keeps each
as its own leaf, so the maps below only reshape, concatenate and slice, and the two
directions undo each other (``to_reference`` is handed sums of squares, which slice alike).

One leaf changes type on the way: the program keeps ``a_log`` in float32 whatever the
parameters' type (an exponent; its values, made in the cell's type, are exact in float32),
and the harness refuses a tree whose types are not the program's."""

from __future__ import annotations

import jax.numpy as jnp

_RENAMED = {"gated_norm": "norm"}
_MOE = {"router": ("gate", "weight"), "experts_gate_up": ("experts", "gate_up_proj"),
        "experts_down": ("experts", "down_proj"), "shared_gate": ("shared_experts", "w_gate"),
        "shared_up": ("shared_experts", "w_up"), "shared_down": ("shared_experts", "w_down")}
_STACKS = ("linear_layers", "full_layers")
# leaves of the reference that the program keeps inside a fused one, and the fused ones
_PLAIN = {"linear_layers": ("wq", "wk", "wv", "wz", "wb", "wa"), "full_layers": ("wq", "wg")}
_FUSED = {"linear_layers": ("wqkvz", "wba"), "full_layers": ("wq",)}


def _fuse(stack: str, leaves: dict) -> dict:
    if stack == "full_layers":
        return {"wq": jnp.concatenate([leaves["wq"], leaves["wg"]], axis=-1)}
    L, D, Hk, _ = leaves["wq"].shape

    def per_key_head(w):  # (L, D, Hv, ...) -> (L, D, Hk, r ...): value heads j // r alike
        return w.reshape(L, D, Hk, -1)

    return {"wqkvz": jnp.concatenate([leaves["wq"], leaves["wk"], per_key_head(leaves["wv"]),
                                      per_key_head(leaves["wz"])], axis=-1),
            "wba": jnp.concatenate([per_key_head(leaves["wb"]), per_key_head(leaves["wa"])],
                                   axis=-1)}


def _split(stack: str, layers: dict) -> dict:
    if stack == "full_layers":
        h = layers["wq"].shape[-1] // 2
        return {"wq": layers["wq"][..., :h], "wg": layers["wq"][..., h:]}
    L, D, Hk, _ = layers["wqkvz"].shape
    r = layers["wba"].shape[-1] // 2
    dv = layers["norm"].shape[-1]
    dk = (layers["wqkvz"].shape[-1] - 2 * r * dv) // 2
    fused, ba = layers["wqkvz"], layers["wba"]
    return {"wq": fused[..., :dk], "wk": fused[..., dk:2 * dk],
            "wv": fused[..., 2 * dk:2 * dk + r * dv].reshape(L, D, Hk * r, dv),
            "wz": fused[..., 2 * dk + r * dv:].reshape(L, D, Hk * r, dv),
            "wb": ba[..., :r].reshape(L, D, Hk * r), "wa": ba[..., r:].reshape(L, D, Hk * r)}


def from_reference(flat: dict) -> dict:
    tree: dict = {}
    plain: dict = {stack: {} for stack in _STACKS}
    for key, value in flat.items():
        stack, dot, leaf = key.partition(".")
        if not dot or stack not in _STACKS:
            tree[key] = value
            continue
        layers = tree.setdefault(stack, {})
        if leaf in _PLAIN[stack]:
            plain[stack][leaf] = value
        elif leaf in _MOE:
            group, name = _MOE[leaf]
            layers.setdefault("moe", {}).setdefault(group, {})[name] = value
        elif leaf == "shared_expert_gate":
            layers.setdefault("moe", {})[leaf] = value
        else:
            layers[_RENAMED.get(leaf, leaf)] = value.astype(jnp.float32) if leaf == "a_log" else value
    for stack in _STACKS:
        if stack in tree:
            tree[stack].update(_fuse(stack, plain[stack]))
    return tree


def to_reference(tree: dict) -> dict:
    flat = {k: v for k, v in tree.items() if k not in _STACKS}
    back = {v: k for k, v in _RENAMED.items()}
    for stack in _STACKS:
        if stack not in tree:
            continue
        layers = tree[stack]
        for leaf, value in layers.items():
            if leaf != "moe" and leaf not in _FUSED[stack]:
                flat[f"{stack}.{back.get(leaf, leaf)}"] = value
        for leaf, value in _split(stack, layers).items():
            flat[f"{stack}.{leaf}"] = value
        for leaf, (group, name) in _MOE.items():
            flat[f"{stack}.{leaf}"] = layers["moe"][group][name]
        flat[f"{stack}.shared_expert_gate"] = layers["moe"]["shared_expert_gate"]
    return flat
