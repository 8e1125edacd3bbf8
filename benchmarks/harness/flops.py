"""FLOPs the forward and backward passes REQUIRE per token, and parameter counts.

6 x every matrix parameter a token meets (2 forward, 4 backward): attention
projections, the dense MLP or the router plus the top-k experts (not all experts), the
output head; nothing for the embedding lookup; attention scores causal (a token at
position t meets t + 1 keys) and nothing for recomputation.
"""

from __future__ import annotations

from benchmarks.reference.decoder import dims


def matrix_params_per_token(m: dict) -> dict[str, float]:
    """Matrix parameters one token is multiplied by, by part."""
    d = dims(m)
    attn = d["L"] * (2 * d["D"] * d["n"] * d["h"] + 2 * d["D"] * d["k"] * d["h"])
    if d["moe"]:
        mlp = d["L"] * d["K"] * 3 * d["D"] * d["I"]
        router = d["L"] * d["E"] * d["D"]
    else:
        mlp, router = d["L"] * 3 * d["D"] * d["F"], 0
    return {"attention_projections": attn, "mlp": mlp, "router": router,
            "head": d["D"] * d["V"]}


def score_flops_per_token(m: dict, seq_len: int) -> float:
    """QK^T and PV, forward and backward, causal: 3 x 4 x n x h x (S + 1) / 2 a layer."""
    d = dims(m)
    return d["L"] * 12.0 * d["n"] * d["h"] * (seq_len + 1) / 2


def flops_per_token(m: dict, seq_len: int) -> float:
    return 6.0 * sum(matrix_params_per_token(m).values()) + score_flops_per_token(m, seq_len)


def parameter_count(m: dict) -> int:
    """Every parameter held (all experts, embedding, norms)."""
    d = dims(m)
    layer = 2 * d["D"] * d["n"] * d["h"] + 2 * d["D"] * d["k"] * d["h"] + 2 * d["D"]
    if d["qk_norm"]:
        layer += 2 * d["h"]
    if d["moe"]:
        layer += d["E"] * d["D"] + d["E"] * 3 * d["D"] * d["I"]
    else:
        layer += 3 * d["D"] * d["F"]
    return d["L"] * layer + 2 * d["V"] * d["D"] + d["D"]
