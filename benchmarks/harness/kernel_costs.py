"""Operations and bytes a kernel call needs, from its shapes."""

from __future__ import annotations


def flash_attention_step(batch: int, seq_len: int, heads: int, kv_heads: int, head_dim: int,
                         layers: int, bytes_per_element: int = 2) -> dict[str, float]:
    """Causal flash attention, forward and backward, of one optimizer step over
    ``layers`` layers. FLOPs: the two forward products (QK^T, PV) and the four the
    gradients need (dV, dP, dQ, dK) over the causal half; the backward kernel's own
    recomputation of the scores is not counted. Bytes: q, k, v, o, do, dq, dk, dv once
    each."""
    pairs = batch * heads * seq_len * (seq_len + 1) / 2
    flops = layers * 6 * 2.0 * pairs * head_dim
    q_like = batch * seq_len * heads * head_dim * bytes_per_element
    kv_like = batch * seq_len * kv_heads * head_dim * bytes_per_element
    return {"flops": flops, "bytes": float(layers * (4 * q_like + 4 * kv_like))}


def roofline_seconds(cost: dict[str, float], peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    t_flops = cost["flops"] / peak["bf16_flops"]
    t_bytes = cost["bytes"] / peak["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
