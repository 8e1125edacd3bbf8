"""Backend-agnostic attention (reference components/attention/utils.py:25).

The reference switches between TE fused attention / SDPA / FlexAttention; here the
switchboard is ``backend="xla" | "flash"``:

- ``xla``: plain einsum-softmax attention. XLA fuses it well and it runs anywhere
  (CPU tests, interpreter); also the reference implementation for kernel parity tests.
- ``flash``: Pallas blockwise flash attention (automodel_tpu.ops.pallas.flash_attention)
  on TPU. Off the TPU, and for arguments the kernel does not take, the call runs
  the ``xla`` path and records why (automodel_tpu.ops.kernels).

Sequence packing uses segment ids (the TPU-native replacement for the reference's whole
BSHD/THD machinery, distributed/thd_utils.py): tokens attend only within their segment.
GQA/MQA is handled by broadcasting kv heads.
"""

from __future__ import annotations

import functools
import math
from typing import Literal

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from automodel_tpu.ops.kernels import check_manual_region, kernel_usable

__all__ = ["dot_product_attention", "sharded_attention"]

Backend = Literal["xla", "flash"]


def _attention_bias(
    seq_q: int,
    seq_kv: int,
    *,
    causal: bool,
    segment_ids_q: jnp.ndarray | None,
    segment_ids_kv: jnp.ndarray | None,
    positions_q: jnp.ndarray | None = None,
    positions_kv: jnp.ndarray | None = None,
    sliding_window: int | None = None,
    dtype=jnp.float32,
) -> jnp.ndarray | None:
    """Additive mask bias (0 allowed / -inf disallowed), shape (b or 1, 1, sq, skv)."""
    masks = []
    if causal:
        if positions_q is None:
            q_pos = jnp.arange(seq_q)[:, None]
            kv_pos = jnp.arange(seq_kv)[None, :]
            masks.append((q_pos >= kv_pos)[None, None])
        else:
            q_pos = positions_q[:, :, None]
            kv_pos = (positions_kv if positions_kv is not None else positions_q)[:, None, :]
            masks.append((q_pos >= kv_pos)[:, None])
    if sliding_window is not None:
        if positions_q is None:
            q_pos = jnp.arange(seq_q)[:, None]
            kv_pos = jnp.arange(seq_kv)[None, :]
            masks.append((q_pos - kv_pos < sliding_window)[None, None])
        else:
            q_pos = positions_q[:, :, None]
            kv_pos = (positions_kv if positions_kv is not None else positions_q)[:, None, :]
            masks.append((q_pos - kv_pos < sliding_window)[:, None])
    if segment_ids_q is not None:
        kv_seg = segment_ids_kv if segment_ids_kv is not None else segment_ids_q
        masks.append((segment_ids_q[:, :, None] == kv_seg[:, None, :])[:, None])
    if not masks:
        return None
    allowed = masks[0]
    for m in masks[1:]:
        allowed = jnp.logical_and(allowed, m)
    return jnp.where(allowed, 0.0, jnp.finfo(dtype).min).astype(dtype)


def _flash_needs(q, k, v, extra_bias, positions_q, positions_kv):
    """What the flash kernel takes, as ``(holds, else-reason)`` pairs."""
    return (
        (extra_bias is None, "extra_bias has no kernel path"),
        # the flash path masks by absolute index, not positions
        (positions_q is None and positions_kv is None,
         "position-masked (decode/cache) attention has no kernel path"),
        (q.shape[-1] == v.shape[-1],
         f"q/v head widths differ ({q.shape[-1]} vs {v.shape[-1]})"),
        # the kernel's block picker halves until it divides; sliding windows may
        # be ints OR traced scalars (they ride into the kernel through SMEM)
        (q.shape[1] % 8 == 0 and k.shape[1] % 8 == 0,
         f"seq lengths ({q.shape[1]}, {k.shape[1]}) are not multiples of 8"),
    )


def dot_product_attention(
    q: jnp.ndarray,  # (b, sq, n_heads, head_dim)
    k: jnp.ndarray,  # (b, skv, n_kv_heads, head_dim)
    v: jnp.ndarray,  # (b, skv, n_kv_heads, head_dim_v)
    *,
    causal: bool = True,
    segment_ids_q: jnp.ndarray | None = None,
    segment_ids_kv: jnp.ndarray | None = None,
    positions_q: jnp.ndarray | None = None,
    positions_kv: jnp.ndarray | None = None,
    sliding_window: int | None = None,
    softmax_scale: float | None = None,
    logit_soft_cap: float | None = None,
    sinks: jnp.ndarray | None = None,  # (n_heads,) attention sink logits (gpt-oss)
    extra_bias: jnp.ndarray | None = None,  # (b, sq, skv) additive logit bias (DSv3.2 sparse mask)
    backend: Backend = "xla",
) -> jnp.ndarray:
    """Multi-head attention with GQA, packing segments, sliding window, soft-cap, sinks.

    ``backend="flash"`` runs the Pallas kernel where it can and the einsum
    below where it cannot; which one, and why, is recorded once per distinct
    answer (:mod:`automodel_tpu.ops.kernels`). Either way the output is named
    ``attn_out`` for the remat policies (``models/common/backend.py``); a caller
    does not name it again. Under a multi-device mesh call
    :func:`sharded_attention` instead: a bare kernel on GSPMD-sharded operands
    is refused by the TPU compiler.
    """
    interpret = backend == "flash_interpret"  # CPU kernel-semantics testing
    if backend in ("flash", "flash_interpret") and kernel_usable(
        "attention", requested="flash", fallback="xla",
        needs=_flash_needs(q, k, v, extra_bias, positions_q, positions_kv),
        interpret=True if interpret else None,
    ):
        from automodel_tpu.ops.pallas.flash_attention import flash_attention

        if not interpret:
            check_manual_region("attention: flash")
        return flash_attention(
            q, k, v,
            causal=causal,
            segment_ids_q=segment_ids_q,
            segment_ids_kv=segment_ids_kv,
            sliding_window=sliding_window,
            softmax_scale=softmax_scale,
            logit_soft_cap=logit_soft_cap,
            sinks=sinks,
            interpret=interpret,
        )

    b, sq, nh, hd = q.shape
    _, skv, nkv, _ = k.shape
    if softmax_scale is None:
        softmax_scale = hd**-0.5
    groups = nh // nkv

    qf = q.astype(jnp.float32) * softmax_scale
    # (b, sq, kv, g, d) x (b, skv, kv, d) -> (b, kv, g, sq, skv)
    qf = qf.reshape(b, sq, nkv, groups, hd)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qf, k.astype(jnp.float32))
    if logit_soft_cap is not None:
        logits = jnp.tanh(logits / logit_soft_cap) * logit_soft_cap
    bias = _attention_bias(
        sq, skv,
        causal=causal,
        segment_ids_q=segment_ids_q,
        segment_ids_kv=segment_ids_kv,
        positions_q=positions_q,
        positions_kv=positions_kv,
        sliding_window=sliding_window,
    )
    if bias is not None:
        logits = logits + bias[:, :, None]  # broadcast over the GQA group dim
    if extra_bias is not None:
        logits = logits + extra_bias[:, None, None].astype(jnp.float32)
    if sinks is not None:
        # gpt-oss attention sinks: an extra per-head logit column that absorbs mass.
        sink = jnp.broadcast_to(sinks.reshape(1, nkv, groups, 1, 1), (b, nkv, groups, sq, 1)).astype(jnp.float32)
        logits_max = jnp.max(jnp.concatenate([logits, sink], axis=-1), axis=-1, keepdims=True)
        unnorm = jnp.exp(logits - logits_max)
        denom = unnorm.sum(-1, keepdims=True) + jnp.exp(sink - logits_max)
        probs = unnorm / denom
    else:
        probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v.astype(jnp.float32))
    # the flash kernel names its own output (and log-sum-exp) where its custom VJP
    # makes its residuals; here the einsum's result carries the name, so a policy
    # that keeps ``attn_out`` keeps one tensor whichever path ran
    return checkpoint_name(out.reshape(b, sq, nh, v.shape[-1]).astype(q.dtype), "attn_out")


def _needs_manual_region(mesh) -> bool:
    """Several devices and plain GSPMD around the call. Inside a region that is
    already manual over some axes (a pipeline stage) there is no wrapping: a
    nested shard_map over the rest does not lower (Shardy wants manual axes
    before free ones in every operand sharding), so that case is left to
    ``check_manual_region``'s named error."""
    if mesh is None or mesh.size == 1:
        return False
    return not jax.sharding.get_abstract_mesh().manual_axes


def _n_shards(mesh, spec_entry) -> int:
    """How many ways one PartitionSpec entry splits its dimension."""
    axes = () if spec_entry is None else (
        (spec_entry,) if isinstance(spec_entry, str) else tuple(spec_entry))
    return math.prod(mesh.shape[a] for a in axes)


def sharded_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    rules,
    backend: Backend = "xla",
    segment_ids_q: jnp.ndarray | None = None,
    sliding_window=None,
    sinks: jnp.ndarray | None = None,
    **kwargs,
) -> jnp.ndarray:
    """:func:`dot_product_attention` for operands that live on ``rules.mesh``.

    With one device, the ``xla`` backend, or arguments the kernel does not
    take, this IS ``dot_product_attention`` (GSPMD partitions the einsum).
    Otherwise the flash kernel runs inside a ``shard_map`` over the axes that
    shard batch and heads, so each device's kernel sees its local block.
    """
    plain = functools.partial(
        dot_product_attention, segment_ids_q=segment_ids_q,
        sliding_window=sliding_window, sinks=sinks, backend=backend, **kwargs,
    )
    mesh = None if rules is None else rules.mesh
    if backend not in ("flash", "flash_interpret") or not _needs_manual_region(mesh):
        return plain(q, k, v)
    interpret = backend == "flash_interpret"
    # batch and heads split over the axes the rules give them; the sequence
    # stays whole (a cp-sharded sequence is ring attention's business)
    qkv = rules.spec(("batch", None, "act_heads", None))
    batch_axes, _, head_axes = (*qkv, None, None, None)[:3]  # spec() drops trailing Nones
    nb, nh = _n_shards(mesh, batch_axes), _n_shards(mesh, head_axes)
    cp = mesh.shape.get("cp", 1)
    if not kernel_usable(
        "attention", requested="flash", fallback="xla",
        needs=(
            *_flash_needs(q, k, v, kwargs.get("extra_bias"),
                          kwargs.get("positions_q"), kwargs.get("positions_kv")),
            (kwargs.get("segment_ids_kv") is None
             or kwargs.get("segment_ids_kv") is segment_ids_q, "separate kv segment ids"),
            (cp == 1, f"sequence is sharded over cp={cp}: needs context_parallel: ring"),
            (q.shape[0] % nb == 0, f"batch {q.shape[0]} does not split {nb} ways"),
            (k.shape[2] % nh == 0 and q.shape[2] % nh == 0,
             f"heads ({q.shape[2]}q/{k.shape[2]}kv) do not split {nh} ways"),
        ),
        interpret=True if interpret else None,
    ):
        return dot_product_attention(
            q, k, v, segment_ids_q=segment_ids_q, sliding_window=sliding_window,
            sinks=sinks, backend="xla", **kwargs)

    # optional operands ride as explicit arguments: a traced per-layer window
    # (gemma/gpt-oss layer scans) cannot be closed over by a manual region
    window = None if sliding_window is None else jnp.asarray(sliding_window, jnp.int32)
    varying = tuple(a for a in mesh.axis_names
                    if a in jax.tree.leaves((batch_axes, head_axes)))

    def body(q, k, v, seg, window, sinks):
        # a kernel's operands must vary over the same axes as one another
        vary = lambda x: None if x is None else jax.lax.pcast(
            x, tuple(a for a in varying if a not in jax.typeof(x).vma), to="varying")
        return dot_product_attention(
            q, k, v, segment_ids_q=vary(seg), sliding_window=vary(window),
            sinks=vary(sinks), backend=backend,
            **{k_: v_ for k_, v_ in kwargs.items() if k_ != "segment_ids_kv"})

    # a Mosaic kernel is only accepted in a region manual over EVERY mesh axis:
    # batch and heads split over the axes the rules give them, operands are
    # replicated over the rest
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(qkv, qkv, qkv,
                  None if segment_ids_q is None else P(batch_axes, None),
                  None if window is None else P(),
                  None if sinks is None else P(head_axes)),
        out_specs=qkv,
        axis_names=frozenset(mesh.axis_names),
        # interpret-mode pallas lowering mixes varying and unvarying operands
        # internally, which the checker rejects; the compiled kernel keeps it on
        check_vma=not interpret,
    )(q, k, v, segment_ids_q, window, sinks)
