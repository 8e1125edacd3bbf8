"""Position-masked flash chunk kernels for ring (context-parallel) attention.

The ring loop (parallel/ring_attention.py) rotates kv chunks around the ``cp``
axis; every shard repeatedly attends its local q against a visiting kv chunk.
These are the per-chunk kernels: flash-style blockwise attention whose
online-softmax state (acc, m, l) carries ACROSS kernel calls, so the ring's
cross-step merge happens in VMEM instead of materializing per-chunk
(Sq_local x Skv_local) score matrices in HBM — the memory profile the
reference gets from TransformerEngine's fused ring attention
(/root/reference/nemo_automodel/components/moe/parallelizer.py:267-285).

Unlike ops/pallas/flash_attention.py, masking here is data-driven: global
token positions travel with the chunks (causality and sliding windows are
position comparisons, segment packing an id comparison), which is what makes
load-balanced interleaved layouts free. That also means no static block
skipping — a visiting chunk's positions are data, not grid arithmetic.

Layout contract (row-form, like flash_attention's internals):
  q        (BN, Sq, D)    BN = batch * num_q_heads
  k        (BK, Skv, D)   BK = batch * num_kv_heads, BN = BK * groups
  v        (BK, Skv, Dv)  Dv may differ from D (MLA)
  pos_q    (B, Sq, LANES) int32, broadcast over the lane dim
  pos_kv   (B, SUBLANES, Skv)
  seg_*    same layouts as pos_* (optional)
  carry    acc (BN, Sq, Dv) f32, m/l (BN, Sq, LANES) f32
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from automodel_tpu.ops.pallas.flash_attention import LANES, NEG_INF, SUBLANES

__all__ = ["chunk_attention_fwd", "chunk_attention_bwd"]


def _sds(shape, dtype, vma):
    """ShapeDtypeStruct with varying-mesh-axes metadata when under shard_map
    (pallas outputs can't infer vma; the ring passes its cp axis)."""
    if vma is None:
        return jax.ShapeDtypeStruct(shape, dtype)
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _make_entry(kernel, segmented):
    """Flat pallas ref list -> kernel(q, k, v, pq, pkv, seg_q|None, seg_kv|None, *rest)."""

    def entry(*refs):
        it = iter(refs)
        q_r, k_r, v_r, pq_r, pkv_r = (next(it) for _ in range(5))
        sq_r = next(it) if segmented else None
        skv_r = next(it) if segmented else None
        kernel(q_r, k_r, v_r, pq_r, pkv_r, sq_r, skv_r, *it)

    return entry


def _qkv_pos_specs(q, k, v, pos_q, pos_kv, seg_q, seg_kv, *,
                   block_q, block_k, groups, n_heads):
    """Shared (in_specs, args) prefix for both chunk kernels: q/k/v blocks with
    GQA via the b // groups index map, per-batch positions/segments via the
    b // n_heads map (no HBM repeats)."""
    d = q.shape[-1]
    dv = v.shape[-1]

    def batch_of(b):
        return b // n_heads

    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // groups, j, 0)),
        pl.BlockSpec((1, block_k, dv), lambda b, i, j: (b // groups, j, 0)),
        pl.BlockSpec((1, block_q, LANES), lambda b, i, j: (batch_of(b), i, 0)),
        pl.BlockSpec((1, SUBLANES, block_k), lambda b, i, j: (batch_of(b), 0, j)),
    ]
    args = [q, k, v, pos_q, pos_kv]
    if seg_q is not None:
        in_specs += [
            pl.BlockSpec((1, block_q, LANES), lambda b, i, j: (batch_of(b), i, 0)),
            pl.BlockSpec((1, SUBLANES, block_k), lambda b, i, j: (batch_of(b), 0, j)),
        ]
        args += [seg_q, seg_kv]
    return in_specs, args


def _pos_mask(pq, pkv, sq, skv, *, causal, window, segmented):
    """(bq, bk) allowed-mask from position/segment tiles; None when unmasked.

    pq (bq, 1) int32 global positions; pkv (1, bk); sq/skv same shapes or None.
    """
    allowed = None

    def _and(a, b):
        return b if a is None else jnp.logical_and(a, b)

    if causal:
        allowed = _and(allowed, pq >= pkv)
    if window is not None:
        allowed = _and(allowed, pq - pkv < window)
    if segmented:
        allowed = _and(allowed, sq == skv)
    return allowed


def _chunk_fwd_kernel(q_ref, k_ref, v_ref, pq_ref, pkv_ref, sq_ref, skv_ref,
                      acc_in, m_in, l_in, acc_out, m_out, l_out,
                      acc_s, m_s, l_s, *, scale, causal, window,
                      num_kv, segmented):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _load_carry():
        acc_s[:] = acc_in[0]
        m_s[:] = m_in[0]
        l_s[:] = l_in[0]

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    allowed = _pos_mask(
        pq_ref[0, :, :1], pkv_ref[0, :1, :],
        sq_ref[0, :, :1] if segmented else None,
        skv_ref[0, :1, :] if segmented else None,
        causal=causal, window=window, segmented=segmented,
    )
    if allowed is not None:
        s = jnp.where(allowed, s, NEG_INF)

    m_prev = m_s[:, :1]
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
    p = jnp.exp(s - m_new)
    if allowed is not None:
        p = jnp.where(allowed, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_s[:] = jnp.broadcast_to(l_s[:, :1] * alpha + p.sum(-1, keepdims=True), l_s.shape)
    acc_s[:] = acc_s[:] * alpha + jax.lax.dot(
        p.astype(v_ref.dtype), v_ref[0], preferred_element_type=jnp.float32
    )
    m_s[:] = jnp.broadcast_to(m_new, m_s.shape)

    @pl.when(ki == num_kv - 1)
    def _store_carry():
        acc_out[0] = acc_s[:]
        m_out[0] = m_s[:]
        l_out[0] = l_s[:]


def chunk_attention_fwd(q, k, v, pos_q, pos_kv, seg_q, seg_kv, acc, m, l, *,
                        scale, causal, window, groups, n_heads,
                        block_q, block_k, interpret, vma=None):
    """One ring step: merge attention against a visiting kv chunk into (acc, m, l)."""
    bn, sq, d = q.shape
    _, skv, dv = v.shape
    num_q, num_kv = sq // block_q, skv // block_k
    segmented = seg_q is not None

    kernel = functools.partial(
        _chunk_fwd_kernel, scale=scale, causal=causal, window=window,
        num_kv=num_kv, segmented=segmented,
    )
    in_specs, args = _qkv_pos_specs(
        q, k, v, pos_q, pos_kv, seg_q, seg_kv,
        block_q=block_q, block_k=block_k, groups=groups, n_heads=n_heads,
    )
    carry_specs = [
        pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_q, LANES), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_q, LANES), lambda b, i, j: (b, i, 0)),
    ]
    base = len(args)  # index of acc among the call operands
    return pl.pallas_call(
        _make_entry(kernel, segmented),
        grid=(bn, num_q, num_kv),
        in_specs=in_specs + carry_specs,
        out_specs=carry_specs,
        # donate the carry: acc/m/l buffers are dead after each ring step, so
        # alias them onto the outputs instead of allocating + copying fresh
        # f32 carry arrays cp times per layer
        input_output_aliases={base: 0, base + 1: 1, base + 2: 2},
        out_shape=[
            _sds(acc.shape, jnp.float32, vma),
            _sds(m.shape, jnp.float32, vma),
            _sds(l.shape, jnp.float32, vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="ring_attention_fwd",
    )(*args, acc, m, l)


def _chunk_bwd_kernel(q_ref, k_ref, v_ref, pq_ref, pkv_ref, sq_ref, skv_ref,
                      do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
                      dq_s, dk_s, dv_s, *, scale, causal, window,
                      num_q, num_kv, segmented):
    """Fused dq-partial + dkv-chunk off one s/p recompute (the ring analogue of
    flash_attention._dqdkv_kernel). dk/dv accumulate in full-(Skv, ·) f32
    scratch across the whole per-row grid; the wrapper kv-sub-chunks to bound
    that footprint."""
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(jnp.logical_and(qi == 0, ki == 0))
    def _init_kv():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    @pl.when(ki == 0)
    def _init_q():
        dq_s[:] = jnp.zeros_like(dq_s)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    allowed = _pos_mask(
        pq_ref[0, :, :1], pkv_ref[0, :1, :],
        sq_ref[0, :, :1] if segmented else None,
        skv_ref[0, :1, :] if segmented else None,
        causal=causal, window=window, segmented=segmented,
    )
    if allowed is not None:
        s = jnp.where(allowed, s, NEG_INF)
    p = jnp.exp(s - lse_ref[0, :, :1])
    if allowed is not None:
        p = jnp.where(allowed, p, 0.0)
    do = do_ref[0].astype(jnp.float32)
    kv_rows = pl.ds(ki * k.shape[0], k.shape[0])
    dv_s[kv_rows, :] += jax.lax.dot_general(
        p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[0, :, :1])
    dq_s[:] += jax.lax.dot(ds, k, preferred_element_type=jnp.float32) * scale
    dk_s[kv_rows, :] += jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32) * scale

    @pl.when(ki == num_kv - 1)
    def _finalize_q():
        dq_ref[0] = dq_s[:]

    @pl.when(jnp.logical_and(qi == num_q - 1, ki == num_kv - 1))
    def _finalize_kv():
        dk_ref[0] = dk_s[:]
        dv_ref[0] = dv_s[:]


def chunk_attention_bwd(q, k, v, pos_q, pos_kv, seg_q, seg_kv, do, lse, delta, *,
                        scale, causal, window, groups, n_heads,
                        block_q, block_k, interpret, vma=None):
    """One backward ring step: (dq_partial, dk_chunk, dv_chunk) vs a visiting
    kv chunk. dk/dv come back per q-head row (BN, Skv, ·) f32 — the caller
    group-sums onto the traveling kv-row accumulators."""
    bn, sq, d = q.shape
    _, skv, dv = v.shape
    num_q, num_kv = sq // block_q, skv // block_k
    segmented = seg_q is not None

    kernel = functools.partial(
        _chunk_bwd_kernel, scale=scale, causal=causal, window=window,
        num_q=num_q, num_kv=num_kv, segmented=segmented,
    )
    in_specs, args = _qkv_pos_specs(
        q, k, v, pos_q, pos_kv, seg_q, seg_kv,
        block_q=block_q, block_k=block_k, groups=groups, n_heads=n_heads,
    )
    in_specs += [
        pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0)),   # do
        pl.BlockSpec((1, block_q, LANES), lambda b, i, j: (b, i, 0)),  # lse
        pl.BlockSpec((1, block_q, LANES), lambda b, i, j: (b, i, 0)),  # delta
    ]
    dq, dk, dv_out = pl.pallas_call(
        _make_entry(kernel, segmented),
        grid=(bn, num_q, num_kv),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, skv, d), lambda b, i, j: (b, 0, 0)),
            pl.BlockSpec((1, skv, dv), lambda b, i, j: (b, 0, 0)),
        ],
        out_shape=[
            _sds((bn, sq, d), jnp.float32, vma),
            _sds((bn, skv, d), jnp.float32, vma),
            _sds((bn, skv, dv), jnp.float32, vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((skv, d), jnp.float32),
            pltpu.VMEM((skv, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name="ring_attention_bwd",
    )(*args, do, lse, delta)
    return dq, dk, dv_out
