"""Blockwise flash attention for TPU (Pallas).

Replaces the reference's TransformerEngine fused attention / flash-attn externals
(components/attention/utils.py:25, models/common/utils.py:166-171) with a single
Pallas kernel pair:

- forward: online-softmax over kv blocks; (q, k, v) stream HBM->VMEM block by block,
  the (block_q, head_dim) accumulator and row stats live in VMEM scratch across the
  innermost kv grid steps. Emits logsumexp for the backward.
- backward: recompute-based (flash-attention-2 style): one kernel accumulates dq over
  kv blocks, one accumulates dk/dv over q blocks; D = rowsum(dO*O) precomputed in XLA.

Masking is composable inside the kernel: causal, sliding window (static), and segment
ids (sequence packing — the TPU replacement for the reference's THD varlen format,
distributed/thd_utils.py). GQA reads each kv head once via grid index maps — kv is
never materialized per q head in the forward.

TPU layout notes: Mosaic requires the last two block dims to be (8k, 128k)-divisible,
so per-row vectors ride in padded layouts (the same scheme as the in-tree
jax.experimental.pallas.ops.tpu.flash_attention): q-oriented vectors (q segment ids,
logsumexp, D) are broadcast across a trailing 128-lane dim; kv-oriented vectors
(kv segment ids) across an 8-sublane dim.

Layout contract: inputs are (batch, seq, heads, head_dim) like ops.attention; the
wrapper folds (batch, heads) into the leading grid dim. Sequence lengths must divide
the block sizes; callers fall back to the XLA path otherwise.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from automodel_tpu.ops.kernels import note, out_struct

__all__ = ["flash_attention"]

NEG_INF = -1e30
LANES = 128
SUBLANES = 8


def _block_mask(q_start, kv_start, block_q, block_k, *, causal, window, seg_q, seg_kv):
    """(bq, bk) bool allowed-mask; seg_q is (bq, 1), seg_kv is (1, bk)."""
    q_idx = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kv_idx = kv_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    allowed = None

    def _and(a, b):
        return b if a is None else jnp.logical_and(a, b)

    if causal:
        allowed = _and(allowed, q_idx >= kv_idx)
    if window is not None:
        allowed = _and(allowed, q_idx - kv_idx < window)
    if seg_q is not None:
        allowed = _and(allowed, seg_q == seg_kv)
    return allowed


def _run_block(q_start, kv_start, block_q, block_k, *, causal, window):
    """Static/cheap predicate: does this (q block, kv block) pair do any work?"""
    run = True
    if causal:
        run = q_start + block_q - 1 >= kv_start
    if window is not None:
        run = jnp.logical_and(run, q_start - (kv_start + block_k - 1) < window)
    return run


def _soft_cap(s, cap):
    """tanh logit capping (gemma2/grok style); None -> identity."""
    return s if cap is None else jnp.tanh(s / cap) * cap


def _soft_cap_jac(s_capped, cap):
    """d(capped)/d(raw) expressed in the *capped* value: 1 - (capped/cap)^2."""
    return 1.0 - (s_capped / cap) ** 2


def _fwd_kernel(q_ref, k_ref, v_ref, sq_ref, skv_ref, sink_ref, w_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, causal, block_q, block_k,
                num_kv, segmented, softcap, has_sink, windowed):
    window = w_ref[0] if windowed else None
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q_start, kv_start = qi * block_q, ki * block_k

    @pl.when(_run_block(q_start, kv_start, block_q, block_k, causal=causal, window=window))
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # (bq, d)
        k = k_ref[0].astype(jnp.float32)  # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bk)
        s = _soft_cap(s, softcap)

        allowed = _block_mask(
            q_start, kv_start, block_q, block_k, causal=causal, window=window,
            seg_q=sq_ref[0, :, :1] if segmented else None,
            seg_kv=skv_ref[0, :1, :] if segmented else None,
        )
        if allowed is not None:
            s = jnp.where(allowed, s, NEG_INF)

        m_prev = m_ref[:, :1]  # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if allowed is not None:
            p = jnp.where(allowed, p, 0.0)  # fully-masked rows stay all-zero
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_ref[:, :1] * alpha + p.sum(-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot(
            p.astype(v_ref.dtype), v_ref[0], preferred_element_type=jnp.float32
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == num_kv - 1)
    def _finalize():
        if has_sink:
            # gpt-oss attention sinks: a per-head extra logit column absorbing
            # softmax mass. Fold it into the running (m, l) stats: the sink
            # contributes exp(sink) to the denominator and nothing to the value
            # accumulator; lse then already accounts for it, so the backward
            # kernels need no change (p = exp(s - lse) sums to < 1).
            sink = sink_ref[0, 0, 0]
            m0, l0 = m_ref[:, :1], l_ref[:, :1]
            m_eff = jnp.maximum(m0, sink)
            alpha = jnp.exp(m0 - m_eff)  # 0 for fully-masked rows (m0 = -inf)
            l = l0 * alpha + jnp.exp(sink - m_eff)
            o_ref[0] = (acc_ref[:] * alpha / l).astype(o_ref.dtype)
            lse_ref[0] = jnp.broadcast_to(m_eff + jnp.log(l), lse_ref.shape[1:])
        else:
            l = l_ref[:, :1]
            safe_l = jnp.where(l == 0.0, 1.0, l)
            o_ref[0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
            lse = jnp.where(l == 0.0, NEG_INF, m_ref[:, :1] + jnp.log(safe_l))
            lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _dq_kernel(q_ref, k_ref, v_ref, sq_ref, skv_ref, w_ref, do_ref, lse_ref, delta_ref,
               dq_ref, acc_ref, *, scale, causal, block_q, block_k, num_kv,
               segmented, softcap, windowed):
    window = w_ref[0] if windowed else None
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q_start, kv_start = qi * block_q, ki * block_k

    @pl.when(_run_block(q_start, kv_start, block_q, block_k, causal=causal, window=window))
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = _soft_cap(s, softcap)
        allowed = _block_mask(
            q_start, kv_start, block_q, block_k, causal=causal, window=window,
            seg_q=sq_ref[0, :, :1] if segmented else None,
            seg_kv=skv_ref[0, :1, :] if segmented else None,
        )
        p = jnp.exp(s - lse_ref[0, :, :1])
        if allowed is not None:
            p = jnp.where(allowed, p, 0.0)
        do = do_ref[0].astype(jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, :, :1])
        if softcap is not None:
            ds = ds * _soft_cap_jac(s, softcap)
        acc_ref[:] += jax.lax.dot(ds, k, preferred_element_type=jnp.float32) * scale

    @pl.when(ki == num_kv - 1)
    def _finalize():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _dqdkv_kernel(q_ref, k_ref, v_ref, sq_ref, skv_ref, w_ref, do_ref, lse_ref,
                  delta_ref, dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *,
                  scale, causal, block_q, block_k, num_q, num_kv, segmented,
                  softcap, windowed):
    """Fused backward: dq, dk, dv off ONE s/p recompute per (q, kv) block pair.

    The split kernels each redo s = qk^T and the dq kernel redoes dp = do v^T,
    so the split backward runs 7 block matmuls per pair; sharing the recompute
    cuts that to 5 (s, dp, dq += ds k, dv += p^T do, dk += ds^T q) and halves
    the q/k/v/do HBM streaming. The price: dk/dv accumulate across the whole
    per-row grid, so they live as full-(Skv, d) f32 VMEM scratch — the wrapper
    gates this path on that footprint and falls back to the split kernels.
    """
    window = w_ref[0] if windowed else None
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(jnp.logical_and(qi == 0, ki == 0))
    def _init_kv():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(ki == 0)
    def _init_q():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q_start, kv_start = qi * block_q, ki * block_k

    @pl.when(_run_block(q_start, kv_start, block_q, block_k, causal=causal, window=window))
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = _soft_cap(s, softcap)
        allowed = _block_mask(
            q_start, kv_start, block_q, block_k, causal=causal, window=window,
            seg_q=sq_ref[0, :, :1] if segmented else None,
            seg_kv=skv_ref[0, :1, :] if segmented else None,
        )
        p = jnp.exp(s - lse_ref[0, :, :1])
        if allowed is not None:
            p = jnp.where(allowed, p, 0.0)
        do = do_ref[0].astype(jnp.float32)
        kv_rows = pl.ds(kv_start, block_k)
        dv_acc[kv_rows, :] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, :, :1])
        if softcap is not None:
            ds = ds * _soft_cap_jac(s, softcap)
        dq_acc[:] += jax.lax.dot(ds, k, preferred_element_type=jnp.float32) * scale
        dk_acc[kv_rows, :] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32) * scale

    @pl.when(ki == num_kv - 1)
    def _finalize_q():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)

    @pl.when(jnp.logical_and(qi == num_q - 1, ki == num_kv - 1))
    def _finalize_kv():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, sq_ref, skv_ref, w_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                block_q, block_k, num_q, segmented, softcap, windowed):
    window = w_ref[0] if windowed else None
    ki, qi = pl.program_id(1), pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_start, kv_start = qi * block_q, ki * block_k

    @pl.when(_run_block(q_start, kv_start, block_q, block_k, causal=causal, window=window))
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = _soft_cap(s, softcap)
        allowed = _block_mask(
            q_start, kv_start, block_q, block_k, causal=causal, window=window,
            seg_q=sq_ref[0, :, :1] if segmented else None,
            seg_kv=skv_ref[0, :1, :] if segmented else None,
        )
        p = jnp.exp(s - lse_ref[0, :, :1])
        if allowed is not None:
            p = jnp.where(allowed, p, 0.0)
        do = do_ref[0].astype(jnp.float32)
        dv_acc[:] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, :, :1])
        if softcap is not None:
            ds = ds * _soft_cap_jac(s, softcap)
        dk_acc[:] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32) * scale

    @pl.when(qi == num_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _q_lanes(x):
    """(BN, S) -> (BN, S, LANES) broadcast along a 128-lane trailing dim."""
    return jax.lax.broadcast_in_dim(x, (*x.shape, LANES), (0, 1))


def _kv_sublanes(x):
    """(BN, S) -> (BN, SUBLANES, S) broadcast along an 8-sublane dim."""
    return jax.lax.broadcast_in_dim(x, (x.shape[0], SUBLANES, x.shape[1]), (0, 2))


def _specs(bn_map, d, block_q, block_k, segmented, has_sink=False, windowed=False):
    """(q, k, v, seg_q, seg_kv, sinks, window) block specs; bn_map maps grid b -> kv row.
    The sliding window rides as a (1,) SMEM scalar so traced per-layer windows
    (gpt-oss/gemma alternating layer types under a layer scan) stay kernel-eligible."""
    return [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (bn_map(b), j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (bn_map(b), j, 0)),
        pl.BlockSpec((1, block_q, LANES), lambda b, i, j: (b, i, 0)) if segmented else None,
        pl.BlockSpec((1, SUBLANES, block_k), lambda b, i, j: (bn_map(b), 0, j)) if segmented else None,
        pl.BlockSpec((1, 1, LANES), lambda b, i, j: (b, 0, 0)) if has_sink else None,
        pl.BlockSpec(memory_space=pltpu.SMEM) if windowed else None,
    ]


def _rows(x):
    """(B, S, H, D) -> (B*H, S, D): the kernels' layout, one grid row a (batch, head)."""
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _unrows(x, b):
    """(B*H, S, D) -> (B, S, H, D)."""
    bh, s, d = x.shape
    return x.reshape(b, bh // b, s, d).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11, 12, 13))
def _flash(q, k, v, seg_q, seg_kv, sinks, warr, scale, causal, softcap,
           block_q, block_k, groups, interpret):
    """q (B, Sq, N, D), k/v (B, Skv, K, D) -> (B, Sq, N, D); the rest as
    ``_flash_fwd_impl`` takes them."""
    o, _ = _flash_fwd_impl(_rows(q), _rows(k), _rows(v), seg_q, seg_kv, sinks, warr,
                           scale, causal, softcap, block_q, block_k, groups, interpret)
    return _unrows(o, q.shape[0])


def _filter_specs(specs, args):
    keep = [(s, a) for s, a in zip(specs, args) if a is not None]
    return [s for s, _ in keep], [a for _, a in keep]


# trace counter for the fused dq+dkv path — lets tests assert the fused kernel
# actually engaged (the VMEM gate silently falls back to the split kernels)
_fused_bwd_traces = 0


def _make_entry(kernel, segmented, windowed, has_sink=False, sink_slot=False):
    """Adapter from pallas_call's flat ref list to a kernel's optional-arg
    signature (q, k, v, seg_q, seg_kv, [sink], window, *rest). `has_sink` says a
    sink ref is actually present in the flat list; `sink_slot` says the kernel's
    signature has a sink parameter at all (the fwd kernel takes one even when no
    sinks input was passed — it receives None)."""

    def entry(*refs):
        it = iter(refs)
        q_r, k_r, v_r = next(it), next(it), next(it)
        sq_r = next(it) if segmented else None
        skv_r = next(it) if segmented else None
        sink_r = next(it) if has_sink else None
        w_r = next(it) if windowed else None
        if sink_slot:
            kernel(q_r, k_r, v_r, sq_r, skv_r, sink_r, w_r, *it)
        else:
            kernel(q_r, k_r, v_r, sq_r, skv_r, w_r, *it)

    return entry


def _gqa_group_sum(dk, dv, groups, k_dtype, v_dtype):
    """Reduce per-q-head dk/dv (bn, skv, d) over the GQA group -> (bk, skv, d)."""
    if groups == 1:
        return dk, dv
    dk = dk.reshape(-1, groups, *dk.shape[1:]).sum(1).astype(k_dtype)
    dv = dv.reshape(-1, groups, *dv.shape[1:]).sum(1).astype(v_dtype)
    return dk, dv


def _flash_fwd_impl(q, k, v, seg_q, seg_kv, sinks, warr, scale, causal,
                    softcap, block_q, block_k, groups, interpret):
    """q: (BN, Sq, D); k/v: (BK, Skv, D) with BN = BK * groups.
    seg_q: (BN, Sq, LANES) or None; seg_kv: (BK, SUBLANES, Skv) or None;
    sinks: (BN, 1, LANES) f32 per-row sink logits or None;
    warr: (1,) int32 sliding window (possibly traced) or None."""
    bn, sq, d = q.shape
    _, skv, _ = k.shape
    num_q, num_kv = sq // block_q, skv // block_k
    segmented = seg_q is not None
    has_sink = sinks is not None
    windowed = warr is not None

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, num_kv=num_kv, segmented=segmented,
        softcap=softcap, has_sink=has_sink, windowed=windowed,
    )

    kernel_entry = _make_entry(kernel, segmented, windowed,
                               has_sink=has_sink, sink_slot=True)

    specs, args = _filter_specs(
        _specs(lambda b: b // groups, d, block_q, block_k, segmented, has_sink, windowed),
        [q, k, v, seg_q, seg_kv, sinks, warr],
    )
    o, lse = pl.pallas_call(
        kernel_entry,
        grid=(bn, num_q, num_kv),
        in_specs=specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            out_struct(q.shape, q.dtype, *args),
            out_struct((bn, sq, LANES), jnp.float32, *args),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention_fwd",
    )(*args)
    return o, lse


def _flash_fwd(q, k, v, seg_q, seg_kv, sinks, warr, scale, causal, softcap,
               block_q, block_k, groups, interpret):
    o, lse = _flash_fwd_impl(_rows(q), _rows(k), _rows(v), seg_q, seg_kv, sinks, warr,
                             scale, causal, softcap, block_q, block_k, groups, interpret)
    # The output and the log-sum-exp are the residuals a remat policy can keep by name
    # (``mlp_attn_dots``): saved, the backward pass does not run the forward kernel
    # again. The output is kept as the caller gets it, (B, Sq, N, D): the one tensor the
    # output projection's backward reads too, and a head narrower than 128 is not padded
    # to the lanes as the kernels' (B*N, Sq, D) rows would be. The kernel writes lse on
    # all 128 lanes alike; one lane is kept and ``_flash_bwd`` spreads it again.
    out = checkpoint_name(_unrows(o, q.shape[0]), "attn_out")
    lse = checkpoint_name(lse[:, :, 0], "attn_lse")
    return out, (q, k, v, seg_q, seg_kv, sinks, warr, out, lse)


def _flash_bwd(scale, causal, softcap, block_q, block_k, groups, interpret,
               residuals, dout):
    q, k, v, seg_q, seg_kv, sinks, warr, out, lse = residuals
    windowed = warr is not None
    b, sq, n, d = q.shape
    # D = rowsum(dO * O) where both lie as the caller has them; only the sums move
    delta = (out.astype(jnp.float32) * dout.astype(jnp.float32)).sum(-1)  # (B, Sq, N)
    delta = _q_lanes(delta.transpose(0, 2, 1).reshape(b * n, sq))
    lse = _q_lanes(lse)
    q, k, v, do = _rows(q), _rows(k), _rows(v), _rows(dout)
    bn = b * n
    bk_heads, skv, _ = k.shape
    num_q, num_kv = sq // block_q, skv // block_k
    segmented = seg_q is not None

    def row_specs(index_q, bq):
        # do / lse / delta blocks, all q-oriented
        return [
            pl.BlockSpec((1, bq, d), index_q),
            pl.BlockSpec((1, bq, LANES), index_q),
            pl.BlockSpec((1, bq, LANES), index_q),
        ]

    # Fused dq+dkv path: one kernel, one s/p recompute (5 block matmuls vs the
    # split kernels' 7, and one q/k/v/do HBM stream instead of two). dk/dv ride
    # full-(Skv, d) f32 VMEM scratch PLUS full-(Skv, d) output windows, so the
    # path is gated on that whole resident footprint (f32 scratch pair + the
    # dk/dv output windows at output dtype); long-context shapes fall back to
    # the split kernels below. Block tiles / dq scratch / double-buffering are
    # roughly shape-independent here and covered by the budget's headroom to
    # the 16MB scoped-VMEM line.
    fused_kv_bytes = 2 * skv * d * (4 + k.dtype.itemsize)
    fused_budget = int(os.environ.get("AUTOMODEL_FLASH_FUSED_KV_BYTES", str(8 << 20)))
    if os.environ.get("AUTOMODEL_FLASH_FUSED_BWD", "1") != "0" and fused_kv_bytes <= fused_budget:
        block_q_f = min(block_q, int(os.environ.get("AUTOMODEL_FLASH_FUSED_Q_BLOCK", "512")))
        if sq % block_q_f:
            # the default (512, capped by block_q — itself a power of two
            # dividing sq) always divides; only an explicit override can't
            raise ValueError(
                f"AUTOMODEL_FLASH_FUSED_Q_BLOCK={block_q_f} must divide seq {sq} "
                "(a silent fallback here would benchmark the split kernels "
                "while reporting a fused config)"
            )
        global _fused_bwd_traces
        _fused_bwd_traces += 1
        note("attention_bwd", "fused", interpret=interpret)
        num_q_f = sq // block_q_f
        fused_kernel = functools.partial(
            _dqdkv_kernel, scale=scale, causal=causal,
            block_q=block_q_f, block_k=block_k, num_q=num_q_f, num_kv=num_kv,
            segmented=segmented, softcap=softcap, windowed=windowed,
        )
        specs, args = _filter_specs(
            _specs(lambda b: b // groups, d, block_q_f, block_k, segmented, False, windowed)
            + row_specs(lambda b, i, j: (b, i, 0), block_q_f),
            [q, k, v, seg_q, seg_kv, None, warr, do, lse, delta],
        )
        dq, dk, dv = pl.pallas_call(
            _make_entry(fused_kernel, segmented, windowed),
            grid=(bn, num_q_f, num_kv),
            in_specs=specs,
            out_specs=[
                pl.BlockSpec((1, block_q_f, d), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, skv, d), lambda b, i, j: (b, 0, 0)),
                pl.BlockSpec((1, skv, d), lambda b, i, j: (b, 0, 0)),
            ],
            out_shape=[
                out_struct(q.shape, q.dtype, *args),
                out_struct((bn, skv, d), k.dtype, *args),
                out_struct((bn, skv, d), v.dtype, *args),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q_f, d), jnp.float32),
                pltpu.VMEM((skv, d), jnp.float32),
                pltpu.VMEM((skv, d), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            ),
            interpret=interpret,
            name="flash_attention_bwd",
        )(*args)
        dk, dv = _gqa_group_sum(dk, dv, groups, k.dtype, v.dtype)
        return (_unrows(dq, b), _unrows(dk, b), _unrows(dv, b), None, None,
                _dsinks_from_residuals(sinks, lse, delta), None)

    note("attention_bwd", "split", interpret=interpret,
         reason=f"fused dq+dkv unusable: resident dk/dv footprint "
                f"{fused_kv_bytes} B over the {fused_budget} B budget (or switched off)")
    dq_kernel = functools.partial(
        _dq_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, num_kv=num_kv, segmented=segmented,
        softcap=softcap, windowed=windowed,
    )

    specs, args = _filter_specs(
        _specs(lambda b: b // groups, d, block_q, block_k, segmented, False, windowed)
        + row_specs(lambda b, i, j: (b, i, 0), block_q),
        [q, k, v, seg_q, seg_kv, None, warr, do, lse, delta],  # None: no sink input in bwd
    )
    dq = pl.pallas_call(
        _make_entry(dq_kernel, segmented, windowed),
        grid=(bn, num_q, num_kv),
        in_specs=specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=out_struct(q.shape, q.dtype, *args),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(*args)

    # dk/dv reduce over the GQA group; expand kv per q head, sum groups after.
    kx = jnp.repeat(k, groups, axis=0) if groups > 1 else k
    vx = jnp.repeat(v, groups, axis=0) if groups > 1 else v
    skx = (
        jnp.repeat(seg_kv, groups, axis=0)
        if (segmented and groups > 1)
        else seg_kv
    )
    # the dkv kernel carries TWO f32 accumulators + the recompute tile; at
    # block_q 1024 it sits ~44KB over the 16MB scoped-VMEM line in some remat
    # contexts — cap ITS q block while dq (one accumulator) keeps the bigger one.
    # The env override exists for on-chip block sweeps (bench scripts); 512 is
    # the measured best at seq 2048 AND 4096 on v5e.
    block_q_kv = min(block_q, int(os.environ.get("AUTOMODEL_FLASH_BWD_Q_BLOCK", "512")))
    if sq % block_q_kv:
        raise ValueError(
            f"AUTOMODEL_FLASH_BWD_Q_BLOCK={block_q_kv} must divide seq {sq} "
            "(a ragged dkv grid would silently drop tail q-blocks from dk/dv)"
        )
    num_q_kv = sq // block_q_kv
    dkv_kernel = functools.partial(
        _dkv_kernel, scale=scale, causal=causal,
        block_q=block_q_kv, block_k=block_k, num_q=num_q_kv, segmented=segmented,
        softcap=softcap, windowed=windowed,
    )

    # grid order here is (bn, kv, q): q/do/lse/delta index with the LAST grid dim
    qkv_specs = [
        pl.BlockSpec((1, block_q_kv, d), lambda b, j, i: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, block_q_kv, LANES), lambda b, j, i: (b, i, 0)) if segmented else None,
        pl.BlockSpec((1, SUBLANES, block_k), lambda b, j, i: (b, 0, j)) if segmented else None,
        pl.BlockSpec(memory_space=pltpu.SMEM) if windowed else None,
    ]
    specs, args = _filter_specs(
        qkv_specs + row_specs(lambda b, j, i: (b, i, 0), block_q_kv),
        [q, kx, vx, seg_q, skx, warr, do, lse, delta],
    )
    dk, dv = pl.pallas_call(
        _make_entry(dkv_kernel, segmented, windowed),
        grid=(bn, num_kv, num_q_kv),
        in_specs=specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            out_struct(kx.shape, k.dtype, *args),
            out_struct(vx.shape, v.dtype, *args),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(*args)
    dk, dv = _gqa_group_sum(dk, dv, groups, k.dtype, v.dtype)
    return (_unrows(dq, b), _unrows(dk, b), _unrows(dv, b), None, None,
            _dsinks_from_residuals(sinks, lse, delta), None)


def _dsinks_from_residuals(sinks, lse, delta):
    """d loss / d sink_b = -sum_i exp(sink_b - lse_{b,i}) * Delta_{b,i}
    (the sink column's p * (dp - Delta) with dp = 0); cheap XLA reduction over
    the saved lse + delta. Gradient lands on lane 0, matching the kernel's
    sink_ref[0, 0, 0] read; the wrapper's broadcast transposes the rest away."""
    if sinks is None:
        return None
    p_sink = jnp.exp(sinks[:, 0, 0][:, None] - lse[:, :, 0])  # (bn, sq)
    dsink_rows = -(p_sink * delta[:, :, 0]).sum(-1)  # (bn,)
    return jnp.zeros_like(sinks).at[:, 0, 0].set(dsink_rows)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jnp.ndarray,  # (B, Sq, N, D)
    k: jnp.ndarray,  # (B, Skv, K, D)
    v: jnp.ndarray,  # (B, Skv, K, D)
    *,
    causal: bool = True,
    segment_ids_q: jnp.ndarray | None = None,  # (B, Sq)
    segment_ids_kv: jnp.ndarray | None = None,  # (B, Skv)
    sliding_window: int | None = None,
    softmax_scale: float | None = None,
    logit_soft_cap: float | None = None,
    sinks: jnp.ndarray | None = None,  # (N,) per-head sink logits (gpt-oss)
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Flash attention over (batch, seq, heads, head_dim); returns same shape as q."""
    b, sq, n, d = q.shape
    _, skv, nk, _ = k.shape
    if softmax_scale is None:
        softmax_scale = d**-0.5
    groups = n // nk
    # measured on v5e at (B4, S2048, H32/KV8, d64): (1024, 1024) beats (512,
    # 1024) by ~2% end-to-end and (128, 128) by ~2x fwd+bwd; (1024, 2048)+ blows
    # scoped VMEM. Fall back to the largest power-of-two block that divides the
    # sequence so the grid stays exact
    def _pick(seq, target):
        # largest power-of-two block <= target that divides seq (>= 8); if none
        # divides, return 8 so the kernel's divisibility check raises clearly
        b = 1 << (max(min(target, seq), 8).bit_length() - 1)
        while b > 8 and seq % b:
            b //= 2
        return b

    # the kv tiles, the score tile's masks and the accumulator grow with the head: at
    # head_dim 256, (1024, 1024) asks 17.0 MB of the 16 MB scoped VMEM (the chip's
    # compiler, Qwen3-Next's 16q/2kv x 256 at seq 4096), so a head wider than 128
    # takes a kv block that keeps block_k x head_dim where it was measured
    block_q = _pick(sq, block_q or 1024)
    block_k = _pick(skv, block_k or 1024 // max(d // 128, 1))
    if sq % block_q or skv % block_k:
        raise ValueError(
            f"flash_attention needs seq lengths divisible by block sizes: "
            f"sq={sq}%{block_q}, skv={skv}%{block_k}"
        )

    # the custom VJP folds (B, S, H, D) into the kernels' (B*H, S, D) rows itself; kv
    # heads stay un-repeated (GQA via index maps)
    seg_q = seg_kv = None
    if segment_ids_q is not None or segment_ids_kv is not None:
        sq_ids = segment_ids_q if segment_ids_q is not None else segment_ids_kv
        skv_ids = segment_ids_kv if segment_ids_kv is not None else segment_ids_q
        seg_q = _q_lanes(jnp.repeat(sq_ids.astype(jnp.int32), n, axis=0))
        seg_kv = _kv_sublanes(jnp.repeat(skv_ids.astype(jnp.int32), nk, axis=0))
    sinks_rows = None
    if sinks is not None:
        # per-head scalar -> one (1, LANES) row per (batch, head) grid row; the
        # kernel reads lane 0 and AD sums the tile/broadcast back to (N,)
        sinks_rows = jnp.broadcast_to(
            jnp.tile(sinks.astype(jnp.float32), b)[:, None, None], (b * n, 1, LANES)
        )

    warr = None
    if sliding_window is not None:
        # (1,) SMEM scalar: keeps traced per-layer windows (gpt-oss/gemma layer
        # scans) kernel-eligible instead of forcing the XLA fallback
        warr = jnp.asarray(sliding_window, jnp.int32).reshape(1)
    return _flash(q, k, v, seg_q, seg_kv, sinks_rows, warr, softmax_scale, causal,
                  logit_soft_cap, block_q, block_k, groups, interpret)
