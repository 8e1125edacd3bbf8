"""Fused linear + cross-entropy Pallas kernels for TPU.

The (tokens, vocab) logits tensor is the HBM wall of large-vocab training: at
Llama-3 scale one microbatch of logits is tokens x 128k x 4B. The reference
escapes it with cut-cross-entropy (components/loss/linear_ce.py:119) and a
Triton TP cross-entropy (components/loss/triton/te_cross_entropy.py:49); this is
the TPU equivalent: logits exist only as a (block_n, block_v) VMEM tile inside
the kernel, never in HBM.

Design (cut-cross-entropy, reshaped for the MXU):

- The loss splits as ``loss = z - gold`` with ``z = logsumexp(h @ w)`` and
  ``gold = (h @ w)[label]``. Only z needs the full vocab sweep; gold is a
  batched vector dot against the gathered label columns, computed in plain XLA
  (with automatic AD — its dW is an exact scatter-add). The kernels therefore
  never see labels at all.
- forward kernel: grid (token_blocks, vocab_blocks), vocab innermost. Per step
  one (block_n, block_v) logits tile = h_tile @ w_tile on the MXU; an online
  logsumexp (m, l) accumulates in VMEM scratch across the vocab sweep. Also
  emits per-(row, vocab-block) maxima for the backward's gradient filter.
- backward: manual VJP, recompute-based. dlogits = softmax * dz is rebuilt
  tile-by-tile from the saved per-token z; one kernel accumulates
  dH = dlogits @ W^T over vocab blocks, a second accumulates dW = H^T @ dlogits
  over token blocks. Vocab-block gradient filtering (cut-cross-entropy's
  argument): blocks whose entire softmax tile underflows ``filter_eps`` carry
  no gradient and skip their matmuls — the skip decision is precomputed in XLA
  from the forward's block maxima and read as an SMEM scalar (scalar prefetch),
  costing nothing per grid step. Residuals are (h, w, z, bmax):
  O(N * V / block_v) bits, never O(N * V) floats.

Vocab sharding contract: pass ``labels`` already *localized* (label - shard
offset); out-of-shard labels fall outside [0, V_local) and contribute nothing,
so ``psum(gold)`` and a logsumexp-combine of ``z`` across the vocab axis
reconstruct the global loss exactly (te_cross_entropy.py:113 does the same
reduction in torch collectives).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from automodel_tpu.ops.kernels import out_struct

__all__ = ["fused_logsumexp", "gold_logits", "pick_blocks"]

NEG_INF = -1e30
LANES = 128


def pick_blocks(e: int, v: int) -> tuple[int, int] | None:
    """Largest (block_n, block_v) fitting the ~16MB VMEM budget, or None.

    Bigger tiles amortize per-step overhead (the grid is num_t * num_v steps) and
    feed the MXU larger matmuls; the budget covers double-buffered h/w tiles, the
    f32 logits tile, and the largest backward accumulator. Callers pad the token
    dim to a block_n multiple; the vocab must divide one of the candidates.
    Empirically on v5e (E=2048, V=128k): (256, 768) runs the forward at raw
    matmul-sweep speed."""
    if e % 128 != 0:
        return None
    return _pick(e, v, acc=False)


def pick_bwd_blocks(e: int, v: int, bv_fwd: int, n: int | None) -> tuple[int, int] | None:
    """Backward blocks, or None if no tile fits: the f32 accumulator joins the
    VMEM budget, and block_v must divide the forward's (so the forward's
    per-block maxima pool exactly onto backward blocks for the gradient filter).
    ``n=None`` skips the token-divisibility constraint (feasibility probe)."""
    return _pick(e, v, acc=True, bv_divides=bv_fwd, n=n)


def _pick(e, v, acc, bv_divides=None, n=None):
    # Mosaic's actual scoped-vmem use runs ~30-40% above this model (extra output
    # buffers, alignment); 9.8MB modeled keeps the compiled kernels under the
    # 16MB scoped limit (measured: modeled 12.3MB compiled to 16.97MB -> OOM)
    budget = 9_800_000
    best = None
    for bn in (512, 256, 128, 64, 32, 16, 8):
        for bv in (1024, 768, 512, 384, 256, 128):
            if v % bv or (bv_divides is not None and bv_divides % bv):
                continue
            if n is not None and n % bn:
                continue
            used = (
                2 * bn * e * 2        # h tile, double-buffered
                + 2 * e * bv * 2      # w tile, double-buffered
                + bn * bv * 4         # logits tile
                + (max(bn * e, e * bv) * 4 if acc else 0)  # f32 accumulator
            )
            # prefer the largest tile; tie-break toward wider vocab tiles (fewer,
            # larger MXU steps measured faster than tall-token tiles on v5e)
            if used <= budget and (
                best is None
                or bn * bv > best[0] * best[1]
                or (bn * bv == best[0] * best[1] and bv > best[1])
            ):
                best = (bn, bv)
    return best


def _fwd_kernel(h_ref, w_ref, z_ref, bmax_ref, m_ref, l_ref, *, num_v):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    s = jax.lax.dot_general(
        h_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (bn, bv) logits tile — the only place logits ever exist

    row_max = s.max(-1, keepdims=True)  # (bn, 1)
    # per-(row, vocab-block) max, consumed by the backward's gradient filter
    bmax_ref[0, 0, :] = row_max[:, 0]

    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, row_max)
    l_new = l_ref[:, :1] * jnp.exp(m_prev - m_new) + jnp.exp(s - m_new).sum(-1, keepdims=True)
    # narrow column stores: broadcasting across all LANES costs ~20% of the step
    m_ref[:, :1] = m_new
    l_ref[:, :1] = l_new

    @pl.when(vi == num_v - 1)
    def _finalize():
        l = l_ref[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        z = jnp.where(l == 0.0, NEG_INF, m_ref[:, :1] + jnp.log(safe_l))
        z_ref[:] = jnp.broadcast_to(z, z_ref.shape)


def _bwd_dh_kernel(sig_ref, h_ref, w_ref, z_ref, dz_ref, dh_ref, acc_ref, *, num_v):
    ti, vi = pl.program_id(0), pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # significance precomputed in XLA from the forward's block maxima; an SMEM
    # scalar read costs nothing vs a per-step VPU reduction over the tile
    @pl.when(sig_ref[ti, vi] != 0)
    def _compute():
        s = jax.lax.dot_general(
            h_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dl = jnp.exp(s - z_ref[:, :1]) * dz_ref[:, :1]
        acc_ref[:] = acc_ref[:] + jax.lax.dot_general(
            dl.astype(w_ref.dtype), w_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (bn, E)

    @pl.when(vi == num_v - 1)
    def _finalize():
        dh_ref[...] = acc_ref[:].astype(dh_ref.dtype)


def _bwd_dw_kernel(sig_ref, h_ref, w_ref, z_ref, dz_ref, dw_ref, acc_ref, *, num_n):
    vi, ti = pl.program_id(0), pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(sig_ref[ti, vi] != 0)
    def _compute():
        s = jax.lax.dot_general(
            h_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dl = jnp.exp(s - z_ref[:, :1]) * dz_ref[:, :1]
        acc_ref[:] = acc_ref[:] + jax.lax.dot_general(
            h_ref[...], dl.astype(h_ref.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (E, bv)

    @pl.when(ti == num_n - 1)
    def _finalize():
        dw_ref[...] = acc_ref[:].astype(dw_ref.dtype)


def _block_significance(bmax, z, num_t, num_v, block_n, vb_ratio, log_eps):
    """(num_t, num_v) int32: which backward (token, vocab) blocks carry gradient.

    A block matters when some row's block-max logit is within log_eps of its
    logsumexp — otherwise its whole softmax tile is below filter_eps and
    contributes nothing to dH/dW (cut-cross-entropy's vocab filter,
    loss/linear_ce.py:119). The exact gold term lives in the XLA gather path,
    so label location is irrelevant here. ``bmax`` is at the forward's vocab
    granularity; each forward block maps onto ``vb_ratio`` backward blocks (a
    conservative superset). log_eps None -> all blocks run."""
    if log_eps is None:
        return jnp.ones((num_t, num_v), jnp.int32)
    sig_rows = (bmax[:, 0, :] - z[None, :]) > log_eps  # (num_v_fwd, n)
    sig = sig_rows.reshape(sig_rows.shape[0], num_t, block_n).any(-1)  # (num_v_fwd, T)
    return jnp.repeat(sig, vb_ratio, axis=0).T.astype(jnp.int32)  # (T, num_v)


def _row_vec(x: jnp.ndarray) -> jnp.ndarray:
    """(N,) -> (N, LANES) broadcast, the Mosaic-friendly per-row layout."""
    return jnp.broadcast_to(x[:, None], (x.shape[0], LANES))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def fused_logsumexp(h, w, block_n, block_v, interpret=False, filter_eps=1e-7):
    """Per-token ``logsumexp(h @ w)`` without materializing the logits.

    h (N, E), w (E, V) -> z (N,) f32. Differentiable w.r.t. h and w via the
    manual recompute VJP; ``filter_eps`` enables backward vocab-block gradient
    filtering (None disables for exact gradients).
    """
    z, _ = _fwd_call(h, w, block_n, block_v, interpret)
    return z


def _fwd_call(h, w, block_n, block_v, interpret):
    n, e = h.shape
    v = w.shape[1]
    num_t, num_v = n // block_n, v // block_v
    z, bmax = pl.pallas_call(
        functools.partial(_fwd_kernel, num_v=num_v),
        grid=(num_t, num_v),
        in_specs=[
            pl.BlockSpec((block_n, e), lambda t, v_: (t, 0)),
            pl.BlockSpec((e, block_v), lambda t, v_: (0, v_)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, LANES), lambda t, v_: (t, 0)),
            pl.BlockSpec((1, 1, block_n), lambda t, v_: (v_, 0, t)),
        ],
        out_shape=[
            out_struct((n, LANES), jnp.float32, h, w),
            out_struct((num_v, 1, n), jnp.float32, h, w),
        ],
        scratch_shapes=[pltpu.VMEM((block_n, LANES), jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="linear_ce_fwd",
    )(h, w)
    return z[:, 0], bmax


def _fwd_rule(h, w, block_n, block_v, interpret, filter_eps):
    z, bmax = _fwd_call(h, w, block_n, block_v, interpret)
    return z, (h, w, z, bmax)


def _bwd_xla_fallback(h, w, z, dz, block_v):
    """Blockwise-vocab XLA backward for shapes whose bwd tiles don't fit VMEM.

    Same math as the kernels (softmax recompute against the saved logsumexp),
    logits exist one (N, block_v) f32 block at a time in HBM instead of VMEM."""
    n, e = h.shape
    v = w.shape[1]
    num_v = v // block_v
    h32 = h.astype(jnp.float32)
    dz32 = dz.astype(jnp.float32)
    w_blocks = jnp.moveaxis(w.reshape(e, num_v, block_v), 1, 0)  # (num_v, E, bv)

    def body(dh_acc, wb):
        s = h32 @ wb.astype(jnp.float32)  # (N, bv)
        p = jnp.exp(s - z[:, None]) * dz32[:, None]
        dh_acc = dh_acc + p @ wb.astype(jnp.float32).T
        # cast per block: each dw block is fully accumulated in f32 here, so
        # casting now is precision-free and keeps the stacked (num_v, E, bv)
        # buffer in w.dtype — an f32 stack at DSv3 scale (E=12k, V=128k) would
        # be a 6.4GB transient in the exact path meant to dodge the memory wall
        dw_b = (h32.T @ p).astype(w.dtype)  # (E, bv)
        return dh_acc, dw_b

    dh, dw_blocks = jax.lax.scan(body, jnp.zeros((n, e), jnp.float32), w_blocks)
    dw = jnp.moveaxis(dw_blocks, 0, 1).reshape(e, v)
    return dh.astype(h.dtype), dw


def _bwd_rule(block_n, block_v, interpret, filter_eps, res, dz):
    h, w, z, bmax = res
    n, e = h.shape
    v = w.shape[1]
    bwd_blocks = pick_bwd_blocks(e, v, block_v, n)  # fwd blocks shadowed
    if bwd_blocks is None:
        return _bwd_xla_fallback(h, w, z, dz, block_v)
    block_n, block_v = bwd_blocks
    vb_ratio = (v // block_v) // bmax.shape[0]  # bwd blocks per fwd block
    num_t, num_v = n // block_n, v // block_v
    z2 = _row_vec(z)
    dz2 = _row_vec(dz.astype(jnp.float32))
    log_eps = None if filter_eps is None else float(np.log(filter_eps))
    sig = _block_significance(bmax, z, num_t, num_v, block_n, vb_ratio, log_eps)

    row = pl.BlockSpec((block_n, LANES), lambda a, b, s_: (a, 0))
    dh = pl.pallas_call(
        functools.partial(_bwd_dh_kernel, num_v=num_v),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(num_t, num_v),
            in_specs=[
                pl.BlockSpec((block_n, e), lambda t, v_, s_: (t, 0)),
                pl.BlockSpec((e, block_v), lambda t, v_, s_: (0, v_)),
                row, row,
            ],
            out_specs=pl.BlockSpec((block_n, e), lambda t, v_, s_: (t, 0)),
            scratch_shapes=[pltpu.VMEM((block_n, e), jnp.float32)],
        ),
        out_shape=out_struct((n, e), h.dtype, sig, h, w, z2, dz2),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="linear_ce_bwd_dh",
    )(sig, h, w, z2, dz2)

    row_vt = pl.BlockSpec((block_n, LANES), lambda v_, t, s_: (t, 0))
    dw = pl.pallas_call(
        functools.partial(_bwd_dw_kernel, num_n=num_t),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(num_v, num_t),
            in_specs=[
                pl.BlockSpec((block_n, e), lambda v_, t, s_: (t, 0)),
                pl.BlockSpec((e, block_v), lambda v_, t, s_: (0, v_)),
                row_vt, row_vt,
            ],
            out_specs=pl.BlockSpec((e, block_v), lambda v_, t, s_: (0, v_)),
            scratch_shapes=[pltpu.VMEM((e, block_v), jnp.float32)],
        ),
        out_shape=out_struct((e, v), w.dtype, sig, h, w, z2, dz2),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="linear_ce_bwd_dw",
    )(sig, h, w, z2, dz2)

    return dh, dw


fused_logsumexp.defvjp(_fwd_rule, _bwd_rule)


def gold_logits(h: jnp.ndarray, w: jnp.ndarray, local_labels: jnp.ndarray) -> jnp.ndarray:
    """logit at the (localized) label column: a batched vector dot in plain XLA.

    Out-of-shard / ignored labels (outside [0, V_local)) return 0. AD gives the
    exact gradient: dW is a scatter-add of h rows into the label columns, dH a
    gather of w columns — no kernel needed for the one-hot term."""
    v = w.shape[1]
    in_shard = (local_labels >= 0) & (local_labels < v)
    safe = jnp.clip(local_labels, 0, v - 1)
    cols = jnp.take(w, safe, axis=1)  # (E, N)
    g = jnp.einsum("ne,en->n", h.astype(jnp.float32), cols.astype(jnp.float32))
    return jnp.where(in_shard, g, 0.0)
