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
- ``linear_ce_fwd``: grid (token_blocks, vocab_blocks), vocab innermost. Per
  step one (block_n, block_v) logits tile = h_tile @ w_tile on the MXU; an
  online logsumexp (m, l) accumulates in VMEM scratch across the vocab sweep.
  Also emits per-(row, vocab-block) maxima for the backward's gradient filter.
- ``linear_ce_bwd``: ONE kernel, manual VJP, recompute-based. Grid
  (vocab_blocks, token_blocks), tokens innermost. Per step the logits tile is
  rebuilt once, ``dl = softmax * dz`` formed from the saved per-token z, and
  both gradients fed from it: ``dW[:, v] += h_tile^T @ dl`` accumulates in an
  f32 VMEM tile over the token sweep and is written once in ``w.dtype``;
  ``dH[t] += dl @ w_tile^T`` accumulates in an f32 (N, E) HBM buffer that the
  kernel reads and writes a block at a time with its own DMAs (the read hides
  under the third GEMM, the write under the next step's first two; a block's
  write is waited for before any later read is issued, so the order of a
  write-back against the block's next fetch is the kernel's, not the
  pipeline's). One XLA op casts dH to ``h.dtype`` after the call. Four GEMMs
  for the three the loss requires: z must be complete before any dl, so the
  logits are rebuilt once while they never reach HBM.
- Vocab-block gradient filtering (cut-cross-entropy's argument): blocks whose
  entire softmax tile underflows ``filter_eps`` carry no gradient and skip
  their step (GEMMs and DMAs) — the skip decision is precomputed in XLA from
  the forward's block maxima and read as an SMEM scalar (scalar prefetch),
  costing nothing per grid step. A backward block is kept when any forward
  block that overlaps it is significant: a conservative superset. Residuals
  are (h, w, z, bmax): O(N * V / block_v) floats, never O(N * V).
- Tiles do not depend on the vocabulary's divisors: the vocab grid is
  ``cdiv(V, block_v)`` and the LAST block computes only its ``V % block_v``
  real columns, a static lane-aligned slice of the tile (no elementwise mask,
  no padded copy of the head; every other step is untouched). V must still be
  a multiple of 128 (one lane group) and E of 128; other shapes stay on the
  XLA scan in ``ops/losses.py``.
- ``pick_blocks`` / ``pick_bwd_blocks`` take the widest vocabulary tile that
  fits the VMEM model, then the tallest token tile under the caps the chip
  showed; the VMEM they may use is a share of what the device has. See
  ``_pick``.
- Both backward grid axes are sequential (the HBM dH accumulation crosses
  vocabulary blocks), so on a part with two TensorCores a device (v4, v5p) the
  backward runs on one of them; the forward's token axis stays "parallel". Only
  the v5e has been timed.

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

__all__ = ["fused_logsumexp", "gold_logits", "pick_blocks", "pick_bwd_blocks"]

NEG_INF = -1e30
LANES = 128

_BLOCK_N = (1024, 512, 256, 128, 64, 32, 16, 8)
_BLOCK_V = (2048, 1024, 512, 256, 128)


def _vmem_limit() -> int:
    """Scoped VMEM these calls ask Mosaic for: 5/8 of what the device's core has
    (80 MiB of a v5e's or v6e's 128, 40 of a v5p's 64, 10 of a v4's 16 — under
    Mosaic's 16 MiB default there, so a part with little VMEM gets small forward
    tiles and the XLA backward, not a refused compile). With no TPU under the
    process (interpret mode; a compile for a described chip from a CPU host)
    there is nothing to ask, and the v5e's figure is taken."""
    try:
        capacity = pltpu.get_tpu_info().vmem_capacity_bytes
    except ValueError:
        capacity = 128 * 2**20
    return capacity * 5 // 8


def _vmem_budget() -> int:
    """What the model in _pick may use of the limit, 5/6 (70 MB of 80 MiB):
    compiled for the v5e, the kernels need 0.96-1.08 of the model (limit lowered
    until Mosaic refused: bwd 256x2048 at E=2048 modeled 60.9 MB took 63 MiB,
    256x1024 at E=4096 modeled 65.1 MB took 67)."""
    return _vmem_limit() * 5 // 6


def pick_blocks(e: int, v: int, n: int | None = None) -> tuple[int, int] | None:
    """Forward (block_n, block_v) for ``n`` tokens (None: a long batch), or None
    where no tile fits or the shape is not lane-aligned (E, V multiples of 128).
    Callers pad the token dim to a block_n multiple; block_v need not divide V."""
    return _pick(e, v, n, bwd=False)


def pick_bwd_blocks(e: int, v: int, n: int | None = None) -> tuple[int, int] | None:
    """Backward (block_n, block_v), or None if no tile fits: the f32 dW
    accumulator, its output buffers and the f32 dH block join the VMEM model,
    so wide models (E >= 12288) tile forward only and take the XLA backward."""
    return _pick(e, v, n, bwd=True)


def _pick(e, v, n, bwd):
    """The widest vocabulary tile the VMEM model admits, then the tallest token
    tile under the caps below. Nothing here asks whether block_v divides V.

    Why width first: the backward moves w and dW once whatever the tile, but h
    and the f32 dH block (read + write) once a VOCABULARY block, so only block_v
    saves traffic; the forward's per-step row reductions, column stores and block
    maxima shrink with the count of vocabulary blocks. Why the caps: the chip
    showed them, and no arithmetic of FLOPs and bytes predicts them (a model of
    MXU and HBM time ranked 1024x1024 first at E=6144, where it takes 103.7 ms
    and 256x2048 takes 82.1).

    Measured on a v5e, bf16, N=8192, V=151936 (128256 where E is starred), ms a
    call, best of five (PR 26). In brackets the GEMMs' time at 197 TFLOP/s; plain
    XLA matmuls of the cell's shape run at 184-188, its three backward products
    in 82.1-82.3 ms.
    Forward, one GEMM:
      E=1024 (12.9): 512x2048 15.8, 256x2048 16.2, 1024x1024 16.6
      E=1536 (19.4): 512x2048 22.1, 256x2048 22.8
      E=2048 (25.9): 512x2048 28.8, 256x2048 29.4, 1024x1024 29.7, 512x1024 30.9,
        1024x512 32.2, 512x512 33.3, 1024x2048 34.0, 1024x1536 41.5 (the parent's
        512x128, inside a step: 57.3)
      E=2560 (32.4): 512x2048 35.2, 256x2048 36.0
      E=3072* (32.8): 256x2048 35.9, 1024x1024 36.2, 512x2048 48.4
      E=4096 (51.8): 256x2048 55.6, 512x2048 66.0, 1024x1024 66.8
      E=6144 (77.6): 256x2048 82.1, 1024x1024 103.7, 512x1024 114.6
      E=8192* (87.4): 256x1024 93.1, 512x1024 111.0, 1024x512 120.1
      E=12288* (131): 256x1024 137.4, 512x512 173.7
    Backward, three GEMMs:
      E=1024 (38.8): 512x2048 41.8, 256x2048 42.6, 128x2048 43.5
      E=1536 (58.2): 256x2048 62.5, 512x2048 74.0
      E=2048 (77.6): 256x2048 82.9 in one call and 88.5, 88.7 in two others,
        256x1024 86.3, 128x2048 90.3, 1024x1024 104.7, 512x1536 113.2, 512x1024
        118.1, 1024x512 125.9, 512x768 129.6, 256x1536 129.9 (the parent's two
        kernels at 512x128, inside a step: 112.4)
      E=2560 (97.0): 128x2048 112.2, 256x1024 121.0
      E=3072* (98.4): 256x1024 122.2, 128x1024 129.5, 64x1024 174.1
      E=4096 (155): 128x1024 202.3, 256x512 224.3, 128x512 233.2, 256x1024 260.0,
        64x1024 270.0 (XLA fallback 609.7)
      E=8192* (262): 128x512 379.9, 64x512 484.0 (XLA fallback 636.7)
    The caps these readings give:
    - widths are powers of two (others run 30-50% slower);
    - a logits tile holds at most 2**20 elements;
    - forward: block_n * E <= 2**20 (an h tile of 2 MiB), but 256 rows where they
      fit: taller tiles fall to 68-79% of peak from E=3072 on, and 256 rows,
      though on the HBM ridge (256 FLOP a byte of w against the chip's 240), hold
      91-95% of peak at every E >= 3072;
    - backward: at most 256 rows and block_n * E < 2**20 (a dH block under 4
      MiB), but 128 rows where they fit: taller tiles stop hiding the dH block's
      DMAs (512 rows gain 1.7% at E=1024 and lose 18% at E=1536), and 64 rows
      leave the dW product's contraction half an MXU deep."""
    if e % LANES or v % LANES:
        return None
    n = 8192 if n is None else n
    # no tile taller than the batch (rounded up to a power of two, 16 at least:
    # one bf16 sublane tile), none wider than the vocabulary
    max_bn = max(16, 1 << (n - 1).bit_length())
    if bwd:
        max_bn = min(max_bn, 256, max(128, (2**20 - 1) // e))
        # backward tiles narrower than these are the 128-column regime this kernel
        # left (MXU at 45%): a shape that fits nothing wider takes the XLA backward
        min_bn, min_bv = min(128, max_bn), min(512, v)
    else:
        max_bn = min(max_bn, max(256, 2**20 // e))
        # w is streamed once a token block, so block_n is the forward's FLOPs a
        # byte of w: below 256 rows the HBM sets the pace, whatever the width
        min_bn, min_bv = min(256, max_bn), LANES
    for bv in _BLOCK_V:
        for bn in _BLOCK_N:
            if not (min_bn <= bn <= max_bn and min_bv <= bv <= v and bn * bv <= 2**20):
                continue
            used = 2 * bn * e * 2 + 2 * e * bv * 2 + 2 * bn * bv * 4  # h, w (double-buffered), logits + exp
            if bwd:
                # dW accumulator f32 + its double-buffered output, dH block + its partial product f32
                used += e * bv * 4 + 2 * e * bv * 2 + 2 * bn * e * 4
            if used <= _vmem_budget():
                return bn, bv
    return None


def _fwd_kernel(h_ref, w_ref, z_ref, bmax_ref, m_ref, l_ref, *, num_v, tail):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def step(cols):
        s = jax.lax.dot_general(
            h_ref[...], w_ref[:, :cols], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (bn, cols) logits tile — the only place logits ever exist

        row_max = s.max(-1, keepdims=True)  # (bn, 1)
        # per-(row, vocab-block) max, consumed by the backward's gradient filter
        bmax_ref[0, 0, :] = row_max[:, 0]

        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, row_max)
        l_new = l_ref[:, :1] * jnp.exp(m_prev - m_new) + jnp.exp(s - m_new).sum(-1, keepdims=True)
        # narrow column stores: broadcasting across all LANES costs ~20% of the step
        m_ref[:, :1] = m_new
        l_ref[:, :1] = l_new

    _sweep_step(step, vi, num_v, w_ref.shape[1], tail)

    @pl.when(vi == num_v - 1)
    def _finalize():
        l = l_ref[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        z = jnp.where(l == 0.0, NEG_INF, m_ref[:, :1] + jnp.log(safe_l))
        z_ref[:] = jnp.broadcast_to(z, z_ref.shape)


def _sweep_step(step, vi, num_v, block_v, tail, keep=None):
    """Run ``step(cols)`` for vocab block ``vi`` (where ``keep``, if given): every
    block at full width but the last, which holds only ``tail`` real columns when
    V % block_v != 0 (the rest of its tile lies beyond the array and is never read)."""
    if tail == block_v:
        widths = [(block_v, keep)]
    else:
        last = vi == num_v - 1
        widths = [(block_v, ~last), (tail, last)]
        if keep is not None:
            widths = [(cols, keep & where) for cols, where in widths]
    for cols, where in widths:
        if where is None:
            step(cols)
        else:
            pl.when(where)(functools.partial(step, cols))


def _bwd_kernel(sig_ref, h_ref, w_ref, z_ref, dz_ref, dh_in_ref, dw_ref, dh_ref,
                dw_acc, dh_buf, read_sem, write_sem, pending, *, num_t, num_v, tail):
    del dh_in_ref  # the zeros dh_ref starts from (aliased); never read as an input
    vi, ti = pl.program_id(0), pl.program_id(1)
    block_n, block_v = h_ref.shape[0], w_ref.shape[1]
    rows = pl.ds(pl.multiple_of(ti * block_n, block_n), block_n)

    def write_back():  # a wait needs only the shape: any block's descriptor does
        return pltpu.make_async_copy(dh_buf, dh_ref.at[rows], write_sem)

    @pl.when((vi == 0) & (ti == 0))
    def _first():
        pending[0] = 0

    @pl.when(ti == 0)
    def _init():
        dw_acc[...] = jnp.zeros_like(dw_acc)

    def step(cols):
        h, w = h_ref[...], w_ref[:, :cols]
        s = jax.lax.dot_general(
            h, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )  # the logits tile, rebuilt once for both gradients
        dl = (jnp.exp(s - z_ref[:, :1]) * dz_ref[:, :1]).astype(w.dtype)
        dw_acc[:, :cols] = dw_acc[:, :cols] + jax.lax.dot_general(
            h, dl, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )  # (E, cols)

        # dH[t] += dl @ w^T, read-modify-write on the f32 HBM buffer. The last
        # step's write has had two GEMMs to land; nothing is read before it has.
        @pl.when(pending[0] == 1)
        def _landed():
            write_back().wait()

        read = pltpu.make_async_copy(dh_ref.at[rows], dh_buf, read_sem)
        read.start()
        part = jax.lax.dot_general(
            dl, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        )  # (bn, E)
        read.wait()
        dh_buf[...] = dh_buf[...] + part
        write_back().start()
        pending[0] = 1

    # significance precomputed in XLA from the forward's block maxima; an SMEM
    # scalar read costs nothing vs a per-step VPU reduction over the tile
    _sweep_step(step, vi, num_v, block_v, tail, keep=sig_ref[ti, vi] != 0)

    @pl.when(ti == num_t - 1)
    def _finalize():
        dw_ref[...] = dw_acc[...].astype(dw_ref.dtype)

    @pl.when((vi == num_v - 1) & (ti == num_t - 1) & (pending[0] == 1))
    def _drain():
        write_back().wait()


def _block_significance(bmax, z, block_v_fwd, num_t, block_n, num_v, block_v, log_eps):
    """(num_t, num_v) int32: which backward (token, vocab) blocks carry gradient.

    A block matters when some row's block-max logit is within log_eps of its
    logsumexp — otherwise its whole softmax tile is below filter_eps and
    contributes nothing to dH/dW (cut-cross-entropy's vocab filter,
    loss/linear_ce.py:119). The exact gold term lives in the XLA gather path,
    so label location is irrelevant here. ``bmax`` is at the forward's vocab
    granularity; a backward block is kept when any forward block overlapping
    its columns is significant (a conservative superset). log_eps None -> all
    blocks run."""
    if log_eps is None:
        return jnp.ones((num_t, num_v), jnp.int32)
    sig_rows = (bmax[:, 0, :] - z[None, :]) > log_eps  # (num_v_fwd, n)
    sig = sig_rows.reshape(sig_rows.shape[0], num_t, block_n).any(-1)  # (num_v_fwd, T)
    lo = np.arange(num_v) * block_v  # backward block j covers columns [lo, lo + block_v)
    fwd_lo = np.arange(sig.shape[0]) * block_v_fwd
    overlap = (fwd_lo[None, :] < lo[:, None] + block_v) & (fwd_lo[None, :] + block_v_fwd > lo[:, None])
    return (jnp.asarray(overlap, jnp.int32) @ sig.astype(jnp.int32) > 0).T.astype(jnp.int32)


def _row_vec(x: jnp.ndarray) -> jnp.ndarray:
    """(N,) -> (N, LANES) broadcast, the Mosaic-friendly per-row layout."""
    return jnp.broadcast_to(x[:, None], (x.shape[0], LANES))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def fused_logsumexp(h, w, blocks, bwd_blocks, interpret=False, filter_eps=1e-7):
    """Per-token ``logsumexp(h @ w)`` without materializing the logits.

    h (N, E), w (E, V) -> z (N,) f32; N a multiple of both block_n. ``blocks``
    and ``bwd_blocks`` are the (block_n, block_v) of the forward and of the
    backward kernel (``bwd_blocks`` None: the blockwise XLA backward).
    Differentiable w.r.t. h and w via the manual recompute VJP; ``filter_eps``
    enables backward vocab-block gradient filtering (None disables for exact
    gradients).
    """
    z, _ = _fwd_call(h, w, blocks, interpret)
    return z


def _tail(v: int, block_v: int) -> int:
    """Real columns of the last vocab block."""
    return v - (pl.cdiv(v, block_v) - 1) * block_v


def _fwd_call(h, w, blocks, interpret):
    n, e = h.shape
    v = w.shape[1]
    block_n, block_v = blocks
    num_t, num_v = n // block_n, pl.cdiv(v, block_v)
    z, bmax = pl.pallas_call(
        functools.partial(_fwd_kernel, num_v=num_v, tail=_tail(v, block_v)),
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
            vmem_limit_bytes=_vmem_limit(),
        ),
        interpret=interpret,
        name="linear_ce_fwd",
    )(h, w)
    return z[:, 0], bmax


def _fwd_rule(h, w, blocks, bwd_blocks, interpret, filter_eps):
    z, bmax = _fwd_call(h, w, blocks, interpret)
    return z, (h, w, z, bmax)


def _bwd_xla_fallback(h, w, z, dz, block_v):
    """Blockwise-vocab XLA backward for shapes whose bwd tiles don't fit VMEM.

    Same math as the kernel (softmax recompute against the saved logsumexp),
    logits exist one (N, block_v) f32 block at a time in HBM instead of VMEM.
    ``block_v`` must divide V."""
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


def _bwd_call(h, w, z, dz, sig, blocks, interpret):
    """dH (f32) and dW (w.dtype) of ``sum(z * dz)`` from the one backward kernel."""
    n, e = h.shape
    v = w.shape[1]
    block_n, block_v = blocks
    num_t, num_v = n // block_n, pl.cdiv(v, block_v)
    z2, dz2 = _row_vec(z), _row_vec(dz.astype(jnp.float32))
    dh0 = jnp.zeros((n, e), jnp.float32)
    row = pl.BlockSpec((block_n, LANES), lambda v_, t, s_: (t, 0))
    dw, dh = pl.pallas_call(
        functools.partial(_bwd_kernel, num_t=num_t, num_v=num_v, tail=_tail(v, block_v)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(num_v, num_t),
            in_specs=[
                pl.BlockSpec((block_n, e), lambda v_, t, s_: (t, 0)),
                pl.BlockSpec((e, block_v), lambda v_, t, s_: (0, v_)),
                row, row,
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[
                pl.BlockSpec((e, block_v), lambda v_, t, s_: (0, v_)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            scratch_shapes=[
                pltpu.VMEM((e, block_v), jnp.float32),
                pltpu.VMEM((block_n, e), jnp.float32),
                pltpu.SemaphoreType.DMA(()),
                pltpu.SemaphoreType.DMA(()),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=[
            out_struct((e, v), w.dtype, sig, h, w, z2, dz2),
            out_struct((n, e), jnp.float32, sig, h, w, z2, dz2),
        ],
        # operand indices count the scalar-prefetch argument: dh0 is the sixth
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(),
        ),
        interpret=interpret,
        name="linear_ce_bwd",
    )(sig, h, w, z2, dz2, dh0)
    return dh, dw


def _bwd_rule(blocks, bwd_blocks, interpret, filter_eps, res, dz):
    h, w, z, bmax = res
    n, v = h.shape[0], w.shape[1]
    if bwd_blocks is None:
        # V is a multiple of 128 wherever the forward kernel ran
        return _bwd_xla_fallback(h, w, z, dz, next(bv for bv in _BLOCK_V if v % bv == 0))
    block_n, block_v = bwd_blocks
    log_eps = None if filter_eps is None else float(np.log(filter_eps))
    sig = _block_significance(
        bmax, z, blocks[1], n // block_n, block_n, pl.cdiv(v, block_v), block_v, log_eps)
    dh, dw = _bwd_call(h, w, z, dz, sig, bwd_blocks, interpret)
    return dh.astype(h.dtype), dw


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
