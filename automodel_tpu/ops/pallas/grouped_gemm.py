"""Blocked grouped (ragged) expert GEMM Pallas kernels for TPU.

The MoE hot path multiplies a sorted-by-expert token matrix against a stack of
per-expert weights: row blocks are contiguous per expert, but expert boundaries
fall anywhere inside a block. ``jax.lax.ragged_dot`` handles this in XLA; this
module is the hand-scheduled equivalent (the megablocks/gmm analogue the
reference reaches via torch grouped_gemm / DeepEP+gmm / TE GroupedLinear,
components/moe/experts.py:158,478,661) with the schedule under our control:

- **Tile schedule, not one-hot masking.** A static-length tile list is
  precomputed in XLA from ``group_sizes``: one (row-block, expert) tile per
  overlap, so each grid step runs exactly one MXU matmul against exactly one
  expert's weights. Rows of other experts inside a boundary block are zero-
  masked (boundary tiles only); interior blocks are full-rate MXU work. The
  schedule rides in as a scalar-prefetch SMEM array — index maps read it to
  pick the x/w blocks per step, costing nothing in the kernel body.
- **bf16 operands, f32 accumulate.** Partial products accumulate in an f32
  VMEM scratch across the tiles of a row block (forward) or of an expert
  (dW), cast to the output dtype once on the final tile of the run.
- **Fused custom VJP.** The backward is two kernels over the same schedule:
  dX is the forward kernel with per-expert transposed weights; dW accumulates
  x_e^T @ dout_e per expert run. Residuals are just (x, w, group_sizes) — no
  saved intermediates, so the kernel composes with every remat rung.
- **Interpret mode.** ``interpret=True`` runs the identical kernel logic on
  CPU (any shape, no Mosaic tiling constraints) — the parity tests diff it
  against ``ragged_dot`` bit-for-bit-ish (bf16 rel err <= 1e-2) including
  grads, empty experts, and ragged boundary blocks.
- **XLA fallback.** Shapes whose tiles don't fit the VMEM budget, or whose
  dims break Mosaic lane alignment, fall back to ``jax.lax.ragged_dot``
  (forward AND backward), so ``backend.experts_backend="pallas"`` is always
  safe to enable.

Contract: ``sum(group_sizes) == x.shape[0]`` (every row belongs to a group) —
both call sites guarantee it via ``jnp.bincount`` over all rows. Rows the
wrapper pads (to a block multiple) belong to no group and are sliced off.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from automodel_tpu.ops.kernels import note, out_struct

__all__ = ["grouped_matmul", "pick_grouped_blocks"]

LANES = 128


def pick_grouped_blocks(d_in: int, d_out: int, n: int | None = None) -> tuple[int, int] | None:
    """Largest (block_n, block_out) tile fitting the VMEM budget, or None.

    A ~9.8MB modeled budget (Mosaic's scoped-vmem use runs ~30-40% above this
    model; this keeps compiled kernels under the default 16MB limit). ``d_in``
    is the contraction dim (untiled: the whole x row and w column strip sit in
    VMEM); ``d_out`` must divide into a candidate tile.
    ``n=None`` skips the row-divisibility constraint (the wrapper pads rows).
    """
    if d_in % LANES or d_out % LANES:
        return None
    budget = 9_800_000
    best = None
    for bn in (512, 256, 128, 64, 32, 16, 8):
        for bo in (1024, 512, 256, 128):
            if d_out % bo:
                continue
            if n is not None and n % bn:
                continue
            used = (
                2 * bn * d_in * 2      # x tile, double-buffered bf16
                + 2 * d_in * bo * 2    # w tile, double-buffered bf16
                + bn * bo * 4          # out tile
                + max(bn * bo, d_in * bo) * 4  # f32 accumulator (fwd or dW)
            )
            if used <= budget and (best is None or bn * bo > best[0] * best[1]):
                best = (bn, bo)
    return best


def _tile_schedule(group_sizes: jnp.ndarray, num_bn: int, block_n: int) -> jnp.ndarray:
    """(4, S) int32 tile list, S = num_bn + E static: rows are (row_block,
    expert, row_start, row_end) per tile, row range relative to the block.

    One tile per (row-block, expert) overlap; empty experts get one empty-range
    tile (so their dW block is still written — with zeros); tail padding tiles
    repeat the last valid (row_block, expert) with an empty range so they
    extend the final accumulation runs instead of opening new ones. Both the
    row_block and expert columns are non-decreasing, which is what the kernels'
    run-boundary detection (init on change, flush before change) relies on.
    """
    E = group_sizes.shape[0]
    S = num_bn + E
    gs = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(gs)
    starts = ends - gs
    nonempty = gs > 0
    # empty experts tile the block their (zero-width) range points at, keeping
    # the row_block column monotone — a 0-index fallback would reopen (and
    # zero-flush) an already-written out block mid-schedule
    fb = jnp.clip(starts // block_n, 0, num_bn - 1)
    lb = jnp.where(nonempty, jnp.clip((ends - 1) // block_n, 0, num_bn - 1), fb)
    ntiles = jnp.where(nonempty, lb - fb + 1, 1)
    tile_end = jnp.cumsum(ntiles)
    tile_start = tile_end - ntiles
    total = tile_end[-1]

    s = jnp.arange(S, dtype=jnp.int32)
    eid = jnp.clip(jnp.searchsorted(tile_end, s, side="right"), 0, E - 1).astype(jnp.int32)
    rb = fb[eid] + (s - tile_start[eid])
    blk0 = rb * block_n
    rs = jnp.clip(starts[eid] - blk0, 0, block_n)
    re = jnp.clip(ends[eid] - blk0, 0, block_n)

    valid = s < total
    last = total - 1
    rb = jnp.where(valid, rb, jnp.take(rb, last))
    eid = jnp.where(valid, eid, jnp.take(eid, last))
    rs = jnp.where(valid, rs, 0)
    re = jnp.where(valid, re, 0)
    return jnp.stack([rb, eid, rs, re]).astype(jnp.int32)


def _gmm_kernel(sched_ref, x_ref, w_ref, o_ref, acc_ref, *, block_n, num_s):
    """out[rb] = sum over this row block's tiles of masked_x @ w[expert]."""
    s = pl.program_id(1)
    rb = sched_ref[0, s]
    rs = sched_ref[2, s]
    re = sched_ref[3, s]

    @pl.when((s == 0) | (sched_ref[0, jnp.maximum(s - 1, 0)] != rb))
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(re > rs)
    def _compute():
        rows = jax.lax.broadcasted_iota(jnp.int32, (block_n, 1), 0)
        xm = jnp.where((rows >= rs) & (rows < re), x_ref[...], 0).astype(x_ref.dtype)
        acc_ref[:] += jax.lax.dot_general(
            xm, w_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when((s == num_s - 1) | (sched_ref[0, jnp.minimum(s + 1, num_s - 1)] != rb))
    def _flush():
        o_ref[...] = acc_ref[:].astype(o_ref.dtype)


def _tgmm_kernel(sched_ref, x_ref, g_ref, dw_ref, acc_ref, *, block_n, num_s):
    """dw[e] = sum over this expert's tiles of masked_x^T @ dout."""
    s = pl.program_id(1)
    e = sched_ref[1, s]
    rs = sched_ref[2, s]
    re = sched_ref[3, s]

    @pl.when((s == 0) | (sched_ref[1, jnp.maximum(s - 1, 0)] != e))
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(re > rs)
    def _compute():
        rows = jax.lax.broadcasted_iota(jnp.int32, (block_n, 1), 0)
        xm = jnp.where((rows >= rs) & (rows < re), x_ref[...], 0).astype(x_ref.dtype)
        acc_ref[:] += jax.lax.dot_general(
            xm, g_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when((s == num_s - 1) | (sched_ref[1, jnp.minimum(s + 1, num_s - 1)] != e))
    def _flush():
        dw_ref[0] = acc_ref[:].astype(dw_ref.dtype)


def _pad_rows(x, block_n):
    n = x.shape[0]
    n_pad = -(-n // block_n) * block_n
    if n_pad != n:
        x = jnp.pad(x, ((0, n_pad - n), (0, 0)))
    return x, n_pad


def _gmm_call(x, w, group_sizes, block_n, block_o, interpret):
    n = x.shape[0]
    e_, d, f = w.shape
    xp, n_pad = _pad_rows(x, block_n)
    num_bn = n_pad // block_n
    sched = _tile_schedule(group_sizes, num_bn, block_n)
    num_s = num_bn + e_
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, block_n=block_n, num_s=num_s),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(f // block_o, num_s),
            in_specs=[
                pl.BlockSpec((block_n, d), lambda fi, s, sd: (sd[0, s], 0)),
                pl.BlockSpec((1, d, block_o), lambda fi, s, sd: (sd[1, s], 0, fi)),
            ],
            out_specs=pl.BlockSpec((block_n, block_o), lambda fi, s, sd: (sd[0, s], fi)),
            scratch_shapes=[pltpu.VMEM((block_n, block_o), jnp.float32)],
        ),
        out_shape=out_struct((n_pad, f), x.dtype, sched, xp, w),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="grouped_gemm_fwd",
    )(sched, xp, w)
    return out[:n]


def _tgmm_call(x, g, group_sizes, block_n, block_o, interpret, e_, d, f):
    xp, n_pad = _pad_rows(x, block_n)
    gp, _ = _pad_rows(g, block_n)
    num_bn = n_pad // block_n
    sched = _tile_schedule(group_sizes, num_bn, block_n)
    num_s = num_bn + e_
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, block_n=block_n, num_s=num_s),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(f // block_o, num_s),
            in_specs=[
                pl.BlockSpec((block_n, d), lambda fi, s, sd: (sd[0, s], 0)),
                pl.BlockSpec((block_n, block_o), lambda fi, s, sd: (sd[0, s], fi)),
            ],
            out_specs=pl.BlockSpec((1, d, block_o), lambda fi, s, sd: (sd[1, s], 0, fi)),
            scratch_shapes=[pltpu.VMEM((d, block_o), jnp.float32)],
        ),
        out_shape=out_struct((e_, d, f), g.dtype, sched, xp, gp),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="grouped_gemm_bwd_dw",
    )(sched, xp, gp)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _grouped_mm(x, w, group_sizes, block_n, block_o, interpret):
    return _gmm_call(x, w, group_sizes, block_n, block_o, interpret)


def _fwd_rule(x, w, group_sizes, block_n, block_o, interpret):
    out = _gmm_call(x, w, group_sizes, block_n, block_o, interpret)
    return out, (x, w, group_sizes)


def _bwd_rule(block_n, block_o, interpret, res, dout):
    x, w, group_sizes = res
    e_, d, f = w.shape
    # dX sweeps the transposed weights (contraction dim f); dW accumulates a
    # (d, block) f32 tile per expert. Re-pick blocks per operand shape; a
    # non-fitting backward falls back to XLA for BOTH grads (ragged_dot's vjp)
    # so the gradient pair always comes from one implementation.
    dx_blocks = (block_n, d) if interpret else pick_grouped_blocks(f, d)
    dw_blocks = (block_n, f) if interpret else pick_grouped_blocks(d, f)
    if dx_blocks is None or dw_blocks is None:
        note("experts_bwd", "ragged_dot",
             reason=f"no VMEM-fitting backward tile for w (E, {d}, {f})")
        _, vjp = jax.vjp(lambda xx, ww: jax.lax.ragged_dot(xx, ww, group_sizes), x, w)
        dx, dw = vjp(dout)
    else:
        dx = _gmm_call(dout, jnp.swapaxes(w, 1, 2), group_sizes,
                       dx_blocks[0], dx_blocks[1], interpret)
        dw = _tgmm_call(x, dout, group_sizes, dw_blocks[0], dw_blocks[1],
                        interpret, e_, d, f)
    return dx, dw, np.zeros(group_sizes.shape, dtype=jax.dtypes.float0)


_grouped_mm.defvjp(_fwd_rule, _bwd_rule)


def grouped_matmul(
    x: jnp.ndarray,  # (N, D) rows sorted so each group's rows are contiguous
    w: jnp.ndarray,  # (E, D, F) per-group weights
    group_sizes: jnp.ndarray,  # (E,) int32, sum == N
    *,
    interpret: bool = False,
    block_n: int | None = None,
    block_o: int | None = None,
) -> jnp.ndarray:
    """``jax.lax.ragged_dot`` semantics via the blocked Pallas schedule.

    Differentiable w.r.t. x and w through the fused Pallas backward. Shapes the
    tile picker rejects (lane misalignment, VMEM overflow) use ``ragged_dot``,
    and say so once through :func:`automodel_tpu.ops.kernels.note`. In
    interpret mode (CPU tests) any shape runs; unspecified blocks default to
    small tiles that exercise multi-block schedules on test-sized inputs.
    """
    if interpret:
        bn = block_n or 8
        bo = block_o or w.shape[2]
    else:
        picked = pick_grouped_blocks(w.shape[1], w.shape[2])
        if picked is None:
            note("experts", "ragged_dot",
                 reason=f"pallas unusable: no lane-aligned VMEM-fitting tile "
                        f"for w (E, {w.shape[1]}, {w.shape[2]})")
            return jax.lax.ragged_dot(x, w, group_sizes)
        bn = block_n or picked[0]
        bo = block_o or picked[1]
    if w.shape[2] % bo:
        note("experts", "ragged_dot",
             reason=f"pallas unusable: block_o {bo} does not divide {w.shape[2]}")
        return jax.lax.ragged_dot(x, w, group_sizes)
    note("experts", "pallas", interpret=interpret)
    return _grouped_mm(x, w, group_sizes.astype(jnp.int32), bn, bo, interpret)
