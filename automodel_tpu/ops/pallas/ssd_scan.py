"""Mamba-2 SSD chunked scan as a Pallas kernel pair for TPU.

``ops/mamba2.mamba_chunk_scan`` runs the same mathematics as XLA einsums and pays
for it in HBM: per layer it writes three (heads, chunks, C, C) float32 tables, the
decay vectors, the per-chunk states and a float32 ``y``, and its inter-chunk
recurrence is a ``lax.scan``. Here a chunk's tables live and die in VMEM:

- ``ssd_scan_fwd``: grid (batch, group, chunk), the chunk axis sequential. A step
  loads the chunk's ``x`` for the group's heads, the group's ``B`` and ``C`` and the
  heads' ``dt`` and cumulative log-decay; per head it builds the masked decay
  table, scales ``C.B^T`` (one product a group) by it and by ``dt``, multiplies by
  ``x``, adds the read-out of the carried state and the ``D`` skip, and writes
  ``y`` once in the output dtype. The state, transposed to (N, heads * head_dim)
  float32, is carried in the resident output block that ends as the final state.
- ``ssd_scan_bwd``: the same grid swept from the last chunk to the first, carrying
  the state's gradient the same way. The tables are rebuilt per chunk; what the
  forward saves is the state entering each chunk (float32; it is only written when
  a gradient is asked for).

Layouts are the model's: ``x`` and ``y`` as (B, S, H * dh) and ``B``, ``C`` as
(B, S, G * N) are free reshapes, read through ``BlockSpec``s with no head-major
copy. Two heads of width 64 share a 128-lane tile: a head's products run on the
whole tile and the halves are picked by lane, so nothing is shifted across lanes. A
head of 128 (or a multiple) has its tile to itself and nothing is picked.
The per-head vectors (``dt``, the cumulative log-decay ``cs``) are needed along
rows and along columns of a table, so the wrapper hands both layouts in (2 MB a
layer at the published widths) and takes ``cs``'s gradient back in both.

Precision. ``x``, ``B`` and ``C`` reach the MXU in the dtype they come in; a bf16
operand times a bf16 operand with float32 accumulation is exact. Whatever the XLA
form keeps in float32 stays float32 here: ``dt``, ``cs``, every ``exp``, the scaled
tables, ``x * w``, the carried state and its gradient, every accumulator. A dot
with such an operand splits it into three bf16 parts (24 bits of mantissa, so the
parts add up to it exactly: what ``Precision.HIGHEST`` does on this hardware) and
runs one pass a part; nothing float32 is rounded to a single bf16.

The cumulative log-decay is an input, not something the kernel derives: the
wrapper computes ``cs = cumsum(dt * A - 50 * reset)`` per chunk in plain JAX and
autodiff carries ``cs``'s gradient on to ``dt`` and ``A``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from automodel_tpu.ops.kernels import out_struct

__all__ = ["ssd_scan", "ssd_scan_needs"]

LANES = 128
MAX_TILES = 16  # lane tiles of heads a group may hold: the kernel unrolls over them
# a group's carried state (state size x heads x head_dim, float32) a grid step may hold:
# six blocks of it are resident (in, out and saved, each double-buffered). Compiled for the
# v5e: 8 MiB (state 1024 x 16 tiles, state 2048 x 8) fits the 64 MiB, 16 MiB does not
MAX_STATE_BYTES = 8 * 2**20
_NEG = -1e30
_PARTS = 3  # bf16 parts a float32 operand is split into

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _vmem_limit() -> int:
    """Half of the core's VMEM (64 MiB of a v5e's 128): the unrolled body keeps a few
    (C, C) float32 tables a head in flight and Mosaic spills them there."""
    try:
        capacity = pltpu.get_tpu_info().vmem_capacity_bytes
    except ValueError:
        capacity = 128 * 2**20
    return capacity // 2


def ssd_scan_needs(x, Bm, chunk_size: int) -> list[tuple[bool, str]]:
    """What the kernels ask of a call's shapes, as ``kernel_usable`` takes it."""
    _, _, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    r = H // G
    per_tile = max(1, LANES // P)
    return [
        (chunk_size % LANES == 0, f"chunk_size {chunk_size} is not a multiple of {LANES}"),
        (N % LANES == 0, f"state size {N} is not a multiple of {LANES}"),
        (P == 64 or P % LANES == 0, f"head_dim {P} is neither 64 nor a multiple of {LANES}"),
        (r % per_tile == 0, f"{r} heads a group do not fill {LANES}-lane tiles at head_dim {P}"),
        (r * P <= MAX_TILES * LANES,
         f"{r} heads x {P} a group is more than {MAX_TILES} lane tiles"),
        (N * r * P * 4 <= MAX_STATE_BYTES,
         f"a group's carried state, {N} x {r} heads x {P} in float32, is more than "
         f"{MAX_STATE_BYTES >> 20} MiB: six blocks of it do not fit the kernels' VMEM"),
        (all(a.dtype in (jnp.bfloat16, jnp.float32) for a in (x, Bm)),
         f"dtypes {x.dtype}/{Bm.dtype} are neither bfloat16 nor float32"),
    ]


# ---- what the kernels share


def _parts(a):
    """``a`` as bf16 addends: itself if bf16, else three parts that add up to it exactly.
    A part is the value cut to its upper 16 bits (8 bits of mantissa) by a mask, not a
    rounded cast: the cut float32 goes to the next part's subtraction as it is, and the
    bf16 copy only to the MXU, which lets Mosaic pack it in the MXU's own tiling; a part
    that is also cast back for the subtraction is packed twice (compiled for the v5e: no
    ``vunpack`` left, 15% fewer vector operations a backward step)."""
    if a.dtype == jnp.bfloat16:
        return [a]
    out = []
    for _ in range(_PARTS - 1):
        cut = jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(a, jnp.int32) & jnp.int32(-65536), jnp.float32)
        out.append(cut.astype(jnp.bfloat16))
        a = a - cut
    out.append(a.astype(jnp.bfloat16))
    return out


def _dot(a, b, dims):
    """``a . b`` in float32 for 2-D operands that are bf16 (exact on the MXU) or float32
    (split, one pass a part): every pair of parts whose product matters, all of them when
    one side is exact, the six of ``bf16_6x`` when both are float32; small terms first."""
    pa, pb = _parts(a), _parts(b)
    total = None
    for i, x in reversed(list(enumerate(pa))):
        for j, y in reversed(list(enumerate(pb))):
            if i + j < max(len(pa), len(pb)):
                t = jax.lax.dot_general(x, y, dims, preferred_element_type=jnp.float32)
                total = t if total is None else total + t
    return total


def _column(ref, h, lanes):
    """Head ``h``'s column of a (1, 1, L, heads) block, copied to every one of ``lanes``
    lanes: one broadcast, after which whatever is derived from it (an ``exp``, a product)
    already has a table's or a tile's shape. An (L, 1) value fills as many registers."""
    return jnp.broadcast_to(ref[0, 0, :, h:h + 1], (ref.shape[2], lanes))


class _Tile:
    """Lane bookkeeping of one 128-lane tile of heads (two heads of 64, or one head)."""

    def __init__(self, rows: int, width: int, head_dim: int):
        self.heads, self.width = width // head_dim, width
        if self.heads > 1:
            lane = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
            self.masks = [(lane >= k * head_dim) & (lane < (k + 1) * head_dim)
                          for k in range(self.heads)]
            self.row_masks = [m[:1] for m in self.masks]

    def pick(self, per_head):
        """(rows, width) whose lanes of head k come from ``per_head[k]``."""
        out = per_head[-1]
        for k in range(self.heads - 2, -1, -1):
            mask = self.row_masks[k] if per_head[k].shape[0] == 1 else self.masks[k]
            out = jnp.where(mask, per_head[k], out)
        return out

    def row(self, scalar):
        """A head's (1, 1) value as :meth:`pick` can place it. Two heads a tile: as it is,
        the lane masks spread it. One head a tile: copied to the tile's lanes here, BEFORE
        whatever is derived from it (an ``exp``), so that the copy and the later one along
        the rows stay two broadcasts; next to each other they fold into (1, 1) ->
        (rows, lanes), along both axes at once, which Mosaic does not take."""
        if self.heads > 1:
            return scalar
        return jnp.broadcast_to(scalar, (1, self.width))

    def only(self, a, k):
        """``a`` with the lanes of every head but ``k`` zeroed."""
        if self.heads == 1:
            return a
        return jnp.where(self.masks[k], a, jnp.zeros_like(a))

    def head_sum(self, row, k):
        """Sum of a (1, width) row over head ``k``'s lanes, as (1, 1)."""
        if self.heads > 1:
            row = jnp.where(self.row_masks[k], row, 0.0)
        return jnp.sum(row, axis=1, keepdims=True)


# ---- forward


def _fwd_kernel(x_ref, dtr_ref, dtc_ref, csr_ref, csc_ref, b_ref, c_ref, d_ref, s0_ref,
                y_ref, fin_ref, *states_ref, head_dim):
    L, RP = x_ref.shape[1], x_ref.shape[2]
    W = max(LANES, head_dim)
    wide = max(L, W)
    tile = _Tile(L, W, head_dim)

    @pl.when(pl.program_id(2) == 0)
    def _start():
        fin_ref[...] = s0_ref[...]

    Bm, Cm = b_ref[0], c_ref[0]
    G = _dot(Cm, Bm, _NT)  # [i, j] = C_i . B_j
    tril = (jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (L, L), 1))

    for u in range(RP // W):
        sl = slice(u * W, (u + 1) * W)
        xs = x_ref[0, :, sl]
        st = fin_ref[0, :, sl]  # (N, W): the state entering the chunk, transposed
        if states_ref:
            states_ref[0][0, 0, :, sl] = st
        intra, e_i, w_j, d_last = [], [], [], []
        for k in range(tile.heads):
            h = u * tile.heads + k
            cs_i = _column(csc_ref, h, wide)  # (L, wide), every lane the same
            cs_j = csr_ref[0, 0, h:h + 1, :]  # (1, L)
            cs_last = csc_ref[0, 0, L - 1:L, h:h + 1]  # (1, 1)
            decay = jnp.exp(jnp.where(tril, cs_i[:, :L] - cs_j, _NEG))
            m = G * decay * dtr_ref[0, 0, h:h + 1, :]
            intra.append(_dot(m, xs, _NN))
            e_i.append(jnp.exp(cs_i[:, :W]))
            # as (L, 1) until the tile's halves are picked: (1, 1) against (L, lanes) is a
            # broadcast along both axes at once, which Mosaic does not take
            w_j.append(jnp.exp(cs_last - csc_ref[0, 0, :, h:h + 1]) * dtc_ref[0, 0, :, h:h + 1])
            d_last.append(jnp.exp(tile.row(cs_last)))
        xf = xs.astype(jnp.float32)
        y = tile.pick(intra) + tile.pick(e_i) * _dot(Cm, st, _NN) + d_ref[0, :, sl] * xf
        y_ref[0, :, sl] = y.astype(y_ref.dtype)
        fin_ref[0, :, sl] = st * tile.pick(d_last) + _dot(Bm, xf * tile.pick(w_j), _TN)


def _specs(dims, chunk):
    """Block specs by operand kind, for a grid (batch, group, step) whose step ``c`` works
    on chunk ``chunk(c)`` (the backward sweeps from the last)."""
    _, _, _, r, P, N, L = dims
    RP = r * P
    return {
        "seq": pl.BlockSpec((1, L, RP), lambda b, g, c: (b, chunk(c), g)),
        "row": pl.BlockSpec((1, 1, r, L), lambda b, g, c: (b, g, 0, chunk(c))),
        "col": pl.BlockSpec((1, 1, L, r), lambda b, g, c: (b, g, chunk(c), 0)),
        "bc": pl.BlockSpec((1, L, N), lambda b, g, c: (b, chunk(c), g)),
        "d": pl.BlockSpec((1, 1, RP), lambda b, g, c: (g, 0, 0)),
        "state": pl.BlockSpec((1, N, RP), lambda b, g, c: (b, 0, g)),
        "states": pl.BlockSpec((1, 1, N, RP), lambda b, g, c: (b, chunk(c), 0, g)),
        "dd": pl.BlockSpec((1, 1, 1, RP), lambda b, g, c: (b, g, 0, 0)),
    }


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_vmem_limit(),
    )


def _fwd_call(x, dtr, dtc, csr, csc, Bm, Cm, drow, s0, *, dims, save_states, interpret):
    batch, S, G, r, P, N, L = dims
    nc = S // L
    spec = _specs(dims, lambda c: c)
    ins = (x, dtr, dtc, csr, csc, Bm, Cm, drow, s0)
    out_shape = [out_struct(x.shape, x.dtype, *ins), out_struct(s0.shape, jnp.float32, *ins)]
    out_specs = [spec["seq"], spec["state"]]
    if save_states:
        out_shape.append(out_struct((batch, nc, N, G * r * P), jnp.float32, *ins))
        out_specs.append(spec["states"])
    return pl.pallas_call(
        functools.partial(_fwd_kernel, head_dim=P),
        grid=(batch, G, nc),
        in_specs=[spec["seq"], spec["row"], spec["col"], spec["row"], spec["col"],
                  spec["bc"], spec["bc"], spec["d"], spec["state"]],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=_params(),
        interpret=interpret,
        name="ssd_scan_fwd",
    )(*ins)


# ---- backward


def _bwd_kernel(x_ref, dy_ref, dtc_ref, csr_ref, csc_ref, b_ref, c_ref, d_ref, st_ref,
                dfin_ref, dx_ref, ddt_ref, dcsc_ref, dcsr_ref, db_ref, dc_ref, dd_ref,
                ds0_ref, *, head_dim):
    """One chunk of one group, tables as [j, i] (row j: the token written, lane i: the
    token read), so every per-token result is a column or a column sum."""
    L, RP = x_ref.shape[1], x_ref.shape[2]
    r, N = dtc_ref.shape[3], b_ref.shape[2]
    W = max(LANES, head_dim)
    wide = max(L, W, N)
    tile = _Tile(L, W, head_dim)
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _start():
        ds0_ref[...] = dfin_ref[...]
        dd_ref[...] = jnp.zeros_like(dd_ref)

    Bm, Cm = b_ref[0], c_ref[0]
    Bf, Cf = Bm.astype(f32), Cm.astype(f32)
    GT = _dot(Bm, Cm, _NT)  # [j, i] = B_j . C_i
    triu = (jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
            >= jax.lax.broadcasted_iota(jnp.int32, (L, L), 0))

    dgt = jnp.zeros((L, L), f32)  # sum over the group's heads of dG^T
    db = jnp.zeros(Bf.shape, f32)
    dc = jnp.zeros(Cf.shape, f32)

    for u in range(RP // W):
        sl = slice(u * W, (u + 1) * W)
        xs, dys = x_ref[0, :, sl], dy_ref[0, :, sl]
        xf, dyf = xs.astype(f32), dys.astype(f32)
        st = st_ref[0, 0, :, sl]  # (N, W): the state that entered the chunk
        dst = ds0_ref[0, :, sl]  # (N, W): gradient of the state that left it
        sd = jnp.sum(dst * st, axis=0, keepdims=True)  # (1, W)
        dx_intra, e_t, w_t, d_last = [], [], [], []
        for k in range(tile.heads):
            h = u * tile.heads + k
            cs_t = _column(csc_ref, h, wide)  # (L, wide), every lane the same
            dt_t = _column(dtc_ref, h, wide)
            cs_i = csr_ref[0, 0, h:h + 1, :]  # (1, L)
            cs_last = csc_ref[0, 0, L - 1:L, h:h + 1]  # (1, 1)
            xk, dyk = tile.only(xs, k), tile.only(dys, k)

            # inside the chunk
            decay = jnp.exp(jnp.where(triu, cs_i - cs_t[:, :L], _NEG))  # [j, i], i >= j
            dmt = _dot(xk, dys, _NT)  # [j, i] = x_j . dy_i
            dx_intra.append(_dot(GT * decay * dt_t[:, :L], dys, _NN))
            pk = dmt * decay
            dgt = dgt + pk * dt_t[:, :L]
            ut = pk * GT
            r1 = jnp.sum(ut, axis=1, keepdims=True)  # (L, 1) over the tokens that read j
            dcsr_ref[0, 0, h:h + 1, :] = jnp.sum(ut * dt_t[:, :L], axis=0, keepdims=True)

            # the carried state: its read-out (q) and its update (rr). (L, 1) values stay
            # (L, 1) where (1, 1) meets them: a broadcast along both axes at once is not
            # something Mosaic takes
            e = jnp.exp(cs_t)
            from_last = jnp.exp(cs_last - csc_ref[0, 0, :, h:h + 1])  # (L, 1)
            w_col = from_last * dtc_ref[0, 0, :, h:h + 1]
            w = jnp.broadcast_to(w_col, (L, wide))
            q = _dot(dyk, st, _NT)  # (L, N) = dy_t . S_prev
            dc = dc + e[:, :N] * q
            read = e[:, :1] * jnp.sum(Cf * q, axis=1, keepdims=True)
            rr = _dot(xk, dst, _NT)  # (L, N) = x_t . dS_new
            db = db + w[:, :N] * rr
            v = jnp.sum(Bf * rr, axis=1, keepdims=True)  # (L, 1) = x_t^T dS_new B_t
            wv = w_col * v
            at_last = (jnp.exp(cs_last) * tile.head_sum(sd, k)
                       + jnp.sum(wv, axis=0, keepdims=True))  # (1, 1)
            ddt_ref[0, 0, :, h:h + 1] = r1 + from_last * v
            dcsc_ref[0, 0, :, h:h + 1] = read - dt_t[:, :1] * r1 - wv
            dcsc_ref[0, 0, L - 1:L, h:h + 1] += at_last
            e_t.append(e[:, :W])
            w_t.append(w[:, :W])
            d_last.append(jnp.exp(tile.row(cs_last)))

        dx = tile.pick(dx_intra) + d_ref[0, :, sl] * dyf + tile.pick(w_t) * _dot(Bm, dst, _NN)
        dx_ref[0, :, sl] = dx.astype(dx_ref.dtype)
        dd_ref[0, 0, :, sl] += jnp.sum(dyf * xf, axis=0, keepdims=True)
        ds0_ref[0, :, sl] = dst * tile.pick(d_last) + _dot(Cm, tile.pick(e_t) * dyf, _TN)

    db_ref[0] = (db + _dot(dgt, Cm, _NN)).astype(db_ref.dtype)
    dc_ref[0] = (dc + _dot(dgt, Bm, _TN)).astype(dc_ref.dtype)


def _bwd_call(x, dy, dtc, csr, csc, Bm, Cm, drow, states, dfin, *, dims, interpret):
    batch, S, G, r, P, N, L = dims
    nc = S // L
    spec = _specs(dims, lambda c: nc - 1 - c)
    ins = (x, dy, dtc, csr, csc, Bm, Cm, drow, states, dfin)
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_bwd_kernel, head_dim=P),
        grid=(batch, G, nc),
        in_specs=[spec["seq"], spec["seq"], spec["col"], spec["row"], spec["col"],
                  spec["bc"], spec["bc"], spec["d"], spec["states"], spec["state"]],
        out_specs=[spec["seq"], spec["col"], spec["col"], spec["row"], spec["bc"], spec["bc"],
                   spec["dd"], spec["state"]],
        out_shape=[
            out_struct(x.shape, x.dtype, *ins),
            out_struct(dtc.shape, f32, *ins),
            out_struct(csc.shape, f32, *ins),
            out_struct(csr.shape, f32, *ins),
            out_struct(Bm.shape, Bm.dtype, *ins),
            out_struct(Cm.shape, Cm.dtype, *ins),
            out_struct((batch, G, 1, r * P), f32, *ins),
            out_struct(dfin.shape, f32, *ins),
        ],
        compiler_params=_params(),
        interpret=interpret,
        name="ssd_scan_bwd",
    )(*ins)


# ---- the differentiable core: layouts in, layouts out


def _rows_cols(v, G):
    """(B, S, H) -> rows (B, G, r, S) and columns (B, G, S, r) of the same values."""
    b, s, h = v.shape
    v = v.reshape(b, s, G, h // G)
    return v.transpose(0, 2, 3, 1), v.transpose(0, 2, 1, 3)


def _from_cols(v):
    b, g, s, r = v.shape
    return v.transpose(0, 2, 1, 3).reshape(b, s, g * r)


def _state_in(s, dims):
    batch, _, G, r, P, N, _ = dims
    return s.astype(jnp.float32).transpose(0, 3, 1, 2).reshape(batch, N, G * r * P)


def _state_out(s, dims):
    batch, _, G, r, P, N, _ = dims
    return s.reshape(batch, N, G * r, P).transpose(0, 2, 3, 1)


def _operands(x, dt, cs, Bm, Cm, D, s0, chunk):
    batch, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    dims = (batch, S, G, H // G, P, N, chunk)
    dtr, dtc = _rows_cols(dt, G)
    csr, csc = _rows_cols(cs, G)
    flat = (x.reshape(batch, S, H * P), dtr, dtc, csr, csc,
            Bm.reshape(batch, S, G * N), Cm.reshape(batch, S, G * N),
            jnp.repeat(D.astype(jnp.float32), P).reshape(G, 1, (H // G) * P),
            _state_in(s0, dims))
    return dims, flat


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _ssd(x, dt, cs, Bm, Cm, D, s0, chunk, interpret):
    """y (B, S, H, dh) and the final state (B, H, dh, N) of the chunked recurrence
    whose cumulative log-decay inside each chunk is ``cs``; S a multiple of ``chunk``."""
    dims, flat = _operands(x, dt, cs, Bm, Cm, D, s0, chunk)
    y, fin = _fwd_call(*flat, dims=dims, save_states=False, interpret=interpret)
    return y.reshape(x.shape), _state_out(fin, dims)


def _ssd_fwd(x, dt, cs, Bm, Cm, D, s0, chunk, interpret):
    dims, flat = _operands(x, dt, cs, Bm, Cm, D, s0, chunk)
    y, fin, states = _fwd_call(*flat, dims=dims, save_states=True, interpret=interpret)
    return (y.reshape(x.shape), _state_out(fin, dims)), (x, dt, cs, Bm, Cm, D, s0, states)


def _ssd_bwd(chunk, interpret, res, cot):
    x, dt, cs, Bm, Cm, D, s0, states = res
    dy, dfin = cot
    dims, (xf, _, dtc, csr, csc, bf, cf, drow, _) = _operands(x, dt, cs, Bm, Cm, D, s0, chunk)
    batch, S, G, r, P, N, _ = dims
    dx, ddt_c, dcs_c, dcs_r, dB, dC, dd, ds0 = _bwd_call(
        xf, dy.astype(x.dtype).reshape(xf.shape), dtc, csr, csc, bf, cf, drow, states,
        _state_in(dfin, dims), dims=dims, interpret=interpret)
    dcs = _from_cols(dcs_c) + dcs_r.transpose(0, 3, 1, 2).reshape(batch, S, G * r)
    dD = dd.sum(0).reshape(G * r, P).sum(-1)
    return (dx.reshape(x.shape), _from_cols(ddt_c).astype(dt.dtype), dcs.astype(cs.dtype),
            dB.reshape(Bm.shape), dC.reshape(Cm.shape), dD.astype(D.dtype),
            _state_out(ds0, dims).astype(s0.dtype))


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_scan(x, dt, A, Bm, Cm, D=None, *, chunk_size: int = 128, initial_state=None,
             output_final_state: bool = False, reset_mask=None, interpret: bool = False):
    """``ops.mamba2.mamba_chunk_scan``'s arguments and results, through the kernels.
    The shapes must pass :func:`ssd_scan_needs`; any sequence length is padded to the
    chunk as the XLA form pads it (``dt`` zero: the state neither decays nor is written)."""
    batch, S, H, P = x.shape
    N = Bm.shape[3]
    f32 = jnp.float32
    dt = dt.astype(f32)
    a = dt * A.astype(f32)
    if reset_mask is not None:
        a = a - 50.0 * reset_mask.astype(f32)[..., None]
    pad = (-S) % chunk_size
    if pad:
        x, Bm, Cm = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0))) for t in (x, Bm, Cm))
        dt, a = (jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in (dt, a))
    nc = (S + pad) // chunk_size
    cs = jnp.cumsum(a.reshape(batch, nc, chunk_size, H), axis=2).reshape(dt.shape)
    s0 = jnp.zeros((batch, H, P, N), f32) if initial_state is None else initial_state
    y, fin = _ssd(x, dt, cs, Bm, Cm, jnp.zeros((H,), f32) if D is None else D, s0,
                  chunk_size, interpret)
    return y[:, :S], (fin if output_final_state else None)
