"""The chunked gated delta rule as a Pallas kernel pair for TPU.

``ops/gated_delta.chunk_gated_delta_rule`` runs the rule as float32 XLA operations:
some thirty (heads, chunks, C, C) and (heads, S, d) float32 tensors a layer in HBM, a
batched triangular solve and a ``lax.scan`` of half a dozen small products a chunk.
Here a chunk's tables live and die in VMEM:

- ``gated_delta_fwd``: grid (batch, key-head group, chunk), the chunk axis sequential.
  The ``r`` value heads that share a key head are stacked along the rows of ONE
  128 x 128 problem (``r`` chunks of ``C = 128 // r`` tokens; 64 at two value heads a
  key head) whose cross-head entries are masked away: ``k k^T`` and ``q k^T`` are one
  product a key head, every table fills a whole tile, and a product of such
  block-diagonal tables serves all ``r`` heads in one pass. A step builds the decay
  table, ``A = strict_tril(beta k^ k^^T decay)``, its inverse ``T = (I + A)^-1``, the
  corrected values ``u = T (beta (v - e^cs k^ S))``, the output ``q^ e^cs S + P u`` and
  the state leaving the chunk. The (dk, dv) float32 state of each value head is carried
  in the resident output block that ends as the final state.
- ``gated_delta_bwd``: the same grid swept from the last chunk to the first, carrying the
  state's gradient the same way. The tables are rebuilt; what the forward saves (only
  when a gradient is asked for) is the state entering each chunk and the inverse ``T``
  (float32; r x C x C a key head and chunk), so the backward runs no inverse:
  ``dw = T^T du`` and ``dA = -T^T dT T^T = -dw u^T``.

Layouts are the model's: ``q`` and ``k`` as (B, S, Hk * dk), ``v`` and the output as
(B, S, Hv * dv) are free reshapes read through ``BlockSpec``s: q and k once a key head,
no ``jnp.repeat``, no head-major copy. The per-token vectors (``beta``, the cumulative
log-decay, the L2 scales of q and k) are needed along rows and along columns of a table,
so the wrapper hands both layouts in (a few MB a layer).

The inverse is built by substitution, not by a power series: rows within 16 x 16
diagonal blocks on the vector unit (``T_i = e_i - sum_{m<i} A_im T_m``, every block of
the tile at once), then the blocks joined pairwise by products
(``[[T1, 0], [-T2 A21 T1, T2]]``), which is block forward substitution.

Precision. The kernels compute what the XLA form computes at ``Precision.HIGHEST``, to
float32 rounding, values and every gradient. q, k and v cross into the kernels in
float32 and the output and the gradients come back in float32: the wrapper upcasts as
the XLA form does at its top, so nothing is rounded at the kernels' edge that the XLA
form's program does not round (bf16 operands work too, one MXU pass where float32 takes
three, 4% faster end to end in ``qwen3next_pretrain_4k`` and as close to the float64
recurrence in the tests; the benchmark's gradient check read the parent's range with
the float32 edge and 5.3e-2 on one seed of thirteen with the bf16 one: PERF.md, PR 40).
The L2 scales (float32, computed by the wrapper), ``beta`` and the decay are applied to
the raw products' rows and columns afterwards (``(q^ k^^T)_ij = (q k^T)_ij rq_i rk_j``),
which rounds less than norming first. Whatever the XLA form keeps in float32 stays
float32: ``A``, ``T``, ``w``, ``u``, the decay tables, the carried state, its gradient, every
accumulator. A dot with a float32 operand splits it into three bf16 parts that add up to
it exactly and runs one pass a pair of parts that matters (``ssd_scan._dot``: the six
products of ``bf16_6x`` when both sides are float32; a bf16 operand is one exact part);
nothing float32 is rounded to a single bf16.

The cumulative log-decay is an input, not something the kernels derive: the wrapper
computes ``cs = cumsum(g)`` per chunk in plain JAX and autodiff carries its gradient on
to ``g`` (``a_log``, ``dt_bias``, ``wba``). The L2 scales' dependence on q and k is
differentiated inside the backward kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from automodel_tpu.ops.kernels import out_struct
from automodel_tpu.ops.pallas.ssd_scan import _NN, _NT, _TN, _dot, _vmem_limit

__all__ = ["gated_delta_rule", "gated_delta_needs"]

LANES = 128
SUB = 16  # side of the diagonal blocks inverted row by row on the vector unit
_NEG = -1e30
_EPS = 1e-6  # ops/gated_delta.l2norm's
_F32 = jnp.float32

# the per-token vectors handed in along the rows of a table (sublanes): beta, the
# cumulative log-decay, the L2 scales of q and k, the chunk's last log-decay ...
_N_COLS = 5
# ... and along its columns (lanes): beta, the log-decay, k's scale; from _R_LAST on one
# row a stacked head, the chunk's last log-decay on every lane
_R_BETA, _R_CS, _R_RK, _R_LAST, _N_ROWS = 0, 1, 2, 3, 8


def gated_delta_needs(query, key, value, *, use_qk_l2norm: bool = True) -> list[tuple[bool, str]]:
    """What the kernels ask of a call's shapes, as ``kernel_usable`` takes it."""
    _, S, Hk, dk = query.shape
    Hv, dv = value.shape[2], value.shape[3]
    r = Hv // max(Hk, 1)
    chunk = LANES // r if r in (1, 2, 4) else LANES
    return [
        (key.shape == query.shape, f"q {query.shape} and k {key.shape} differ"),
        (Hv % Hk == 0 and r in (1, 2, 4),
         f"{Hv} value heads over {Hk} key heads: not 1, 2 or 4 to a key head"),
        (dk % LANES == 0 and dv % LANES == 0,
         f"head widths {dk}/{dv} are not multiples of {LANES}"),
        (S % chunk == 0, f"sequence {S} is not a multiple of the chunk {chunk}"),
        (use_qk_l2norm, "the kernels norm q and k themselves"),
        (all(a.dtype in (jnp.bfloat16, jnp.float32) for a in (query, key, value)),
         f"dtypes {query.dtype}/{key.dtype}/{value.dtype} are neither bfloat16 nor float32"),
    ]


# ---- what the kernels share


def _log2(n: int) -> int:
    assert n & (n - 1) == 0, n
    return n.bit_length() - 1


class _Masks:
    """Boolean (128, 128) tables of the stacked problem: ``r`` chunks of ``C`` rows."""

    def __init__(self, C: int):
        self.C = C
        i = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
        j = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
        self.i, self.j = i, j
        same = self.block(i, C) == self.block(j, C)
        self.lower = same & (i >= j)  # a token reads itself and the chunk's earlier ones
        self.strict = same & (i > j)
        self.upper = same & (i < j)

    @staticmethod
    def block(x, n: int):
        return jax.lax.shift_right_logical(x, _log2(n))

    def join(self, n: int):
        """Lower-left blocks of side ``n`` inside diagonal blocks of side ``2n``."""
        return ((self.block(self.i, 2 * n) == self.block(self.j, 2 * n))
                & (self.block(self.i, n) > self.block(self.j, n)))


def _stack(x, r: int):
    """(C, d) -> (r * C, d): a key head's rows once for each value head that shares it."""
    return x if r == 1 else jnp.concatenate([x] * r, axis=0)


def _heads(x, r: int):
    """(r * C, d) -> r arrays (C, d)."""
    C = x.shape[0] // r
    return [x[a * C:(a + 1) * C] for a in range(r)]


def _rowsum(x):
    return jnp.sum(x, axis=1, keepdims=True)


def _unit_lower_inverse(A, AT, at_ref, masks: _Masks):
    """``(I + A)^-1`` of a block-diagonal strictly lower ``A`` (128, 128), by substitution.
    ``AT`` is ``A`` transposed (built by the caller from the symmetric ``k k^T``), written
    to ``at_ref`` so that row ``i`` of a block is read as a column, one weight a row."""
    at_ref[...] = AT
    lane = jax.lax.broadcasted_iota(jnp.int32, (SUB, LANES), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (SUB, LANES), 0)
    bands = []
    for b in range(LANES // SUB):
        lo = b * SUB
        Tb = (lane == row + lo).astype(_F32)  # the block's rows of I
        for i in range(1, SUB):
            # weights A[lo + i, lo + m] for m < i, zero from the diagonal on
            col = at_ref[lo:lo + SUB, lo + i:lo + i + 1]
            s = jnp.sum(col * Tb, axis=0, keepdims=True)
            Tb = jnp.where(row == i, Tb - s, Tb)
        bands.append(Tb)
    T = jnp.concatenate(bands, axis=0)
    n = SUB
    while n < masks.C:
        low = jnp.where(masks.join(n), A, 0.0)
        T = T - _dot(_dot(T, low, _NN), T, _NN)
        n *= 2
    return T


class _Chunk:
    """One key head's chunk as the stacked (128, 128) problem: the tables both kernels
    build, from the raw q and k, the per-token vectors and the state entering the chunk."""

    def __init__(self, q, k, col, rowv, masks: _Masks, r: int):
        self.k, self.masks = k, masks
        self.k2, self.q2 = _stack(k, r), _stack(q, r)
        self.beta, self.cs, self.rq, self.rk, self.last = (col(n) for n in range(_N_COLS))
        self.beta_r, self.cs_r, self.rk_r = rowv(_R_BETA), rowv(_R_CS), rowv(_R_RK)
        self.KK = _dot(self.k2, self.k2, _NT)  # [i, j] = k_i . k_j, raw
        QK = _dot(self.q2, self.k2, _NT)
        decay = jnp.exp(jnp.where(masks.lower, self.cs - self.cs_r, _NEG))
        # A = beta_i WA KK below the diagonal; P = WP QK on and below it
        self.WA = jnp.where(masks.strict, (self.rk * self.rk_r) * decay, 0.0)
        self.WP = (self.rq * self.rk_r) * decay
        self.Ahat = self.WA * self.KK
        self.P = self.WP * QK
        e = jnp.exp(self.cs)
        self.alpha = e * self.rq  # o reads the state through q^ e^cs
        self.gamma = e * self.rk  # and the delta through k^ e^cs
        self.m = self.rk * jnp.exp(self.last - self.cs)  # what of u_j the state keeps

    def transposed_A(self):
        m = self.masks
        decay_t = jnp.exp(jnp.where(m.upper, self.cs_r - self.cs, _NEG))  # [i, j]: decay[j, i]
        return (self.beta_r * self.rk_r * self.rk) * decay_t * self.KK

    def read_state(self, states):
        """``k S`` and ``q S`` of every stacked head: (r * C, dv) each."""
        C = self.k.shape[0]
        self.kq = jnp.concatenate([self.k, self.q2[:C]], axis=0)
        both = [_dot(self.kq, S, _NN) for S in states]
        self.kS = jnp.concatenate([x[:C] for x in both], axis=0)
        self.qS = jnp.concatenate([x[C:] for x in both], axis=0)


def _columns(ref, p, G):
    return lambda n: ref[0, 0, 0, :, n * G + p:n * G + p + 1]  # (128, 1)


def _rows(ref, p):
    return lambda n: ref[0, 0, p, n:n + 1, :]  # (1, 128)


def _keep(row_ref, p, a, dv):
    """exp of the chunk's last log-decay of stacked head ``a``, as a (1, dv) row."""
    keep = jnp.exp(row_ref[0, 0, p, _R_LAST + a:_R_LAST + a + 1, :])
    return keep if dv == LANES else jnp.concatenate([keep] * (dv // LANES), axis=1)


def _value_rows(ref, p, r, dv):
    """The r value heads of key head ``p`` of a (1, C, heads * dv) block, stacked."""
    return jnp.concatenate([ref[0, :, (p * r + a) * dv:(p * r + a + 1) * dv] for a in range(r)],
                           axis=0)


# ---- forward


def _fwd_kernel(q_ref, k_ref, v_ref, col_ref, row_ref, s0_ref, o_ref, fin_ref, *rest, dims,
                save):
    _, _, _, G, r, dk, dv, C = dims
    if save:
        states_ref, t_ref, at_ref = rest
    else:
        (at_ref,) = rest
    masks = _Masks(C)

    @pl.when(pl.program_id(2) == 0)
    def _start():
        fin_ref[...] = s0_ref[...]

    for p in range(G):
        q, k = q_ref[0, :, p * dk:(p + 1) * dk], k_ref[0, :, p * dk:(p + 1) * dk]
        ch = _Chunk(q, k, _columns(col_ref, p, G), _rows(row_ref, p), masks, r)
        A = ch.beta * ch.Ahat
        T = _unit_lower_inverse(A, ch.transposed_A(), at_ref.at[p], masks)
        states = [fin_ref[0, p * r + a] for a in range(r)]
        if save:
            for a in range(r):
                states_ref[0, 0, p * r + a] = states[a]
            # the r diagonal blocks side by side: (C, r * C)
            t_ref[0, :, p * LANES:(p + 1) * LANES] = sum(_heads(T, r))
        ch.read_state(states)
        v2 = _value_rows(v_ref, p, r, dv).astype(_F32)
        w = ch.beta * (v2 - ch.gamma * ch.kS)
        u = _dot(T, w, _NN)
        o = ch.alpha * ch.qS + _dot(ch.P, u, _NN)
        z = ch.m * u
        for a, (o_a, z_a) in enumerate(zip(_heads(o, r), _heads(z, r))):
            h = p * r + a
            o_ref[0, :, h * dv:(h + 1) * dv] = o_a.astype(o_ref.dtype)
            fin_ref[0, h] = states[a] * _keep(row_ref, p, a, dv) + _dot(k, z_a, _TN)


def _specs(dims, chunk):
    """Block specs by operand kind, for a grid (batch, group, step) whose step ``c`` works
    on chunk ``chunk(c)`` (the backward sweeps from the last)."""
    _, _, _, G, r, dk, dv, C = dims
    return {
        "qk": pl.BlockSpec((1, C, G * dk), lambda b, g, c: (b, chunk(c), g)),
        "v": pl.BlockSpec((1, C, G * r * dv), lambda b, g, c: (b, chunk(c), g)),
        "col": pl.BlockSpec((1, 1, 1, LANES, _N_COLS * G), lambda b, g, c: (b, chunk(c), g, 0, 0)),
        "dcol": pl.BlockSpec((1, 1, 1, LANES, 2 * G), lambda b, g, c: (b, chunk(c), g, 0, 0)),
        "row": pl.BlockSpec((1, 1, G, _N_ROWS, LANES), lambda b, g, c: (b, chunk(c), g, 0, 0)),
        "state": pl.BlockSpec((1, G * r, dk, dv), lambda b, g, c: (b, g, 0, 0)),
        "states": pl.BlockSpec((1, 1, G * r, dk, dv), lambda b, g, c: (b, chunk(c), g, 0, 0)),
        "t": pl.BlockSpec((1, C, G * LANES), lambda b, g, c: (b, chunk(c), g)),
    }


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_vmem_limit(),
    )


@functools.partial(jax.jit, static_argnames=("dims", "save", "interpret"))
def _fwd_call(q, k, v, cols, rows, s0, *, dims, save, interpret):
    # jitted so that a model's layers share one trace and one lowering of the kernel
    batch, S, Hk, G, r, dk, dv, C = dims
    nc = S // C
    spec = _specs(dims, lambda c: c)
    ins = (q, k, v, cols, rows, s0)
    out_shape = [out_struct(v.shape, q.dtype, *ins), out_struct(s0.shape, _F32, *ins)]
    out_specs = [spec["v"], spec["state"]]
    if save:
        out_shape += [out_struct((batch, nc, Hk * r, dk, dv), _F32, *ins),
                      out_struct((batch, S, Hk * LANES), _F32, *ins)]
        out_specs += [spec["states"], spec["t"]]
    return pl.pallas_call(
        functools.partial(_fwd_kernel, dims=dims, save=save),
        grid=(batch, Hk // G, nc),
        in_specs=[spec["qk"], spec["qk"], spec["v"], spec["col"], spec["row"], spec["state"]],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((G, LANES, LANES), _F32)],
        compiler_params=_params(),
        interpret=interpret,
        name="gated_delta_fwd",
    )(*ins)


# ---- backward


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, col_ref, row_ref, st_ref, t_ref, dfin_ref,
                dq_ref, dk_ref, dv_ref, dcol_ref, ds_ref, *, dims, scale):
    _, _, _, G, r, dk, dv, C = dims
    f32 = _F32
    masks = _Masks(C)
    lane = jax.lax.broadcasted_iota(jnp.int32, (C, LANES), 1)
    row_id = jax.lax.broadcasted_iota(jnp.int32, (LANES, 1), 0)
    ones = jnp.ones((LANES, LANES), jnp.bfloat16)

    @pl.when(pl.program_id(2) == 0)
    def _start():
        ds_ref[...] = dfin_ref[...]

    for p in range(G):
        q, k = q_ref[0, :, p * dk:(p + 1) * dk], k_ref[0, :, p * dk:(p + 1) * dk]
        ch = _Chunk(q, k, _columns(col_ref, p, G), _rows(row_ref, p), masks, r)
        saved = t_ref[0, :, p * LANES:(p + 1) * LANES]  # (C, r * C)
        T = jnp.concatenate(
            [jnp.where(masks.block(lane, C) == a, saved, 0.0) for a in range(r)], axis=0)
        states = [st_ref[0, 0, p * r + a] for a in range(r)]  # entering the chunk
        dnext = [ds_ref[0, p * r + a] for a in range(r)]  # gradient of the state leaving it
        ch.read_state(states)
        v2 = _value_rows(v_ref, p, r, dv).astype(f32)
        y = v2 - ch.gamma * ch.kS
        w = ch.beta * y
        u = _dot(T, w, _NN)

        do = _value_rows(do_ref, p, r, dv)
        dof = do.astype(f32)
        dz = jnp.concatenate([_dot(k, d, _NN) for d in dnext], axis=0)
        du = _dot(ch.P, do, _TN) + ch.m * dz
        dP = jnp.where(masks.lower, _dot(do, u, _NT), 0.0)
        dw = _dot(T, du, _TN)
        dA = jnp.where(masks.strict, -_dot(dw, u, _NT), 0.0)  # -T^T dT T^T with dT = du w^T
        dy = ch.beta * dw

        # what runs through the per-token scalars: each is (scalar) x (its own gradient),
        # so it lands on the logarithm of whatever the scalar is a product of
        a_read = ch.alpha * _rowsum(dof * ch.qS)
        a_delta = -ch.gamma * _rowsum(dy * ch.kS)
        a_keep = ch.m * _rowsum(dz * u)
        gb = dA * ch.Ahat
        r_gb = _rowsum(gb)
        r_g = ch.beta * r_gb
        h = dP * ch.P
        r_h = _rowsum(h)
        c_gh = _dot(ch.beta * gb + h, ones, _TN)[:, :1]  # column sums, as a column
        log_rq = r_h + a_read
        log_rk = r_g + c_gh + a_delta + a_keep
        dcs = r_g + r_h - c_gh + a_read + a_delta - a_keep
        dbeta = _rowsum(dw * y) + r_gb

        dKK = dA * (ch.beta * ch.WA)
        dQK = dP * ch.WP
        dq2 = _dot(dQK, ch.k2, _NN)
        dk2 = _dot(dKK, ch.k2, _NN) + _dot(dKK, ch.k2, _TN) + _dot(dQK, ch.q2, _TN)

        xq = ch.alpha * dof  # gradient of q S
        xk = -ch.gamma * dy  # gradient of k S
        z = ch.m * u
        dq = jnp.zeros((C, dk), f32)
        dkk = jnp.zeros((C, dk), f32)
        lq = jnp.zeros((C, 1), f32)
        lk = jnp.zeros((C, 1), f32)
        for a in range(r):
            rows = slice(a * C, (a + 1) * C)
            hd = p * r + a
            x = jnp.concatenate([xk[rows], xq[rows]], axis=0)  # (2C, dv)
            xs = _dot(x, states[a], _NT)  # (2C, dk)
            dkk = dkk + dk2[rows] + xs[:C] + _dot(z[rows], dnext[a], _NT)
            dq = dq + dq2[rows] + xs[C:]
            lq, lk = lq + log_rq[rows], lk + log_rk[rows]
            ds_ref[0, hd] = dnext[a] * _keep(row_ref, p, a, dv) + _dot(ch.kq, x, _TN)
            dv_ref[0, :, hd * dv:(hd + 1) * dv] = dy[rows].astype(dv_ref.dtype)
            # the chunk's last log-decay: through the state's own decay and through m
            d_keep = jnp.sum(_rowsum(dnext[a] * states[a]), axis=0, keepdims=True)  # (1, 1)
            at_last = (jnp.exp(ch.last[a * C:a * C + 1]) * d_keep
                       + jnp.sum(a_keep[rows], axis=0, keepdims=True))
            dcs = dcs + jnp.where(row_id == (a + 1) * C - 1, at_last, 0.0)
        # the L2 scales are functions of q and k: d rq / dq = -rq^3 / scale^2 q
        rq, rk = ch.rq[:C], ch.rk[:C]
        dq = dq - (lq * rq * rq * (1.0 / (scale * scale))) * q.astype(f32)
        dkk = dkk - (lk * rk * rk) * k.astype(f32)
        dq_ref[0, :, p * dk:(p + 1) * dk] = dq.astype(dq_ref.dtype)
        dk_ref[0, :, p * dk:(p + 1) * dk] = dkk.astype(dk_ref.dtype)
        dcol_ref[0, 0, 0, :, p:p + 1] = dbeta
        dcol_ref[0, 0, 0, :, G + p:G + p + 1] = dcs


@functools.partial(jax.jit, static_argnames=("dims", "scale", "interpret"))
def _bwd_call(q, k, v, do, cols, rows, states, tsave, dfin, *, dims, scale, interpret):
    batch, S, Hk, G, r, dk, dv, C = dims
    nc = S // C
    spec = _specs(dims, lambda c: nc - 1 - c)
    ins = (q, k, v, do, cols, rows, states, tsave, dfin)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, dims=dims, scale=scale),
        grid=(batch, Hk // G, nc),
        in_specs=[spec["qk"], spec["qk"], spec["v"], spec["v"], spec["col"], spec["row"],
                  spec["states"], spec["t"], spec["state"]],
        out_specs=[spec["qk"], spec["qk"], spec["v"], spec["dcol"], spec["state"]],
        out_shape=[
            out_struct(q.shape, q.dtype, *ins),
            out_struct(k.shape, k.dtype, *ins),
            out_struct(v.shape, v.dtype, *ins),
            out_struct((batch, nc, Hk // G, LANES, 2 * G), _F32, *ins),
            out_struct(dfin.shape, _F32, *ins),
        ],
        compiler_params=_params(),
        interpret=interpret,
        name="gated_delta_bwd",
    )(*ins)


# ---- the differentiable core: layouts in, layouts out


def _dims(q, v):
    batch, S, Hk, dk = q.shape
    Hv, dv = v.shape[2], v.shape[3]
    r = Hv // Hk
    G = 2 if Hk % 2 == 0 else 1  # key heads a grid step: two independent chains to interleave
    return (batch, S, Hk, G, r, dk, dv, LANES // r)


def _vectors(cs, beta, rq, rk, dims):
    """The per-token vectors in the two layouts the kernels read: along the stacked rows,
    (B, chunks, groups, r * C, vectors * G), and along the lanes, (B, chunks, Hk, 8, r * C)."""
    batch, S, Hk, G, r, _, _, C = dims
    nc = S // C
    rq, rk = (jnp.repeat(x, r, axis=2) for x in (rq, rk))  # (B, S, Hk) -> (B, S, Hv)
    chunked = lambda x: x.reshape(batch, nc, C, Hk // G, G, r)  # noqa: E731
    last = jnp.broadcast_to(chunked(cs)[:, :, -1:], (batch, nc, C, Hk // G, G, r))
    cols = jnp.stack([chunked(beta), chunked(cs), chunked(rq), chunked(rk), last],
                     axis=0)  # (5, B, nc, C, groups, G, r)
    cols = cols.transpose(1, 2, 4, 6, 3, 0, 5).reshape(batch, nc, Hk // G, r * C, _N_COLS * G)
    flat = lambda x: (x.reshape(batch, nc, C, Hk, r).transpose(0, 1, 3, 4, 2)  # noqa: E731
                      .reshape(batch, nc, Hk, 1, r * C))
    last_rows = jnp.broadcast_to(
        cs.reshape(batch, nc, C, Hk, r)[:, :, -1].reshape(batch, nc, Hk, r, 1),
        (batch, nc, Hk, r, r * C))
    pad = jnp.zeros((batch, nc, Hk, _N_ROWS - _R_LAST - r, r * C), _F32)
    rows = jnp.concatenate([flat(beta), flat(cs), flat(rk), last_rows, pad], axis=3)
    return cols, rows


def _from_cols(x, dims):
    """(B, chunks, groups, r * C, G) -> (B, S, Hv)."""
    batch, S, Hk, G, r, _, _, C = dims
    x = x.reshape(batch, S // C, Hk // G, r, C, G)
    return x.transpose(0, 1, 4, 2, 5, 3).reshape(batch, S, Hk * r)


def _operands(q, k, v, cs, beta, rq, rk, s0):
    dims = _dims(q, v)
    batch, S, Hk, _, r, dk, dv, _ = dims
    f32 = _F32
    cols, rows = _vectors(cs.astype(f32), beta.astype(f32), rq.astype(f32), rk.astype(f32), dims)
    flat = (q.reshape(batch, S, Hk * dk), k.reshape(batch, S, Hk * dk),
            v.reshape(batch, S, Hk * r * dv), cols, rows, s0.astype(f32))
    return dims, flat


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def _rule(q, k, v, cs, beta, rq, rk, s0, interpret):
    """o (B, S, Hv, dv) and the final state (B, Hv, dk, dv) of the rule whose cumulative
    log-decay inside each chunk is ``cs`` and whose q and k are scaled by ``rq``, ``rk``."""
    dims, flat = _operands(q, k, v, cs, beta, rq, rk, s0)
    o, fin = _fwd_call(*flat, dims=dims, save=False, interpret=interpret)
    return o.reshape(v.shape), fin


def _rule_fwd(q, k, v, cs, beta, rq, rk, s0, interpret):
    dims, flat = _operands(q, k, v, cs, beta, rq, rk, s0)
    o, fin, states, tsave = _fwd_call(*flat, dims=dims, save=True, interpret=interpret)
    return (o.reshape(v.shape), fin), (q, k, v, cs, beta, rq, rk, s0, states, tsave)


def _rule_bwd(interpret, res, cot):
    q, k, v, cs, beta, rq, rk, s0, states, tsave = res
    do, dfin = cot
    dims, (qf, kf, vf, cols, rows, _) = _operands(q, k, v, cs, beta, rq, rk, s0)
    dq, dk, dv, dcol, ds0 = _bwd_call(
        qf, kf, vf, do.astype(q.dtype).reshape(vf.shape), cols, rows, states, tsave,
        dfin.astype(_F32), dims=dims, scale=q.shape[-1] ** -0.5, interpret=interpret)
    G = dims[3]
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            _from_cols(dcol[..., G:], dims).astype(cs.dtype),
            _from_cols(dcol[..., :G], dims).astype(beta.dtype),
            jnp.zeros_like(rq), jnp.zeros_like(rk), ds0.astype(s0.dtype))


_rule.defvjp(_rule_fwd, _rule_bwd)


def gated_delta_rule(query, key, value, g, beta, *, initial_state=None,
                     output_final_state: bool = False, interpret: bool = False):
    """``ops.gated_delta.chunk_gated_delta_rule``'s results through the kernels, q and k
    L2-normed: query, key (B, S, Hk, dk), value (B, S, Hv, dv), g and beta (B, S, Hv). The shapes must pass :func:`gated_delta_needs`; the chunk is the
    kernels' own (128 rows over the value heads of a key head)."""
    batch, S, Hk, dk = query.shape
    Hv, dv = value.shape[2], value.shape[3]
    f32 = _F32
    C = LANES // (Hv // Hk)
    sq = lambda x: jnp.sum(jnp.square(x.astype(f32)), axis=-1)  # noqa: E731
    # the L2 scales as values: their gradient is the backward kernel's
    rq = jax.lax.stop_gradient(jax.lax.rsqrt(sq(query) + _EPS) * dk ** -0.5)
    rk = jax.lax.stop_gradient(jax.lax.rsqrt(sq(key) + _EPS))
    cs = jnp.cumsum(g.astype(f32).reshape(batch, S // C, C, Hv), axis=2).reshape(batch, S, Hv)
    s0 = (jnp.zeros((batch, Hv, dk, dv), f32) if initial_state is None
          else initial_state.astype(f32))
    # float32 across the boundary, as the XLA form upcasts at its top: see "Precision"
    out, fin = _rule(query.astype(f32), key.astype(f32), value.astype(f32), cs, beta.astype(f32),
                     rq, rk, s0, interpret)
    return out.astype(query.dtype), (fin if output_final_state else None)
