"""Mamba2 (SSD) chunked scan — TPU-native (reference nemotron_v3/layers.py:155
delegates to mamba_ssm's Triton mamba_chunk_scan_combined; math per the Mamba2
paper's state-space dual form).

Same chunking skeleton as ops/gated_delta.py: intra-chunk terms are dense
MXU-friendly products under a cumulative log-decay mask; the inter-chunk recurrence
carries the (H, dh, N) state from chunk to chunk.

Two implementations of that one algorithm, and :func:`mamba_chunk_scan` picks by what
the call can observe (``ops.kernels.kernel_usable``, kernel name ``ssd_scan``; the
answer and its reason go to the run header's ``kernels`` block):

- ``pallas`` (``ops/pallas/ssd_scan.py``, kernels ``ssd_scan_fwd`` / ``ssd_scan_bwd``): on
  a TPU, on one device, at lane-aligned shapes (chunk and state multiples of 128,
  head_dim 64 with an even number of heads a group, or a multiple of 128). The decay
  tables and the carried state stay in VMEM; the backward is its own kernel.
- ``xla`` (:func:`mamba_chunk_scan_xla`): everywhere else, and the tests' reference.
  Einsums over tables held in HBM, a ``lax.scan`` over chunks, autodiff's backward.

What is bfloat16 and what is float32, in both. ``x``, ``B`` and ``C`` arrive in the
model's dtype (bf16 under ``backend.dtype: bfloat16``) and ``y`` leaves in it. Float32:
``dt`` (``softplus_dt`` returns it), ``A``, the cumulative log-decay and every ``exp``
of it (decay exponentials underflow bf16 and their differences cancel), the
decay-weighted scores, ``x * w``, the carried state, every accumulator. The XLA form
upcasts ``x``, ``B``, ``C`` and multiplies at ``Precision.HIGHEST``; the kernels feed
the bf16 operands to the MXU as they are (bf16 x bf16 into float32 is exact: the same
product) and split every float32 operand of a dot into three bf16 parts, which is
what ``HIGHEST`` does: no float32 quantity is rounded to one bf16 in either.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from automodel_tpu.ops.fp8 import project
from automodel_tpu.ops.gated_delta import causal_conv1d
from automodel_tpu.ops.kernels import kernel_usable

_P = jax.lax.Precision.HIGHEST  # recurrence compounds matmul error; keep fp32 MXU passes

__all__ = ["mamba2_mixer", "mamba_chunk_scan", "mamba_chunk_scan_xla", "group_rms_norm_gated",
           "softplus_dt"]


def mamba2_mixer(
    lp: dict,  # one layer's leaves, in the compute dtype (``a_log`` float32)
    x: jnp.ndarray,  # (B, S, D): the block's normed input
    *,
    num_heads: int,
    head_dim: int,
    n_groups: int,
    state_size: int,
    chunk_size: int,
    eps: float,
    time_step_limit: tuple[float, float] | None = None,
    linear: str = "default",  # ``BackendConfig.linear``: both projections go through it
    segment_ids: jnp.ndarray | None = None,  # packed documents: conv taps stay inside one
    reset_mask: jnp.ndarray | None = None,  # (B, S) True where a document starts
    mesh=None,
    segment_scale: jnp.ndarray | None = None,  # (proj,) float32 over z | x | B | C | dt
) -> jnp.ndarray:
    """The Mamba-2 mixer every family shares (Nemotron-H's blocks, Falcon-H1's beside its
    attention): ``[z | xBC | dt] = x W_in`` (times ``segment_scale``, Falcon-H1's muP
    vector, where given), depthwise causal conv and SiLU over ``xBC``, ``dt =
    softplus(dt + dt_bias)``, the SSD scan with the ``D`` skip, the gated group RMSNorm
    (gate before norm), ``W_out``. Returns the mixer's output (B, S, D); residual and
    input norm are the block's.

    Leaves: ``in_proj (D, 2 I + 2 G N + H)``, ``conv_w (I + 2 G N, K)``, ``dt_bias``,
    ``a_log``, ``d_skip (H,)``, ``gated_norm (I,)``, ``out_proj (I, D)``; optional
    ``b_conv``, ``b_in``, ``b_out``. Scopes: ``mamba_proj`` round the two projections,
    ``mamba_ssd`` round the scan alone (what the SSD kernels replace)."""
    B, S, _ = x.shape
    inter = num_heads * head_dim
    gns = n_groups * state_size
    with jax.named_scope("mamba_proj"):
        proj = project(x, lp["in_proj"], 1, linear)
        if "b_in" in lp:
            proj = proj + lp["b_in"]
        if segment_scale is not None:  # float32 scalars: the product is rounded once
            proj = (proj * segment_scale).astype(proj.dtype)
    gate, xbc, dt_raw = jnp.split(proj, [inter, 2 * inter + 2 * gns], axis=-1)
    xbc = causal_conv1d(xbc, lp["conv_w"], segment_ids=segment_ids, bias=lp.get("b_conv"))
    xi, Bm, Cm = jnp.split(xbc, [inter, inter + gns], axis=-1)
    dt = softplus_dt(dt_raw, lp["dt_bias"], time_step_limit)
    A = -jnp.exp(lp["a_log"].astype(jnp.float32))
    with jax.named_scope("mamba_ssd"):  # the scan alone: what an SSD kernel replaces
        y, _ = mamba_chunk_scan(
            xi.reshape(B, S, num_heads, head_dim), dt, A,
            Bm.reshape(B, S, n_groups, state_size), Cm.reshape(B, S, n_groups, state_size),
            lp["d_skip"], chunk_size=chunk_size, reset_mask=reset_mask, mesh=mesh,
        )
    y = group_rms_norm_gated(
        y.reshape(B, S, inter), lp["gated_norm"], gate, group_size=inter // n_groups, eps=eps,
    )
    with jax.named_scope("mamba_proj"):
        out = project(y, lp["out_proj"], 1, linear)
        if "b_out" in lp:
            out = out + lp["b_out"]
    return out


def softplus_dt(
    dt_raw: jnp.ndarray, dt_bias: jnp.ndarray, limit: tuple[float, float] | None = None
) -> jnp.ndarray:
    """softplus(dt + bias) with optional (min, max) clamp (config time_step_limit)."""
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + dt_bias.astype(jnp.float32))
    if limit is not None and tuple(limit) != (0.0, float("inf")):
        dt = jnp.clip(dt, limit[0], limit[1])
    return dt


def group_rms_norm_gated(
    x: jnp.ndarray,  # (..., inter)
    weight: jnp.ndarray,  # (inter,)
    gate: jnp.ndarray | None,  # (..., inter)
    group_size: int,
    eps: float = 1e-5,
    norm_before_gate: bool = False,
) -> jnp.ndarray:
    """mamba_ssm rmsnorm_fn semantics: with norm_before_gate=False (NemotronV3),
    the gate multiplies *before* the group-wise RMS normalization."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    if gate is not None and not norm_before_gate:
        xf = xf * jax.nn.silu(gate.astype(jnp.float32))
    g = xf.shape[-1] // group_size
    xg = xf.reshape(*xf.shape[:-1], g, group_size)
    xg = xg * jax.lax.rsqrt(jnp.mean(xg * xg, axis=-1, keepdims=True) + eps)
    out = xg.reshape(xf.shape) * weight.astype(jnp.float32)
    if gate is not None and norm_before_gate:
        out = out * jax.nn.silu(gate.astype(jnp.float32))
    return out.astype(dtype)


def mamba_chunk_scan(
    x: jnp.ndarray,  # (B, S, H, dh)
    dt: jnp.ndarray,  # (B, S, H) post-softplus step sizes
    A: jnp.ndarray,  # (H,) negative per-head decay rates
    Bm: jnp.ndarray,  # (B, S, G, N) input gates (grouped, broadcast over H//G heads)
    Cm: jnp.ndarray,  # (B, S, G, N) output gates
    D: jnp.ndarray | None = None,  # (H,) skip connection
    *,
    chunk_size: int = 128,
    initial_state: jnp.ndarray | None = None,  # (B, H, dh, N)
    output_final_state: bool = False,
    reset_mask: jnp.ndarray | None = None,  # (B, S) True at packed-document starts
    mesh=None,  # the mesh the operands live on, where the caller knows it
    interpret: bool | None = None,  # True: the kernels through the interpreter (CPU tests)
):
    """SSD: h_t = h_{t-1}·exp(dt_t A) + dt_t·(x_t ⊗ B_t); y_t = h_t·C_t + D·x_t.
    Returns (y (B, S, H, dh), final_state | None).

    Runs the Pallas kernels where they can run and :func:`mamba_chunk_scan_xla` where
    they cannot; which one, and why, is recorded once per distinct answer
    (:mod:`automodel_tpu.ops.kernels`, kernel ``ssd_scan``). ``reset_mask``,
    ``initial_state`` and ``output_final_state`` are served by both."""
    from automodel_tpu.ops.pallas.ssd_scan import ssd_scan, ssd_scan_needs

    if mesh is not None:
        devices = mesh.size
    else:
        am = jax.sharding.get_abstract_mesh()
        devices = 1 if am.empty else am.size
    options = dict(chunk_size=chunk_size, initial_state=initial_state,
                   output_final_state=output_final_state, reset_mask=reset_mask)
    if kernel_usable(
        "ssd_scan", requested="pallas", fallback="xla",
        needs=(*ssd_scan_needs(x, Bm, chunk_size),
               (devices == 1, f"operands on a mesh of {devices} devices: no Mosaic kernel is "
                              "partitioned automatically, and the scan has no manual region yet")),
        interpret=interpret or None,
    ):
        return ssd_scan(x, dt, A, Bm, Cm, D, interpret=bool(interpret), **options)
    return mamba_chunk_scan_xla(x, dt, A, Bm, Cm, D, **options)


def mamba_chunk_scan_xla(
    x: jnp.ndarray,  # (B, S, H, dh)
    dt: jnp.ndarray,  # (B, S, H) post-softplus step sizes
    A: jnp.ndarray,  # (H,) negative per-head decay rates
    Bm: jnp.ndarray,  # (B, S, G, N) input gates (grouped, broadcast over H//G heads)
    Cm: jnp.ndarray,  # (B, S, G, N) output gates
    D: jnp.ndarray | None = None,  # (H,) skip connection
    *,
    chunk_size: int = 128,
    initial_state: jnp.ndarray | None = None,  # (B, H, dh, N)
    output_final_state: bool = False,
    reset_mask: jnp.ndarray | None = None,  # (B, S) True at packed-document starts
):
    """:func:`mamba_chunk_scan` as XLA operations, float32 throughout, cast back at the end.

    ``reset_mask`` zeroes the recurrence across packed-document boundaries by
    injecting a large negative log-decay at segment starts (within-segment decays
    are cumulative-sum differences, so the injection cancels exactly there)."""
    out_dtype = x.dtype
    batch, S, H, dh = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    r = H // G

    # heads as (group, head in group): B and C are shared by a group's r heads, and the
    # einsums below broadcast them over r instead of holding r copies (at 128 heads in 8
    # groups, 4096 tokens and state 128 a repeated B, C or C.B^T is 268 MB in float32)
    xf = x.astype(jnp.float32).transpose(0, 2, 1, 3)  # (B,H,S,dh)
    dtf = dt.astype(jnp.float32).transpose(0, 2, 1)  # (B,H,S)
    Bf = Bm.astype(jnp.float32).transpose(0, 2, 1, 3)  # (B,G,S,N)
    Cf = Cm.astype(jnp.float32).transpose(0, 2, 1, 3)

    C_ = chunk_size
    pad = (-S) % C_
    if pad:
        xf = jnp.pad(xf, ((0, 0), (0, 0), (0, pad), (0, 0)))
        dtf = jnp.pad(dtf, ((0, 0), (0, 0), (0, pad)))
        Bf, Cf = (jnp.pad(t, ((0, 0), (0, 0), (0, pad), (0, 0))) for t in (Bf, Cf))
    Nc = (S + pad) // C_

    xf = xf.reshape(batch, G, r, Nc, C_, dh)
    dtf = dtf.reshape(batch, G, r, Nc, C_)
    Bf = Bf.reshape(batch, G, Nc, C_, N)
    Cf = Cf.reshape(batch, G, Nc, C_, N)

    dA = dtf * A.astype(jnp.float32).reshape(1, G, r, 1, 1)  # (B,G,r,Nc,C)
    if reset_mask is not None:
        rm = reset_mask.astype(jnp.float32)
        if pad:
            rm = jnp.pad(rm, ((0, 0), (0, pad)))
        dA = dA - 50.0 * rm.reshape(batch, 1, 1, Nc, C_)
    gcs = jnp.cumsum(dA, axis=-1)

    tril = jnp.tril(jnp.ones((C_, C_), bool))
    log_decay = jnp.where(tril, gcs[..., :, None] - gcs[..., None, :], -jnp.inf)
    decay = jnp.exp(log_decay)  # (B,G,r,Nc,C,C)

    # intra-chunk: y[i] = sum_{j<=i} (C_i·B_j) decay[i,j] dt_j x_j
    CB = jnp.einsum("bgnck,bgnmk->bgncm", Cf, Bf, precision=_P)  # (B,G,Nc,C,C)
    M = CB[:, :, None] * decay * dtf[..., None, :]
    y = jnp.einsum("bgrncm,bgrnmd->bgrncd", M, xf, precision=_P)

    # chunk state contributions: S_c = sum_j exp(gcs_last - gcs_j) dt_j B_j ⊗ x_j
    w = jnp.exp(gcs[..., -1:] - gcs) * dtf  # (B,G,r,Nc,C)
    chunk_states = jnp.einsum("bgrncd,bgnck->bgrndk", xf * w[..., None], Bf, precision=_P)

    # inter-chunk recurrence
    state0 = (
        jnp.zeros((batch, G, r, dh, N), jnp.float32)
        if initial_state is None
        else initial_state.astype(jnp.float32).reshape(batch, G, r, dh, N)
    )
    chunk_decay = jnp.exp(gcs[..., -1])  # (B,G,r,Nc)
    in_decay = jnp.exp(gcs)  # (B,G,r,Nc,C)

    def step(state, xs):
        cs_i, cd_i, ind_i, C_i = xs
        inter = jnp.einsum("bgck,bgrdk->bgrcd", C_i, state, precision=_P) * ind_i[..., None]
        state = state * cd_i[..., None, None] + cs_i
        return state, inter

    xs = (
        chunk_states.transpose(3, 0, 1, 2, 4, 5),  # (Nc,B,G,r,dh,N)
        chunk_decay.transpose(3, 0, 1, 2),
        in_decay.transpose(3, 0, 1, 2, 4),
        Cf.transpose(2, 0, 1, 3, 4),  # (Nc,B,G,C,N)
    )
    final_state, inters = jax.lax.scan(step, state0, xs)
    y = y + inters.transpose(1, 2, 3, 0, 4, 5)

    y = y.reshape(batch, H, Nc * C_, dh)[:, :, :S].transpose(0, 2, 1, 3)
    if D is not None:
        y = y + D.astype(jnp.float32)[None, None, :, None] * x.astype(jnp.float32)
    final_state = final_state.reshape(batch, H, dh, N) if output_final_state else None
    return y.astype(out_dtype), final_state
