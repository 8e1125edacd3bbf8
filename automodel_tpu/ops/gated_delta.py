"""Gated DeltaNet linear attention (Qwen3-Next) — TPU-native chunked form.

Implements the chunked gated delta rule used by Qwen3-Next's ``linear_attention``
layers (reference models/qwen3_next/model.py:39 delegates to HF/flash-linear-attention;
math mirrored from transformers torch_chunk_gated_delta_rule,
modeling_qwen3_next.py:442-517).

Two implementations of that one algorithm, and :func:`chunk_gated_delta_rule` picks by
what the call can observe (``ops.kernels.kernel_usable``, kernel name ``gated_delta``;
the answer and its reason go to the run header's ``kernels`` block):

- ``pallas`` (``ops/pallas/gated_delta.py``, kernels ``gated_delta_fwd`` /
  ``gated_delta_bwd``): on a TPU, on one device, q and k L2-normed, head widths
  multiples of 128, one, two or four value heads a key head, the sequence a multiple
  of the kernels' chunk (128 over the value heads of a key head: 64 at two). Training
  and prefill at such shapes (``initial_state`` and ``output_final_state`` are carried).
  The tables and the carried state stay in VMEM; the backward is its own kernel.
- ``xla`` (:func:`chunk_gated_delta_rule_xla`): everywhere else (the CPU, a mesh of
  several devices, decode's one token, unaligned shapes), and the tests' reference:
  - the intra-chunk "UT transform" — the reference builds the inverse of the unit
    lower-triangular matrix ``(I - tril(kᵝ·kᵀ ⊙ decay))`` with a Python loop over rows —
    is a batched ``solve_triangular`` (one fused MXU-friendly op, differentiable);
  - the inter-chunk recurrence is a ``lax.scan`` over chunks carrying the (dk, dv)
    state, so XLA sees a compact loop with static shapes;
  - everything runs in fp32 (the decays ``exp(g)`` underflow in bf16), cast back at the
    end, matching the reference kernel's fp32 accumulation.

Both upcast q, k and v to float32 first and keep in float32 whatever follows: the
L2-normed q and k, ``k_beta``, the decay tables, ``A``, its inverse, the carried state and
every accumulator; the output leaves in query's dtype. The kernels' "Precision"
paragraph says how they do it on the MXU. query and key may come with the
key heads alone, (B, S, Hk, dk) under Hv value heads: value head ``h`` reads key head
``h // (Hv // Hk)`` (``jnp.repeat``'s order; the kernels read a key head once).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from automodel_tpu.ops.kernels import kernel_usable

_P = jax.lax.Precision.HIGHEST  # delta-rule recurrence compounds matmul error; keep fp32 MXU passes

__all__ = [
    "l2norm", "causal_conv1d", "conv_state_from_prefill", "conv_step",
    "gated_rms_norm", "chunk_gated_delta_rule", "chunk_gated_delta_rule_xla",
]


def conv_state_from_prefill(x: jnp.ndarray, lens: jnp.ndarray, kernel: int) -> jnp.ndarray:
    """Trailing ``kernel-1`` VALID pre-conv inputs per row — the decode conv state
    after a right-padded prefill. ``x`` (B, S, C), ``lens`` (B,) valid lengths
    (valid region contiguous from 0). Short prompts left-fill with zeros, matching
    the causal conv's implicit left padding."""
    padded = jnp.pad(x, ((0, 0), (kernel - 1, 0), (0, 0)))
    return jax.vmap(
        lambda p, n: jax.lax.dynamic_slice(p, (n, 0), (kernel - 1, p.shape[-1]))
    )(padded, lens.astype(jnp.int32))


def conv_step(
    state: jnp.ndarray,  # (B, K-1, C) trailing pre-conv inputs
    x: jnp.ndarray,  # (B, s, C) new pre-conv inputs (decode: s = 1)
    weight: jnp.ndarray,  # (C, K)
    bias: jnp.ndarray | None = None,
    activation: str = "silu",
):
    """Continue a causal depthwise conv from carried state: returns
    ``(out (B, s, C), new_state (B, K-1, C))``."""
    kernel = weight.shape[-1]
    full = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    out = causal_conv1d(full, weight, activation=activation, bias=bias)[:, kernel - 1:]
    return out, full[:, full.shape[1] - (kernel - 1):]


def l2norm(x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    """FLA-style L2 normalization over the last dim (modeling_qwen3_next.py:436)."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def causal_conv1d(
    x: jnp.ndarray,
    weight: jnp.ndarray,
    activation: str = "silu",
    segment_ids: jnp.ndarray | None = None,
    bias: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Depthwise causal conv over the sequence dim.

    x: (B, S, C), weight: (C, K). Left-pads K-1 so output[t] only sees inputs <= t
    (HF causal_conv1d_fn semantics, conv state = trailing K-1 inputs). With
    ``segment_ids`` (B, S), taps from other packed documents are zeroed — K explicit
    shifted adds (K is 4; cheaper than a masked conv and fuses into one XLA loop).
    """
    if segment_ids is not None:
        K = weight.shape[-1]
        y = x * weight[:, K - 1]
        for j in range(1, K):
            shifted = jnp.pad(x, ((0, 0), (j, 0), (0, 0)))[:, : x.shape[1]]
            seg_shift = jnp.pad(segment_ids, ((0, 0), (j, 0)))[:, : x.shape[1]]
            same = (seg_shift == segment_ids)[..., None].astype(x.dtype)
            y = y + shifted * same * weight[:, K - 1 - j]
        if bias is not None:
            y = y + bias
        if activation == "silu":
            y = jax.nn.silu(y)
        return y
    ch = x.shape[-1]
    lhs = x.swapaxes(1, 2)  # (B, C, S)
    rhs = weight[:, None, :]  # (C, 1, K) = (out, in/groups, K)
    y = jax.lax.conv_general_dilated(
        lhs, rhs,
        window_strides=(1,),
        padding=[(weight.shape[-1] - 1, 0)],
        dimension_numbers=("NCH", "OIH", "NCH"),
        feature_group_count=ch,
    )
    y = y.swapaxes(1, 2)
    if bias is not None:
        y = y + bias
    if activation == "silu":
        y = jax.nn.silu(y)
    elif activation is not None and activation != "none":
        raise NotImplementedError(f"conv activation {activation!r}")
    return y


def gated_rms_norm(x: jnp.ndarray, weight: jnp.ndarray, gate: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    """RMSNorm(x) * w, gated by silu(gate) — Qwen3NextRMSNormGated
    (modeling_qwen3_next.py:68-83; norm before gate)."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    xn = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    out = weight.astype(jnp.float32) * xn
    out = out * jax.nn.silu(gate.astype(jnp.float32))
    return out.astype(dtype)


def chunk_gated_delta_rule(
    query: jnp.ndarray,  # (B, S, Hk, dk), Hk the value heads or a divisor of them
    key: jnp.ndarray,  # (B, S, Hk, dk)
    value: jnp.ndarray,  # (B, S, H, dv)
    g: jnp.ndarray,  # (B, S, H) log-decay (<= 0)
    beta: jnp.ndarray,  # (B, S, H) write strength in (0, 1)
    *,
    chunk_size: int = 64,
    initial_state: jnp.ndarray | None = None,  # (B, H, dk, dv)
    output_final_state: bool = False,
    use_qk_l2norm: bool = True,
    mesh=None,  # the mesh the operands live on, where the caller knows it
    interpret: bool | None = None,  # True: the kernels through the interpreter (CPU tests)
):
    """Chunked gated delta rule: S_t = S_{t-1}·exp(g_t)·(I − β_t k_t k_tᵀ) + β_t k_t v_tᵀ,
    o_t = q_tᵀ S_t. Returns (out (B, S, H, dv), final_state | None).

    Runs the Pallas kernels where they can run and :func:`chunk_gated_delta_rule_xla`
    where they cannot; which one, and why, is recorded once per distinct answer
    (:mod:`automodel_tpu.ops.kernels`, kernel ``gated_delta``). ``chunk_size`` is the XLA
    form's (the rule is the same for any; the kernels choose their own)."""
    from automodel_tpu.ops.pallas.gated_delta import gated_delta_needs, gated_delta_rule

    if mesh is not None:
        devices = mesh.size
    else:
        am = jax.sharding.get_abstract_mesh()
        devices = 1 if am.empty else am.size
    if kernel_usable(
        "gated_delta", requested="pallas", fallback="xla",
        needs=(*gated_delta_needs(query, key, value, use_qk_l2norm=use_qk_l2norm),
               (devices == 1, f"operands on a mesh of {devices} devices: no Mosaic kernel is "
                              "partitioned automatically, and the rule has no manual region yet")),
        interpret=interpret or None,
    ):
        return gated_delta_rule(query, key, value, g, beta, initial_state=initial_state,
                                output_final_state=output_final_state,
                                interpret=bool(interpret))
    return chunk_gated_delta_rule_xla(
        query, key, value, g, beta, chunk_size=chunk_size, initial_state=initial_state,
        output_final_state=output_final_state, use_qk_l2norm=use_qk_l2norm)


def chunk_gated_delta_rule_xla(
    query: jnp.ndarray,  # (B, S, Hk, dk)
    key: jnp.ndarray,  # (B, S, Hk, dk)
    value: jnp.ndarray,  # (B, S, H, dv)
    g: jnp.ndarray,  # (B, S, H) log-decay (<= 0)
    beta: jnp.ndarray,  # (B, S, H) write strength in (0, 1)
    *,
    chunk_size: int = 64,
    initial_state: jnp.ndarray | None = None,  # (B, H, dk, dv)
    output_final_state: bool = False,
    use_qk_l2norm: bool = True,
):
    """:func:`chunk_gated_delta_rule` as XLA operations, float32 throughout, cast back at
    the end."""
    if query.shape[2] != value.shape[2]:  # key heads alone: each serves H // Hk value heads
        query, key = (jnp.repeat(t, value.shape[2] // t.shape[2], axis=2) for t in (query, key))
    out_dtype = query.dtype
    B, S, H, dk = query.shape
    dv = value.shape[-1]

    if use_qk_l2norm:
        query = l2norm(query.astype(jnp.float32))
        key = l2norm(key.astype(jnp.float32))

    # (B, H, S, d) fp32
    q = query.astype(jnp.float32).transpose(0, 2, 1, 3) * (dk**-0.5)
    k = key.astype(jnp.float32).transpose(0, 2, 1, 3)
    v = value.astype(jnp.float32).transpose(0, 2, 1, 3)
    gf = g.astype(jnp.float32).transpose(0, 2, 1)
    bf = beta.astype(jnp.float32).transpose(0, 2, 1)

    C = chunk_size
    pad = (-S) % C
    if pad:
        q, k, v = (jnp.pad(t, ((0, 0), (0, 0), (0, pad), (0, 0))) for t in (q, k, v))
        gf, bf = (jnp.pad(t, ((0, 0), (0, 0), (0, pad))) for t in (gf, bf))
    N = (S + pad) // C

    # chunked views (B, H, N, C, d)
    q, k, v = (t.reshape(B, H, N, C, -1) for t in (q, k, v))
    gf = gf.reshape(B, H, N, C)
    bf = bf.reshape(B, H, N, C)

    k_beta = k * bf[..., None]
    v_beta = v * bf[..., None]

    gcs = jnp.cumsum(gf, axis=-1)  # within-chunk cumulative log decay
    # decay[i, j] = exp(gcs_i - gcs_j) for j <= i (lower incl diag), else 0.
    # Mask the exp *argument*, not its result: upper-triangle arguments are positive
    # and overflow, and where(mask, inf, 0) still propagates NaN cotangents.
    tril = jnp.tril(jnp.ones((C, C), bool))
    strict = jnp.tril(jnp.ones((C, C), bool), -1)
    log_decay = jnp.where(tril, gcs[..., :, None] - gcs[..., None, :], -jnp.inf)
    decay = jnp.exp(log_decay)

    # intra-chunk UT transform: T = (I + A)^-1, A = strict_tril(kᵝ kᵀ ⊙ decay)
    # (the reference builds this inverse with a Python loop over rows, :486-490)
    A = jnp.where(strict, jnp.einsum("bhncd,bhnmd->bhncm", k_beta, k, precision=_P) * decay, 0.0)
    eye = jnp.eye(C, dtype=jnp.float32)
    T = jax.scipy.linalg.solve_triangular(eye + A, jnp.broadcast_to(eye, A.shape), lower=True)

    v_new_c = jnp.einsum("bhncm,bhnmd->bhncd", T, v_beta, precision=_P)
    k_cumdecay = jnp.einsum("bhncm,bhnmd->bhncd", T, k_beta * jnp.exp(gcs)[..., None], precision=_P)

    # inter-chunk recurrence over N chunks
    if initial_state is None:
        state0 = jnp.zeros((B, H, dk, dv), jnp.float32)
    else:
        state0 = initial_state.astype(jnp.float32)

    # local (within-chunk) attention, lower-triangular incl diag
    attn_local = jnp.where(tril, jnp.einsum("bhncd,bhnmd->bhncm", q, k, precision=_P) * decay, 0.0)

    def step(state, xs):
        q_i, k_i, vn_i, kcd_i, al_i, gcs_i = xs
        v_prime = jnp.einsum("bhcd,bhde->bhce", kcd_i, state, precision=_P)
        v_new = vn_i - v_prime
        inter = jnp.einsum("bhcd,bhde->bhce", q_i * jnp.exp(gcs_i)[..., None], state, precision=_P)
        out_i = inter + jnp.einsum("bhcm,bhme->bhce", al_i, v_new, precision=_P)
        g_last = gcs_i[..., -1]
        k_scaled = k_i * jnp.exp(g_last[..., None] - gcs_i)[..., None]
        state = state * jnp.exp(g_last)[..., None, None] + jnp.einsum(
            "bhcd,bhce->bhde", k_scaled, v_new, precision=_P
        )
        return state, out_i

    xs = tuple(
        t.transpose(2, 0, 1, *range(3, t.ndim))  # chunk axis to front for scan
        for t in (q, k, v_new_c, k_cumdecay, attn_local, gcs)
    )
    final_state, outs = jax.lax.scan(step, state0, xs)
    out = outs.transpose(1, 2, 0, 3, 4).reshape(B, H, N * C, dv)[:, :, :S]
    out = out.transpose(0, 2, 1, 3).astype(out_dtype)  # (B, S, H, dv)
    return out, (final_state if output_final_state else None)
