"""Ring attention over the ``cp`` mesh axis — long-context context parallelism.

TPU-native replacement for the reference's two CP mechanisms (SURVEY.md §5): torch
DTensor experimental ``context_parallel`` ring SDPA (distributed/cp_utils.py:68) and
TransformerEngine p2p ring attention (moe/parallelizer.py:267-285). Here: q/k/v arrive
sequence-sharded over ``cp``; k/v (+ their positions/segment ids) rotate around the
ring via ``lax.ppermute`` while each shard accumulates online-softmax partials in
fp32. ppermute rides ICI neighbor links, and XLA overlaps the permute with the
current chunk's attention math.

Causality is enforced by *global* positions (each shard's token positions travel with
it), so any seq-dim layout works — including the load-balanced interleave the
reference gets from THD round-robin sharding (cp_utils.py:296-321).

Two per-chunk implementations:

- ``flash`` (default): Pallas chunk kernels (ops/pallas/ring_chunk.py) carrying the
  online-softmax state (acc, m, l) across ring steps in VMEM — no per-chunk
  (Sq_local x Skv_local) score matrix ever reaches HBM, which is the whole point of
  CP at long context. The ring is a ``lax.fori_loop`` (O(1) HLO at any cp), wrapped
  in a custom VJP whose backward runs a second ring: dk/dv accumulators travel WITH
  their kv chunk and arrive home after cp rotations.
- ``dense``: the plain-XLA partial-attention path (materializes per-chunk scores;
  differentiable by plain AD through an unrolled ring). Kept as the fallback for
  shapes the kernels can't tile and as the parity oracle in tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from automodel_tpu.ops import kernels
from automodel_tpu.ops.kernels import check_manual_region, manual_axes, note
from automodel_tpu.ops.pallas.flash_attention import (
    LANES,
    NEG_INF,
    _kv_sublanes,
    _q_lanes,
)

__all__ = ["ring_attention_local", "make_ring_attention"]


def _partial_attention(q, k, v, allowed, scale):
    """Unnormalized blockwise attention; returns (acc, m, l) in fp32.

    q/k (B, S, N|K, D); v (B, Sk, K, Dv) — Dv may differ from D (MLA's v_head_dim,
    moe/parallelizer.py:267-285 runs ring CP through TE for MLA the same way);
    allowed (B, Sq, Sk) bool or None. acc (B, K, G, Sq, Dv), m/l (B, K, G, Sq).
    """
    b, sq, n, d = q.shape
    kh = k.shape[2]
    g = n // kh
    qf = q.astype(jnp.float32).reshape(b, sq, kh, g, d)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qf, k.astype(jnp.float32)) * scale
    if allowed is not None:
        logits = jnp.where(allowed[:, None, None], logits, NEG_INF)
    m = logits.max(-1)  # (b, kh, g, sq)
    p = jnp.exp(logits - m[..., None])
    if allowed is not None:
        # fully-masked rows would otherwise contribute exp(0)=1 per masked entry
        p = jnp.where(allowed[:, None, None], p, 0.0)
    l = p.sum(-1)
    acc = jnp.einsum("bkgqs,bskd->bkgqd", p, v.astype(jnp.float32))
    return acc, m, l


def _rotate(tree, axis, perm):
    return jax.tree.map(
        lambda x: jax.lax.ppermute(x, axis, perm) if x is not None else None,
        tree, is_leaf=lambda x: x is None,
    )


def _gqa_sum(g, groups):
    """(BN, S, d) per-q-head grads -> (BK, S, d) kv-row grads."""
    if groups == 1:
        return g
    return g.reshape(-1, groups, *g.shape[1:]).sum(1)


# cfg: (axis, causal, window, scale, block_q, block_k, groups, n_heads,
#       interpret, kv_chunk) — hashable, so it rides nondiff_argnums.
@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _ring_flash(q, k, v, pq, pkv, sq, skv, cfg):
    out, _ = _ring_flash_fwd(q, k, v, pq, pkv, sq, skv, cfg)
    return out


def _ring_flash_fwd(q, k, v, pq, pkv, sq, skv, cfg):
    from automodel_tpu.ops.pallas.ring_chunk import chunk_attention_fwd

    axis, causal, window, scale, bq, bk, groups, nh, interp, _ = cfg
    cp = jax.lax.axis_size(axis)
    bn, sqlen, _ = q.shape
    dv = v.shape[-1]
    perm = [(j, (j + 1) % cp) for j in range(cp)]

    # pvary: the carry must be marked varying-over-cp like the pallas outputs
    # that replace it each iteration, or shard_map's vma check rejects the loop
    acc = jax.lax.pcast(jnp.zeros((bn, sqlen, dv), jnp.float32), axis, to='varying')
    m = jax.lax.pcast(jnp.full((bn, sqlen, LANES), NEG_INF, jnp.float32), axis, to='varying')
    l = jax.lax.pcast(jnp.zeros((bn, sqlen, LANES), jnp.float32), axis, to='varying')

    def body(_, carry):
        kv_bundle, acc, m, l = carry
        k_i, v_i, pkv_i, skv_i = kv_bundle
        acc, m, l = chunk_attention_fwd(
            q, k_i, v_i, pq, pkv_i, sq, skv_i, acc, m, l,
            scale=scale, causal=causal, window=window, groups=groups,
            n_heads=nh, block_q=bq, block_k=bk, interpret=interp,
            vma=frozenset({axis}),
        )
        # rotate every step: after cp rotations the bundle is home again, and
        # an unconditional rotate keeps the loop body collective-uniform
        return _rotate(kv_bundle, axis, perm), acc, m, l

    _, acc, m, l = jax.lax.fori_loop(0, cp, body, ((k, v, pkv, skv), acc, m, l))

    l0 = l[:, :, :1]
    out = (acc / jnp.where(l0 == 0.0, 1.0, l0)).astype(q.dtype)
    # save lse COMPACT (bn, sq, 1): every lane is identical by construction,
    # and the residual lives from fwd to bwd — a LANES-broadcast copy here
    # would 128x the per-layer activation memory at exactly the long-context
    # sizes CP exists for; the bwd re-broadcasts transiently
    lse = jnp.where(l0 == 0.0, NEG_INF,
                    m[:, :, :1] + jnp.log(jnp.where(l0 == 0.0, 1.0, l0)))
    return out, (q, k, v, pq, pkv, sq, skv, out, lse)


def _ring_flash_bwd(cfg, res, do):
    from automodel_tpu.ops.pallas.ring_chunk import chunk_attention_bwd

    axis, causal, window, scale, bq, bk, groups, nh, interp, kv_chunk = cfg
    q, k, v, pq, pkv, sq, skv, out, lse = res
    cp = jax.lax.axis_size(axis)
    perm = [(j, (j + 1) % cp) for j in range(cp)]
    skv_len = k.shape[1]
    lse = jnp.broadcast_to(lse, (*lse.shape[:2], LANES))  # compact -> lanes
    delta = _q_lanes((out.astype(jnp.float32) * do.astype(jnp.float32)).sum(-1))

    # bound the bwd kernel's full-(Skv, d) dk/dv scratch by sub-chunking kv;
    # each sub-chunk is an independent kernel call (dq partials sum, dk/dv
    # slices concatenate), so VMEM stays flat in sequence length. The chunk
    # must hold whole kernel blocks AND tile the local kv length — otherwise
    # fall back to one full-length chunk.
    kvc = max(bk, (kv_chunk // bk) * bk) if kv_chunk else skv_len
    if skv_len % kvc:
        kvc = skv_len
    # the fused kernel holds four (bq, bk) f32 intermediates (s, p, dp, ds): at
    # 1024 x 1024 the v5e compiler counts 17.2 MiB against its 16 MiB of scoped
    # VMEM and refuses the step. 512 q rows, as flash_attention's fused backward.
    bq = min(bq, _BWD_BLOCK_Q)

    def body(_, carry):
        bundle, dq = carry
        k_i, v_i, pkv_i, skv_i, dk_i, dv_i = bundle
        for c in range(skv_len // kvc):
            rows = slice(c * kvc, (c + 1) * kvc)
            dq_p, dk_c, dv_c = chunk_attention_bwd(
                q, k_i[:, rows], v_i[:, rows], pq, pkv_i[:, :, rows], sq,
                None if skv_i is None else skv_i[:, :, rows], do, lse, delta,
                scale=scale, causal=causal, window=window, groups=groups,
                n_heads=nh, block_q=bq, block_k=bk, interpret=interp,
                vma=frozenset({axis}),
            )
            dq = dq + dq_p
            dk_i = dk_i.at[:, rows].add(_gqa_sum(dk_c, groups))
            dv_i = dv_i.at[:, rows].add(_gqa_sum(dv_c, groups))
        # dk/dv travel WITH their kv chunk; after cp rotations they are home
        return _rotate((k_i, v_i, pkv_i, skv_i, dk_i, dv_i), axis, perm), dq

    dq0 = jax.lax.pcast(jnp.zeros(q.shape, jnp.float32), axis, to='varying')
    dk0 = jax.lax.pcast(jnp.zeros(k.shape, jnp.float32), axis, to='varying')
    dv0 = jax.lax.pcast(jnp.zeros(v.shape, jnp.float32), axis, to='varying')
    bundle, dq = jax.lax.fori_loop(
        0, cp, body, ((k, v, pkv, skv, dk0, dv0), dq0)
    )
    _, _, _, _, dk, dv = bundle
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            None, None, None, None)


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


_BWD_BLOCK_Q = 512


def _pick_block(seq, target):
    """Largest power-of-two block <= target dividing seq (>= 8); 0 if none."""
    b = 1 << (max(min(target, seq), 8).bit_length() - 1)
    while b > 8 and seq % b:
        b //= 2
    return b if seq % b == 0 else 0


def ring_attention_local(
    q: jnp.ndarray,  # (B, Sq_local, N, D)
    k: jnp.ndarray,  # (B, Skv_local, K, D)
    v: jnp.ndarray,
    positions_q: jnp.ndarray,  # (B, Sq_local) global positions
    positions_kv: jnp.ndarray,  # (B, Skv_local)
    segment_ids_q: jnp.ndarray | None = None,  # (B, Sq_local)
    segment_ids_kv: jnp.ndarray | None = None,
    *,
    axis: str = "cp",
    causal: bool = True,
    sliding_window: int | None = None,
    softmax_scale: float | None = None,
    impl: str | None = None,  # "flash" | "dense" | None = auto
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,  # None = auto (True off-TPU)
    # kv rows per backward kernel call at head_dim <= 128 (fewer for wider
    # heads): 2048 rows of f32 dk/dv scratch and double-buffered output blocks
    # are 6 MiB; at 4096 the v5e compiler refuses the backward (19 MiB against
    # 16 MiB of scoped VMEM)
    kv_chunk: int = 2048,
) -> jnp.ndarray:
    """The per-shard body — call inside shard_map manual over ``axis``."""
    cp = jax.lax.axis_size(axis)
    b, sq, n, d = q.shape
    dv = v.shape[-1]
    kh = k.shape[2]
    g = n // kh
    scale = softmax_scale if softmax_scale is not None else d**-0.5
    if impl not in (None, "flash", "dense"):
        raise ValueError(f"unknown ring impl {impl!r} (flash | dense | None=auto)")

    if impl is None or impl == "flash":
        # (1024, 1024) blocks fit the v5e's 16 MiB of scoped VMEM up to
        # head_dim 128; at 256 the forward's accumulators take 18 MiB there
        target = 1024 if max(d, dv) <= 128 else 512
        bq = _pick_block(sq, block_q or target)
        bk = _pick_block(k.shape[1], block_k or target)
        flash_ok = bq > 0 and bk > 0
        if impl == "flash" and not flash_ok:
            raise ValueError(
                f"ring flash needs power-of-two-tileable local seqs, got "
                f"sq={sq}, skv={k.shape[1]}"
            )
        if flash_ok:
            if interpret is None:
                interpret = kernels.interpret_mode()
            note("ring_attention", "flash", interpret=interpret)
            if not interpret:
                check_manual_region("ring attention: flash")
            # rows: (B, S, H, D) -> (B*H, S, D); kv heads stay un-repeated
            qf = q.transpose(0, 2, 1, 3).reshape(b * n, sq, d)
            kf = k.transpose(0, 2, 1, 3).reshape(b * kh, k.shape[1], d)
            vf = v.transpose(0, 2, 1, 3).reshape(b * kh, v.shape[1], dv)
            pq = _q_lanes(positions_q.astype(jnp.int32))
            pkv = _kv_sublanes(positions_kv.astype(jnp.int32))
            sq_ids = skv_ids = None
            if segment_ids_q is not None or segment_ids_kv is not None:
                a = segment_ids_q if segment_ids_q is not None else segment_ids_kv
                c = segment_ids_kv if segment_ids_kv is not None else segment_ids_q
                sq_ids = _q_lanes(a.astype(jnp.int32))
                skv_ids = _kv_sublanes(c.astype(jnp.int32))
            cfg = (axis, causal, sliding_window, scale, bq, bk, g, n,
                   interpret, kv_chunk * 128 // max(d, dv, 128))
            o = _ring_flash(qf, kf, vf, pq, pkv, sq_ids, skv_ids, cfg)
            return o.reshape(b, n, sq, dv).transpose(0, 2, 1, 3)

    # dense fallback: plain-XLA partials, unrolled ring, plain AD
    note("ring_attention", "dense",
         reason="asked for" if impl == "dense" else
         f"flash unusable: local seqs ({sq}, {k.shape[1]}) do not tile by a power of two >= 8")
    perm = [(j, (j + 1) % cp) for j in range(cp)]
    acc = jnp.zeros((b, kh, g, sq, dv), jnp.float32)
    m = jnp.full((b, kh, g, sq), NEG_INF, jnp.float32)
    l = jnp.zeros((b, kh, g, sq), jnp.float32)
    kv = (k, v, positions_kv, segment_ids_kv)

    for step in range(cp):
        k_i, v_i, pos_kv, seg_kv = kv
        allowed = None

        def _and(a, b):
            return b if a is None else jnp.logical_and(a, b)

        if causal:
            allowed = _and(allowed, positions_q[:, :, None] >= pos_kv[:, None, :])
        if sliding_window is not None:
            allowed = _and(
                allowed, positions_q[:, :, None] - pos_kv[:, None, :] < sliding_window
            )
        if segment_ids_q is not None:
            allowed = _and(
                allowed, segment_ids_q[:, :, None] == seg_kv[:, None, :]
            )

        acc_i, m_i, l_i = _partial_attention(q, k_i, v_i, allowed, scale)
        m_new = jnp.maximum(m, m_i)
        alpha = jnp.exp(m - m_new)
        beta = jnp.exp(m_i - m_new)
        acc = acc * alpha[..., None] + acc_i * beta[..., None]
        l = l * alpha + l_i * beta
        m = m_new

        if step < cp - 1:
            kv = _rotate(kv, axis, perm)

    out = acc / jnp.where(l == 0.0, 1.0, l)[..., None]  # (b, kh, g, sq, dv)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, n, dv).astype(q.dtype)


def _flash_interpret_mode(global_seq: int, cp: int, impl: str | None,
                          block_q: int | None, block_k: int | None) -> bool:
    """True iff :func:`ring_attention_local` will run interpret-mode pallas.

    Mirrors the local body's decision: the flash path is taken when it isn't
    disabled (``impl="dense"``) and the per-shard seq lengths tile, and it
    interprets only off-TPU. Only that combination needs ``check_vma=False``
    on the enclosing shard_map (see make_ring_attention).
    """
    if impl == "dense" or not kernels.interpret_mode():
        return False
    sq = global_seq // cp
    return _pick_block(sq, block_q or 1024) > 0 and _pick_block(sq, block_k or 1024) > 0


def make_ring_attention(
    mesh: Mesh,
    *,
    cp_axis: str = "cp",
    causal: bool = True,
    sliding_window: int | None = None,
    softmax_scale: float | None = None,
    impl: str | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
):
    """Wrap :func:`ring_attention_local` in a partial-manual shard_map over ``cp``.

    Inputs are global arrays with the seq dim sharded over ``cp`` (other axes stay
    GSPMD-managed). Returns ``fn(q, k, v, positions, segment_ids=None) -> out``.
    """

    # jit: eager shard_map dispatch rejects partial-manual + check_vma=False;
    # the traced path (the only one models ever take) is fine
    @jax.jit
    def fn(q, k, v, positions, segment_ids=None):
        seq_spec = P(None, cp_axis)

        def body(q, k, v, positions, segment_ids):
            return ring_attention_local(
                q, k, v, positions, positions,
                segment_ids, segment_ids,
                axis=cp_axis, causal=causal,
                sliding_window=sliding_window, softmax_scale=softmax_scale,
                impl=impl, block_q=block_q, block_k=block_k,
            )

        return jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(
                P(None, cp_axis, None, None),
                P(None, cp_axis, None, None),
                P(None, cp_axis, None, None),
                seq_spec,
                None if segment_ids is None else seq_spec,
            ),
            out_specs=P(None, cp_axis, None, None),
            axis_names=manual_axes(mesh, cp_axis),
            # interpret-mode pallas lowering (the flash path off-TPU)
            # internally mixes varying and unvarying operands
            # (dynamic_slice), which the vma checker rejects; JAX's own
            # error message prescribes check_vma=False there. Real-TPU runs
            # (and the dense fallback anywhere) keep the varying-mesh-axes
            # consistency check — it's exactly the multi-chip configurations
            # that benefit from it
            check_vma=not _flash_interpret_mode(
                q.shape[1], mesh.shape[cp_axis], impl, block_q, block_k),
        )(q, k, v, positions, segment_ids)

    return fn
