"""Grouped expert FFNs (reference GroupedExperts*, components/moe/experts.py:158,478,661).

TPU-native compute paths replacing the reference's four CUDA backends
(loop / torch._grouped_mm / DeepEP+gmm / TransformerEngine):

- ``ragged_dot`` (default, dropless): sort token copies by expert id, one
  ``jax.lax.ragged_dot`` per projection (XLA's native grouped GEMM), weighted sum back
  into token order. No capacity, no dropped tokens, static shapes. A layer that holds
  all its experts moves its rows by gathers both ways, forward and backward (over the
  sort and its inverse: no scatter-add); a layer that holds a share of its router's
  experts takes the sorted rows in blocks of ``HELD_BLOCK_ROWS``, runs as many blocks
  as rows came and scatter-adds each block back (:func:`grouped_experts_apply`).
- ``pallas``: the same sorted layout through the blocked Pallas grouped GEMM
  (``ops/pallas/grouped_gemm.py``) — a hand-scheduled tile list with a fused
  custom-VJP backward, selected via ``backend.experts_backend="pallas"``. Falls
  back to ``ragged_dot`` per-shape when the tile picker rejects the dims.
- ``capacity`` (GShard-style): one-hot dispatch/combine einsums with a fixed per-expert
  capacity. Fully dense — XLA lays the all-to-all automatically when experts are sharded
  on ``ep`` — at the cost of dropped tokens past capacity.

Weight layout: ``gate_up_proj`` (E, D, 2I) with [gate | up] concatenated on the last dim
(non-gated activations: (E, D, I)), ``down_proj`` (E, I, D). HF interleaved layouts
(gpt-oss) are de-interleaved by the family state-dict adapter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from automodel_tpu.moe.config import MoEConfig

__all__ = [
    "HELD_BLOCK_ROWS",
    "init_expert_params",
    "expert_logical_axes",
    "expert_activation",
    "grouped_experts_apply",
    "capacity_experts_apply",
    "sort_held_rows",
]


def init_expert_params(cfg: MoEConfig, key: jax.Array, dtype=jnp.float32, init_std: float = 0.02) -> dict:
    E, D, I = cfg.held_experts, cfg.expert_dim, cfg.moe_inter_dim
    up_cols = 2 * I if cfg.gated else I
    k1, k2 = jax.random.split(key)
    params = {
        "gate_up_proj": (jax.random.normal(k1, (E, D, up_cols), jnp.float32) * init_std).astype(dtype),
        "down_proj": (jax.random.normal(k2, (E, I, D), jnp.float32) * init_std).astype(dtype),
    }
    if cfg.expert_bias:
        params["gate_up_bias"] = jnp.zeros((E, up_cols), dtype)
        params["down_bias"] = jnp.zeros((E, D), dtype)
    return params


def expert_logical_axes(cfg: MoEConfig) -> dict:
    axes = {
        "gate_up_proj": ("expert", "expert_embed", "expert_mlp"),
        "down_proj": ("expert", "expert_mlp", "expert_embed"),
    }
    if cfg.expert_bias:
        axes["gate_up_bias"] = ("expert", "expert_mlp")
        axes["down_bias"] = ("expert", "expert_embed")
    return axes


def expert_activation(cfg: MoEConfig, h: jnp.ndarray) -> jnp.ndarray:
    """Activation between the two expert GEMMs; h is (..., 2I) gated or (..., I) not.

    quick_geglu matches gpt-oss (reference quick_geglu_deepep, moe/experts.py:434):
    clamp, x*sigmoid(alpha*x) gate, and a +1 linear offset on the up branch.
    """
    if cfg.expert_activation == "swiglu":
        gate, up = jnp.split(h, 2, axis=-1)
        return jax.nn.silu(gate) * up
    if cfg.expert_activation == "quick_geglu":
        gate, up = jnp.split(h, 2, axis=-1)
        gate = jnp.minimum(gate, cfg.activation_limit)
        up = jnp.clip(up, -cfg.activation_limit, cfg.activation_limit)
        glu = gate * jax.nn.sigmoid(cfg.activation_alpha * gate)
        return glu * (up + 1.0)
    # relu2
    return jnp.square(jax.nn.relu(h))


def _expert_gemm(xs, w, group_sizes, experts_backend: str):
    """One grouped GEMM over the sorted-by-expert layout, backend-selected."""
    if experts_backend == "pallas":
        from automodel_tpu.ops import kernels
        from automodel_tpu.ops.pallas.grouped_gemm import grouped_matmul

        # interpret off-TPU: CPU tests exercise the real kernel logic; the
        # tile picker still gates the compiled path per shape on TPU
        interpret = kernels.interpret_mode()
        if not interpret:
            kernels.check_manual_region("experts_backend: pallas")
        return grouped_matmul(xs, w, group_sizes, interpret=interpret)
    return jax.lax.ragged_dot(xs, w, group_sizes)


def sorted_ragged_ffn(
    cfg: MoEConfig,
    params: dict,
    xs: jnp.ndarray,  # (N, D) tokens sorted so each expert's rows are contiguous
    sorted_expert_ids: jnp.ndarray,  # (N,) expert id of each row (ascending)
    group_sizes: jnp.ndarray,  # (n_experts_in_params,) per-expert row counts
    *,
    experts_backend: str = "ragged_dot",  # "ragged_dot" | "pallas"
    in_group: jnp.ndarray | None = None,  # (N, 1) bool, where xs holds more than the groups
) -> jnp.ndarray:
    """The grouped-GEMM FFN core shared by the GSPMD and explicit-EP paths:
    grouped GEMM gate_up -> bias -> activation -> grouped GEMM down -> bias.

    ``in_group`` (a share of the experts: ``xs`` is one block of fixed height and the
    groups cover its first rows only): a grouped GEMM promises nothing for the rows behind
    its groups, forward or backward, so they are zeroed between the two GEMMs; the caller
    zeroes them going in and coming out."""
    from jax.ad_checkpoint import checkpoint_name

    # "mlp_gate"/"mlp_act": the (tokens*K, 2I) expert intermediates are the MoE
    # analogue of the dense gate/up tensors — the mlp_* remat policies
    # (backend.py) save/recompute them the same way
    h = checkpoint_name(
        _expert_gemm(xs, params["gate_up_proj"], group_sizes, experts_backend), "mlp_gate"
    )
    if "gate_up_bias" in params:
        h = h + params["gate_up_bias"][sorted_expert_ids]
    if in_group is not None:  # before the activation: its derivative at garbage is garbage
        h = jnp.where(in_group, h, 0)
    act = checkpoint_name(expert_activation(cfg, h).astype(xs.dtype), "mlp_act")
    out = _expert_gemm(act, params["down_proj"], group_sizes, experts_backend)
    if "down_bias" in params:
        out = out + params["down_bias"][sorted_expert_ids]
    return out


def sort_held_rows(local_ids: jnp.ndarray, n_held: int, bound: int | None = None):
    """Order the rows of the held experts for the grouped GEMMs.

    ``local_ids`` (N,) is each row's expert counted from the first expert held here.
    Returns ``(order, sorted_ids, group_sizes (n_held,), n_rows)``.

    ``bound=None``: every row's expert is held (a layer with all its experts; the a2a
    body's received rows). ``order`` is the stable sort of all N rows, ``n_rows`` None.

    With a static ``bound``: a row whose id lies outside ``[0, n_held)`` belongs to an
    expert that is not here and takes no part. ``order`` (bound,) lists the held rows
    expert by expert, then rows that are not held up to the bound; ``group_sizes`` count
    the held rows alone, so ``n_rows = sum(group_sizes)`` rows are real however large the
    bound, and the rows behind them belong to no group. ``bound`` has to cover the most
    rows that can be routed here, so that nothing is dropped: it sizes the index arrays
    alone and is the ceiling of the caller's block loop, not the rows it works on.

    The one-chip share of an expert-parallel layer (:func:`grouped_experts_apply` with
    ``cfg.n_held_experts``) and the a2a body after its exchange
    (``dispatch._local_grouped_gemm``) both sort through here."""
    if bound is None:
        order = jnp.argsort(local_ids)  # stable: preserves token order within expert
        group_sizes = jnp.bincount(local_ids, length=n_held).astype(jnp.int32)
        return order, local_ids[order], group_sizes, None
    held = (local_ids >= 0) & (local_ids < n_held)
    key = jnp.where(held, local_ids, n_held)
    order = jnp.argsort(key)[:bound]
    group_sizes = jnp.bincount(key, length=n_held + 1)[:n_held].astype(jnp.int32)
    return order, jnp.minimum(key[order], n_held - 1), group_sizes, group_sizes.sum()


HELD_BLOCK_ROWS = 4096
"""Height of one block of a held share's sorted rows (:func:`grouped_experts_apply`).

A property of the operation, like a tile height: one value for every configuration,
a multiple of the MXU's 128-row tile, read from no routing. A block costs a fixed part
whatever its height (its grouped GEMMs read the held experts' weights, forward and
backward, and its backward adds a whole ``dW`` of both expert parameters into the
carried gradient) and a part that grows with its rows, real or not (gather, masks,
activation, scatter-add, their gradients). On a v5e at 16 experts of 1024 x 2688, value
and gradients (PERF.md, PR 33): the fixed part is 2.5-3 ms a block, the rows 0.6 us
each, so the two are equal near 4,500 rows. A lower block pays the fixed part more often
for the same rows (2,816 rows: 12.8 ms at 1024, 10.9 at 2048, 8.7 at 4096; every block
of the ceiling: 192, 119, 83 ms), a taller one pays for more rows that belong to no
expert (700 rows: 7.0, 7.2, 7.7 ms)."""


def _held_block(cfg, experts_backend, params, x, weights, rows, sorted_ids, group_sizes, b):
    """Block ``b`` of a held share: sorted rows ``[b B, (b + 1) B)``, ``B = HELD_BLOCK_ROWS``.

    Returns ``(contribution (B, D) float32, token_ids (B,))``: what these rows add to
    the tokens they came from. The block's groups are the whole groups clipped to its
    window (an expert's rows may straddle blocks); the rows behind the last group belong
    to no expert and are zeroed going in, between the GEMMs and coming out, because what
    a grouped GEMM leaves in them, forward or backward, must reach neither the result
    nor a gradient (the TPU's leaves what was there: not zeros). The forward loop and
    the backward loop of :func:`_held_share_apply` both run this one function."""
    B = HELD_BLOCK_ROWS
    lo = b * B
    group_ends = jnp.cumsum(group_sizes)
    block_sizes = jnp.clip(group_ends, lo, lo + B) - jnp.clip(group_ends - group_sizes, lo, lo + B)
    block_rows = jax.lax.dynamic_slice_in_dim(rows, lo, B)
    block_ids = jax.lax.dynamic_slice_in_dim(sorted_ids, lo, B)
    token_ids = block_rows // weights.shape[1]  # source token of each sorted copy
    in_group = (lo + jnp.arange(B) < group_ends[-1])[:, None]

    with jax.named_scope("moe_dispatch"):
        xs = jnp.where(in_group, x[token_ids], 0)
    out = sorted_ragged_ffn(cfg, params, xs, block_ids, block_sizes,
                            experts_backend=experts_backend, in_group=in_group)
    with jax.named_scope("moe_combine"):
        w_sorted = weights.reshape(-1)[block_rows].astype(jnp.float32)
        out = jnp.where(in_group, out, 0)  # before the weights: their gradient is this times dy
        return out.astype(jnp.float32) * w_sorted[:, None], token_ids


def _held_share_apply(cfg, params, x, weights, local_ids, experts_backend):
    """The held experts' part of the layer's output, (T, D) float32, at a cost that grows
    with the rows that came: a loop over blocks of ``HELD_BLOCK_ROWS`` sorted rows whose
    trip count, ``ceil(n_rows / B)``, is a run-time value. ``T * min(K, held)`` (a token
    picks distinct experts) is only the loop's ceiling: no routing can pass it, so
    nothing is ever dropped, and no routing pays for it but the one that fills it.

    A run-time trip count has no reverse-mode derivative, so the share brings its own:
    a second loop over the same blocks rebuilds one block's forward (``jax.vjp`` of
    :func:`_held_block`) and adds its cotangents into the carried gradients of ``x``,
    ``weights`` and the expert parameters. Saved for it are the inputs and the sort
    alone, nothing of the ceiling's height; the price is one more forward of the real
    rows. An expert's ``dW`` is summed over the blocks its rows straddle (at most
    ``ceil(T / B) + 1``) in the parameters' dtype. The names ``mlp_gate`` / ``mlp_act``
    inside :func:`sorted_ragged_ffn` are not visible to an outer remat policy from in
    here: no block's intermediates outlive its iteration, whatever the policy."""
    T, D = x.shape
    K = weights.shape[1]
    B = HELD_BLOCK_ROWS
    bound = T * min(K, cfg.held_experts)
    rows, sorted_ids, group_sizes, _ = sort_held_rows(local_ids, cfg.held_experts, bound)
    whole_blocks = -(-bound // B) * B  # a block's slice never runs off the end
    rows = jnp.pad(rows, (0, whole_blocks - bound))
    sorted_ids = jnp.pad(sorted_ids, (0, whole_blocks - bound))
    block = functools.partial(_held_block, cfg, experts_backend)

    def n_blocks(group_sizes):
        return (group_sizes.sum() + B - 1) // B

    def run(params, x, weights, rows, sorted_ids, group_sizes):
        def body(b, y):
            contribution, token_ids = block(params, x, weights, rows, sorted_ids, group_sizes, b)
            with jax.named_scope("moe_combine"):
                return y.at[token_ids].add(contribution)

        return jax.lax.fori_loop(0, n_blocks(group_sizes), body, jnp.zeros((T, D), jnp.float32))

    def fwd(*args):
        return run(*args), args

    def bwd(args, dy):
        params, x, weights, *sort = args

        def body(b, grads):
            _, pull, token_ids = jax.vjp(lambda p, x_, w: block(p, x_, w, *sort, b),
                                         params, x, weights, has_aux=True)
            with jax.named_scope("moe_combine"):
                return jax.tree.map(jnp.add, grads, pull(dy[token_ids]))

        zeros = jax.tree.map(jnp.zeros_like, (params, x, weights))
        return (*jax.lax.fori_loop(0, n_blocks(sort[-1]), body, zeros), None, None, None)

    share = jax.custom_vjp(run)
    share.defvjp(fwd, bwd)
    return share(params, x, weights, rows, sorted_ids, group_sizes)


def _take_rows(a: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``a[idx]`` along axis 0 for indices that lie in bounds: no clamp, no fill."""
    return a.at[idx].get(mode="promise_in_bounds")


# The row moves of a layer that holds all its experts. Every token has exactly K rows
# there, so ``rows`` (the stable sort of the flat (token, k) expert ids) is a permutation
# of ``arange(T * K)``, and with its inverse ``inv`` (``inv[rows[j]] = j``) the transpose
# of a gather by ``rows`` is a gather by ``inv`` and a sum over K. Autodiff would write
# both transposes as scatter-adds of (T K, D) rows, which the TPU runs as a sort, a gather
# and a segmented sum (4.8 ms a layer each at 65,536 x 2,048 against 2.7 for the gather and
# the sum over K: PERF.md, PR 49); the two functions below bring their own derivatives
# instead and save the index vectors alone beside their operands.


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _copies_in_expert_order(x, rows, inv, K):
    """Dispatch: ``x[rows // K]``, (T, D) -> (T K, D), each token's K copies laid expert
    by expert. Backward: the copies' cotangents gathered back into token order by
    ``inv`` and summed over K in float32."""
    # an identity on x's values, here for where its result lives: the compiler puts a
    # fresh 33 MB result into the chip's fast memory, and a gather from there runs at
    # 6.7 ns a row against 35 from HBM. Without it the forward pass of the Qwen3-MoE cell
    # gathered from an evicted HBM copy of x: 2.16 ms a layer against 0.42 (PERF.md, PR 49)
    bits = jnp.finfo(x.dtype)
    x = jax.lax.reduce_precision(x, exponent_bits=bits.nexp, mantissa_bits=bits.nmant)
    return _take_rows(x, rows // K)


def _copies_fwd(x, rows, inv, K):
    return _copies_in_expert_order(x, rows, inv, K), inv


def _copies_bwd(K, inv, dxs):
    dx = _take_rows(dxs, inv).reshape(-1, K, dxs.shape[1]).astype(jnp.float32).sum(1)
    return dx.astype(dxs.dtype), None, None


_copies_in_expert_order.defvjp(_copies_fwd, _copies_bwd)


@jax.custom_vjp
def _weighted_sum_in_token_order(out, weights, rows, inv):
    """Combine: ``y[t] = sum_k weights[t, k] * out[inv[t K + k]]``, (T K, D) -> (T, D)
    float32. Backward: ``d_out`` is ``dy``'s rows gathered into expert order times each
    row's weight; ``d_weights`` is reckoned in expert order from those same rows and its
    T K scalars move back by ``inv``."""
    T, K = weights.shape
    out_tk = _take_rows(out, inv).reshape(T, K, out.shape[1])
    return (out_tk.astype(jnp.float32) * weights.astype(jnp.float32)[:, :, None]).sum(1)


def _weighted_sum_fwd(out, weights, rows, inv):
    return _weighted_sum_in_token_order(out, weights, rows, inv), (out, weights, rows, inv)


def _weighted_sum_bwd(res, dy):
    out, weights, rows, inv = res
    dy_sorted = _take_rows(dy, rows // weights.shape[1])  # (T K, D), one gather for both
    w_sorted = _take_rows(weights.reshape(-1), rows).astype(jnp.float32)
    d_out = (dy_sorted * w_sorted[:, None]).astype(out.dtype)
    d_w_sorted = (out.astype(jnp.float32) * dy_sorted).sum(-1)
    d_weights = _take_rows(d_w_sorted, inv).reshape(weights.shape).astype(weights.dtype)
    return d_out, d_weights, None, None


_weighted_sum_in_token_order.defvjp(_weighted_sum_fwd, _weighted_sum_bwd)


def grouped_experts_apply(
    cfg: MoEConfig,
    params: dict,
    x: jnp.ndarray,  # (T, expert_dim)
    weights: jnp.ndarray,  # (T, K)
    indices: jnp.ndarray,  # (T, K) int32, over all cfg.n_routed_experts
    token_mask: jnp.ndarray | None = None,  # (T,) bool; masked tokens contribute zero
    *,
    experts_backend: str = "ragged_dot",
) -> jnp.ndarray:
    """Dropless grouped-GEMM expert compute; returns (T, expert_dim).

    Token copies are sorted by expert id so each expert's tokens are contiguous, which
    is exactly the operand layout ``lax.ragged_dot`` wants (group_sizes = per-expert
    counts). The final combine sums each token's K weighted rows in fp32.

    Where the layer holds a share of the experts (``cfg.n_held_experts``), only the
    (token, expert) pairs whose expert is held are gathered, multiplied and combined:
    the result is these experts' part of the layer's output, and the work is that of the
    pairs that came (:func:`_held_share_apply`). A layer that holds all its experts (every
    row is real, exactly K a token) takes the straight-line code below: its rows go to
    expert order and back by gathers over the sort and its inverse, in the backward pass
    too (:func:`_copies_in_expert_order`, :func:`_weighted_sum_in_token_order`).
    """
    K = indices.shape[1]
    if token_mask is not None:
        weights = weights * token_mask[:, None].astype(weights.dtype)

    local = indices.reshape(-1) - cfg.first_held_expert  # (T*K,)
    if not cfg.holds_all_experts:
        y = _held_share_apply(cfg, params, x, weights, local, experts_backend)
        return y.astype(x.dtype)
    rows, sorted_ids, group_sizes, _ = sort_held_rows(local, cfg.held_experts)
    inv = jnp.argsort(rows)  # rows is a permutation of arange(T * K): inv[rows[j]] = j

    # named scopes label the dispatch/combine regions in the optimized HLO, so
    # hlo_costs can attribute GSPMD-inserted reshard collectives to moe_a2a and
    # a trace reader can sum their device time (same labels the explicit-EP
    # path uses as ep_dispatch/ep_combine)
    with jax.named_scope("moe_dispatch"):
        xs = _copies_in_expert_order(x, rows, inv, K)  # gathered copies, expert-contiguous
    out = sorted_ragged_ffn(cfg, params, xs, sorted_ids, group_sizes,
                            experts_backend=experts_backend)

    with jax.named_scope("moe_combine"):
        y = _weighted_sum_in_token_order(out, weights, rows, inv)
    return y.astype(x.dtype)


def capacity_experts_apply(
    cfg: MoEConfig,
    params: dict,
    x: jnp.ndarray,  # (T, D)
    weights: jnp.ndarray,  # (T, K)
    indices: jnp.ndarray,  # (T, K)
    token_mask: jnp.ndarray | None = None,  # (T,) bool; masked tokens take no slots
    *,
    capacity_factor: float = 1.25,
    capacity: int | None = None,
) -> jnp.ndarray:
    """GShard-style one-hot dispatch/combine with per-expert capacity; returns (T, D).

    Tokens past an expert's capacity are dropped (contribute zero), the standard
    capacity-factor trade-off. Position within each expert's queue comes from a cumsum
    over the token dim, so earlier tokens win slots deterministically. Masked (padding)
    tokens neither consume capacity nor contribute output.
    """
    T, D = x.shape
    E, K = cfg.n_routed_experts, cfg.n_activated_experts
    if capacity is None:
        capacity = max(1, int(capacity_factor * T * K / E))

    onehot = jax.nn.one_hot(indices, E, dtype=jnp.int32)  # (T, K, E)
    if token_mask is not None:
        onehot = onehot * token_mask[:, None, None].astype(jnp.int32)
    # Queue position of each (token, k) copy within its expert, counting across both
    # the token dim and the k dim (k-major within a token).
    flat = onehot.reshape(T * K, E)
    pos = jnp.cumsum(flat, axis=0) - flat  # (T*K, E) position if routed there
    pos = (pos * flat).sum(-1).reshape(T, K)  # (T, K) queue position of each copy
    keep = pos < capacity

    # (T, K, C) slot one-hot for kept copies (dropped copies -> all-zero row)
    slot = jax.nn.one_hot(jnp.where(keep, pos, -1), capacity, dtype=x.dtype)
    expert_oh = onehot.astype(x.dtype)  # (T, K, E); masked tokens already zeroed
    with jax.named_scope("moe_dispatch"):
        disp = jnp.einsum("tke,tkc->tec", expert_oh, slot)
        xd = jnp.einsum("tec,td->ecd", disp, x)  # (E, C, D)

    from jax.ad_checkpoint import checkpoint_name

    h = checkpoint_name(
        jnp.einsum("ecd,edf->ecf", xd, params["gate_up_proj"].astype(x.dtype)), "mlp_gate"
    )
    if "gate_up_bias" in params:
        h = h + params["gate_up_bias"][:, None, :]
    act = checkpoint_name(expert_activation(cfg, h).astype(x.dtype), "mlp_act")
    out = jnp.einsum("ecf,efd->ecd", act, params["down_proj"].astype(x.dtype))
    if "down_bias" in params:
        out = out + params["down_bias"][:, None, :]

    with jax.named_scope("moe_combine"):
        combine = jnp.einsum("tke,tkc,tk->tec", expert_oh, slot, weights.astype(x.dtype))
        return jnp.einsum("tec,ecd->td", combine, out)
