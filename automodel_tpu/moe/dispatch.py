"""Explicit expert-parallel token dispatch over the ``ep`` mesh axis.

TPU-native replacement for DeepEP fused dispatch/combine
(reference moe/megatron/fused_a2a.py:250,282 + MoEFlexTokenDispatcher,
token_dispatcher.py:339): NVSHMEM buffers + fused CUDA all-to-alls become
``lax.all_to_all`` collectives over ICI inside a partial-manual ``shard_map`` —
manual over ``ep`` only, so FSDP/TP sharding on other axes stays GSPMD-managed.

Protocol per ep-shard (capacity-bucketed, static shapes):
  route -> bucket token copies by destination rank (expert // E_local) with a fixed
  per-destination capacity -> all_to_all (dispatch) -> local grouped GEMM -> all_to_all
  (combine) -> weighted scatter-add at origin.
Copies beyond capacity are dropped (standard capacity-factor trade-off; DeepEP is
dropless, the dropless path here is ``grouped_experts_apply`` under plain GSPMD, which
scatters nothing where a layer holds all its experts: gathers over its sort's inverse).
The dispatch *accounts* for every drop: it returns ``dropped_frac`` (dropped copies /
valid copies, globally summed) so a mis-set ``capacity_factor`` is visible in the
training metrics instead of silently changing the loss.

a2a/compute overlap (``n_chunks > 1``): the capacity dim is split into K slices
and the dispatch a2a / expert GEMM / combine a2a run as three software-pipelined
sweeps, so chunk *i*'s GEMM has no data dependence on chunk *i+1*'s all_to_all
and XLA's latency-hiding scheduler overlaps them (the DeepEP async-dispatch
discipline, expressed as graph structure instead of CUDA streams). Routing, the
capacity cutoff, and ``dropped_frac`` are computed globally BEFORE slicing, so
which copies survive — and the forward output, loss, and activation gradients —
are bit-exact under any chunk count (per-row GEMM results don't depend on which
rows share a chunk). The one numeric difference: expert WEIGHT grads accumulate
per-chunk partial sums, a float reassociation of the monolithic GEMM's reduction
(measured ~2e-7 relative on fp32).

The body (:func:`make_ep_dispatch_body`) is shard_map-free: it assumes it is
already inside a region manual over ``ep_axis``. :func:`make_ep_moe_forward`
wraps it in its own partial-manual shard_map (the standalone GSPMD path);
``parallel/pipeline.py`` calls it directly inside the flattened {pp, ep} manual
region (a2a x PP composition — a nested shard_map over ep would be rejected).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from automodel_tpu.moe.config import MoEConfig
from automodel_tpu.moe.experts import sort_held_rows, sorted_ragged_ffn
from automodel_tpu.moe.gate import fake_balanced_route, route
from automodel_tpu.moe.layers import (
    _shared_experts_forward,
    from_expert_width,
    moe_forward,
    to_expert_width,
)
from automodel_tpu.ops import kernels

__all__ = ["make_ep_dispatch_body", "make_ep_moe_forward", "make_moe_block_forward"]


def make_moe_block_forward(cfg: MoEConfig, backend, rules=None, *, training: bool = True,
                           ep_manual_axis: str | None = None):
    """Dispatcher-aware MoE block shared by every MoE model family.

    Returns ``fn(moe_params, x, token_mask) -> (y, aux_loss, expert_load, dropped_frac)``
    with ``x`` (B, S, D). ``backend.dispatcher``:

    - ``"a2a"``: explicit EP all-to-all dispatch over the mesh's ``ep`` axis
      (:func:`make_ep_moe_forward`; the DeepEP deployment shape, reference
      fused_a2a.py:250). ``dropped_frac`` reports capacity overflow.
    - ``"dense"`` (default): GSPMD-managed :func:`moe_forward` — ``ragged_dot``
      sorted path is dropless, so ``dropped_frac`` is a constant 0.

    ``ep_manual_axis``: set when the caller is ALREADY inside a manual region
    over that axis (the pp pipeline's flattened {pp, ep} region). The a2a body
    then runs directly — no nested shard_map, no sharding constraints (which
    clash with manual axes) — with ``x`` already carrying the per-ep-shard
    batch slice and expert params the local expert shard.
    """
    if backend.dispatcher == "a2a" and ep_manual_axis is not None:
        def manual_fn(moe_params, x, token_mask=None):
            if token_mask is None:
                token_mask = jnp.ones(x.shape[:2], bool)
            # axis_size is static inside the manual region; the body builder is
            # a cheap closure, so deriving ep at trace time costs nothing
            ep = jax.lax.axis_size(ep_manual_axis)
            body = make_ep_dispatch_body(
                cfg, ep,
                capacity_factor=backend.ep_capacity_factor,
                training=training,
                fake_balanced_gate=backend.fake_balanced_gate,
                fake_gate_noise=backend.fake_gate_noise,
                ep_axis=ep_manual_axis,
                n_chunks=backend.a2a_chunks,
                experts_backend=backend.experts_backend,
                linear=backend.linear,
            )
            return body(moe_params, x, token_mask)

        return manual_fn

    if backend.dispatcher == "a2a":
        mesh = getattr(rules, "mesh", None)
        if mesh is None or "ep" not in mesh.axis_names:
            raise ValueError(
                "backend.dispatcher='a2a' requires sharding rules bound to a mesh "
                f"with an 'ep' axis (MeshContext(ep=...)); got mesh={mesh!r}"
            )
        if mesh.shape["ep"] == 1:
            import logging

            # measured (tools/bench_a2a_dispatch.py): at ep=1 the all_to_all is
            # a self-copy, so the delta is pure bucketing overhead (one-hot-
            # cumsum queue positions + (ep, cap, D) scatter layout) — a2a was
            # 2.25x slower than dense on a v5e chip (577ms vs 257ms/step).
            # With real expert parallelism (--ep 4 --devices 8, virtual mesh)
            # the explicit a2a measured ~2.05x FASTER than the dense GSPMD
            # path (1.77s vs 3.63s/step) — which is what it exists for.
            logging.getLogger(__name__).warning(
                "dispatcher='a2a' with ep=1: measured ~2.3x slower than the "
                "default dense dispatcher on one chip; use dispatcher='dense' "
                "unless ep > 1"
            )
        ep_fn = make_ep_moe_forward(
            cfg,
            mesh,
            capacity_factor=backend.ep_capacity_factor,
            training=training,
            fake_balanced_gate=backend.fake_balanced_gate,
            fake_gate_noise=backend.fake_gate_noise,
            n_chunks=backend.a2a_chunks,
            experts_backend=backend.experts_backend,
            linear=backend.linear,
        )
        act_sharding = rules.sharding(("batch", "act_seq", "act_embed"))

        def pinned(moe_params, x, token_mask=None):
            # pin the activation sharding at the shard_map boundary: the
            # partial-manual region leaves the auto dims unconstrained, and
            # GSPMD otherwise invents a carry sharding for the layer scan that
            # forces a replicate-then-repartition in the backward
            x = jax.lax.with_sharding_constraint(x, act_sharding)
            y, aux, load, dropped = ep_fn(moe_params, x, token_mask)
            y = jax.lax.with_sharding_constraint(y, act_sharding)
            return y, aux, load, dropped

        return pinned

    def fn(moe_params, x, token_mask=None):
        y, aux, load = moe_forward(
            cfg, moe_params, x, token_mask,
            training=training,
            dispatcher="capacity" if backend.experts_backend == "dense" else "ragged",
            fake_balanced_gate=backend.fake_balanced_gate,
            fake_gate_noise=backend.fake_gate_noise,
            experts_backend=backend.experts_backend,
            linear=backend.linear,
        )
        return y, aux, load, jnp.float32(0)

    return fn


def _local_grouped_gemm(cfg: MoEConfig, expert_params: dict, x, expert_ids,
                        n_local_experts, experts_backend: str = "ragged_dot"):
    """Sorted grouped GEMM over the local expert shard; x (N, D), expert_ids (N,)."""
    sort_idx, sorted_ids, group_sizes, _ = sort_held_rows(expert_ids, n_local_experts)
    out = sorted_ragged_ffn(cfg, expert_params, x[sort_idx], sorted_ids,
                            group_sizes, experts_backend=experts_backend)
    # unsort back to slot order
    return jnp.zeros_like(out).at[sort_idx].set(out)


def make_ep_dispatch_body(
    cfg: MoEConfig,
    ep: int,
    *,
    capacity_factor: float = 1.5,
    capacity: int | None = None,
    training: bool = True,
    fake_balanced_gate: bool = False,
    fake_gate_noise: float = 0.0,
    ep_axis: str = "ep",
    n_chunks: int = 1,
    experts_backend: str = "ragged_dot",
    linear: str = "default",
):
    """The per-shard a2a dispatch protocol, assuming a manual region over
    ``ep_axis`` is already open. Returns ``shard_fn(params, x, token_mask) ->
    (y, aux_loss, expert_load, dropped_frac)`` with ``x`` (B_local, S, D).

    The ``ep`` ranks share the experts the layer holds (``cfg.held_experts``: all of the
    router's unless the configuration says this mesh holds a share); a pair routed to an
    expert held nowhere on the mesh is not sent and not counted as dropped.
    """
    if cfg.held_experts % ep != 0:
        raise ValueError(f"{cfg.held_experts} held experts not divisible by ep {ep}")
    n_local = cfg.held_experts // ep
    nch = max(1, int(n_chunks))

    def shard_fn(params, x, token_mask):
        B, S, D = x.shape  # B already divided by ep (manual), auto-sharded over dp
        x2 = x.reshape(-1, D)
        mask = token_mask.reshape(-1)
        T = x2.shape[0]
        K = cfg.n_activated_experts

        if fake_balanced_gate:
            weights, indices, aux_loss, expert_load = fake_balanced_route(
                cfg, x2, noise=fake_gate_noise
            )
        else:
            weights, indices, aux_loss, expert_load = route(
                cfg, params["gate"], x2, mask, training=training
            )

        cap = capacity if capacity is not None else max(1, int(capacity_factor * T * K / ep))
        # send buffers pad the capacity dim up to a chunk multiple; the cutoff
        # itself stays `cap`, so which copies survive — and dropped_frac — are
        # EXACT under any chunk count (the pad slots are never filled)
        cap_pad = -(-cap // nch) * nch
        cc = cap_pad // nch

        held_id = (indices - cfg.first_held_expert).reshape(-1)  # (T*K,)
        dest = held_id // n_local  # destination ep rank
        local_eid = held_id % n_local
        tok = jnp.arange(T * K) // K
        # Masked (padding) copies go to rank `ep` (out of bounds): they neither
        # consume capacity (all-zero one_hot row) nor get scattered (drop mode).
        valid_copy = mask[tok]
        if not cfg.holds_all_experts:
            valid_copy &= (held_id >= 0) & (held_id < cfg.held_experts)
        dest = jnp.where(valid_copy, dest, ep)
        x2_full, x2 = x2, to_expert_width(cfg, params, x2, linear)
        D = x2.shape[1]

        # Queue position of each copy within its destination bucket.
        oh = jax.nn.one_hot(dest, ep, dtype=jnp.int32)
        pos = ((jnp.cumsum(oh, axis=0) - oh) * oh).sum(-1)
        keep = (pos < cap) & valid_copy
        slot = jnp.where(keep, pos, cap_pad)  # cap_pad is out-of-bounds -> scatter drops it

        send_x = jnp.zeros((ep, cap_pad, D), x.dtype).at[dest, slot].set(x2[tok], mode="drop")
        send_eid = jnp.zeros((ep, cap_pad), jnp.int32).at[dest, slot].set(local_eid, mode="drop")
        sx = send_x.reshape(ep, nch, cc, D)
        se = send_eid.reshape(ep, nch, cc)

        # Three software-pipelined sweeps: chunk i's GEMM depends only on chunk
        # i's dispatch, so the scheduler runs it under chunk i+1's all_to_all
        # (and chunk i's combine under chunk i+1's GEMM). With nch=1 this is
        # the original monolithic dispatch -> GEMM -> combine.
        recvs = []
        for i in range(nch):
            with jax.named_scope("ep_dispatch"):
                rx = jax.lax.all_to_all(sx[:, i], ep_axis, split_axis=0, concat_axis=0)
                rid = jax.lax.all_to_all(se[:, i], ep_axis, split_axis=0, concat_axis=0)
            recvs.append((rx, rid))

        outs = []
        for rx, rid in recvs:
            with jax.named_scope("ep_experts"):
                outs.append(
                    _local_grouped_gemm(
                        cfg, params["experts"], rx.reshape(ep * cc, D), rid.reshape(-1),
                        n_local, experts_backend,
                    ).reshape(ep, cc, D)
                )

        backs = []
        for out in outs:
            with jax.named_scope("ep_combine"):
                backs.append(jax.lax.all_to_all(out, ep_axis, split_axis=0, concat_axis=0))
        back = jnp.stack(backs, axis=1).reshape(ep, cap_pad, D)

        # Combine at origin: gather each copy's result, weight it, drop overflow.
        gathered = back[dest, jnp.minimum(slot, cap_pad - 1)]  # (T*K, D)
        w = (weights.reshape(-1) * keep).astype(jnp.float32)
        y = jnp.zeros((T, D), jnp.float32).at[tok].add(gathered.astype(jnp.float32) * w[:, None])
        y = from_expert_width(cfg, params, y.astype(x.dtype), linear)

        if cfg.n_shared_experts > 0:
            y = y + _shared_experts_forward(cfg, params, x2_full, linear)

        if aux_loss is not None:
            aux_loss = jax.lax.pmean(aux_loss, ep_axis)
        expert_load = jax.lax.psum(expert_load, ep_axis)
        n_valid = jax.lax.psum(valid_copy.sum().astype(jnp.float32), ep_axis)
        n_dropped = jax.lax.psum(
            (valid_copy & ~keep).sum().astype(jnp.float32), ep_axis
        )
        dropped_frac = n_dropped / jnp.maximum(n_valid, 1.0)
        return y.reshape(x.shape), aux_loss, expert_load, dropped_frac

    return shard_fn


def make_ep_moe_forward(
    cfg: MoEConfig,
    mesh: Mesh,
    *,
    capacity_factor: float = 1.5,
    capacity: int | None = None,
    training: bool = True,
    fake_balanced_gate: bool = False,
    fake_gate_noise: float = 0.0,
    ep_axis: str = "ep",
    n_chunks: int = 1,
    experts_backend: str = "ragged_dot",
    linear: str = "default",
):
    """Build ``fn(params, x, token_mask) -> (y, aux_loss, expert_load, dropped_frac)``
    with explicit EP a2a dispatch. ``x`` is (B, S, D) with batch sharded over data axes
    (incl. ep); expert params are sharded over ``ep`` on their leading dim.
    ``dropped_frac`` is a global fp32 scalar: token copies dropped over capacity /
    valid token copies — exact regardless of ``n_chunks``.
    """
    ep = mesh.shape[ep_axis]
    shard_fn = make_ep_dispatch_body(
        cfg, ep,
        capacity_factor=capacity_factor, capacity=capacity, training=training,
        fake_balanced_gate=fake_balanced_gate, fake_gate_noise=fake_gate_noise,
        ep_axis=ep_axis, n_chunks=n_chunks, experts_backend=experts_backend, linear=linear,
    )

    # Manual specs cover only the ep axis; every other axis of size > 1 stays
    # auto/GSPMD (manual_axes: the size-1 ones join the region, for Mosaic).
    def param_specs(params):
        return {
            "gate": jax.tree.map(lambda _: P(), params["gate"]),
            "experts": jax.tree.map(lambda _: P(ep_axis), params["experts"]),
            **(
                {"shared_experts": jax.tree.map(lambda _: P(), params["shared_experts"])}
                if "shared_experts" in params
                else {}
            ),
            **(
                {"shared_expert_gate": P()}
                if "shared_expert_gate" in params
                else {}
            ),
            **({"latent": jax.tree.map(lambda _: P(), params["latent"])}
               if "latent" in params else {}),
        }

    def fn(params, x, token_mask=None):
        if token_mask is None:
            token_mask = jnp.ones(x.shape[:2], bool)
        aux_spec = P() if (cfg.aux_loss_coeff > 0 and training and not fake_balanced_gate) else None
        out_specs = (P(ep_axis), aux_spec, P(), P())
        mapped = jax.shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(param_specs(params), P(ep_axis), P(ep_axis)),
            out_specs=out_specs,
            axis_names=kernels.manual_axes(mesh, ep_axis),
            # the checker is on around the COMPILED kernel (pinned for a
            # described ep=4 mesh in tests/unit/test_chip_compile.py). Off the
            # TPU the Pallas interpreter evaluates grouped_matmul's index maps
            # itself: a dynamic_slice of the scalar-prefetch group table
            # (varying over ep) by its own loop counters (unvarying), inside
            # jax/_src/pallas/core.py, where no pcast of ours can reach; JAX's
            # error prescribes check_vma=False for it
            check_vma=not (experts_backend == "pallas" and kernels.interpret_mode()),
        )
        return mapped(params, x, token_mask)

    return fn
