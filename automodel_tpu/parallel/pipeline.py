"""Pipeline parallelism over the ``pp`` mesh axis (SPMD collective pipelining).

TPU-native replacement for torch.distributed.pipelining (reference AutoPipeline,
distributed/pipelining/autopipeline.py:46 + functional.py:289,490): instead of
FQN-slicing a module tree into per-rank stage graphs with explicit P2P send/recv and a
hand-built 1F1B schedule, the layer-stacked param layout makes stage slicing a
*sharding*: layer dim -> ``pp`` axis. Every rank runs the same jitted program; a
``lax.scan`` over pipeline ticks moves activations stage->stage with ``ppermute``
(neighbor ICI hops). Reverse-mode AD differentiates through the scan + ppermute,
yielding the mirrored backward pipeline automatically — no schedule code, no shape
inference, no stage graphs.

Schedules. The base schedule is GPipe-shaped (a forward tick sweep; reverse-mode
AD emits the mirrored backward sweep), bubble fraction (pp-1)/(n_micro+pp-1) per
sweep. The reference's literal 1F1B (pipelining/functional.py:490) is a
*per-rank asynchronous* schedule: ranks do different work at the same wall-clock
instant, which XLA's SPMD lockstep (one program, every rank the same tick) cannot
express — emulating it with a fwd+bwd-per-tick uniform program makes warmup/drain
ticks cost 3 flop-units instead of 1 and is strictly slower than the AD schedule
(1F1B's remaining advantage, O(pp) in-flight activations, is covered here by
per-stage rematerialization). What DOES map to SPMD is 1F1B's *interleaved
virtual-stage* refinement (functional.py:166): ``circular_repeats=V`` assigns
each rank V non-contiguous layer blocks (round-major: global block v*pp + r on
rank r); activations wrap pp-1 -> 0 between rounds, total ticks shrink from
V*(n+pp-1) to V*n + pp - 1, and the bubble fraction drops ~V-fold to
(pp-1)/(V*n + pp - 1). AD again yields the mirrored interleaved backward.

Composition: shard_map is manual over ``pp`` only; FSDP/TP shardings on other mesh
axes stay GSPMD-managed inside (same partial-manual pattern as moe.dispatch).
Embedding AND the final-norm/head/loss run *outside* the manual region in plain
GSPMD: the token gather and the head matmul partition over tp/fsdp normally and
the head/embed params are never replicated per pp rank. The last stage's hidden
states reach the head via one activation-sized psum broadcast.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from automodel_tpu.ops import kernels

__all__ = [
    "pipeline_spmd", "pipeline_ticks", "make_pipeline_forward",
    "make_dense_decoder_pp_loss", "make_dense_decoder_pp_hidden",
    "make_moe_pp_hidden", "make_moe_pp_loss",
]


def pipeline_ticks(n_micro: int, pp: int, circular_repeats: int = 1) -> int:
    """Forward tick count; the per-sweep bubble fraction is (ticks - work) / ticks.

    V=1: n + pp - 1 ticks of 1 layer-block each (work = n). Circular V>1: each
    tick runs 1/V of a rank's layers, total V*n + pp - 1 ticks (work = V*n) —
    the bubble fraction (pp-1)/(V*n + pp - 1) shrinks ~V-fold."""
    if circular_repeats > 1:
        return circular_repeats * n_micro + pp - 1
    return n_micro + pp - 1


def pipeline_spmd(
    stage_params,  # pytree; leaves (L_local, ...) — or (V, L_local, ...) circular
    x_stack,  # pytree; leaves (n_micro, ...) — stage-0 inputs (already embedded)
    layer_apply: Callable,  # (stage_params, x) -> y  or -> (y, aux) with with_aux
    *,
    axis: str = "pp",
    with_aux: bool = False,
    circular_repeats: int = 1,
):
    """Run the pipeline; returns an x_stack-like pytree of outputs, valid on the
    LAST stage (other ranks hold garbage — mask with axis_index == pp-1).

    ``x_stack`` may be a pytree (e.g. {"h": ..., "positions": ..., "segment_ids":
    ...}) — side inputs like positions ride along with the activation through the
    ring so each stage sees its microbatch's metadata. Call inside shard_map manual
    over ``axis``.

    ``circular_repeats=V`` enables interleaved virtual stages (reference
    functional.py:166): ``stage_params`` leaves carry a leading (V, ...) round
    dim — this rank's V non-contiguous blocks in round-major global order — and
    activations wrap pp-1 -> 0 between rounds. Requires n_micro % pp == 0.
    Schedule: stage 0 feeds wave w's fresh microbatch j at tick w*pp*V + j and
    services round v of that wave at phase v*pp + j, so fresh feeds and wrapped
    activations never contend; total ticks = V*n_micro + pp - 1.

    ``with_aux``: ``layer_apply`` returns ``(y, aux_tree)``; aux is *summed* over
    the ticks where this stage held a real microbatch (warmup/drain ticks carry
    garbage activations and are masked out) — the per-stage accumulation MoE
    expert-load/aux-loss stats need. With circular repeats the aux gains a
    leading (V, ...) round dim. Returns ``(outputs, aux_sum)``.
    """
    pp = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    leaves = jax.tree.leaves(x_stack)
    n_micro = leaves[0].shape[0]
    V = circular_repeats
    if V > 1 and n_micro % pp != 0:
        raise ValueError(
            f"circular pipeline needs n_micro % pp == 0, got {n_micro} % {pp}"
        )
    steps = pipeline_ticks(n_micro, pp, V)
    # stage s -> s+1; with circular repeats the wraparound edge (pp-1 -> 0)
    # carries real activations between rounds (with V=1 it carries only garbage,
    # which stage 0 immediately overwrites with fresh microbatch input).
    perm = [(i, (i + 1) % pp) for i in range(pp)]

    def _round_params(v):
        if V == 1:
            return stage_params
        return jax.tree.map(
            lambda p: jax.lax.dynamic_index_in_dim(p, v, 0, keepdims=False), stage_params
        )

    def _apply(params, x):
        out = layer_apply(params, x)
        return out if with_aux else (out, {})

    def tick(carry, t):
        outputs, state, aux_acc = carry
        # this stage's position in the schedule: elapsed ticks since the work
        # now arriving here left stage 0
        e = t - idx
        cycle = pp * V
        wave = jnp.maximum(e, 0) // cycle
        phase = jnp.maximum(e, 0) % cycle
        v = phase // pp  # virtual-stage round being serviced
        j = phase % pp
        mb = jnp.clip(wave * pp + j, 0, n_micro - 1)
        real = (e >= 0) & (wave * pp + j < n_micro)
        feed = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, mb, 0, keepdims=False), x_stack
        )
        x = jax.tree.map(
            lambda f, s: jnp.where((idx == 0) & (v == 0), f, s), feed, state
        )
        y, aux = _apply(_round_params(v), x)
        # where (not multiply-by-0): 0 * nan = nan would survive a multiply mask.
        # Forward finiteness on garbage ticks is owned by the aux math itself
        # (gate.py clamps its token count so all-masked batches give 0, not 0/0);
        # this where is the schedule-level backstop for the primal values
        aux = jax.tree.map(lambda a: jnp.where(real, a, jnp.zeros_like(a)), aux)
        if V == 1:
            aux_acc = jax.tree.map(jnp.add, aux_acc, aux)
        else:
            aux_acc = jax.tree.map(lambda acc, a: acc.at[v].add(a), aux_acc, aux)
        # last stage emits microbatch mb when it finishes the final round; writes
        # are unconditional and time-ordered — slot mb's ticks ascend in round, so
        # the final-round write always lands last and intermediate/garbage writes
        # are harmlessly overwritten (only the last stage's buffer is ever read)
        outputs = jax.tree.map(
            lambda o, yl: jax.lax.dynamic_update_index_in_dim(o, yl, mb, 0),
            outputs, y,
        )
        state = jax.tree.map(lambda yl: jax.lax.ppermute(yl, axis, perm), y)
        return (outputs, state, aux_acc), None

    # mark the carries pp-varying (the body's ppermute/axis_index make them so)
    def _vary(x):
        return jax.lax.pcast(x, (axis,), to="varying")

    outputs = jax.tree.map(lambda a: _vary(jnp.zeros_like(a)), x_stack)
    state = jax.tree.map(lambda a: _vary(jnp.zeros_like(a[0])), x_stack)
    x0 = jax.tree.map(lambda a: a[0], x_stack)
    # probe with pp-varying inputs: stage params are varying inside the manual
    # region, so layer_apply's internal scans require varying carries
    aux_shapes = jax.eval_shape(
        lambda x: _apply(_round_params(jnp.int32(0)), jax.tree.map(_vary, x))[1], x0
    )
    zero_aux = jax.tree.map(
        lambda s: _vary(jnp.zeros((V, *s.shape) if V > 1 else s.shape, s.dtype)),
        aux_shapes,
    )
    (outputs, _, aux_sum), _ = jax.lax.scan(tick, (outputs, state, zero_aux), jnp.arange(steps))
    if with_aux:
        return outputs, aux_sum
    return outputs


def make_pipeline_forward(mesh: Mesh, *, pp_axis: str = "pp", with_aux: bool = False,
                          aux_out_specs=None, circular_repeats: int = 1,
                          extra_manual_axes: tuple = (),
                          layer_param_specs=None, x_stack_specs=None,
                          h_out_spec: P = P(), check_vma: bool = True):
    """Wrap (layer_apply, head_loss) into a pp-pipelined loss function.

    Returns ``fn(layer_params, other_params, x_stack, batch_stack, layer_apply,
    head_loss_fn)`` where:
      - ``x_stack`` — already-embedded stage-0 inputs, (n_micro, ...) leaves,
        computed by the caller OUTSIDE the manual region (plain GSPMD: the token
        gather and any dense prefix partition over tp/fsdp normally)
      - ``layer_apply(stage_layer_params, x) -> y`` scans this rank's layer slice
        (``-> (y, aux)`` with ``with_aux``: aux sums over valid ticks per stage;
        ``aux_out_specs`` — a pytree of PartitionSpecs matching aux, typically
        ``P(pp_axis)`` so per-stage layer stats reassemble in layer order; with
        circular repeats the aux carries a leading round dim -> P(None, pp_axis))
      - ``head_loss_fn(params, y, microbatch) -> scalar`` final-norm + head + loss
        (additive across microbatches)

    The manual region contains ONLY the layer pipeline. The last stage's output
    stack is psum-broadcast over ``pp`` (non-last ranks contribute zeros) and the
    head+loss run OUTSIDE in plain GSPMD: head/embed params never enter the
    region, so they keep their native tp/fsdp shardings (no per-rank replica —
    the r2 design paid ~1.8GB/rank at DSv3 scale) and the head matmul partitions
    over tp normally. This also sidesteps an XLA SpmdPartitioner CHECK-abort
    (spmd_partitioner_util.cc:495 device-group mismatch, jax 0.9) on
    full-logit CE reductions over a tp-sharded vocab inside partial-manual(pp).
    The extra psum of the (n_micro, b, s, d) output stack is one activation-sized
    all-reduce per step — the same order as the schedule's own ppermute traffic.

    Layer params must be stacked (L, ...) with the layer dim sharded over ``pp``
    (sharding rule "layers" -> pp). With ``circular_repeats=V`` the caller
    reshapes them to (V, pp, L/(V*pp), ...) — round-major interleaving — and this
    wrapper shards dim 1 over pp.

    ``extra_manual_axes``: additional mesh axes to make manual alongside ``pp``
    in ONE flattened region (a2a x PP: the explicit-EP MoE dispatcher must issue
    its ``all_to_all`` over a manual ep axis, and shard_map cannot nest — so ep
    joins the pp region instead). The caller then supplies matching manual
    specs: ``layer_param_specs`` / ``x_stack_specs`` are callables
    ``tree -> spec-tree`` (e.g. expert weights P(pp, "ep"); activations
    P(None, "ep") — batch split over ep), ``h_out_spec`` covers the output
    stack. Each defaults to the pp-only behavior when None.
    """
    pp = mesh.shape[pp_axis]
    V = circular_repeats

    def fn(layer_params, other_params, x_stack, batch_stack, layer_apply, head_loss_fn):
        def body(layer_params, x_stack):
            if V > 1:
                # (V, 1, Lb, ...) local slice -> (V, Lb, ...)
                layer_params = jax.tree.map(lambda p: p[:, 0], layer_params)
            outs = pipeline_spmd(
                layer_params, x_stack, layer_apply, axis=pp_axis,
                with_aux=with_aux, circular_repeats=V,
            )
            outs, aux = outs if with_aux else (outs, None)
            is_last = jax.lax.axis_index(pp_axis) == pp - 1
            # broadcast the last stage's hidden states to every rank (backward:
            # the psum transposes to identity and the where-mask routes the head
            # cotangent to the last stage only); positions/segment-ids that rode
            # along the ring are dropped — the head only needs h
            h = outs["h"]
            h = jax.lax.psum(jnp.where(is_last, h, jnp.zeros_like(h)), pp_axis)
            return (h, aux) if with_aux else h

        layer_specs = layer_param_specs(layer_params) if layer_param_specs is not None else (
            jax.tree.map(lambda _: P(None, pp_axis) if V > 1 else P(pp_axis), layer_params)
        )
        x_specs = x_stack_specs(x_stack) if x_stack_specs is not None else (
            jax.tree.map(lambda _: P(), x_stack)
        )
        out_specs = (h_out_spec, aux_out_specs) if with_aux else h_out_spec
        outs = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(layer_specs, x_specs),
            out_specs=out_specs,
            axis_names=kernels.manual_axes(mesh, pp_axis, *extra_manual_axes),
            check_vma=check_vma,
        )(layer_params, x_stack)
        h_stack, aux = outs if with_aux else (outs, None)
        if head_loss_fn is None:
            # hidden-state mode: the caller owns the head (KD needs full student
            # logits next to teacher logits; VLM heads differ per family)
            return (h_stack, aux) if with_aux else h_stack
        # head + loss in plain GSPMD. Sequential over microbatches: only one
        # microbatch's logits live at a time (vmap would materialize n_micro
        # full logits tensors at once, forfeiting exactly the peak-memory win
        # pipelining exists for).
        losses = jax.lax.map(
            lambda ymb: head_loss_fn(other_params, {"h": ymb[0]}, ymb[1]),
            (h_stack, batch_stack),
        )
        loss = losses.sum()
        return (loss, aux) if with_aux else loss

    return fn


def _make_head_loss(cfg, dtype, loss_name: str = "masked_ce"):
    """Final-norm + unembed + additive CE, shared by both pp loss builders.

    ``linear_ce`` (the default for the big models PP exists for) never
    materializes the (tokens, vocab) logits — the XLA blockwise scan, which
    GSPMD partitions cleanly over tp/fsdp now that the head runs outside the
    pp-manual region (pallas stays single-device-only, like the non-pp recipe).
    ``chunked_ce`` bounds the fp32 logits working set; ``masked_ce``
    materializes per-microbatch logits.
    """
    from automodel_tpu.ops.losses import (
        chunked_cross_entropy, linear_cross_entropy, masked_cross_entropy,
    )

    if loss_name not in ("masked_ce", "linear_ce", "chunked_ce"):
        raise NotImplementedError(
            f"pp loss {loss_name!r} (use masked_ce | linear_ce | chunked_ce)"
        )

    def head_loss(other, y, mb):
        h, unembed = _head_pre(cfg, dtype, other, y["h"])
        # additive (sum/num) microbatch losses, same contract as make_train_step
        if loss_name == "linear_ce":
            # impl="xla": pp implies a multi-device mesh, and GSPMD cannot
            # partition a pallas_call — impl="auto" on TPU would force the
            # partitioner to all-gather the full (E,V) unembed around the kernel,
            # reinstating the per-rank head replication this design removes (the
            # recipe gates its non-pp loss on mesh.size==1 for the same reason)
            return linear_cross_entropy(h, unembed, mb["labels"], 1.0, impl="xla")
        logits = jnp.einsum("bsd,dv->bsv", h, unembed)
        if loss_name == "chunked_ce":
            return chunked_cross_entropy(logits, mb["labels"], 1.0)
        return masked_cross_entropy(logits, mb["labels"], 1.0)

    return head_loss


def _head_pre(cfg, dtype, other, h):
    """Final-norm + unembed (transformer.resolve_unembed: tied fallback +
    granite logits_scaling) — shared by every pp loss/composition."""
    from automodel_tpu.models.common.transformer import apply_final_norm, resolve_unembed

    h = apply_final_norm(cfg, other, h, dtype)
    return h, resolve_unembed(cfg, other, dtype)


def make_head_logits(cfg, dtype):
    """(other_params, h) -> logits; for compositions that need raw logits next
    to the hidden-state pipeline (KD's KL term)."""

    def head_logits(other, h):
        h, unembed = _head_pre(cfg, dtype, other, h)
        return jnp.einsum("bsd,dv->bsv", h, unembed)

    return head_logits


def _circular_reshape(tree, V: int, pp: int):
    """(L, ...) layer stacks -> (V, pp, L/(V*pp), ...) round-major blocks."""

    def reshape(p):
        L = p.shape[0]
        if L % (V * pp) != 0:
            raise ValueError(
                f"circular pipeline needs layers % (V*pp) == 0, got {L} % {V * pp}"
            )
        return p.reshape(V, pp, L // (V * pp), *p.shape[1:])

    return jax.tree.map(reshape, tree)


def make_dense_decoder_pp_loss(model, mesh: Mesh, rules=None, loss_name: str = "masked_ce",
                               circular_repeats: int = 1):
    """Pipelined forward+loss for Llama-lineage models (the reference's PP covers HF
    decoder LMs the same way: embed on first stage, head+loss on last,
    recipes/llm/train_ft.py:1234-1242). ``circular_repeats`` enables interleaved
    virtual stages (reference functional.py:166 ``microbatch_group_size_per_vp_stage``).

    Returns ``forward_loss(params, batch_stack, num_label_tokens)`` where
    ``batch_stack`` leaves are (n_micro, ...) — the pipeline consumes all
    microbatches in one call (grad accum *is* the pipeline schedule).
    """
    from automodel_tpu.models.common.transformer import apply_layer_stack, embed_lookup

    cfg, backend = model.config, model.backend
    dtype = backend.jnp_dtype
    pp = mesh.shape["pp"]
    V = circular_repeats
    pipeline = make_pipeline_forward(mesh, circular_repeats=V)

    # NB: no sharding-constraint rules inside the pp-manual region —
    # with_sharding_constraint over the full mesh clashes with manual pp axes;
    # GSPMD propagates dp/tp activation shardings from the params instead.
    # ``rules`` is used only OUTSIDE the region (the embedding lookup below).

    def layer_apply(stage, x):
        lp, sliding = stage
        return apply_layer_stack(cfg, backend, lp, sliding, x, None)

    head_loss = _make_head_loss(cfg, dtype, loss_name)

    def forward_loss(params, batch_stack, num_label_tokens):
        sliding = jnp.asarray(cfg.layer_flags, jnp.int32)
        layer_params = (params["layers"], sliding)
        if V > 1:
            layer_params = _circular_reshape(layer_params, V, pp)
        other = {k: v for k, v in params.items() if k != "layers"}
        # embedding in plain GSPMD land (partitions over tp/fsdp normally);
        # unshard the table's fsdp (hidden-dim) axes first — same
        # involuntary-full-remat dodge as transformer.decoder_forward
        x_stack = {
            "h": embed_lookup(other["embed"], batch_stack["input_ids"], dtype, rules,
                              scale=getattr(cfg, "embedding_multiplier", 1.0)),
            "positions": batch_stack["positions"],
            "segment_ids": batch_stack["segment_ids"],
        }
        total = pipeline(layer_params, other, x_stack, batch_stack,
                         layer_apply, head_loss)
        return total / num_label_tokens

    return forward_loss


def make_dense_decoder_pp_hidden(cfg, backend, mesh: Mesh, *,
                                 circular_repeats: int = 1):
    """Pipelined dense layer stack -> FINAL HIDDEN STATES (no head).

    Returns ``hidden_fn(layer_stack, x_stack) -> h_stack (n_micro, B, S, D)``
    where ``x_stack`` holds already-embedded stage-0 inputs — the building block
    for compositions that own their head: KD (student logits must meet teacher
    logits in one loss) and VLM (per-family heads). The caller computes
    embeddings/final-norm/unembed OUTSIDE, in plain GSPMD.
    """
    from automodel_tpu.models.common.transformer import apply_layer_stack

    pp = mesh.shape["pp"]
    V = circular_repeats
    pipeline = make_pipeline_forward(mesh, circular_repeats=V)

    def layer_apply(stage, x):
        lp, sliding = stage
        return apply_layer_stack(cfg, backend, lp, sliding, x, None)

    def hidden_fn(layer_stack, x_stack):
        sliding = jnp.asarray(cfg.layer_flags, jnp.int32)
        layer_params = (layer_stack, sliding)
        if V > 1:
            layer_params = _circular_reshape(layer_params, V, pp)
        return pipeline(layer_params, None, x_stack, None, layer_apply, None)

    return hidden_fn


def make_moe_pp_hidden(model, mesh: Mesh, rules=None, *, pp_axis: str = "pp",
                       seq_len_hint: int = 0, circular_repeats: int = 1):
    """Pipelined MoE decoder -> FINAL HIDDEN STATES (no head): embedding + dense
    prefix run per microbatch in plain GSPMD, the MoE layer stack pipelines over
    ``pp`` with per-stage expert-load/aux accumulation, and the caller owns the
    head (KD needs full student logits next to teacher logits; train_ft adds the
    standard CE head via :func:`make_moe_pp_loss`).

    Returns ``hidden_fn(params, batch_stack, num_label_tokens) ->
    (h_stack, aux_loss, {"expert_load": (num_moe_layers, E)})`` where
    ``aux_loss`` is the already-weighted load-balance penalty (0 when disabled)
    to ADD to the caller's data loss. Under ``backend.dispatcher == "a2a"`` the
    manual region flattens to {pp, ep} (the EP all_to_all runs inside each
    stage) and extras gains ``dropped_token_frac``.
    """
    from automodel_tpu.models.common.moe_transformer import make_moe_layer_fns
    from automodel_tpu.models.common.transformer import embed_lookup

    cfg, backend = model.config, model.backend
    dtype = backend.jnp_dtype
    pp = mesh.shape[pp_axis]
    V = circular_repeats
    # a2a x PP: the explicit-EP dispatcher's all_to_all needs a manual ep axis,
    # and shard_map cannot nest — so the pp manual region FLATTENS to {pp, ep}
    # and the MoE layer fns dispatch directly over ep inside each stage. Expert
    # weights enter manual-sharded over both (layer dim -> pp, expert dim ->
    # ep); activations enter batch-split over ep, exactly the per-shard slice
    # make_ep_dispatch_body's protocol expects.
    a2a = backend.dispatcher == "a2a"
    ep_axis = "ep"
    if a2a and ep_axis not in mesh.axis_names:
        raise ValueError(
            "dispatcher='a2a' under pp requires the mesh to carry an 'ep' axis "
            f"(MeshContext(ep=...)); got axes {mesh.axis_names}"
        )
    attention_fn = model.make_attention_fn() if hasattr(model, "make_attention_fn") else None
    dense_layer_fn, moe_layer_fn = make_moe_layer_fns(
        cfg, backend, rules=None, attention_fn=attention_fn, training=True,
        seq_len_hint=seq_len_hint, ep_manual_axis=ep_axis if a2a else None,
    )
    k_dense = cfg.first_k_dense_replace
    emit_aux = cfg.moe.aux_loss_coeff > 0 and not backend.fake_balanced_gate
    load_spec = P(None, pp_axis) if V > 1 else P(pp_axis)
    aux_specs = {"load": load_spec}
    if emit_aux:
        aux_specs["aux"] = load_spec
    if a2a:
        # per-stage capacity-overflow accounting rides the aux channel (the
        # dispatch body psums it over ep, so it leaves the region pp-sharded
        # per layer and ep-replicated, same shape discipline as load)
        aux_specs["dropped"] = load_spec

    def _a2a_layer_specs(layer_params):
        """Manual specs for the flattened {pp, ep} region: expert-weight leaves
        (keyed by the exact 'experts' dict level — 'shared_experts' stays
        replicated over ep) shard expert dim over ep on top of layer dim -> pp."""
        def spec(path, _):
            is_expert = any(
                isinstance(k, jax.tree_util.DictKey) and k.key == "experts" for k in path
            )
            if not is_expert:
                return P(None, pp_axis) if V > 1 else P(pp_axis)
            # (L, E, ...) -> P(pp, ep); circular (V, pp, Lb, E, ...) -> dim 3
            return P(None, pp_axis, None, ep_axis) if V > 1 else P(pp_axis, ep_axis)

        return jax.tree_util.tree_map_with_path(spec, layer_params)

    def _a2a_x_specs(x_stack):
        # (n_micro, B, ...) activation/metadata stacks split batch over ep;
        # rank-1 ride-alongs (aux_weight) stay replicated
        return jax.tree.map(lambda a: P(None, ep_axis) if a.ndim >= 2 else P(), x_stack)

    pipeline = make_pipeline_forward(
        mesh, pp_axis=pp_axis, with_aux=True, aux_out_specs=aux_specs,
        circular_repeats=V,
        extra_manual_axes=(ep_axis,) if a2a else (),
        layer_param_specs=_a2a_layer_specs if a2a else None,
        x_stack_specs=_a2a_x_specs if a2a else None,
        h_out_spec=P(None, ep_axis) if a2a else P(),
        # interpret-mode pallas lowering mixes varying and unvarying operands
        # internally (its scalar-prefetch dynamic_slice), which the checker
        # rejects; the compiled kernel keeps the check on (moe/dispatch.py
        # says where it trips)
        check_vma=not (backend.experts_backend == "pallas" and kernels.interpret_mode()),
    )

    def embed_fn(other, mb):
        h = embed_lookup(other["embed"], mb["input_ids"], dtype, rules,
                         scale=getattr(cfg, "embedding_multiplier", 1.0))
        state = {
            "h": h,
            "positions": mb["positions"],
            "segment_ids": mb["segment_ids"],
            "token_mask": mb["segment_ids"] != 0,
        }
        if k_dense > 0:
            sliding = jnp.asarray(cfg.sliding_flags[:k_dense], jnp.int32)
            state, _ = jax.lax.scan(
                backend.layer_remat(dense_layer_fn), state, (other["dense_layers"], sliding)
            )
        return state

    def layer_apply(stage, state):
        lp_stack, sliding = stage
        aux_weight = state.pop("aux_weight", None)
        state, (auxs, loads, droppeds) = jax.lax.scan(
            backend.layer_remat(moe_layer_fn), state, (lp_stack, sliding)
        )
        out = {"load": loads}
        if a2a:
            # (Lb,) per-layer dropped fraction; the tick loop sums it over the
            # stage's real microbatches (hidden_fn divides the mean back out)
            out["dropped"] = droppeds
        if emit_aux:
            # weight this stage's aux by the CURRENT microbatch's label-token
            # fraction (rides the ring with the activation, see forward_loss) —
            # the exact non-pp contract (train_ft._forward_loss weights each
            # microbatch's aux by mb_tokens/num_label_tokens); (1,)-shaped so
            # the per-stage scalars gather along pp
            out["aux"] = (auxs.sum() * aux_weight)[None]
        if aux_weight is not None:
            state["aux_weight"] = aux_weight
        return state, out

    def hidden_fn(params, batch_stack, num_label_tokens):
        moe_sliding = jnp.asarray(cfg.sliding_flags[k_dense:], jnp.int32)
        layer_params = (params["moe_layers"], moe_sliding)
        if V > 1:
            layer_params = _circular_reshape(layer_params, V, pp)
        other = {k: v for k, v in params.items() if k != "moe_layers"}
        # embedding + dense prefix in plain GSPMD land, vmapped over microbatches
        x_stack = jax.vmap(lambda mb: embed_fn(other, mb))(batch_stack)
        if emit_aux:
            # per-microbatch label-token fractions ride the ring as (n_micro,)
            # scalars so each stage weights its aux by the microbatch it is
            # actually holding — exact parity with the non-pp objective even
            # when microbatch label counts are uneven (real SFT batches are)
            mb_tokens = (batch_stack["labels"] != -100).sum(axis=tuple(
                range(1, batch_stack["labels"].ndim))).astype(jnp.float32)
            x_stack["aux_weight"] = mb_tokens / jnp.asarray(num_label_tokens, jnp.float32)
        h_stack, aux = pipeline(layer_params, other, x_stack, None,
                                layer_apply, None)
        load = aux["load"]
        if V > 1:
            # (V, pp*Lb, E) round-major -> (L, E) global layer order
            load = load.reshape(-1, *load.shape[2:])
        extras = {"expert_load": load}
        if a2a:
            n_micro = jax.tree.leaves(batch_stack)[0].shape[0]
            # per-layer sums over microbatch ticks -> mean over layers & micros,
            # matching the non-pp stats["dropped_token_frac"] contract
            extras["dropped_token_frac"] = aux["dropped"].mean() / n_micro
        if emit_aux:
            aux_loss = cfg.moe.aux_loss_coeff * aux["aux"].sum()
            # unscaled balance loss for the moe/aux_loss telemetry row
            extras["moe_aux_loss"] = aux["aux"].sum()
        else:
            aux_loss = 0.0
        return h_stack, aux_loss, extras

    return hidden_fn


def make_moe_pp_loss(model, mesh: Mesh, rules=None, *, pp_axis: str = "pp",
                     loss_name: str = "masked_ce", seq_len_hint: int = 0,
                     circular_repeats: int = 1):
    """Pipelined forward+loss for MoE decoders: the dense prefix + embedding run
    replicated on every rank (cheap, avoids a ragged first stage), the MoE layer
    stack pipelines over ``pp``, and expert-load stats accumulate per stage with
    warmup/drain ticks masked (reference composes PP with EP/FSDP inside each stage,
    infrastructure.py:107 -> autopipeline; here the ep/fsdp axes stay GSPMD-managed
    inside the pp-manual region).

    Returns ``forward_loss(params, batch_stack, num_label_tokens) ->
    (loss, {"expert_load": (num_moe_layers, E)})`` matching the MoE train-step
    contract (gate-bias balancing consumes expert_load). ``seq_len_hint``: the
    training sequence length, needed for the sliding-window disable bound.

    Built on :func:`make_moe_pp_hidden` — the head+CE close per microbatch
    outside the manual region (lax.map: one microbatch's logits live at a time),
    exactly where :func:`make_pipeline_forward` would run them.
    """
    cfg = model.config
    dtype = model.backend.jnp_dtype
    hidden_fn = make_moe_pp_hidden(
        model, mesh, rules, pp_axis=pp_axis, seq_len_hint=seq_len_hint,
        circular_repeats=circular_repeats,
    )
    head_loss = _make_head_loss(cfg, dtype, loss_name)

    def forward_loss(params, batch_stack, num_label_tokens):
        h_stack, aux_loss, extras = hidden_fn(params, batch_stack, num_label_tokens)
        other = {k: v for k, v in params.items() if k != "moe_layers"}
        losses = jax.lax.map(
            lambda args: head_loss(other, {"h": args[0]}, args[1]),
            (h_stack, batch_stack),
        )
        loss = losses.sum() / num_label_tokens + aux_loss
        return loss, extras

    return forward_loss
