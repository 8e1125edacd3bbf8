"""Shared GQA + MoE decoder machinery (reference per-family model.py Block pattern,
e.g. models/qwen3_moe/model.py, models/gpt_oss/model.py).

Same contract as models.common.transformer: pure functions over stacked param pytrees,
``lax.scan`` over layers. A model may have a *dense prefix* (DeepSeek's
first_k_dense_replace) — those layers are stacked separately and scanned first; the MoE
layers follow. Scans emit per-layer ``(aux_loss, expert_load)`` which the forward
returns as a stats dict for the recipe (aux-loss term, load-balance metrics).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from automodel_tpu.models.common.backend import BackendConfig
from automodel_tpu.models.common.transformer import (
    DenseDecoderConfig,
    _LAYER_AXES,
    _attention_block,
    _constrain,
    _layer_shapes,
    _mlp_block,
    embed_lookup,
)
from automodel_tpu.moe.config import MoEConfig
from automodel_tpu.moe.dispatch import make_moe_block_forward
from automodel_tpu.moe.layers import (
    cast_moe_compute_params,
    init_moe_params,
    moe_logical_axes,
)
from automodel_tpu.ops.norms import rms_norm
from automodel_tpu.ops.rope import apply_rope, rope_attention_scaling, rope_frequencies
from automodel_tpu.utils.tracing import scope_blocks

__all__ = [
    "MoEDecoderConfig",
    "init_moe_decoder_params",
    "moe_decoder_logical_axes",
    "moe_decoder_forward",
]


@dataclasses.dataclass
class MoEDecoderConfig(DenseDecoderConfig):
    """GQA decoder where layers >= first_k_dense_replace use an MoE block."""

    moe: MoEConfig | None = None
    first_k_dense_replace: int = 0

    def __post_init__(self):
        super().__post_init__()
        if self.moe is None:
            raise ValueError("MoEDecoderConfig requires a MoEConfig in .moe")

    @property
    def num_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace


def _attn_only_shapes(cfg: MoEDecoderConfig) -> dict:
    """Attention + norms from the dense layer table, minus the dense-MLP weights."""
    shapes = _layer_shapes(cfg)
    for k in ("w_gate", "w_up", "w_down"):
        shapes.pop(k)
    return shapes


def init_moe_decoder_params(
    cfg: MoEDecoderConfig,
    key: jax.Array,
    dtype=jnp.float32,
    *,
    attn_shapes: dict | None = None,  # family override (e.g. MLA projections)
    dense_mlp_shapes: dict | None = None,
) -> dict:
    """Stacked params: [dense_layers] (attn + dense MLP) + moe_layers (attn + moe).

    Families with non-GQA attention (DeepSeek MLA) pass their own per-layer
    ``attn_shapes``; dense-prefix MLP weights default to w_gate/w_up/w_down.
    """
    std = cfg.initializer_range
    k_embed, k_dense, k_moe_attn, k_moe, k_head = jax.random.split(key, 5)
    if attn_shapes is None:
        attn_shapes = _attn_only_shapes(cfg)
    if dense_mlp_shapes is None:
        d, i = cfg.hidden_size, cfg.intermediate_size
        dense_mlp_shapes = {"w_gate": (d, i), "w_up": (d, i), "w_down": (i, d)}

    def init_layer_stack(shapes: dict, L: int, key) -> dict:
        keys = jax.random.split(key, len(shapes))
        out = {}
        for idx, (name, shape) in enumerate(shapes.items()):
            if name.endswith("norm"):
                out[name] = jnp.ones((L, *shape), dtype)
            elif name.startswith("b") or name == "sinks":
                out[name] = jnp.zeros((L, *shape), dtype)
            else:
                out[name] = (jax.random.normal(keys[idx], (L, *shape), jnp.float32) * std).astype(dtype)
        return out

    params: dict = {
        "embed": (jax.random.normal(k_embed, (cfg.vocab_size, cfg.hidden_size), jnp.float32) * std).astype(dtype),
        "final_norm": jnp.ones((cfg.hidden_size,), dtype),
    }
    if cfg.first_k_dense_replace > 0:
        params["dense_layers"] = init_layer_stack(
            attn_shapes | dense_mlp_shapes, cfg.first_k_dense_replace, k_dense
        )
    Lm = cfg.num_moe_layers
    moe_layers = init_layer_stack(attn_shapes, Lm, k_moe_attn)
    moe_layers["moe"] = jax.vmap(
        lambda k: init_moe_params(cfg.moe, k, dtype, std)
    )(jax.random.split(k_moe, Lm))
    params["moe_layers"] = moe_layers
    if not cfg.tie_word_embeddings:
        params["lm_head"] = (
            jax.random.normal(k_head, (cfg.hidden_size, cfg.vocab_size), jnp.float32) * std
        ).astype(dtype)
    return params


_DENSE_MLP_AXES = {"w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"), "w_down": ("mlp", "embed")}


def moe_decoder_logical_axes(
    cfg: MoEDecoderConfig,
    *,
    attn_axes: dict | None = None,
    attn_names: "list[str] | None" = None,
) -> dict:
    if attn_axes is None:
        attn_axes = _LAYER_AXES
    if attn_names is None:
        attn_names = list(_attn_only_shapes(cfg))
    axes: dict = {
        "embed": ("vocab", "embed"),
        "final_norm": ("norm",),
    }
    if cfg.first_k_dense_replace > 0:
        # distinct logical axis: the short dense prefix replicates across pp (it
        # runs on every pipeline rank) while moe "layers" shard over pp
        axes["dense_layers"] = {
            name: ("dense_layers",) + (attn_axes | _DENSE_MLP_AXES)[name]
            for name in attn_names + list(_DENSE_MLP_AXES)
        }
    moe_axes = {name: ("layers",) + attn_axes[name] for name in attn_names}
    moe_axes["moe"] = jax.tree.map(
        lambda t: ("layers",) + t,
        moe_logical_axes(cfg.moe),
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x),
    )
    axes["moe_layers"] = moe_axes
    if not cfg.tie_word_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def make_moe_layer_fns(
    cfg: MoEDecoderConfig,
    backend: BackendConfig,
    rules=None,
    attention_fn=None,
    training: bool = True,
    seq_len_hint: int = 0,
    ep_manual_axis: str | None = None,
):
    """State-dict layer bodies shared by moe_decoder_forward and the pp pipeline.

    Returns ``(dense_layer_fn, moe_layer_fn)`` over a carried state
    ``{"h", "positions", ["segment_ids"], ["token_mask"]}``:
    ``dense_layer_fn(state, (lp, is_sliding)) -> (state, None)``;
    ``moe_layer_fn(state, (lp, is_sliding)) -> (state, (aux, load, dropped_frac))``
    (``dropped_frac`` is a constant 0 unless ``backend.dispatcher == "a2a"``).

    ``attention_fn(lp, x, positions, segment_ids, is_sliding, rules) -> attn_out``
    overrides the default GQA block — the hook MLA-style families plug into (so the
    scan / aux / dense-prefix machinery here is the single copy).

    ``ep_manual_axis``: the caller runs these layer fns inside a manual region
    over that axis (the pp pipeline's flattened {pp, ep} region) — the a2a MoE
    block then dispatches directly over it instead of opening a nested shard_map
    (see moe.dispatch.make_moe_block_forward).
    """
    dtype = backend.jnp_dtype
    emit_aux = cfg.moe.aux_loss_coeff > 0 and training and not backend.fake_balanced_gate
    custom_attention = attention_fn is not None

    if attention_fn is None:
        inv_freq = rope_frequencies(
            cfg.head_dim, cfg.rope_theta, cfg.rope_scaling,
            partial_rotary_factor=cfg.partial_rotary_factor,
        )
        attn_scale = rope_attention_scaling(cfg.rope_scaling)
        window = jnp.int32(cfg.sliding_window or 0)
        any_sliding = any(cfg.sliding_flags)

        def attention_fn(lp, x, positions, segment_ids, is_sliding, rules, cache=None,
                         cache_meta=None):
            # "disabled" window must exceed every causal q-kv distance; under
            # cached decode that distance is bounded by the CACHE length, not
            # the (length-1) decode chunk — seq_len_hint would silently turn
            # full-attention layers into max_pos-window ones past the config
            # length (same derivation as the dense stack's layer_fn)
            kv_len = x.shape[1] if cache is None else cache[0].shape[1]
            big = jnp.int32(cfg.max_position_embeddings + max(seq_len_hint, kv_len))
            eff_window = jnp.where(is_sliding > 0, window, big) if any_sliding else None
            return _attention_block(cfg, backend, lp, x, positions, segment_ids,
                                    inv_freq, attn_scale, eff_window, rules,
                                    cache=cache, cache_meta=cache_meta)

    if custom_attention:
        import inspect

        custom_supports_cache = "cache" in inspect.signature(attention_fn).parameters

    def attn(state, lp, is_sliding, kv=None):
        h = state["h"]
        x = rms_norm(h, lp["attn_norm"], cfg.rms_norm_eps)
        if kv is None:
            out, kv_out = attention_fn(lp, x, state["positions"],
                                       state.get("segment_ids"), is_sliding, rules), None
        else:
            if custom_attention and not custom_supports_cache:
                raise NotImplementedError(
                    "this model plugs in a custom attention_fn without a cache "
                    "path (hybrid recurrence) — export to HF for generation instead"
                )
            cache_meta = {"write_idx": state["write_idx"], "valid": state["valid"],
                          "positions": state["kv_positions"]}
            out, kv_out = attention_fn(lp, x, state["positions"],
                                       state.get("segment_ids"), is_sliding, rules,
                                       cache=kv, cache_meta=cache_meta)
        h = h + out
        return _constrain(h, rules, ("batch", "act_seq", "act_embed")), kv_out

    def _split(layer_inputs):
        if len(layer_inputs) == 3:
            return layer_inputs
        return (*layer_inputs, None)

    moe_block = make_moe_block_forward(cfg.moe, backend, rules, training=training,
                                       ep_manual_axis=ep_manual_axis)

    def mlp_sublayer(lp, h):
        x = rms_norm(h, lp["mlp_norm"], cfg.rms_norm_eps)
        return h + _mlp_block(cfg, backend, lp, x, rules)

    # profiler scopes on the shared MoE decoder path (autonvtx parity,
    # utils/tracing.py): attention / dense-mlp / moe regions are legible in
    # every family's trace, matching the stacks that annotate per-family
    # (nemotron_v3, qwen3_next, step3p5)
    blocks = scope_blocks({"attention": attn, "mlp": mlp_sublayer, "moe": moe_block})

    def dense_layer_fn(state, layer_inputs):
        lp, is_sliding, kv = _split(layer_inputs)
        lp = jax.tree.map(lambda a: a.astype(dtype), lp)
        h, kv_out = blocks["attention"](state, lp, is_sliding, kv)
        h = blocks["mlp"](lp, h)
        state = dict(state, h=_constrain(h, rules, ("batch", "act_seq", "act_embed")))
        return state, kv_out

    def moe_layer_fn(state, layer_inputs):
        lp, is_sliding, kv = _split(layer_inputs)
        moe_params = lp["moe"]
        lp = jax.tree.map(lambda a: a.astype(dtype), {k: v for k, v in lp.items() if k != "moe"})
        h, kv_out = blocks["attention"](state, lp, is_sliding, kv)
        x = rms_norm(h, lp["mlp_norm"], cfg.rms_norm_eps)
        moe_params = cast_moe_compute_params(moe_params, dtype)
        y, aux, load, dropped = blocks["moe"](moe_params, x, state.get("token_mask"))
        h = h + y
        h = _constrain(h, rules, ("batch", "act_seq", "act_embed"))
        # decode (kv given) swaps the aux/load ys for the updated kv cache —
        # inference never consumes balance stats
        ys = kv_out if kv is not None else (aux if emit_aux else jnp.float32(0), load, dropped)
        return dict(state, h=h), ys

    return dense_layer_fn, moe_layer_fn


def moe_decoder_forward(
    cfg: MoEDecoderConfig,
    backend: BackendConfig,
    params: dict,
    input_ids: jnp.ndarray,  # (B, S)
    positions: jnp.ndarray | None = None,
    segment_ids: jnp.ndarray | None = None,
    token_mask: jnp.ndarray | None = None,  # (B, S) True = valid (counts for routing)
    rules=None,
    return_hidden: bool = False,
    training: bool = True,
    attention_fn=None,
    inputs_embeds: jnp.ndarray | None = None,  # (B, S, D) overrides the embed lookup (VLM merge)
    cache=None,  # generation.init_kv_cache dict -> returns (logits, cache)
) -> tuple[jnp.ndarray, dict[str, Any]]:
    """Returns ``(logits_or_hidden, stats)``; stats has ``aux_loss`` (scalar or None),
    ``expert_load`` (num_moe_layers, E), and — under ``backend.dispatcher == "a2a"`` —
    ``dropped_token_frac`` (mean over MoE layers). With ``cache`` (decode path, GQA
    stacks only) returns ``(logits, cache)`` instead."""
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(input_ids.shape[1]), input_ids.shape)
    if cache is not None and segment_ids is None:
        raise ValueError("cache decoding requires segment_ids (1 = real token)")
    dtype = backend.jnp_dtype
    h = (inputs_embeds.astype(dtype) if inputs_embeds is not None
         else embed_lookup(params["embed"], input_ids, dtype, rules,
                           scale=getattr(cfg, "embedding_multiplier", 1.0)))
    h = _constrain(h, rules, ("batch", "act_seq", "act_embed"))

    sliding_flags = jnp.asarray(cfg.sliding_flags, dtype=jnp.int32)
    emit_aux = cfg.moe.aux_loss_coeff > 0 and training and not backend.fake_balanced_gate

    state = {"h": h, "positions": positions}
    if segment_ids is not None:
        state["segment_ids"] = segment_ids
    if token_mask is not None:
        state["token_mask"] = token_mask
    if cache is not None:
        state["kv_positions"] = cache["positions"]
        state["valid"] = cache["valid"]
        state["write_idx"] = cache["write_idx"]
    dense_layer_fn, moe_layer_fn = make_moe_layer_fns(
        cfg, backend, rules, attention_fn, training, seq_len_hint=input_ids.shape[1]
    )

    # as transformer.apply_layer_stack: the scans' own slicing and stacking
    with jax.named_scope("layer_stack"):
        # per-layer cache slots: k/v always; "idx_k" when the model adds a third
        # slot (DSv32's indexer-key cache) — the attention fn returns the same
        # tuple shape it received, so the slot list is uniform across layers
        ckeys = [c for c in ("k", "v", "idx_k") if cache is not None and c in cache]
        k_dense = cfg.first_k_dense_replace
        dense_new = ()
        if k_dense > 0:
            body = backend.layer_remat(dense_layer_fn)
            if cache is not None:
                kv_dense = tuple(cache[c][:k_dense] for c in ckeys)
                state, dense_new = jax.lax.scan(
                    body, state, (params["dense_layers"], sliding_flags[:k_dense], kv_dense)
                )
            elif backend.scan_layers:
                state, _ = jax.lax.scan(body, state, (params["dense_layers"], sliding_flags[:k_dense]))
            else:
                for i in range(k_dense):
                    lp = jax.tree.map(lambda a: a[i], params["dense_layers"])
                    state, _ = body(state, (lp, sliding_flags[i]))

        moe_sliding = sliding_flags[k_dense:]
        body = backend.layer_remat(moe_layer_fn)
        if cache is not None:
            kv_moe = tuple(cache[c][k_dense:] for c in ckeys)
            state, moe_new = jax.lax.scan(
                body, state, (params["moe_layers"], moe_sliding, kv_moe)
            )
            cache = dict(cache, **{
                c: (jnp.concatenate([d, m], 0) if k_dense > 0 else m)
                for c, d, m in zip(ckeys, dense_new or (None,) * len(ckeys), moe_new)
            })
        elif backend.scan_layers:
            state, (auxs, loads, droppeds) = jax.lax.scan(
                body, state, (params["moe_layers"], moe_sliding)
            )
        else:
            auxs, loads, droppeds = [], [], []
            for i in range(cfg.num_moe_layers):
                lp = jax.tree.map(lambda a: a[i], params["moe_layers"])
                state, (aux, load, dropped) = body(state, (lp, moe_sliding[i]))
                auxs.append(aux)
                loads.append(load)
                droppeds.append(dropped)
            auxs = jnp.stack(auxs)
            loads = jnp.stack(loads)
            droppeds = jnp.stack(droppeds)

    with jax.named_scope("lm_head_loss"):  # as transformer.decoder_forward
        h = rms_norm(state["h"], params["final_norm"].astype(dtype), cfg.rms_norm_eps)
        if cache is not None:
            # next-token logits only (B, 1, V) — see transformer.decoder_forward
            last = jnp.maximum(segment_ids.sum(-1) - 1, 0).astype(jnp.int32)
            h = jnp.take_along_axis(h, last[:, None, None], axis=1)
            unembed = params.get("lm_head")
            if unembed is None:
                unembed = params["embed"].T
            logits = jnp.einsum("bsd,dv->bsv", h, unembed.astype(dtype))
            return logits, cache

        stats = {
            "aux_loss": auxs.sum() if emit_aux else None,
            "expert_load": loads,
        }
        if backend.dispatcher == "a2a":
            stats["dropped_token_frac"] = droppeds.mean()
        if return_hidden:
            return h, stats
        unembed = params.get("lm_head")
        if unembed is None:
            unembed = params["embed"].T
        logits = jnp.einsum("bsd,dv->bsv", h, unembed.astype(dtype))
        return logits, stats
