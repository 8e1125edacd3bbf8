"""Qwen3-VL-MoE — TPU-native (reference models/qwen3_vl_moe/model.py:317; the
reference keeps HF's vision tower and swaps the text stack — here both are native).

Composition: vision tower (models/vision/qwen3_vl_vit.py) -> merged visual embeds
scattered into the token embedding at image-token slots, plus *deepstack* features
added into the hidden states of the first N text layers (DeepStack,
arXiv:2406.04334). Text decoder = Qwen3-MoE blocks with interleaved mrope (3D t/h/w
position ids, transformers Qwen3VLMoeTextRotaryEmbedding).

TPU-first contract: everything data-dependent (3D rope index construction from
vision token spans, scatter coordinates of visual tokens) is host-side numpy
(``get_mrope_positions``/``visual_token_coords``); the jitted forward takes only
static-shaped arrays.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp

from automodel_tpu.models.common.backend import BackendConfig
from automodel_tpu.models.common.moe_transformer import (
    MoEDecoderConfig,
    init_moe_decoder_params,
    make_moe_layer_fns,
    moe_decoder_logical_axes,
)
from automodel_tpu.models.common.transformer import _constrain
from automodel_tpu.models.vision.qwen3_vl_vit import (
    Qwen3VLVisionConfig,
    init_vision_params,
    prepare_vision_inputs,
    vision_forward,
    vision_logical_axes,
)
from automodel_tpu.moe.config import MoEConfig
from automodel_tpu.ops.attention import dot_product_attention, sharded_attention
from automodel_tpu.ops.norms import rms_norm
from automodel_tpu.ops.rope import (
    apply_rope_angles,
    mrope_angles,
    rope_attention_scaling,
    rope_frequencies,
)

__all__ = ["Qwen3VLMoeConfig", "Qwen3VLMoeForConditionalGeneration"]


@dataclasses.dataclass
class Qwen3VLMoeConfig:
    text: MoEDecoderConfig = None
    vision: Qwen3VLVisionConfig = None
    mrope_section: tuple[int, int, int] = (24, 20, 20)
    image_token_id: int = 151655
    video_token_id: int = 151656
    vision_start_token_id: int = 151652

    @classmethod
    def from_hf(cls, hf: dict[str, Any]) -> "Qwen3VLMoeConfig":
        t = hf.get("text_config", hf)
        rope_scaling = t.get("rope_scaling") or {}
        moe = MoEConfig(
            n_routed_experts=t["num_experts"],
            n_activated_experts=t["num_experts_per_tok"],
            dim=t["hidden_size"],
            moe_inter_dim=t["moe_intermediate_size"],
            score_func="softmax",
            softmax_before_topk=True,
            norm_topk_prob=True,  # HF hardcodes renorm for this family
            aux_loss_coeff=t.get("router_aux_loss_coef", 0.0),
        )
        text = MoEDecoderConfig(
            vocab_size=t["vocab_size"],
            hidden_size=t["hidden_size"],
            intermediate_size=t.get("intermediate_size", 0),
            num_hidden_layers=t["num_hidden_layers"],
            num_attention_heads=t["num_attention_heads"],
            num_key_value_heads=t.get("num_key_value_heads", t["num_attention_heads"]),
            head_dim=t.get("head_dim"),
            max_position_embeddings=t.get("max_position_embeddings", 4096),
            rope_theta=t.get("rope_theta", 10000.0),
            rope_scaling=rope_scaling or None,  # mrope keys are ignored by rope_frequencies
            rms_norm_eps=t.get("rms_norm_eps", 1e-6),
            tie_word_embeddings=hf.get("tie_word_embeddings", t.get("tie_word_embeddings", False)),
            attention_bias=t.get("attention_bias", False),
            qk_norm=True,
            initializer_range=t.get("initializer_range", 0.02),
            moe=moe,
            first_k_dense_replace=0,
        )
        return cls(
            text=text,
            vision=Qwen3VLVisionConfig.from_hf(hf.get("vision_config", {})),
            mrope_section=tuple(rope_scaling.get("mrope_section", (24, 20, 20))),
            image_token_id=hf.get("image_token_id", 151655),
            video_token_id=hf.get("video_token_id", 151656),
            vision_start_token_id=hf.get("vision_start_token_id", 151652),
        )


class Qwen3VLMoeForConditionalGeneration:
    """Functional model: holds config + backend, operates on param pytrees."""

    config_class = Qwen3VLMoeConfig
    hf_architectures = ("Qwen3VLMoeForConditionalGeneration",)

    def __init__(self, config: Qwen3VLMoeConfig, backend: BackendConfig | None = None):
        self.config = config
        self.backend = backend or BackendConfig()

    # ---- params ----

    def init(self, key: jax.Array, dtype=jnp.float32) -> dict:
        k_text, k_vis = jax.random.split(key)
        params = init_moe_decoder_params(self.config.text, k_text, dtype)
        params["visual"] = init_vision_params(self.config.vision, k_vis, dtype)
        return params

    def abstract_params(self, dtype=jnp.bfloat16) -> dict:
        return jax.eval_shape(lambda k: self.init(k, dtype), jax.random.key(0))

    def logical_axes(self) -> dict:
        axes = moe_decoder_logical_axes(self.config.text)
        axes["visual"] = vision_logical_axes(self.config.vision)
        return axes

    # ---- host-side bookkeeping (collator/test helpers) ----

    def prepare_vision_inputs(self, grid_thw: np.ndarray) -> dict[str, np.ndarray]:
        return prepare_vision_inputs(grid_thw, self.config.vision)

    def visual_token_coords(self, input_ids: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """(batch_idx, seq_idx) of image/video placeholder tokens, in scan order —
        matches the order merged vision tokens come out of the tower for batches
        whose images appear in reading order."""
        mask = (input_ids == self.config.image_token_id) | (
            input_ids == self.config.video_token_id
        )
        b, s = np.where(mask)
        return b.astype(np.int32), s.astype(np.int32)

    def get_mrope_positions(
        self,
        input_ids: np.ndarray,  # (B, S)
        grid_thw: np.ndarray | None,  # image grids, (n_images, 3), reading order
        attention_mask: np.ndarray | None = None,
        video_grid_thw: np.ndarray | None = None,  # (n_videos, 3)
    ) -> np.ndarray:
        """3D (t, h, w) position ids, (3, B, S) — numpy mirror of HF get_rope_index
        (modeling_qwen3_vl_moe.py:1082): text tokens advance all three axes together;
        a vision span of (t, h, w) patches gets grid coordinates offset after the
        preceding text, and the following text resumes from max+1. Video grids are
        split into per-frame t=1 spans (Qwen3-VL timestamp encoding — frames are
        separate placeholder runs separated by timestamp text, :1091-1094)."""
        cfg = self.config
        B, S = input_ids.shape
        ms = cfg.vision.spatial_merge_size
        if video_grid_thw is not None:
            v = np.asarray(video_grid_thw)
            v = np.repeat(v, v[:, 0], axis=0)
            v[:, 0] = 1
            video_grid_thw = v
        pos = np.zeros((3, B, S), dtype=np.int64)
        img_idx, vid_idx = 0, 0
        for b in range(B):
            valid = np.ones((S,), bool) if attention_mask is None else attention_mask[b].astype(bool)
            ids = input_ids[b][valid]
            out = np.zeros((3, len(ids)), dtype=np.int64)
            st = 0
            cursor = 0
            is_vis = (ids == cfg.image_token_id) | (ids == cfg.video_token_id)
            while st < len(ids):
                if not is_vis[st]:
                    out[:, st] = cursor
                    cursor += 1
                    st += 1
                    continue
                if ids[st] == cfg.video_token_id:
                    t, h, w = (int(x) for x in video_grid_thw[vid_idx])
                    vid_idx += 1
                else:
                    t, h, w = (int(x) for x in grid_thw[img_idx])
                    img_idx += 1
                gh, gw = h // ms, w // ms
                n = t * gh * gw
                ti = np.repeat(np.arange(t), gh * gw)
                hi = np.tile(np.repeat(np.arange(gh), gw), t)
                wi = np.tile(np.arange(gw), t * gh)
                out[0, st : st + n] = ti + cursor
                out[1, st : st + n] = hi + cursor
                out[2, st : st + n] = wi + cursor
                cursor = int(out[:, st : st + n].max()) + 1
                st += n
            pos[:, b, valid] = out
        return pos

    # ---- forward ----

    def embed_with_vision(self, params, input_ids, pixel_values=None,
                          vision_inputs=None, visual_coords=None, extra_embeds=None,
                          rules=None):
        """Token embedding with visual tokens scattered in at image-token slots.
        Returns ``(h, ds)`` — ds is the (n_ds, Tm, D) deepstack feature stack
        (None without pixels). Shared by __call__ and the pp hidden path."""
        dtype = self.backend.jnp_dtype
        h = params["embed"].astype(dtype)[input_ids]
        ds = None
        if pixel_values is not None:
            vis, ds = vision_forward(
                self.config.vision, self.backend, params["visual"],
                pixel_values, vision_inputs["pos_pairs"], vision_inputs["pos_idx"],
                vision_inputs["pos_w"], vision_inputs["segment_ids"], rules=rules,
            )
            b_idx, s_idx = visual_coords
            h = h.at[b_idx, s_idx].set(vis.astype(dtype))
        if extra_embeds is not None:
            (eb_idx, es_idx), toks = extra_embeds
            h = h.at[eb_idx, es_idx].set(toks.astype(dtype))
        return h, ds

    # vlm x pp capability flag for the recipe's _check_pp_support
    pp_hidden_supported = True

    def _pp_extra_embeds(self, params, mb, rules=None):
        """Hook for subclasses with extra scatter modalities (omni audio): maps
        a microbatch to ``((b_idx, s_idx), tokens)`` for embed_with_vision, or
        None. The base family has none."""
        del params, mb, rules
        return None

    def make_pp_hidden(self, mesh, rules=None, *, seq_len_hint: int = 0,
                       circular_repeats: int = 1):
        """Pipelined text stack -> FINAL HIDDEN STATES for vlm x pp (VERDICT r3
        #5; the reference pipelines the wrapped VLM module by FQN slicing,
        distributed/pipelining/functional.py:289).

        Per microbatch OUTSIDE the manual region (plain GSPMD): vision tower,
        embed scatter, mrope angles. INSIDE, the per-layer deepstack features
        ride the ring as a dense (n_ds, B, S, D) addend next to the activation
        — side-riders over pipeline_spmd's pytree ring — and are injected at
        their GLOBAL layer index by whichever stage owns it, so the deepstack
        window may even straddle a stage boundary.

        Returns ``hidden_fn(params, batch_stack, num_label_tokens) ->
        (h_stack, aux_loss, {"expert_load": (L, E)})`` — the same contract as
        :func:`parallel.pipeline.make_moe_pp_hidden`.
        """
        from jax.sharding import PartitionSpec as P

        from automodel_tpu.parallel.pipeline import make_pipeline_forward

        if circular_repeats > 1:
            raise NotImplementedError(
                "qwen3-vl deepstack pp is wired for V=1 (circular rounds need a "
                "round-major layer-index remap for the deepstack injection)"
            )
        cfg, backend = self.config.text, self.backend
        if backend.dispatcher == "a2a":
            # same fence as make_moe_pp_loss (parallel/pipeline.py): the a2a
            # dispatch is its own shard_map and cannot nest in the pp region
            raise ValueError(
                "dispatcher='a2a' cannot run inside the pp manual region (nested "
                "shard_map over ep); use the default GSPMD dispatcher under pp"
            )
        pp = mesh.shape["pp"]
        L = cfg.num_hidden_layers
        if L % pp:
            raise ValueError(f"num_hidden_layers {L} % pp {pp} != 0")
        Lb = L // pp
        n_ds = len(self.config.vision.deepstack_visual_indexes)
        inv_freq = rope_frequencies(cfg.head_dim, cfg.rope_theta, cfg.rope_scaling)
        attn_scale = rope_attention_scaling(cfg.rope_scaling)
        emit_aux = cfg.moe.aux_loss_coeff > 0 and not backend.fake_balanced_gate
        mrope_section = self.config.mrope_section

        def attention_fn(lp, x, angles, seg, is_sliding, rules_):
            # the state's ``positions`` slot carries the per-microbatch mrope
            # ANGLES through the ring (moe_layer_fn just forwards it here)
            del is_sliding, rules_
            q = jnp.einsum("bsd,dnh->bsnh", x, lp["wq"])
            k = jnp.einsum("bsd,dnh->bsnh", x, lp["wk"])
            v = jnp.einsum("bsd,dnh->bsnh", x, lp["wv"])
            q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
            k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
            q = apply_rope_angles(q, angles, attn_scale)
            k = apply_rope_angles(k, angles, attn_scale)
            out = dot_product_attention(
                q, k, v, causal=True, segment_ids_q=seg, backend=backend.attention,
            )
            return jnp.einsum("bsnh,nhd->bsd", out, lp["wo"])

        # rules=None: no sharding constraints inside the pp-manual region (the
        # same contract as make_moe_pp_loss)
        _, moe_layer_fn = make_moe_layer_fns(
            cfg, backend, None, attention_fn, True, seq_len_hint=seq_len_hint
        )
        body = backend.layer_remat(moe_layer_fn)
        aux_specs = {"load": P("pp")}
        if emit_aux:
            aux_specs["aux"] = P("pp")
        pipeline = make_pipeline_forward(mesh, with_aux=True, aux_out_specs=aux_specs)

        def layer_apply(lp_stack, x):
            state = {"h": x["h"], "positions": x["angles"],
                     "segment_ids": x["segment_ids"],
                     "token_mask": x["segment_ids"] != 0}
            base = jax.lax.axis_index("pp") * Lb

            def scan_body(st, inp):
                lp, j = inp
                st, (aux, load, dropped) = body(st, (lp, jnp.int32(0)))
                if n_ds:
                    gi = base + j
                    inj = jnp.where(
                        gi < n_ds,
                        x["ds"][jnp.clip(gi, 0, n_ds - 1)].astype(st["h"].dtype),
                        jnp.zeros_like(st["h"]),
                    )
                    st = dict(st, h=st["h"] + inj)
                return st, (aux, load, dropped)

            state, (auxs, loads, _dropped) = jax.lax.scan(
                scan_body, state, (lp_stack, jnp.arange(Lb))
            )
            out = {"load": loads}
            if emit_aux:
                out["aux"] = (auxs.sum() * x["aux_weight"])[None]
            return dict(x, h=state["h"]), out

        def hidden_fn(params, batch_stack, num_label_tokens):
            def embed_mb(mb):
                h, ds = self.embed_with_vision(
                    params, mb["input_ids"], mb.get("pixel_values"),
                    mb.get("vision_inputs"),
                    (mb["visual_coords_b"], mb["visual_coords_s"])
                    if "visual_coords_b" in mb else None,
                    extra_embeds=self._pp_extra_embeds(params, mb, rules),
                    rules=rules,
                )
                pos3 = mb.get("positions3")
                if pos3 is None:
                    B, S = mb["input_ids"].shape
                    pos3 = jnp.broadcast_to(jnp.arange(S), (3, B, S))
                entry = {
                    "h": h,
                    "angles": mrope_angles(pos3, inv_freq, mrope_section),
                    "segment_ids": mb["segment_ids"],
                }
                if n_ds:
                    dsd = jnp.zeros((n_ds, *h.shape), h.dtype)
                    if ds is not None:
                        b_idx, s_idx = mb["visual_coords_b"], mb["visual_coords_s"]
                        dsd = dsd.at[:, b_idx, s_idx].add(ds.astype(h.dtype))
                    entry["ds"] = dsd
                return entry

            x_stack = jax.lax.map(embed_mb, batch_stack)
            if emit_aux:
                mb_tokens = (batch_stack["labels"] != -100).sum(axis=tuple(
                    range(1, batch_stack["labels"].ndim))).astype(jnp.float32)
                x_stack["aux_weight"] = mb_tokens / jnp.asarray(
                    num_label_tokens, jnp.float32)
            h_stack, aux = pipeline(
                params["moe_layers"], None, x_stack, None, layer_apply, None
            )
            aux_loss = (cfg.moe.aux_loss_coeff * aux["aux"].sum()) if emit_aux else 0.0
            return h_stack, aux_loss, {"expert_load": aux["load"]}

        return hidden_fn

    def __call__(
        self,
        params,
        input_ids,  # (B, S)
        pixel_values=None,  # (Tv, patch_dim)
        vision_inputs=None,  # dict from prepare_vision_inputs (jnp arrays ok)
        visual_coords=None,  # (b_idx (Tm,), s_idx (Tm,)) from visual_token_coords
        positions3=None,  # (3, B, S) from get_mrope_positions; None = text-only arange
        extra_embeds=None,  # ((b_idx, s_idx), tokens): extra modality scatter (omni audio)
        segment_ids=None,
        token_mask=None,
        rules=None,
        return_hidden=False,
        training=True,
    ):
        cfg, backend = self.config.text, self.backend
        dtype = backend.jnp_dtype
        B, S = input_ids.shape

        if positions3 is None:
            positions3 = jnp.broadcast_to(jnp.arange(S), (3, B, S))
        inv_freq = rope_frequencies(cfg.head_dim, cfg.rope_theta, cfg.rope_scaling)
        attn_scale = rope_attention_scaling(cfg.rope_scaling)
        angles = mrope_angles(positions3, inv_freq, self.config.mrope_section)

        h, ds = self.embed_with_vision(
            params, input_ids, pixel_values, vision_inputs, visual_coords, extra_embeds,
            rules=rules,
        )
        h = _constrain(h, rules, ("batch", "act_seq", "act_embed"))
        emit_aux = cfg.moe.aux_loss_coeff > 0 and training and not backend.fake_balanced_gate

        def attention_fn(lp, x, positions, seg, is_sliding, rules_):
            del positions, is_sliding
            q = jnp.einsum("bsd,dnh->bsnh", x, lp["wq"])
            k = jnp.einsum("bsd,dnh->bsnh", x, lp["wk"])
            v = jnp.einsum("bsd,dnh->bsnh", x, lp["wv"])
            q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
            k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
            q = apply_rope_angles(q, angles, attn_scale)
            k = apply_rope_angles(k, angles, attn_scale)
            q = _constrain(q, rules_, ("batch", "act_attn_seq", "act_heads", None))
            k = _constrain(k, rules_, ("batch", "act_attn_seq", "act_heads", None))
            out = sharded_attention(
                q, k, v, rules=rules_, causal=True, segment_ids_q=seg,
                backend=backend.attention,
            )
            return jnp.einsum("bsnh,nhd->bsd", out, lp["wo"])

        _, moe_layer_fn = make_moe_layer_fns(
            cfg, backend, rules, attention_fn, training, seq_len_hint=S
        )
        body = backend.layer_remat(moe_layer_fn)

        state = {"h": h, "positions": positions3[0]}
        if segment_ids is not None:
            state["segment_ids"] = segment_ids
        if token_mask is not None:
            state["token_mask"] = token_mask

        sliding = jnp.zeros((cfg.num_hidden_layers,), jnp.int32)
        n_ds = 0 if ds is None else ds.shape[0]
        auxs, loads, droppeds = [], [], []
        # deepstack: unrolled first n_ds layers, each followed by a visual-feature add
        for i in range(n_ds):
            lp = jax.tree.map(lambda a: a[i], params["moe_layers"])
            state, (aux, load, dropped) = body(state, (lp, sliding[i]))
            b_idx, s_idx = visual_coords
            state["h"] = state["h"].at[b_idx, s_idx].add(ds[i].astype(dtype))
            auxs.append(aux)
            loads.append(load)
            droppeds.append(dropped)
        rest = jax.tree.map(lambda a: a[n_ds:], params["moe_layers"])
        if backend.scan_layers:
            state, (aux_s, load_s, drop_s) = jax.lax.scan(body, state, (rest, sliding[n_ds:]))
        else:
            aux_l, load_l, drop_l = [], [], []
            for i in range(cfg.num_hidden_layers - n_ds):
                lp = jax.tree.map(lambda a: a[i], rest)
                state, (aux, load, dropped) = body(state, (lp, sliding[n_ds + i]))
                aux_l.append(aux)
                load_l.append(load)
                drop_l.append(dropped)
            aux_s, load_s, drop_s = jnp.stack(aux_l), jnp.stack(load_l), jnp.stack(drop_l)
        if auxs:
            aux_s = jnp.concatenate([jnp.stack(auxs), aux_s])
            load_s = jnp.concatenate([jnp.stack(loads), load_s])
            drop_s = jnp.concatenate([jnp.stack(droppeds), drop_s])

        stats = {"aux_loss": aux_s.sum() if emit_aux else None, "expert_load": load_s}
        if backend.dispatcher == "a2a":
            stats["dropped_token_frac"] = drop_s.mean()

        h = rms_norm(state["h"], params["final_norm"].astype(dtype), cfg.rms_norm_eps)
        if return_hidden:
            return h, stats
        unembed = params.get("lm_head")
        if unembed is None:
            unembed = params["embed"].T
        logits = jnp.einsum("bsd,dv->bsv", h, unembed.astype(dtype))
        return logits, stats

    # ---- interop ----

    def state_dict_adapter(self):
        from automodel_tpu.models.qwen3_vl_moe.state_dict_adapter import (
            Qwen3VLMoeStateDictAdapter,
        )

        return Qwen3VLMoeStateDictAdapter(self.config)

    @classmethod
    def from_config(cls, config, backend: BackendConfig | None = None):
        if isinstance(config, dict):
            config = Qwen3VLMoeConfig.from_hf(config)
        return cls(config, backend)
