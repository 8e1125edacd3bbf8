"""LLaVA-style image-text-to-text model (the VLM composition pattern the reference
serves through NeMoAutoModelForImageTextToText, _transformers/auto_model.py:614).

CLIP vision tower -> 2-layer GELU projector -> any causal decoder. Image features
replace the embedding rows whose token id equals ``image_token_index`` (HF LLaVA
merge semantics) — implemented with a static-shape gather: every sample must carry
exactly ``num_image_tokens`` placeholders (the collator guarantees it).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from automodel_tpu.models.common.backend import BackendConfig
from automodel_tpu.models.llama.model import LlamaConfig, LlamaForCausalLM
from automodel_tpu.models.vision.clip_vit import CLIPVisionConfig, CLIPVisionTower

__all__ = ["LlavaConfig", "LlavaForConditionalGeneration"]


@dataclasses.dataclass
class LlavaConfig:
    vision: CLIPVisionConfig
    text: LlamaConfig
    image_token_index: int = 32000
    vision_feature_layer: int = -2
    vision_feature_select_strategy: str = "default"  # "default" drops CLS
    projector_hidden_act: str = "gelu"

    @classmethod
    def from_hf(cls, hf: dict[str, Any]) -> "LlavaConfig":
        return cls(
            vision=CLIPVisionConfig.from_hf(hf["vision_config"]),
            text=LlamaConfig.from_hf(hf["text_config"]),
            image_token_index=hf.get("image_token_index", 32000),
            vision_feature_layer=hf.get("vision_feature_layer", -2),
            vision_feature_select_strategy=hf.get("vision_feature_select_strategy", "default"),
            projector_hidden_act=hf.get("projector_hidden_act", "gelu"),
        )

    @property
    def num_image_tokens(self) -> int:
        n = self.vision.num_patches
        return n if self.vision_feature_select_strategy == "default" else n + 1


class LlavaForConditionalGeneration:
    config_class = LlavaConfig
    hf_architectures = ("LlavaForConditionalGeneration",)

    def __init__(self, config: LlavaConfig, backend: BackendConfig | None = None):
        self.config = config
        self.backend = backend or BackendConfig()
        self.vision_tower = CLIPVisionTower(config.vision, self.backend)
        self.language_model = LlamaForCausalLM(config.text, self.backend)

    # -- params -------------------------------------------------------------
    def init(self, key: jax.Array, dtype=jnp.float32) -> dict:
        kv, kp, kt = jax.random.split(key, 3)
        dv, dt = self.config.vision.hidden_size, self.config.text.hidden_size
        std = self.config.text.initializer_range
        return {
            "vision_tower": self.vision_tower.init(kv, dtype),
            "projector": {
                "linear_1": (jax.random.normal(kp, (dv, dt), jnp.float32) * std).astype(dtype),
                "linear_1_b": jnp.zeros((dt,), dtype),
                "linear_2": (jax.random.normal(jax.random.fold_in(kp, 1), (dt, dt), jnp.float32) * std).astype(dtype),
                "linear_2_b": jnp.zeros((dt,), dtype),
            },
            "language_model": self.language_model.init(kt, dtype),
        }

    def logical_axes(self) -> dict:
        return {
            "vision_tower": self.vision_tower.logical_axes(),
            "projector": {
                "linear_1": (None, "embed"), "linear_1_b": ("embed",),
                "linear_2": ("embed", "embed"), "linear_2_b": ("embed",),
            },
            "language_model": self.language_model.logical_axes(),
        }

    def abstract_params(self, dtype=jnp.bfloat16) -> dict:
        return jax.eval_shape(lambda k: self.init(k, dtype), jax.random.key(0))

    # -- forward ------------------------------------------------------------
    def image_features(self, params, pixel_values: jnp.ndarray, rules=None) -> jnp.ndarray:
        """(B, 3, H, W) -> (B, num_image_tokens, D_text)."""
        cfg = self.config
        feats = self.vision_tower(
            params["vision_tower"], pixel_values, feature_layer=cfg.vision_feature_layer,
            rules=rules,
        )
        if cfg.vision_feature_select_strategy == "default":
            feats = feats[:, 1:]  # drop CLS
        p = params["projector"]
        dtype = self.backend.jnp_dtype
        x = feats @ p["linear_1"].astype(dtype) + p["linear_1_b"].astype(dtype)
        x = jax.nn.gelu(x, approximate=False)
        return x @ p["linear_2"].astype(dtype) + p["linear_2_b"].astype(dtype)

    def merged_embeds(self, params, input_ids, pixel_values=None, rules=None):
        """Token embeddings with image placeholders swapped for projected vision
        features (B, S, D) — the prefill input for generation."""
        cfg = self.config
        lm_params = params["language_model"]
        dtype = self.backend.jnp_dtype
        embeds = lm_params["embed"].astype(dtype)[input_ids]
        if pixel_values is not None:
            feats = self.image_features(params, pixel_values, rules)  # (B, P, D)
            mask = input_ids == cfg.image_token_index  # (B, S)
            # static-shape merge: k-th placeholder in a row takes feats[b, k]
            idx = jnp.clip(jnp.cumsum(mask, axis=1) - 1, 0, feats.shape[1] - 1)
            gathered = jnp.take_along_axis(feats, idx[..., None], axis=1)
            embeds = jnp.where(mask[..., None], gathered.astype(dtype), embeds)
        return embeds

    def __call__(self, params, input_ids, pixel_values=None, positions=None,
                 segment_ids=None, rules=None, return_hidden=False, cache=None,
                 inputs_embeds=None):
        cfg = self.config
        if inputs_embeds is None:
            inputs_embeds = self.merged_embeds(params, input_ids, pixel_values, rules)
        from automodel_tpu.models.common.transformer import decoder_forward

        return decoder_forward(
            cfg.text, self.backend, params["language_model"], input_ids,
            positions=positions, segment_ids=segment_ids, rules=rules,
            return_hidden=return_hidden, inputs_embeds=inputs_embeds, cache=cache,
        )

    def generate(self, params, input_ids, pixel_values=None, **kw):
        """Image-conditioned sampling: vision features merge into the prompt's
        prefill embeddings, decode is the plain text KV-cache loop (the
        reference's vlm_generate example does the same through HF .generate)."""
        from automodel_tpu.generation import generate

        embeds = None
        if pixel_values is not None:
            embeds = self.merged_embeds(params, jnp.asarray(input_ids, jnp.int32),
                                        pixel_values)
        return generate(self, params, input_ids, inputs_embeds=embeds,
                        decode_config=self.config.text, **kw)

    # -- HF interop ---------------------------------------------------------
    def state_dict_adapter(self):
        from automodel_tpu.models.llava.state_dict_adapter import LlavaStateDictAdapter

        return LlavaStateDictAdapter(self.config, self.backend.scan_layers)

    @classmethod
    def from_config(cls, config, backend: BackendConfig | None = None):
        if isinstance(config, dict):
            config = LlavaConfig.from_hf(config)
        return cls(config, backend)
