"""Kimi-VL — TPU-native (reference models/kimivl/model.py:625 KimiVLForConditionalGeneration).

MoonViT native-resolution vision tower (models/vision/moonvit.py) + multimodal
projector (pre-norm LayerNorm -> merge-flatten -> 2-layer GELU MLP,
reference :378-399) + DeepSeek-V2/V3 MLA text decoder (reused from the
deepseek_v3 family). Vision features replace the embedding rows at
``media_placeholder_token_id`` positions (reference _merge_with_image_features).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp

from automodel_tpu.models.common.backend import BackendConfig
from automodel_tpu.models.common.moe_transformer import moe_decoder_forward
from automodel_tpu.models.deepseek_v3.model import (
    DeepseekV3Config,
    DeepseekV3ForCausalLM,
)
from automodel_tpu.models.vision.moonvit import (
    MoonViTConfig,
    init_moonvit_params,
    moonvit_forward,
    moonvit_logical_axes,
    prepare_moonvit_inputs,
)
from automodel_tpu.ops.norms import layer_norm

__all__ = ["KimiVLConfig", "KimiVLForConditionalGeneration"]


@dataclasses.dataclass
class KimiVLConfig:
    text: DeepseekV3Config = None
    vision: MoonViTConfig = None
    media_placeholder_token_id: int = 163605

    @classmethod
    def from_hf(cls, hf: dict[str, Any]) -> "KimiVLConfig":
        return cls(
            text=DeepseekV3Config.from_hf(hf["text_config"]),
            vision=MoonViTConfig.from_hf(hf.get("vision_config", {})),
            media_placeholder_token_id=hf.get("media_placeholder_token_id", 163605),
        )


class KimiVLForConditionalGeneration:
    """Functional model: holds config + backend, operates on param pytrees."""

    config_class = KimiVLConfig
    hf_architectures = ("KimiVLForConditionalGeneration",)

    def __init__(self, config: KimiVLConfig, backend: BackendConfig | None = None):
        self.config = config
        self.backend = backend or BackendConfig()
        self._text = DeepseekV3ForCausalLM(config.text, self.backend)

    # ---- params ----

    def init(self, key: jax.Array, dtype=jnp.float32) -> dict:
        cfg = self.config
        k_text, k_vis, k_proj = jax.random.split(key, 3)
        params = self._text.init(k_text, dtype)
        params["visual"] = init_moonvit_params(cfg.vision, k_vis, dtype)
        d_vis = cfg.vision.hidden_size
        mu = cfg.vision.merge_kernel_size[0] * cfg.vision.merge_kernel_size[1]
        dm = d_vis * mu
        std = cfg.text.initializer_range
        k1, k2 = jax.random.split(k_proj)
        params["projector"] = {
            "pre_ln_w": jnp.ones((d_vis,), dtype), "b_pre_ln": jnp.zeros((d_vis,), dtype),
            "w1": (jax.random.normal(k1, (dm, dm), jnp.float32) * std).astype(dtype),
            "b1": jnp.zeros((dm,), dtype),
            "w2": (jax.random.normal(k2, (dm, cfg.text.hidden_size), jnp.float32) * std).astype(dtype),
            "b2": jnp.zeros((cfg.text.hidden_size,), dtype),
        }
        return params

    def abstract_params(self, dtype=jnp.bfloat16) -> dict:
        return jax.eval_shape(lambda k: self.init(k, dtype), jax.random.key(0))

    def logical_axes(self) -> dict:
        axes = self._text.logical_axes()
        axes["visual"] = moonvit_logical_axes(self.config.vision)
        axes["projector"] = {
            "pre_ln_w": ("norm",), "b_pre_ln": ("norm",),
            "w1": ("embed", "mlp"), "b1": ("mlp",),
            "w2": ("mlp", "embed"), "b2": ("norm",),
        }
        return axes

    # ---- host-side helpers ----

    def prepare_vision_inputs(self, grid_hws: np.ndarray) -> dict[str, np.ndarray]:
        return prepare_moonvit_inputs(grid_hws, self.config.vision)

    def media_token_coords(self, input_ids: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        b, s = np.where(input_ids == self.config.media_placeholder_token_id)
        return b.astype(np.int32), s.astype(np.int32)

    # ---- forward ----

    def __call__(
        self,
        params,
        input_ids,
        pixel_values=None,  # (T, C*P*P) flattened patches
        vision_inputs=None,  # dict from prepare_vision_inputs
        media_coords=None,  # (b_idx, s_idx) of placeholder tokens
        positions=None,
        segment_ids=None,
        token_mask=None,
        rules=None,
        return_hidden=False,
        training=True,
    ):
        cfg = self.config
        dtype = self.backend.jnp_dtype
        embeds = params["embed"].astype(dtype)[input_ids]

        if pixel_values is not None:
            vi = vision_inputs
            mu = cfg.vision.merge_kernel_size[0] * cfg.vision.merge_kernel_size[1]
            # merged-slot count is a static shape: one projector row per media token.
            # OOB scatter indices are silently dropped by .at[].add, so mismatched
            # placeholder/pixel counts must fail loudly here (shapes are host-known).
            n_merged_units = media_coords[0].shape[0] * mu
            if vi["out_idx"].shape[0] != pixel_values.shape[0]:
                raise ValueError("vision_inputs do not match pixel_values token count")
            feats = moonvit_forward(
                cfg.vision, self.backend, params["visual"], pixel_values,
                vi["rope_angles"], vi["segment_ids"], vi["pos_idx"], vi["pos_w"],
                vi["out_idx"], vi["out_w"], n_merged_units,
                time_emb=vi.get("time_emb"), rules=rules,
            )  # (Tm, mu, d_vis)
            pp = params["projector"]
            ln_eps = getattr(cfg, "projector_ln_eps", 1e-5)
            x = layer_norm(feats, pp["pre_ln_w"].astype(dtype), pp["b_pre_ln"].astype(dtype), ln_eps)
            x = x.reshape(feats.shape[0], -1)
            x = jax.nn.gelu(x @ pp["w1"].astype(dtype) + pp["b1"].astype(dtype), approximate=False)
            x = x @ pp["w2"].astype(dtype) + pp["b2"].astype(dtype)
            b_idx, s_idx = media_coords
            embeds = embeds.at[b_idx, s_idx].set(x.astype(dtype))

        return moe_decoder_forward(
            cfg.text, self.backend, params, input_ids,
            positions=positions, segment_ids=segment_ids, token_mask=token_mask,
            rules=rules, return_hidden=return_hidden, training=training,
            attention_fn=self._text.make_attention_fn(),
            inputs_embeds=embeds,
        )

    # ---- interop ----

    def state_dict_adapter(self):
        from automodel_tpu.models.kimivl.state_dict_adapter import KimiVLStateDictAdapter

        return KimiVLStateDictAdapter(self.config)

    @classmethod
    def from_config(cls, config, backend: BackendConfig | None = None):
        if isinstance(config, dict):
            config = KimiVLConfig.from_hf(config)
        return cls(config, backend)
