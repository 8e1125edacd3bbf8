"""CLIP vision tower — TPU-native ViT (the vision half of the reference's VLM
support; the reference reuses HF towers directly, e.g. recipes/vlm/finetune.py
freeze_config vision handling).

Standard CLIP ViT: bias-free patch conv, class token, learned absolute positions,
pre-LN encoder with quick-GELU MLPs, attention with biases. ``feature_layer``
selects which encoder layer's output to return (LLaVA uses -2, skipping the last
layer and the post-layernorm) — matching HF ``vision_feature_layer`` semantics.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from automodel_tpu.ops.norms import layer_norm

from automodel_tpu.models.common.backend import BackendConfig
from automodel_tpu.ops.attention import sharded_attention

__all__ = ["CLIPVisionConfig", "CLIPVisionTower"]


@dataclasses.dataclass
class CLIPVisionConfig:
    hidden_size: int = 1024
    intermediate_size: int = 4096
    num_hidden_layers: int = 24
    num_attention_heads: int = 16
    image_size: int = 336
    patch_size: int = 14
    layer_norm_eps: float = 1e-5
    hidden_act: str = "quick_gelu"
    initializer_range: float = 0.02

    @classmethod
    def from_hf(cls, hf: dict[str, Any]) -> "CLIPVisionConfig":
        return cls(
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_hidden_layers=hf["num_hidden_layers"],
            num_attention_heads=hf["num_attention_heads"],
            image_size=hf.get("image_size", 336),
            patch_size=hf.get("patch_size", 14),
            layer_norm_eps=hf.get("layer_norm_eps", 1e-5),
            hidden_act=hf.get("hidden_act", "quick_gelu"),
            initializer_range=hf.get("initializer_range", 0.02),
        )

    @property
    def num_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2

    @property
    def num_positions(self) -> int:
        return self.num_patches + 1  # + class token

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


def _act(name: str, x):
    if name == "quick_gelu":
        return x * jax.nn.sigmoid(1.702 * x)
    if name in ("gelu", "gelu_new", "gelu_pytorch_tanh"):
        return jax.nn.gelu(x, approximate=name != "gelu")
    raise ValueError(f"unknown activation {name!r}")


class CLIPVisionTower:
    def __init__(self, config: CLIPVisionConfig, backend: BackendConfig | None = None):
        self.config = config
        self.backend = backend or BackendConfig()

    # -- params -------------------------------------------------------------
    def init(self, key: jax.Array, dtype=jnp.float32) -> dict:
        cfg = self.config
        d, i, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_hidden_layers
        std = cfg.initializer_range
        ks = iter(jax.random.split(key, 10))

        def w(k, shape, scale=std):
            return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

        layers = {
            "ln1_w": jnp.ones((L, d), dtype), "ln1_b": jnp.zeros((L, d), dtype),
            "wq": w(next(ks), (L, d, d)), "bq": jnp.zeros((L, d), dtype),
            "wk": w(next(ks), (L, d, d)), "bk": jnp.zeros((L, d), dtype),
            "wv": w(next(ks), (L, d, d)), "bv": jnp.zeros((L, d), dtype),
            "wo": w(next(ks), (L, d, d)), "bo": jnp.zeros((L, d), dtype),
            "ln2_w": jnp.ones((L, d), dtype), "ln2_b": jnp.zeros((L, d), dtype),
            "fc1": w(next(ks), (L, d, i)), "fc1_b": jnp.zeros((L, i), dtype),
            "fc2": w(next(ks), (L, i, d)), "fc2_b": jnp.zeros((L, d), dtype),
        }
        return {
            "patch_embed": w(next(ks), (cfg.patch_size, cfg.patch_size, 3, d)),
            "class_embed": w(next(ks), (d,)),
            "pos_embed": w(next(ks), (cfg.num_positions, d)),
            "pre_ln_w": jnp.ones((d,), dtype), "pre_ln_b": jnp.zeros((d,), dtype),
            "layers": layers,
            "post_ln_w": jnp.ones((d,), dtype), "post_ln_b": jnp.zeros((d,), dtype),
        }

    def logical_axes(self) -> dict:
        d2 = ("embed", None)
        layers = {
            "ln1_w": ("layers", "norm"), "ln1_b": ("layers", "norm"),
            "wq": ("layers", *d2), "bq": ("layers", None),
            "wk": ("layers", *d2), "bk": ("layers", None),
            "wv": ("layers", *d2), "bv": ("layers", None),
            "wo": ("layers", *d2), "bo": ("layers", None),
            "ln2_w": ("layers", "norm"), "ln2_b": ("layers", "norm"),
            "fc1": ("layers", "embed", "mlp"), "fc1_b": ("layers", "mlp"),
            "fc2": ("layers", "mlp", "embed"), "fc2_b": ("layers", None),
        }
        return {
            "patch_embed": (None, None, None, "embed"),
            "class_embed": ("embed",),
            "pos_embed": (None, "embed"),
            "pre_ln_w": ("norm",), "pre_ln_b": ("norm",),
            "layers": layers,
            "post_ln_w": ("norm",), "post_ln_b": ("norm",),
        }

    # -- forward ------------------------------------------------------------
    def __call__(self, params, pixel_values: jnp.ndarray, feature_layer: int | None = None,
                 rules=None):
        """pixel_values (B, 3, H, W) -> features (B, 1+P, D).

        ``feature_layer`` follows HF ``hidden_states`` indexing: index k (or L+1+k
        for negative k) = output after k encoder layers, never post-layernormed —
        LLaVA reads hidden_states[-2]. ``None`` = the full tower's pooled-style
        output: all layers + post-LN (HF last_hidden_state).
        """
        cfg = self.config
        dtype = self.backend.jnp_dtype
        eps = cfg.layer_norm_eps
        x = jnp.transpose(pixel_values, (0, 2, 3, 1)).astype(dtype)  # BHWC
        patches = jax.lax.conv_general_dilated(
            x, params["patch_embed"].astype(dtype),
            window_strides=(cfg.patch_size, cfg.patch_size), padding="VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        b = patches.shape[0]
        patches = patches.reshape(b, -1, cfg.hidden_size)
        cls_tok = jnp.broadcast_to(params["class_embed"].astype(dtype), (b, 1, cfg.hidden_size))
        h = jnp.concatenate([cls_tok, patches], axis=1) + params["pos_embed"].astype(dtype)
        h = layer_norm(h, params["pre_ln_w"], params["pre_ln_b"], eps)

        L = cfg.num_hidden_layers
        if feature_layer is None:
            stop_at = L
        else:
            stop_at = L + 1 + feature_layer if feature_layer < 0 else feature_layer
            if not 0 <= stop_at <= L:
                raise ValueError(
                    f"vision_feature_layer {feature_layer} out of range for {L}-layer tower"
                )

        def layer_fn(h, lp):
            lp = jax.tree.map(lambda a: a.astype(dtype), lp)
            x = layer_norm(h, lp["ln1_w"], lp["ln1_b"], eps)
            shape = (b, x.shape[1], cfg.num_attention_heads, cfg.head_dim)
            q = (x @ lp["wq"] + lp["bq"]).reshape(shape)
            k = (x @ lp["wk"] + lp["bk"]).reshape(shape)
            v = (x @ lp["wv"] + lp["bv"]).reshape(shape)
            out = sharded_attention(
                q, k, v, rules=rules, causal=False, backend=self.backend.attention)
            h = h + (out.reshape(b, x.shape[1], -1) @ lp["wo"] + lp["bo"])
            x = layer_norm(h, lp["ln2_w"], lp["ln2_b"], eps)
            h = h + (_act(cfg.hidden_act, x @ lp["fc1"] + lp["fc1_b"]) @ lp["fc2"] + lp["fc2_b"])
            return h

        # unrolled loop: feature_layer selection needs per-layer outputs; vision
        # towers are shallow (24 layers) so compile cost is fine
        for li in range(stop_at):
            lp = jax.tree.map(lambda a: a[li], params["layers"])
            h = layer_fn(h, lp)
        if feature_layer is None:
            h = layer_norm(h, params["post_ln_w"], params["post_ln_b"], eps)
        return h
