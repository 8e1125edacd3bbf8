"""NemotronParse — TPU-native seq2seq OCR family (reference
models/nemotron_parse/model.py:431 NemotronParseForConditionalGeneration).

Encoder–decoder: a RADIO vision trunk (external trust_remote_code model in the
reference too, :375) feeds a native *neck* — 1x1 conv (linear) -> LayerNorm ->
(1,4)-stride conv merging 4 horizontal patches -> LayerNorm, plus a projected
summary token appended — whose output cross-attends into an mBART-style decoder.
The decoder is MBartDecoder minus positional embeddings (reference :212-243
creates no embed_positions): scaled word embeddings, pre-norm layers with
self-attention, cross-attention and GELU FFN, embedding/final LayerNorms.

The vision trunk is pluggable: pass ``encoder_features (B, N, 1280)`` and
``summary (B, 3840)`` (RADIO outputs) and the native neck runs on device. The
``extra_heads``/``extra_proj`` linears exist for checkpoint compatibility (the
reference creates but never calls them in forward)."""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from automodel_tpu.models.common.backend import BackendConfig
from automodel_tpu.models.common.transformer import _constrain
from automodel_tpu.ops.attention import dot_product_attention, sharded_attention
from automodel_tpu.ops.norms import layer_norm

__all__ = ["NemotronParseConfig", "NemotronParseForConditionalGeneration"]


@dataclasses.dataclass
class NemotronParseConfig:
    vocab_size: int = 250027
    d_model: int = 1024
    decoder_layers: int = 12
    decoder_attention_heads: int = 16
    decoder_ffn_dim: int = 4096
    activation_function: str = "gelu"
    scale_embedding: bool = True
    num_extra_heads: int = 0
    # neck geometry (reference RadioWithNeck :366-407)
    radio_feature_dim: int = 1280
    radio_summary_dim: int = 3840
    neck_dim: int = 1024
    neck_merge: int = 4  # (1, 4) stride conv merges 4 horizontal patches
    pad_token_id: int = 1
    decoder_start_token_id: int = 2
    initializer_range: float = 0.02

    @property
    def head_dim(self) -> int:
        return self.d_model // self.decoder_attention_heads

    def __post_init__(self):
        if self.num_extra_heads:
            # reference creates but never calls these heads (model.py:448-460);
            # checkpoints with them are not yet supported
            raise NotImplementedError("num_extra_heads > 0 is not supported")

    @classmethod
    def from_hf(cls, hf: dict[str, Any]) -> "NemotronParseConfig":
        dec = hf.get("decoder", hf)
        return cls(
            vocab_size=dec.get("vocab_size", 250027),
            d_model=dec.get("d_model", 1024),
            decoder_layers=dec.get("decoder_layers", 12),
            decoder_attention_heads=dec.get("decoder_attention_heads", 16),
            decoder_ffn_dim=dec.get("decoder_ffn_dim", 4096),
            activation_function=dec.get("activation_function", "gelu"),
            scale_embedding=dec.get("scale_embedding", True),
            num_extra_heads=hf.get("num_extra_heads", 0),
            pad_token_id=hf.get("pad_token_id", dec.get("pad_token_id", 1)),
            decoder_start_token_id=hf.get("decoder_start_token_id", 2),
            initializer_range=dec.get("init_std", 0.02),
        )

    def shift_tokens_right(self, labels):
        """Host/device helper mirroring transformers shift_tokens_right (mBART):
        decoder inputs = labels rolled right with the start token prepended and
        ignore(-100) replaced by pad."""
        import numpy as np

        labels = np.asarray(labels)
        shifted = np.zeros_like(labels)
        shifted[:, 1:] = labels[:, :-1]
        shifted[:, 0] = self.decoder_start_token_id
        shifted[shifted == -100] = self.pad_token_id
        return shifted


def _attn_shapes(cfg: NemotronParseConfig, prefix: str) -> dict:
    d, H, dh = cfg.d_model, cfg.decoder_attention_heads, cfg.head_dim
    return {
        f"{prefix}_wq": (d, H, dh), f"{prefix}_bq": (H, dh),
        f"{prefix}_wk": (d, H, dh), f"{prefix}_bk": (H, dh),
        f"{prefix}_wv": (d, H, dh), f"{prefix}_bv": (H, dh),
        f"{prefix}_wo": (H, dh, d), f"{prefix}_bo": (d,),
        f"{prefix}_ln_w": (d,), f"b_{prefix}_ln": (d,),
    }


def _layer_shapes(cfg: NemotronParseConfig) -> dict:
    d, f = cfg.d_model, cfg.decoder_ffn_dim
    return (
        _attn_shapes(cfg, "self")
        | _attn_shapes(cfg, "cross")
        | {
            "fc1": (d, f), "b_fc1": (f,),
            "fc2": (f, d), "b_fc2": (d,),
            "final_ln_w": (d,), "b_final_ln": (d,),
        }
    )


class NemotronParseForConditionalGeneration:
    """Functional model: holds config + backend, operates on param pytrees."""

    config_class = NemotronParseConfig
    hf_architectures = ("NemotronParseForConditionalGeneration",)

    def __init__(self, config: NemotronParseConfig, backend: BackendConfig | None = None):
        self.config = config
        self.backend = backend or BackendConfig()

    # ---- params ----

    def init(self, key: jax.Array, dtype=jnp.float32) -> dict:
        cfg = self.config
        std = cfg.initializer_range
        d, L = cfg.d_model, cfg.decoder_layers
        keys = iter(jax.random.split(key, 16))

        def w(shape):
            return (jax.random.normal(next(keys), shape, jnp.float32) * std).astype(dtype)

        shapes = _layer_shapes(cfg)
        ks = jax.random.split(next(keys), len(shapes))
        layers = {}
        for j, (name, shape) in enumerate(shapes.items()):
            if name.endswith("ln_w"):
                layers[name] = jnp.ones((L, *shape), dtype)
            elif name.startswith("b_") or "_b" in name:
                layers[name] = jnp.zeros((L, *shape), dtype)
            else:
                layers[name] = (jax.random.normal(ks[j], (L, *shape), jnp.float32) * std).astype(dtype)

        nd = cfg.neck_dim
        params: dict = {
            "embed": w((cfg.vocab_size, d)),
            "emb_ln_w": jnp.ones((d,), dtype), "b_emb_ln": jnp.zeros((d,), dtype),
            "final_ln_w": jnp.ones((d,), dtype), "b_final_ln": jnp.zeros((d,), dtype),
            "layers": layers,
            "lm_head": w((d, cfg.vocab_size)),
            "neck": {
                "conv1_w": w((cfg.radio_feature_dim, nd)), "b_conv1": jnp.zeros((nd,), dtype),
                "ln1_w": jnp.ones((nd,), dtype), "b_ln1": jnp.zeros((nd,), dtype),
                "conv2_w": w((cfg.neck_merge * nd, nd)),  # (1,4) conv, no bias
                "ln2_w": jnp.ones((nd,), dtype), "b_ln2": jnp.zeros((nd,), dtype),
                "sum_w": w((cfg.radio_summary_dim, nd)), "b_sum": jnp.zeros((nd,), dtype),
                "ln3_w": jnp.ones((nd,), dtype), "b_ln3": jnp.zeros((nd,), dtype),
            },
        }
        return params

    def abstract_params(self, dtype=jnp.bfloat16) -> dict:
        return jax.eval_shape(lambda k: self.init(k, dtype), jax.random.key(0))

    def logical_axes(self) -> dict:
        cfg = self.config
        ax = {"embed": ("vocab", "embed"), "emb_ln_w": ("norm",), "b_emb_ln": ("norm",),
              "final_ln_w": ("norm",), "b_final_ln": ("norm",), "lm_head": ("embed", "vocab")}
        layer_ax = {}
        for name, shape in _layer_shapes(cfg).items():
            if len(shape) == 3:
                layer_ax[name] = ("layers", "embed", "heads", "head_dim")[: len(shape) + 1]
            elif len(shape) == 2:
                kind = ("embed", "mlp") if name in ("fc1",) else (
                    ("mlp", "embed") if name == "fc2" else ("heads", "head_dim")
                )
                layer_ax[name] = ("layers",) + kind
            elif name == "b_fc1":
                layer_ax[name] = ("layers", "mlp")
            else:
                layer_ax[name] = ("layers", "norm")
        # fix 3-d projections explicitly
        for p in ("self", "cross"):
            layer_ax[f"{p}_wq"] = ("layers", "embed", "heads", "head_dim")
            layer_ax[f"{p}_wk"] = ("layers", "embed", "heads", "head_dim")
            layer_ax[f"{p}_wv"] = ("layers", "embed", "heads", "head_dim")
            layer_ax[f"{p}_wo"] = ("layers", "heads", "head_dim", "embed")
        ax["layers"] = layer_ax
        ax["neck"] = {
            "conv1_w": ("embed", "mlp"), "b_conv1": ("norm",),
            "ln1_w": ("norm",), "b_ln1": ("norm",),
            "conv2_w": ("embed", "mlp"),
            "ln2_w": ("norm",), "b_ln2": ("norm",),
            "sum_w": ("embed", "mlp"), "b_sum": ("norm",),
            "ln3_w": ("norm",), "b_ln3": ("norm",),
        }
        return ax

    # ---- forward ----

    def encode(self, params, encoder_features, summary, grid_hw):
        """Neck: RADIO features (B, N, 1280) with N = h*w patches -> tokens
        (B, h*(w//4) + 1, neck_dim); summary (B, 3840) appended last."""
        cfg = self.config
        dtype = self.backend.jnp_dtype
        np_ = params["neck"]
        np_ = jax.tree.map(lambda a: a.astype(dtype), np_)
        h, w = grid_hw
        B = encoder_features.shape[0]
        x = encoder_features.astype(dtype) @ np_["conv1_w"] + np_["b_conv1"]
        x = layer_norm(x, np_["ln1_w"], np_["b_ln1"], 1e-6)
        # (1, merge)-stride conv == reshape merge horizontal neighbours + matmul
        x = x.reshape(B, h * (w // cfg.neck_merge), cfg.neck_merge * cfg.neck_dim) @ np_["conv2_w"]
        x = layer_norm(x, np_["ln2_w"], np_["b_ln2"], 1e-6)
        s = summary.astype(dtype) @ np_["sum_w"] + np_["b_sum"]
        s = layer_norm(s, np_["ln3_w"], np_["b_ln3"], 1e-6)
        return jnp.concatenate([x, s[:, None, :]], axis=1)

    def __call__(
        self,
        params,
        decoder_input_ids,  # (B, S)
        encoder_hidden_states=None,  # (B, N, d_model) pre-necked tokens
        encoder_features=None,  # (B, N_patches, 1280) raw RADIO features
        summary=None,  # (B, 3840) RADIO summary
        grid_hw=None,  # (h, w) patch grid for the neck reshape
        segment_ids=None,
        rules=None,
        training=True,
    ):
        cfg = self.config
        dtype = self.backend.jnp_dtype
        backend = self.backend
        d, H, dh = cfg.d_model, cfg.decoder_attention_heads, cfg.head_dim
        scale = d**0.5 if cfg.scale_embedding else 1.0

        if encoder_hidden_states is None and encoder_features is not None:
            encoder_hidden_states = self.encode(params, encoder_features, summary, grid_hw)

        h = params["embed"].astype(dtype)[decoder_input_ids] * jnp.asarray(scale, dtype)
        h = layer_norm(h, params["emb_ln_w"].astype(dtype), params["b_emb_ln"].astype(dtype))
        h = _constrain(h, rules, ("batch", "act_seq", "act_embed"))
        enc = None if encoder_hidden_states is None else encoder_hidden_states.astype(dtype)

        def mha(lp, p, xq, xkv, causal):
            q = jnp.einsum("bsd,dnh->bsnh", xq, lp[f"{p}_wq"]) + lp[f"{p}_bq"]
            k = jnp.einsum("bsd,dnh->bsnh", xkv, lp[f"{p}_wk"]) + lp[f"{p}_bk"]
            v = jnp.einsum("bsd,dnh->bsnh", xkv, lp[f"{p}_wv"]) + lp[f"{p}_bv"]
            out = sharded_attention(
                q, k, v, rules=rules, causal=causal,
                segment_ids_q=segment_ids if causal else None,
                backend=backend.attention,
            )
            return jnp.einsum("bsnh,nhd->bsd", out, lp[f"{p}_wo"]) + lp[f"{p}_bo"]

        def layer_fn(hh, lp):
            lp = jax.tree.map(lambda a: a.astype(dtype), lp)
            x = layer_norm(hh, lp["self_ln_w"], lp["b_self_ln"])
            hh = hh + mha(lp, "self", x, x, causal=True)
            if enc is not None:
                x = layer_norm(hh, lp["cross_ln_w"], lp["b_cross_ln"])
                hh = hh + mha(lp, "cross", x, enc, causal=False)
            x = layer_norm(hh, lp["final_ln_w"], lp["b_final_ln"])
            act = jax.nn.gelu(x @ lp["fc1"] + lp["b_fc1"], approximate=False)
            hh = hh + (act @ lp["fc2"] + lp["b_fc2"])
            return _constrain(hh, rules, ("batch", "act_seq", "act_embed")), None

        if backend.scan_layers:
            h, _ = jax.lax.scan(backend.layer_remat(layer_fn), h, params["layers"])
        else:
            for i in range(cfg.decoder_layers):
                lp = jax.tree.map(lambda a: a[i], params["layers"])
                h, _ = backend.layer_remat(layer_fn)(h, lp)

        h = layer_norm(h, params["final_ln_w"].astype(dtype), params["b_final_ln"].astype(dtype))
        logits = jnp.einsum("bsd,dv->bsv", h, params["lm_head"].astype(dtype))
        return logits, {}

    # ---- interop ----

    def state_dict_adapter(self):
        from automodel_tpu.models.nemotron_parse.state_dict_adapter import (
            NemotronParseStateDictAdapter,
        )

        return NemotronParseStateDictAdapter(self.config)

    @classmethod
    def from_config(cls, config, backend: BackendConfig | None = None):
        if isinstance(config, dict):
            config = NemotronParseConfig.from_hf(config)
        return cls(config, backend)
