"""Gemma 2 / Gemma 3 (text) family — TPU-native.

The reference serves Gemma through its generic HF factory
(_transformers/model_init.py:89). Gemma is NOT a llama config-delta — it has its
own layer body — so it gets a native stack here:

- sandwich norms: input_layernorm -> attn -> post_attention_layernorm -> +res;
  pre_feedforward_layernorm -> GeGLU MLP -> post_feedforward_layernorm -> +res
- zero-centered RMSNorm weights: ``x_norm * (1 + w)`` (rms_norm offset=1.0)
- embeddings scaled by sqrt(hidden_size)
- attention scale from ``query_pre_attn_scalar`` (not head_dim)
- gelu-tanh gated MLP
- gemma2: attn + final logit soft-capping, alternating sliding layers
- gemma3: per-head q/k RMSNorm and DUAL rope — sliding layers use
  ``rope_local_base_freq`` unscaled, full layers use ``rope_theta`` with the
  config's rope_scaling (linear 8x on 4B+)

One ``lax.scan`` over stacked layer params; both rope angle tables are computed
once and the per-layer sliding flag selects between them inside the scan.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from automodel_tpu.models.common.backend import BackendConfig
from automodel_tpu.models.common.transformer import _constrain
from automodel_tpu.ops.attention import dot_product_attention, sharded_attention
from automodel_tpu.ops.norms import rms_norm
from automodel_tpu.ops.rope import apply_rope, rope_frequencies

__all__ = ["GemmaConfig", "GemmaForCausalLM"]


@dataclasses.dataclass
class GemmaConfig:
    vocab_size: int = 262144
    hidden_size: int = 2304
    intermediate_size: int = 9216
    num_hidden_layers: int = 26
    num_attention_heads: int = 8
    num_key_value_heads: int = 4
    head_dim: int = 256
    max_position_embeddings: int = 131072
    rope_theta: float = 1_000_000.0
    rope_local_base_freq: float | None = 10_000.0  # gemma3 sliding-layer rope
    rope_scaling: dict[str, Any] | None = None  # applies to FULL layers only
    query_pre_attn_scalar: float = 256.0
    rms_norm_eps: float = 1e-6
    sliding_window: int | None = 4096
    layer_types: "list[str] | None" = None
    attn_logit_softcapping: float | None = None  # gemma2
    final_logit_softcapping: float | None = None  # gemma2
    qk_norm: bool = True  # gemma3; False for gemma2
    tie_word_embeddings: bool = True
    initializer_range: float = 0.02
    causal: bool = True

    @classmethod
    def from_hf(cls, hf: dict[str, Any]) -> "GemmaConfig":
        archs = "".join(hf.get("architectures") or [])
        is_g2 = "Gemma2" in archs
        layer_types = hf.get("layer_types")
        if layer_types is None:
            # gemma2 default: alternating sliding/full starting at layer 0;
            # gemma3 default: 5 sliding : 1 full (sliding_window_pattern=6)
            pat = hf.get("sliding_window_pattern") or (2 if is_g2 else 6)
            layer_types = [
                "full_attention" if (i + 1) % pat == 0 else "sliding_attention"
                for i in range(hf["num_hidden_layers"])
            ]
        return cls(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_hidden_layers=hf["num_hidden_layers"],
            num_attention_heads=hf["num_attention_heads"],
            num_key_value_heads=hf.get("num_key_value_heads", hf["num_attention_heads"]),
            head_dim=hf.get("head_dim", 256),
            max_position_embeddings=hf.get("max_position_embeddings", 131072),
            rope_theta=hf.get("rope_theta", 10000.0 if is_g2 else 1_000_000.0),
            rope_local_base_freq=None if is_g2 else hf.get("rope_local_base_freq", 10_000.0),
            rope_scaling=hf.get("rope_scaling"),
            query_pre_attn_scalar=hf.get("query_pre_attn_scalar", 256.0),
            rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
            sliding_window=hf.get("sliding_window", 4096),
            layer_types=list(layer_types),
            attn_logit_softcapping=hf.get("attn_logit_softcapping") if is_g2 else None,
            final_logit_softcapping=hf.get("final_logit_softcapping") if is_g2 else None,
            qk_norm=not is_g2,
            tie_word_embeddings=hf.get("tie_word_embeddings", True),
            initializer_range=hf.get("initializer_range", 0.02),
        )

    @property
    def sliding_flags(self) -> "list[bool]":
        if self.layer_types is not None:
            return [t == "sliding_attention" for t in self.layer_types]
        return [False] * self.num_hidden_layers


def _layer_shapes(cfg: GemmaConfig) -> dict:
    d, n, k, h, i = (cfg.hidden_size, cfg.num_attention_heads,
                     cfg.num_key_value_heads, cfg.head_dim, cfg.intermediate_size)
    shapes = {
        "attn_norm": (d,), "post_attn_norm": (d,),
        "pre_ffn_norm": (d,), "post_ffn_norm": (d,),
        "wq": (d, n, h), "wk": (d, k, h), "wv": (d, k, h), "wo": (n, h, d),
        "w_gate": (d, i), "w_up": (d, i), "w_down": (i, d),
    }
    if cfg.qk_norm:
        shapes["q_norm"] = (h,)
        shapes["k_norm"] = (h,)
    return shapes


_LAYER_AXES = {
    "attn_norm": ("norm",), "post_attn_norm": ("norm",),
    "pre_ffn_norm": ("norm",), "post_ffn_norm": ("norm",),
    "wq": ("embed", "heads", None), "wk": ("embed", "kv_heads", None),
    "wv": ("embed", "kv_heads", None), "wo": ("heads", None, "embed"),
    "w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"), "w_down": ("mlp", "embed"),
    "q_norm": ("norm",), "k_norm": ("norm",),
}


class GemmaForCausalLM:
    """Functional model: holds config + backend, operates on param pytrees."""

    config_class = GemmaConfig
    hf_architectures = ("Gemma2ForCausalLM", "Gemma3ForCausalLM", "Gemma3ForConditionalGeneration")

    def __init__(self, config: GemmaConfig, backend: BackendConfig | None = None):
        self.config = config
        self.backend = backend or BackendConfig()

    # -- params -------------------------------------------------------------
    def init(self, key: jax.Array, dtype=jnp.float32) -> dict:
        cfg = self.config
        std = cfg.initializer_range
        shapes = _layer_shapes(cfg)
        k_embed, k_layers = jax.random.split(key)
        keys = jax.random.split(k_layers, len(shapes))
        L = cfg.num_hidden_layers
        layers = {}
        for idx, (name, shape) in enumerate(shapes.items()):
            if name.endswith("norm"):
                # zero-centered weights: effective scale is (1 + w)
                layers[name] = jnp.zeros((L, *shape), dtype)
            else:
                layers[name] = (
                    jax.random.normal(keys[idx], (L, *shape), jnp.float32) * std
                ).astype(dtype)
        params = {
            "embed": (jax.random.normal(k_embed, (cfg.vocab_size, cfg.hidden_size),
                                        jnp.float32) * std).astype(dtype),
            "final_norm": jnp.zeros((cfg.hidden_size,), dtype),
            "layers": layers,
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = (
                jax.random.normal(k_embed, (cfg.hidden_size, cfg.vocab_size), jnp.float32) * std
            ).astype(dtype)
        return params

    def logical_axes(self) -> dict:
        cfg = self.config
        axes = {
            "embed": ("vocab", "embed"),
            "final_norm": ("norm",),
            "layers": {
                name: ("layers",) + _LAYER_AXES[name] for name in _layer_shapes(cfg)
            },
        }
        if not cfg.tie_word_embeddings:
            axes["lm_head"] = ("embed", "vocab")
        return axes

    def abstract_params(self, dtype=jnp.bfloat16) -> dict:
        return jax.eval_shape(lambda k: self.init(k, dtype), jax.random.key(0))

    # -- forward ------------------------------------------------------------
    def __call__(self, params, input_ids, positions=None, segment_ids=None,
                 token_mask=None, rules=None, return_hidden=False, training=True,
                 cache=None):
        cfg, backend = self.config, self.backend
        del token_mask, training
        dtype = backend.jnp_dtype
        B, S = input_ids.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        if cache is not None and segment_ids is None:
            raise ValueError("cache decoding requires segment_ids (1 = real token)")
        eps = cfg.rms_norm_eps

        h = params["embed"].astype(dtype)[input_ids]
        # HF scales by the normalizer CAST to the embed dtype (bf16 rounding is
        # part of the checkpoint contract, modeling_gemma3 normalizer)
        h = h * jnp.asarray(cfg.hidden_size**0.5, dtype)
        h = _constrain(h, rules, ("batch", "act_seq", "act_embed"))

        # dual rope tables: full layers scale by rope_scaling; sliding layers
        # (gemma3) use the unscaled local base frequency
        inv_full = rope_frequencies(cfg.head_dim, cfg.rope_theta, cfg.rope_scaling)
        inv_local = (
            rope_frequencies(cfg.head_dim, cfg.rope_local_base_freq)
            if cfg.rope_local_base_freq is not None else inv_full
        )
        scale = float(cfg.query_pre_attn_scalar) ** -0.5
        sliding = jnp.asarray(cfg.sliding_flags, jnp.bool_)
        any_sliding = any(cfg.sliding_flags)
        window = cfg.sliding_window

        def layer_fn(h, inputs):
            if cache is not None:
                lp, is_sliding, kv = inputs
            else:
                (lp, is_sliding), kv = inputs, None
            lp = jax.tree.map(lambda a: a.astype(dtype), lp)
            x = rms_norm(h, lp["attn_norm"], eps, offset=1.0)
            q = jnp.einsum("bsd,dnh->bsnh", x, lp["wq"])
            k = jnp.einsum("bsd,dnh->bsnh", x, lp["wk"])
            v = jnp.einsum("bsd,dnh->bsnh", x, lp["wv"])
            if cfg.qk_norm:
                q = rms_norm(q, lp["q_norm"], eps, offset=1.0)
                k = rms_norm(k, lp["k_norm"], eps, offset=1.0)
            inv = jnp.where(is_sliding, inv_local, inv_full)
            q = apply_rope(q, positions, inv)
            k = apply_rope(k, positions, inv)
            eff_window = None
            if any_sliding and window is not None:
                # "disabled" bound must exceed every causal q-kv distance; under
                # cached decode that distance is bounded by the CACHE length
                kv_len = S if kv is None else kv[0].shape[1]
                big = jnp.int32(cfg.max_position_embeddings + max(S, kv_len))
                eff_window = jnp.where(is_sliding, jnp.int32(window), big)
            if kv is not None:
                from automodel_tpu.models.common.transformer import _cache_write

                k_cache = _cache_write(kv[0], k.astype(kv[0].dtype), cache["write_idx"])
                v_cache = _cache_write(kv[1], v.astype(kv[1].dtype), cache["write_idx"])
                out = dot_product_attention(
                    q, k_cache.astype(q.dtype), v_cache.astype(q.dtype),
                    causal=cfg.causal, segment_ids_q=segment_ids,
                    segment_ids_kv=cache["valid"],
                    positions_q=positions, positions_kv=cache["positions"],
                    sliding_window=eff_window, softmax_scale=scale,
                    logit_soft_cap=cfg.attn_logit_softcapping,
                    backend="xla",  # q_len 1 / position-masked
                )
                kv_out = (k_cache, v_cache)
            else:
                out = sharded_attention(
                    q, k, v, rules=rules, causal=cfg.causal, segment_ids_q=segment_ids,
                    sliding_window=eff_window, softmax_scale=scale,
                    logit_soft_cap=cfg.attn_logit_softcapping, backend=backend.attention,
                )
                kv_out = None
            attn = jnp.einsum("bsnh,nhd->bsd", out, lp["wo"])
            attn = rms_norm(attn, lp["post_attn_norm"], eps, offset=1.0)
            h = _constrain(h + attn, rules, ("batch", "act_seq", "act_embed"))

            x = rms_norm(h, lp["pre_ffn_norm"], eps, offset=1.0)
            act = jax.nn.gelu(x @ lp["w_gate"], approximate=True) * (x @ lp["w_up"])
            mlp = act @ lp["w_down"]
            mlp = rms_norm(mlp, lp["post_ffn_norm"], eps, offset=1.0)
            h = _constrain(h + mlp, rules, ("batch", "act_seq", "act_embed"))
            return h, kv_out

        body = backend.layer_remat(layer_fn)
        if cache is not None:
            h, (k_new, v_new) = jax.lax.scan(
                body, h, (params["layers"], sliding, (cache["k"], cache["v"]))
            )
            cache = dict(cache, k=k_new, v=v_new)
        elif backend.scan_layers:
            h, _ = jax.lax.scan(body, h, (params["layers"], sliding))
        else:
            for i in range(cfg.num_hidden_layers):
                lp = jax.tree.map(lambda a: a[i], params["layers"])
                h, _ = body(h, (lp, sliding[i]))

        h = rms_norm(h, params["final_norm"].astype(dtype), eps, offset=1.0)
        if cache is not None:
            # next-token logits only (B, 1, V) — see transformer.decoder_forward
            last = jnp.maximum(segment_ids.sum(-1) - 1, 0).astype(jnp.int32)
            h = jnp.take_along_axis(h, last[:, None, None], axis=1)
        if return_hidden:
            return h if cache is None else (h, cache)
        unembed = params.get("lm_head")
        if unembed is None:
            unembed = params["embed"].T
        logits = jnp.einsum("bsd,dv->bsv", h, unembed.astype(dtype))
        if cfg.final_logit_softcapping:
            cap = cfg.final_logit_softcapping
            logits = jnp.tanh(logits / cap) * cap
        return logits if cache is None else (logits, cache)

    def generate(self, params, input_ids, **kw):
        """Sample with a KV cache (see :func:`automodel_tpu.generation.generate`)."""
        from automodel_tpu.generation import generate

        return generate(self, params, input_ids, **kw)

    # -- HF interop ---------------------------------------------------------
    def state_dict_adapter(self):
        from automodel_tpu.models.gemma.state_dict_adapter import GemmaStateDictAdapter

        return GemmaStateDictAdapter(self.config, scan_layers=self.backend.scan_layers)

    @classmethod
    def from_config(cls, config, backend: BackendConfig | None = None):
        if isinstance(config, dict):
            if "text_config" in config:  # Gemma3ForConditionalGeneration wrapper
                inner = dict(config["text_config"])
                inner.setdefault("architectures", config.get("architectures"))
                config = inner
            config = GemmaConfig.from_hf(config)
        return cls(config, backend)
