"""GPT-2 family — TPU-native (reference models/gpt2.py).

The one pre-Llama architecture in the inventory: learned absolute positions (wpe),
LayerNorm with bias (not RMSNorm), fused qkv ``c_attn``, tanh-approx GELU, tied
lm_head. HF stores Conv1D weights already (in, out)-oriented, so the adapter is
mostly pass-through. Useful with the nanogpt data path for speedrun-style pretraining.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from automodel_tpu.ops.norms import layer_norm

from automodel_tpu.models.common.backend import BackendConfig
from automodel_tpu.ops.attention import dot_product_attention, sharded_attention

__all__ = ["GPT2Config", "GPT2LMHeadModel"]


@dataclasses.dataclass
class GPT2Config:
    vocab_size: int = 50257
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02

    @classmethod
    def from_hf(cls, hf: dict[str, Any]) -> "GPT2Config":
        return cls(
            vocab_size=hf["vocab_size"],
            n_positions=hf.get("n_positions", 1024),
            n_embd=hf["n_embd"],
            n_layer=hf["n_layer"],
            n_head=hf["n_head"],
            layer_norm_epsilon=hf.get("layer_norm_epsilon", 1e-5),
            initializer_range=hf.get("initializer_range", 0.02),
        )

    @property
    def num_hidden_layers(self) -> int:
        """Alias for the generic KV-cache layout (generation.init_kv_cache)."""
        return self.n_layer

    @property
    def num_key_value_heads(self) -> int:
        """MHA: every head caches (GPT-2 predates GQA)."""
        return self.n_head

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head


class GPT2LMHeadModel:
    """Functional model: holds config + backend, operates on param pytrees."""

    config_class = GPT2Config
    hf_architectures = ("GPT2LMHeadModel",)

    def __init__(self, config: GPT2Config, backend: BackendConfig | None = None):
        self.config = config
        self.backend = backend or BackendConfig()

    # -- params -------------------------------------------------------------
    def init(self, key: jax.Array, dtype=jnp.float32) -> dict:
        cfg = self.config
        d, L = cfg.n_embd, cfg.n_layer
        std = cfg.initializer_range
        keys = iter(jax.random.split(key, 8))

        def norm(shape):  # (w, b)
            return jnp.ones((L, *shape), dtype), jnp.zeros((L, *shape), dtype)

        def w(k, shape, scale=std):
            return (jax.random.normal(k, (L, *shape), jnp.float32) * scale).astype(dtype)

        ln1_w, ln1_b = norm((d,))
        ln2_w, ln2_b = norm((d,))
        layers = {
            "ln1_w": ln1_w, "ln1_b": ln1_b,
            "c_attn": w(next(keys), (d, 3 * d)),
            "c_attn_b": jnp.zeros((L, 3 * d), dtype),
            "c_proj": w(next(keys), (d, d), std / (2 * L) ** 0.5),
            "c_proj_b": jnp.zeros((L, d), dtype),
            "ln2_w": ln2_w, "ln2_b": ln2_b,
            "c_fc": w(next(keys), (d, 4 * d)),
            "c_fc_b": jnp.zeros((L, 4 * d), dtype),
            "c_proj2": w(next(keys), (4 * d, d), std / (2 * L) ** 0.5),
            "c_proj2_b": jnp.zeros((L, d), dtype),
        }
        return {
            "wte": (jax.random.normal(next(keys), (cfg.vocab_size, d), jnp.float32) * std).astype(dtype),
            "wpe": (jax.random.normal(next(keys), (cfg.n_positions, d), jnp.float32) * 0.01).astype(dtype),
            "layers": layers,
            "lnf_w": jnp.ones((d,), dtype),
            "lnf_b": jnp.zeros((d,), dtype),
        }

    def logical_axes(self) -> dict:
        layers = {
            "ln1_w": ("layers", "norm"), "ln1_b": ("layers", "norm"),
            "c_attn": ("layers", "embed", "mlp"), "c_attn_b": ("layers", "mlp"),
            "c_proj": ("layers", "mlp", "embed"), "c_proj_b": ("layers", "embed"),
            "ln2_w": ("layers", "norm"), "ln2_b": ("layers", "norm"),
            "c_fc": ("layers", "embed", "mlp"), "c_fc_b": ("layers", "mlp"),
            "c_proj2": ("layers", "mlp", "embed"), "c_proj2_b": ("layers", "embed"),
        }
        return {
            "wte": ("vocab", "embed"),
            "wpe": (None, "embed"),
            "layers": layers,
            "lnf_w": ("norm",),
            "lnf_b": ("norm",),
        }

    def abstract_params(self, dtype=jnp.bfloat16) -> dict:
        return jax.eval_shape(lambda k: self.init(k, dtype), jax.random.key(0))

    # -- forward ------------------------------------------------------------
    def __call__(self, params, input_ids, positions=None, segment_ids=None, rules=None,
                 return_hidden=False, cache=None):
        cfg = self.config
        backend = self.backend
        dtype = backend.jnp_dtype
        eps = cfg.layer_norm_epsilon
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(input_ids.shape[1]), input_ids.shape)
        if cache is not None:
            if segment_ids is None:
                raise ValueError("cache decoding requires segment_ids (1 = real token)")
            if cache["k"].shape[2] > cfg.n_positions:
                raise ValueError(
                    f"decode length {cache['k'].shape[2]} exceeds the learned position "
                    f"table n_positions={cfg.n_positions}; out-of-range positions would "
                    "silently clamp into wpe and degrade output"
                )
        h = params["wte"].astype(dtype)[input_ids] + params["wpe"].astype(dtype)[positions]

        def layer_fn(h, inputs):
            if cache is not None:
                lp, kv = inputs
            else:
                lp, kv = inputs, None
            lp = jax.tree.map(lambda a: a.astype(dtype), lp)
            x = layer_norm(h, lp["ln1_w"], lp["ln1_b"], eps)
            qkv = x @ lp["c_attn"] + lp["c_attn_b"]
            q, k, v = jnp.split(qkv, 3, axis=-1)
            b, s, d = q.shape
            shape = (b, s, cfg.n_head, cfg.head_dim)
            q, k, v = q.reshape(shape), k.reshape(shape), v.reshape(shape)
            if kv is not None:
                from automodel_tpu.models.common.transformer import _cache_write

                k_cache = _cache_write(kv[0], k.astype(kv[0].dtype), cache["write_idx"])
                v_cache = _cache_write(kv[1], v.astype(kv[1].dtype), cache["write_idx"])
                out = dot_product_attention(
                    q, k_cache.astype(q.dtype), v_cache.astype(q.dtype),
                    causal=True, segment_ids_q=segment_ids,
                    segment_ids_kv=cache["valid"],
                    positions_q=positions, positions_kv=cache["positions"],
                    backend="xla",
                )
                kv_out = (k_cache, v_cache)
            else:
                out = sharded_attention(
                    q, k, v, rules=rules,
                    causal=True, segment_ids_q=segment_ids, backend=backend.attention,
                )
                kv_out = None
            h = h + (out.reshape(b, s, d) @ lp["c_proj"] + lp["c_proj_b"])
            x = layer_norm(h, lp["ln2_w"], lp["ln2_b"], eps)
            act = jax.nn.gelu(x @ lp["c_fc"] + lp["c_fc_b"], approximate=True)
            h = h + (act @ lp["c_proj2"] + lp["c_proj2_b"])
            return h, kv_out

        body = backend.layer_remat(layer_fn)
        if cache is not None:
            h, (k_new, v_new) = jax.lax.scan(
                body, h, (params["layers"], (cache["k"], cache["v"]))
            )
            cache = dict(cache, k=k_new, v=v_new)
        elif backend.scan_layers:
            h, _ = jax.lax.scan(body, h, params["layers"])
        else:
            for i in range(cfg.n_layer):
                lp = jax.tree.map(lambda a: a[i], params["layers"])
                h, _ = body(h, lp)
        h = layer_norm(h, params["lnf_w"].astype(dtype), params["lnf_b"].astype(dtype), eps)
        if cache is not None:
            # next-token logits only (B, 1, V)
            last = jnp.maximum(segment_ids.sum(-1) - 1, 0).astype(jnp.int32)
            h = jnp.take_along_axis(h, last[:, None, None], axis=1)
            if return_hidden:  # decoder_forward contract: (hidden, cache)
                return h, cache
            logits = jnp.einsum("bsd,vd->bsv", h, params["wte"].astype(dtype))
            return logits, cache
        if return_hidden:
            return h
        return jnp.einsum("bsd,vd->bsv", h, params["wte"].astype(dtype))

    # -- decode -------------------------------------------------------------
    def generate(self, params, input_ids, **kw):
        """Sample with a KV cache (see :func:`automodel_tpu.generation.generate`)."""
        from automodel_tpu.generation import generate

        return generate(self, params, input_ids, **kw)

    # -- HF interop ---------------------------------------------------------
    def state_dict_adapter(self):
        from automodel_tpu.models.gpt2.state_dict_adapter import GPT2StateDictAdapter

        return GPT2StateDictAdapter(self.config, self.backend.scan_layers)

    @classmethod
    def from_config(cls, config, backend: BackendConfig | None = None):
        if isinstance(config, dict):
            config = GPT2Config.from_hf(config)
        return cls(config, backend)
