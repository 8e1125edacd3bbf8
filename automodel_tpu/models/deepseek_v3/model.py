"""DeepSeek-V3 family — TPU-native (reference models/deepseek_v3/model.py:233,
layers.py:37 MLA).

Multi-head Latent Attention: queries and key/values factor through low-rank latents
(q_lora_rank / kv_lora_rank); the rope sub-dimension rides a separate single-head
stream concatenated onto every head. Interleaved (complex-pair) rope, YaRN mscale^2
softmax-scale correction. MoE layers use sigmoid noaux-tc routing with group-limited
selection, shared experts, and the loss-free balancing correction bias; the first
``first_k_dense_replace`` layers stay dense. Also serves DeepSeek-V2/V2-Lite
(q_lora_rank None -> direct q projection), Moonlight, and Kimi-K2 configs, which share
the architecture.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from automodel_tpu.models.common.backend import BackendConfig
from automodel_tpu.models.common.moe_transformer import (
    init_moe_decoder_params,
    moe_decoder_forward,
    moe_decoder_logical_axes,
)
from automodel_tpu.models.common.transformer import _constrain
from automodel_tpu.moe.config import MoEConfig
from automodel_tpu.ops.attention import dot_product_attention, sharded_attention
from automodel_tpu.ops.norms import rms_norm
from automodel_tpu.ops.rope import apply_rope_interleaved, rope_frequencies

__all__ = ["DeepseekV3Config", "DeepseekV3ForCausalLM"]


@dataclasses.dataclass
class DeepseekV3Config:
    vocab_size: int = 129280
    hidden_size: int = 7168
    intermediate_size: int = 18432
    num_hidden_layers: int = 61
    num_attention_heads: int = 128
    q_lora_rank: int | None = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    first_k_dense_replace: int = 3
    max_position_embeddings: int = 4096
    rope_theta: float = 10000.0
    rope_scaling: dict[str, Any] | None = None
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    moe: MoEConfig | None = None

    def __post_init__(self):
        if self.moe is None:
            raise ValueError("DeepseekV3Config requires a MoEConfig in .moe")

    # moe_decoder_forward duck-type surface (MLA has no sliding-window variants)
    sliding_window = None

    @property
    def sliding_flags(self) -> list[bool]:
        return [False] * self.num_hidden_layers

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def num_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def softmax_scale(self) -> float:
        """qk_head_dim^-0.5 with the YaRN mscale^2 correction
        (reference layers.py:103-117)."""
        scale = self.qk_head_dim**-0.5
        rs = self.rope_scaling
        if rs and all(k in rs for k in ("factor", "mscale", "original_max_position_embeddings")):
            mscale = float(rs["mscale"])
            if self.max_position_embeddings > rs["original_max_position_embeddings"]:
                factor = float(rs["factor"])
                if factor > 1:
                    mscale = 0.1 * mscale * math.log(factor) + 1.0
            scale = scale * mscale * mscale
        return scale

    @classmethod
    def from_hf(cls, hf: dict[str, Any]) -> "DeepseekV3Config":
        # V3 scores with sigmoid + noaux-tc correction bias; V2 softmaxes before a
        # greedy / group-limited-greedy top-k (HF scoring_func / topk_method fields,
        # absent on V3 configs where noaux_tc is the only mode).
        scoring = hf.get("scoring_func", "sigmoid")
        topk_method = hf.get("topk_method", "noaux_tc")
        moe = MoEConfig(
            n_routed_experts=hf["n_routed_experts"],
            n_activated_experts=hf["num_experts_per_tok"],
            dim=hf["hidden_size"],
            moe_inter_dim=hf["moe_intermediate_size"],
            n_shared_experts=hf.get("n_shared_experts", 0),
            n_expert_groups=max(hf.get("n_group") or 1, 1),
            n_limited_groups=max(hf.get("topk_group") or 1, 1),
            gate_bias_update_factor=0.001 if topk_method == "noaux_tc" else 0.0,
            score_func=scoring,
            softmax_before_topk=scoring == "softmax",
            route_scale=hf.get("routed_scaling_factor", 1.0),
            norm_topk_prob=hf.get("norm_topk_prob", True),
        )
        return cls(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_hidden_layers=hf["num_hidden_layers"],
            num_attention_heads=hf["num_attention_heads"],
            q_lora_rank=hf.get("q_lora_rank"),
            kv_lora_rank=hf["kv_lora_rank"],
            qk_nope_head_dim=hf["qk_nope_head_dim"],
            qk_rope_head_dim=hf["qk_rope_head_dim"],
            v_head_dim=hf["v_head_dim"],
            first_k_dense_replace=_first_k_dense(hf),
            max_position_embeddings=hf.get("max_position_embeddings", 4096),
            rope_theta=hf.get("rope_theta", 10000.0),
            rope_scaling=hf.get("rope_scaling"),
            rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
            tie_word_embeddings=hf.get("tie_word_embeddings", False),
            initializer_range=hf.get("initializer_range", 0.02),
            moe=moe,
        )


def _first_k_dense(hf: dict[str, Any]) -> int:
    """first_k_dense_replace, or a GLM4-MoE-Lite style mlp_layer_types prefix
    (["dense", "sparse", ...] — only dense-prefix patterns are supported)."""
    layer_types = hf.get("mlp_layer_types")
    if layer_types:
        flags = [t == "sparse" for t in layer_types]
        first = flags.index(True) if any(flags) else len(flags)
        if not all(flags[first:]):
            raise NotImplementedError("non-prefix dense/sparse interleavings are not supported")
        return first
    return hf.get("first_k_dense_replace", 0)


def _mla_shapes(cfg: DeepseekV3Config) -> dict[str, tuple[int, ...]]:
    d, n = cfg.hidden_size, cfg.num_attention_heads
    shapes: dict[str, tuple[int, ...]] = {"attn_norm": (d,), "mlp_norm": (d,)}
    if cfg.q_lora_rank is None:
        shapes["wq"] = (d, n, cfg.qk_head_dim)
    else:
        shapes |= {
            "wq_a": (d, cfg.q_lora_rank),
            "q_a_norm": (cfg.q_lora_rank,),
            "wq_b": (cfg.q_lora_rank, n, cfg.qk_head_dim),
        }
    shapes |= {
        "wkv_a": (d, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
        "kv_a_norm": (cfg.kv_lora_rank,),
        "wkv_b": (cfg.kv_lora_rank, n, cfg.qk_nope_head_dim + cfg.v_head_dim),
        "wo": (n, cfg.v_head_dim, d),
    }
    return shapes


_MLA_AXES = {
    "attn_norm": ("norm",),
    "mlp_norm": ("norm",),
    "wq": ("embed", "heads", "head_dim"),
    "wq_a": ("embed", None),
    "q_a_norm": ("norm",),
    "wq_b": (None, "heads", "head_dim"),
    "wkv_a": ("embed", None),
    "kv_a_norm": ("norm",),
    "wkv_b": (None, "heads", "head_dim"),
    "wo": ("heads", "head_dim", "embed"),
}

def init_params(cfg: DeepseekV3Config, key: jax.Array, dtype=jnp.float32) -> dict:
    return init_moe_decoder_params(cfg, key, dtype, attn_shapes=_mla_shapes(cfg))


def logical_axes(cfg: DeepseekV3Config) -> dict:
    return moe_decoder_logical_axes(
        cfg, attn_axes=_MLA_AXES, attn_names=list(_mla_shapes(cfg))
    )


def _mla_block(cfg: DeepseekV3Config, backend: BackendConfig, lp: dict, x, positions,
               segment_ids, inv_freq, rules, bias_fn=None, bias_decode_fn=None,
               cache=None, cache_meta=None):
    """MLA attention (reference layers.py:122-198). ``bias_fn(lp, x, q_latent,
    positions, segment_ids) -> (B, S, S) additive logit bias`` is the V3.2 sparse
    indexer hook (reference deepseek_v32/layers.py:430-500).

    With ``cache=(k_cache, v_cache)`` (decode): the EXPANDED per-head k/v are
    written at ``cache_meta["write_idx"]`` and attention runs against the whole
    cache (k head-dim = nope+rope, v head-dim = v_head_dim — they differ; the
    XLA path handles the asymmetry). The latent-absorbed decode (caching only
    c_kv + k_pe) is a memory optimization left on the table — sampling
    correctness is what this path buys. Returns ``(out, (k_cache, v_cache))``."""
    q_latent = None
    if cfg.q_lora_rank is None:
        q = jnp.einsum("bsd,dnh->bsnh", x, lp["wq"])
    else:
        q_latent = rms_norm(jnp.einsum("bsd,dr->bsr", x, lp["wq_a"]), lp["q_a_norm"], cfg.rms_norm_eps)
        q = jnp.einsum("bsr,rnh->bsnh", q_latent, lp["wq_b"])
    q_nope, q_pe = jnp.split(q, [cfg.qk_nope_head_dim], axis=-1)

    kv = jnp.einsum("bsd,dr->bsr", x, lp["wkv_a"])
    c_kv, k_pe = jnp.split(kv, [cfg.kv_lora_rank], axis=-1)
    c_kv = rms_norm(c_kv, lp["kv_a_norm"], cfg.rms_norm_eps)

    q_pe = apply_rope_interleaved(q_pe, positions, inv_freq)
    k_pe = apply_rope_interleaved(k_pe[:, :, None, :], positions, inv_freq)

    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    kv = jnp.einsum("bsr,rnh->bsnh", c_kv, lp["wkv_b"])
    k_nope, v = jnp.split(kv, [cfg.qk_nope_head_dim], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, (*k_nope.shape[:-1], cfg.qk_rope_head_dim))], axis=-1
    )

    if cache is not None:
        from automodel_tpu.models.common.transformer import _cache_write

        extra_bias = None
        idx_out = ()
        if len(cache) == 3:
            # V3.2: third cache slot is the per-layer indexer-key cache; the
            # decode fn writes the chunk's keys and returns the (B,s,S_max)
            # sparse bias over the whole cache (deepseek_v32.make_indexer_decode_fn)
            if bias_decode_fn is None:
                raise NotImplementedError(
                    "3-slot MLA cache needs a bias_decode_fn (V3.2 indexer)"
                )
            extra_bias, idx_cache = bias_decode_fn(
                lp, x, q_latent, positions, cache[2], cache_meta
            )
            idx_out = (idx_cache,)
        elif bias_fn is not None:
            raise NotImplementedError(
                "V3.2 sparse-indexer decode needs the indexer-key cache slot "
                "(init_decode_cache) — got a 2-slot k/v cache"
            )
        k_cache = _cache_write(cache[0], k.astype(cache[0].dtype), cache_meta["write_idx"])
        v_cache = _cache_write(cache[1], v.astype(cache[1].dtype), cache_meta["write_idx"])
        out = dot_product_attention(
            q, k_cache.astype(q.dtype), v_cache.astype(q.dtype),
            causal=True,
            segment_ids_q=segment_ids,
            segment_ids_kv=cache_meta["valid"],
            positions_q=positions,
            positions_kv=cache_meta["positions"],
            softmax_scale=cfg.softmax_scale,
            extra_bias=extra_bias,
            backend="xla",  # q_len 1 / position-masked: the flash kernel doesn't apply
        )
        return jnp.einsum("bsnh,nhd->bsd", out, lp["wo"]), (k_cache, v_cache, *idx_out)

    from jax.ad_checkpoint import checkpoint_name

    q = checkpoint_name(_constrain(q, rules, ("batch", "act_attn_seq", "act_heads", None)), "attn_q")
    k = checkpoint_name(_constrain(k, rules, ("batch", "act_attn_seq", "act_heads", None)), "attn_k")
    v = checkpoint_name(v, "attn_v")
    extra_bias = None
    if bias_fn is not None:
        extra_bias = bias_fn(lp, x, q_latent, positions, segment_ids)
    mesh = rules.mesh if rules is not None else None
    use_ring = (
        backend.context_parallel == "ring"
        and mesh is not None
        and mesh.shape.get("cp", 1) > 1
        and extra_bias is None  # V3.2 sparse-indexer bias is (S_global, S_global)
    )
    if use_ring:
        # MLA ring CP (reference runs MLA through TE ring attention the same way,
        # moe/parallelizer.py:267-285): v_head_dim != qk dim is fine — the ring
        # accumulator follows v's dim
        from automodel_tpu.parallel.ring_attention import make_ring_attention

        ring = make_ring_attention(mesh, causal=True, softmax_scale=cfg.softmax_scale)
        out = ring(q, k, v, positions, segment_ids)
    else:
        out = sharded_attention(
            q, k, v,
            rules=rules,
            causal=True,
            segment_ids_q=segment_ids,
            softmax_scale=cfg.softmax_scale,
            extra_bias=extra_bias,
            backend=backend.attention,
        )
    return jnp.einsum("bsnh,nhd->bsd", out, lp["wo"])


def forward(
    cfg: DeepseekV3Config,
    backend: BackendConfig,
    params: dict,
    input_ids: jnp.ndarray,
    positions: jnp.ndarray | None = None,
    segment_ids: jnp.ndarray | None = None,
    token_mask: jnp.ndarray | None = None,
    rules=None,
    return_hidden: bool = False,
    training: bool = True,
    cache=None,
):
    """moe_decoder_forward with the MLA attention hook; returns (out, stats)
    (or ``(logits, cache)`` on the decode path)."""
    return moe_decoder_forward(
        cfg, backend, params, input_ids,
        positions=positions, segment_ids=segment_ids, token_mask=token_mask,
        rules=rules, return_hidden=return_hidden, training=training,
        attention_fn=make_mla_attention_fn(cfg, backend),
        cache=cache,
    )


def mla_inv_freq(cfg: DeepseekV3Config) -> jnp.ndarray:
    """Rope frequencies for the MLA rope sub-dim; the reference applies the YaRN
    correction only when training beyond the original context
    (rope_utils.py:113-117). V3.2's indexer shares these frequencies."""
    rs = cfg.rope_scaling
    use_yarn = bool(
        rs
        and all(k in rs for k in ("factor", "beta_fast", "beta_slow", "original_max_position_embeddings"))
        and cfg.max_position_embeddings > rs["original_max_position_embeddings"]
    )
    return rope_frequencies(
        cfg.qk_rope_head_dim, cfg.rope_theta, dict(rs, rope_type="yarn") if use_yarn else None
    )


def make_mla_attention_fn(cfg: DeepseekV3Config, backend: BackendConfig, bias_fn=None,
                          bias_decode_fn=None):
    """MLA attention hook for moe_decoder_forward / the pp pipeline."""
    inv_freq = mla_inv_freq(cfg)

    def mla_attention(lp, x, positions, segment_ids, is_sliding, rules,
                      cache=None, cache_meta=None):
        del is_sliding
        with jax.named_scope("mla_attention"):
            return _mla_block(cfg, backend, lp, x, positions, segment_ids, inv_freq, rules,
                              bias_fn=bias_fn, bias_decode_fn=bias_decode_fn,
                              cache=cache, cache_meta=cache_meta)

    return mla_attention


class DeepseekV3ForCausalLM:
    """Functional model: holds config + backend, operates on param pytrees."""

    config_class = DeepseekV3Config
    hf_architectures = ("DeepseekV3ForCausalLM", "DeepseekV2ForCausalLM")

    def __init__(self, config: DeepseekV3Config, backend: BackendConfig | None = None):
        self.config = config
        self.backend = backend or BackendConfig()

    def init(self, key: jax.Array, dtype=jnp.float32) -> dict:
        return init_params(self.config, key, dtype)

    def logical_axes(self) -> dict:
        return logical_axes(self.config)

    def abstract_params(self, dtype=jnp.bfloat16) -> dict:
        return jax.eval_shape(lambda k: self.init(k, dtype), jax.random.key(0))

    def make_attention_fn(self):
        """Hook the pp pipeline uses to build the MLA block (parallel/pipeline.py)."""
        return make_mla_attention_fn(self.config, self.backend)

    def __call__(self, params, input_ids, positions=None, segment_ids=None, token_mask=None,
                 rules=None, return_hidden=False, training=True, cache=None):
        return forward(
            self.config, self.backend, params, input_ids,
            positions=positions, segment_ids=segment_ids, token_mask=token_mask,
            rules=rules, return_hidden=return_hidden, training=training, cache=cache,
        )

    def generate(self, params, input_ids, **kw):
        """Sample with an expanded-head MLA KV cache (automodel_tpu.generation)."""
        from automodel_tpu.generation import generate

        return generate(self, params, input_ids, **kw)

    def state_dict_adapter(self):
        from automodel_tpu.models.deepseek_v3.state_dict_adapter import DeepseekV3StateDictAdapter

        return DeepseekV3StateDictAdapter(self.config)

    @classmethod
    def from_config(cls, config, backend: BackendConfig | None = None):
        if isinstance(config, dict):
            config = DeepseekV3Config.from_hf(config)
        return cls(config, backend)
