"""Shared dense-decoder machinery for all Llama-lineage model families.

TPU-native counterpart of the reference's per-family model.py/layers.py pairs
(e.g. models/llama/model.py, models/qwen2/): models here are *pure functions over
param pytrees* — no modules, no wrappers — so pjit/GSPMD shards them by annotating
logical axes, and parallelism never appears in model code (the reference's
"parallelism is configuration" contract, README.md:74-80, taken to its fixed point).

Layers are stacked along a leading axis and iterated with ``lax.scan``: one layer
gets traced/compiled once regardless of depth (fast compiles at 100+ layers), and the
stacked layout is exactly what pipeline-stage slicing wants later.

Param tree layout (per layer, stacked to (L, ...) under scan):
  attn_norm (D,) | wq (D,N,H) | wk/wv (D,K,H) | wo (N,H,D) | [bq (N,H) bk/bv (K,H)]
  [q_norm/k_norm (H,)] | mlp_norm (D,) | w_gate/w_up (D,I) | w_down (I,D)
Top level: embed (V,D) | final_norm (D,) | [lm_head (D,V) unless tied].
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from automodel_tpu.models.common.backend import BackendConfig
from automodel_tpu.ops.attention import dot_product_attention, sharded_attention
from automodel_tpu.ops.fp8 import project
from automodel_tpu.ops.norms import rms_norm
from automodel_tpu.ops.rope import (
    apply_rope, apply_rope_interleaved, rope_attention_scaling, rope_frequencies,
)
from automodel_tpu.utils.tracing import scope_blocks, scoped

__all__ = [
    "DenseDecoderConfig",
    "init_dense_decoder_params",
    "dense_decoder_logical_axes",
    "decoder_forward",
    "make_layer_body",
    "apply_layer_stack",
]


@dataclasses.dataclass
class DenseDecoderConfig:
    """Architecture knobs shared by Llama/Qwen2/Qwen3/Mistral-style decoders."""

    vocab_size: int = 32000
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_hidden_layers: int = 16
    num_attention_heads: int = 16
    num_key_value_heads: int = 8
    head_dim: int | None = None
    max_position_embeddings: int = 4096
    rope_theta: float = 10000.0
    rope_scaling: dict[str, Any] | None = None
    partial_rotary_factor: float = 1.0  # glm4/minimax: rope only the first fraction of head_dim
    rope_interleaved: bool = False  # helium/ernie4.5: consecutive-pair rotation, not half-split
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    attention_bias: bool = False  # qwen2: bias on q/k/v only
    attention_out_bias: bool = False  # gpt-oss: bias on o_proj too
    attention_sinks: bool = False  # gpt-oss: per-head sink logits absorbing mass
    qk_norm: bool = False  # qwen3: RMSNorm on per-head q/k
    qk_norm_whole: bool = False  # olmo2: RMSNorm over the WHOLE q/k projection (n*h)
    # "pre" (llama) | "post" (olmo2: norm the sublayer OUTPUT, no input norm)
    # | "sandwich" (glm4/gemma2 style: input norm AND a second norm on the output)
    norm_placement: str = "pre"
    norm_type: str = "rms"  # "rms" | "layernorm" (mean-centered, no bias — cohere)
    norm_bias: bool = False  # starcoder2/stablelm: LayerNorm with learnable bias
    norm_param: bool = True  # False (olmo-v1): non-parametric LayerNorm (no weight)
    parallel_block: bool = False  # cohere: h + attn(norm(h)) + mlp(norm(h)), ONE norm
    mlp_gated: bool = True  # False (arcee): down(act(up(x))), no gate matrix
    mlp_act: str = "silu"  # "silu" | "gelu" | "relu2" (arcee)
    mlp_bias: bool = False  # starcoder2: bias terms on the MLP projections
    clip_qkv: float | None = None  # olmo: clamp q/k/v projection outputs
    # ungated-MLP HF tensor names when they differ from up_proj/down_proj
    # (starcoder2: c_fc/c_proj); None = llama names
    hf_mlp_names: tuple[str, str] | None = None
    sliding_window: int | None = None
    layer_types: list[str] | None = None  # "full_attention" | "sliding_attention"
    # SmolLM3-style NoPE: per-layer rope enable (HF semantics: 1 = rope ON);
    # None = rope everywhere
    no_rope_layers: list | None = None
    initializer_range: float = 0.02
    causal: bool = True  # False: bidirectional encoder (llama_bidirectional)
    # Granite mup-style static scalars (all at the llama value = identity;
    # transformers modeling_granite.py applies exactly these four)
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float | None = None  # None = 1/sqrt(head_dim)
    logits_scaling: float = 1.0
    # Ministral-3 llama-4-style long-context q scaling: q *= 1 + beta*log(1 + pos//orig)
    # (reference mistral3/model.py:282-284)
    llama4_attn_scale_beta: float | None = None
    original_max_position_embeddings: int | None = None

    def __post_init__(self):
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_attention_heads

    @property
    def sliding_flags(self) -> list[bool]:
        if self.layer_types is not None:
            return [t == "sliding_attention" for t in self.layer_types]
        if self.sliding_window is not None:
            return [True] * self.num_hidden_layers
        return [False] * self.num_hidden_layers

    @property
    def layer_flags(self) -> list[int]:
        """Per-layer bitfield scanned alongside the layer params: bit 0 =
        sliding window, bit 1 = NoPE (rope disabled). One int stream keeps the
        scan/pipeline tuple shapes unchanged as flags accrue."""
        rope_on = self.no_rope_layers or [1] * self.num_hidden_layers
        return [int(s) | (0 if rope_on[i] else 2)
                for i, s in enumerate(self.sliding_flags)]


def _layer_shapes(cfg: DenseDecoderConfig) -> dict[str, tuple[int, ...]]:
    d, n, k, h, i = (
        cfg.hidden_size,
        cfg.num_attention_heads,
        cfg.num_key_value_heads,
        cfg.head_dim,
        cfg.intermediate_size,
    )
    shapes = {
        "attn_norm": (d,),
        "wq": (d, n, h),
        "wk": (d, k, h),
        "wv": (d, k, h),
        "wo": (n, h, d),
        "mlp_norm": (d,),
        "w_gate": (d, i),
        "w_up": (d, i),
        "w_down": (i, d),
    }
    if cfg.attention_bias:
        shapes |= {"bq": (n, h), "bk": (k, h), "bv": (k, h)}
    if cfg.attention_out_bias:
        shapes |= {"bo": (d,)}
    if cfg.attention_sinks:
        shapes |= {"sinks": (n,)}
    if cfg.parallel_block:
        del shapes["mlp_norm"]  # one shared input norm (cohere)
    if not cfg.mlp_gated:
        del shapes["w_gate"]  # arcee: two-matrix ungated MLP
    if cfg.mlp_bias:
        shapes |= {"b_up": (i,), "b_down": (d,)}
        if cfg.mlp_gated:
            shapes |= {"b_gate": (i,)}
    if cfg.norm_bias:
        shapes |= {k + "_b": (d,) for k in ("attn_norm", "mlp_norm") if k in shapes}
    if not cfg.norm_param:
        # olmo-v1: LayerNorm with NO learnable weight — the params simply
        # don't exist (a trainable ones-init would drift from HF semantics)
        for k in ("attn_norm", "mlp_norm"):
            shapes.pop(k, None)
    if cfg.norm_placement == "sandwich":  # glm4: post_self_attn/post_mlp norms
        shapes |= {"attn_post_norm": (d,), "mlp_post_norm": (d,)}
    if cfg.qk_norm_whole:
        shapes |= {"q_norm": (n, h), "k_norm": (k, h)}
    elif cfg.qk_norm and cfg.norm_type == "layernorm":
        # cohere: per-head LN with per-head weights, stored (n, h)/(k, h) as HF does
        shapes |= {"q_norm": (n, h), "k_norm": (k, h)}
    elif cfg.qk_norm:
        shapes |= {"q_norm": (h,), "k_norm": (h,)}
    return shapes


_LAYER_AXES = {
    "attn_norm": ("norm",),
    "wq": ("embed", "heads", "head_dim"),
    "wk": ("embed", "kv_heads", "head_dim"),
    "wv": ("embed", "kv_heads", "head_dim"),
    "wo": ("heads", "head_dim", "embed"),
    "bq": ("heads", "head_dim"),
    "bk": ("kv_heads", "head_dim"),
    "bv": ("kv_heads", "head_dim"),
    "bo": ("embed",),
    "sinks": ("heads",),
    "q_norm": ("norm",),
    "k_norm": ("norm",),
    "mlp_norm": ("norm",),
    "attn_post_norm": ("norm",),
    "mlp_post_norm": ("norm",),
    "w_gate": ("embed", "mlp"),
    "w_up": ("embed", "mlp"),
    "w_down": ("mlp", "embed"),
    "b_gate": ("mlp",),
    "b_up": ("mlp",),
    "b_down": ("embed",),
    "attn_norm_b": ("norm",),
    "mlp_norm_b": ("norm",),
}


def init_dense_decoder_params(
    cfg: DenseDecoderConfig, key: jax.Array, dtype=jnp.float32, scan_layers: bool = True
) -> dict:
    """Random init matching HF conventions (normal(0, initializer_range), norms=1).

    Layer params are always stacked (L, ...); ``scan_layers`` only controls whether the
    forward iterates them with lax.scan or an unrolled loop.
    """
    del scan_layers
    shapes = _layer_shapes(cfg)
    keys = jax.random.split(key, len(shapes) + 2)
    std = cfg.initializer_range
    L = cfg.num_hidden_layers

    layers = {}
    for idx, (name, shape) in enumerate(shapes.items()):
        if name.endswith("_b"):  # norm biases init to zero like linear biases
            layers[name] = jnp.zeros((L, *shape), dtype)
        elif name.endswith("norm"):
            layers[name] = jnp.ones((L, *shape), dtype)
        elif name.startswith("b"):
            layers[name] = jnp.zeros((L, *shape), dtype)
        else:
            layers[name] = (jax.random.normal(keys[idx], (L, *shape), jnp.float32) * std).astype(dtype)

    params = {
        "embed": (jax.random.normal(keys[-2], (cfg.vocab_size, cfg.hidden_size), jnp.float32) * std).astype(dtype),
        "layers": layers,
    }
    if cfg.norm_param:
        params["final_norm"] = jnp.ones((cfg.hidden_size,), dtype)
    if cfg.norm_bias:
        params["final_norm_b"] = jnp.zeros((cfg.hidden_size,), dtype)
    if not cfg.tie_word_embeddings:
        params["lm_head"] = (
            jax.random.normal(keys[-1], (cfg.hidden_size, cfg.vocab_size), jnp.float32) * std
        ).astype(dtype)
    return params


def dense_decoder_logical_axes(cfg: DenseDecoderConfig, scan_layers: bool = True) -> dict:
    """Pytree of logical-axis tuples matching init_dense_decoder_params' layout."""
    del scan_layers  # layer params are always stacked (L, ...)
    layers = {name: ("layers",) + _LAYER_AXES[name] for name in _layer_shapes(cfg)}
    if cfg.qk_norm_whole or (cfg.qk_norm and cfg.norm_type == "layernorm"):
        # (n, h)-shaped norm weights
        layers["q_norm"] = ("layers", "heads", "head_dim")
        layers["k_norm"] = ("layers", "kv_heads", "head_dim")
    axes = {
        "embed": ("vocab", "embed"),
        "layers": layers,
    }
    if cfg.norm_param:
        axes["final_norm"] = ("norm",)
    if cfg.norm_bias:
        axes["final_norm_b"] = ("norm",)
    if not cfg.tie_word_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def _constrain(x, rules, names):
    if rules is None or rules.mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, rules.sharding(names))


def embed_lookup(table, input_ids, dtype, rules=None, scale: float = 1.0):
    """Token-embedding gather with the table's FSDP (hidden-dim) axes unsharded
    FIRST — a plain all-gather (FSDP's param-on-use collective). Without it the
    gather output inherits the table's hidden-dim sharding and the partitioner
    falls back to involuntary full rematerialization resharding it to the
    (batch, act_seq) activation layout (seen in the r2 cp-ring dryrun HLO).
    "vocab" stays: under TP the vocab-parallel local-gather+psum path holds.
    Shared by the dense/MoE forwards and the pipeline's stage-0 embedding."""
    with jax.named_scope("embed"):
        table = _constrain(table.astype(dtype), rules, ("vocab", None))
        h = table[input_ids]
        if scale != 1.0:  # granite embedding_multiplier
            h = h * jnp.asarray(scale, h.dtype)
    return h


def _centered_norm(x, w, eps, b=None):
    """Mean-centered LayerNorm (CohereLayerNorm and friends): works for (d,)
    block weights and per-head (n, h) qk weights alike (stats over last dim).
    ``w=None`` is the non-parametric form (olmo-v1); ``b`` the optional bias
    (starcoder2/stablelm). Affine math stays in fp32 before the downcast,
    matching HF."""
    xf = x.astype(jnp.float32)
    mean = xf.mean(-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    out = (xf - mean) * jax.lax.rsqrt(var + eps)
    if w is not None:
        out = out * w.astype(jnp.float32)
    if b is not None:
        out = out + b.astype(jnp.float32)
    return out.astype(x.dtype)


def apply_final_norm(cfg, params, h, dtype):
    """Top-level final norm shared by decoder_forward and the pipeline head —
    weight/bias may be absent (olmo-v1 non-parametric LN / no-bias families)."""
    w = params.get("final_norm")
    b = params.get("final_norm_b")
    return _block_norm(cfg, h, None if w is None else w.astype(dtype),
                       None if b is None else b.astype(dtype))


def _block_norm(cfg, x, w, b=None):
    """The block-level norm the config selects (rms | mean-centered LN).
    getattr: family configs outside the dense lineage (MLA) reach here via the
    shared pipeline head and carry no norm_type — they are all RMSNorm."""
    if getattr(cfg, "norm_type", "rms") == "layernorm":
        return _centered_norm(x, w, cfg.rms_norm_eps, b)
    return rms_norm(x, w, cfg.rms_norm_eps)


def resolve_unembed(cfg, params, dtype):
    """lm_head | tied embed.T (gpt2: wte), cast to compute dtype, with granite
    logits_scaling folded in (logits/ls == unembed/ls) — the ONE copy every
    head consumer (decoder_forward, pipeline._head_pre, linear-CE recipes)
    resolves through. Returns None when the params carry no table."""
    unembed = params.get("lm_head")
    if unembed is None:
        table = params.get("embed", params.get("wte"))
        if table is None:
            return None
        unembed = table.T
    unembed = jnp.asarray(unembed).astype(dtype)
    ls = getattr(cfg, "logits_scaling", 1.0)
    return unembed / ls if ls != 1.0 else unembed


def _rms_norm_2d(x, w, eps):
    """RMSNorm with the mean over the LAST TWO dims (whole-projection norm,
    olmo2): x (..., n, h), w (n, h)."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=(-2, -1), keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps)).astype(x.dtype) * w


def _cache_write(cache, new, idx):
    """Write ``new (B, s, ...)`` into ``cache (B, S_max, ...)`` at per-row slot
    ``idx (B,)`` — a vmapped dynamic_update_slice (rows decode at different
    lengths when prompts are right-padded unevenly)."""
    zeros = (0,) * (cache.ndim - 2)
    return jax.vmap(
        lambda c, n, i: jax.lax.dynamic_update_slice(c, n, (i, *zeros))
    )(cache, new, idx)


def _attention_block(cfg: DenseDecoderConfig, backend: BackendConfig, lp: dict, x, positions,
                     segment_ids, inv_freq, attn_scale, sliding, rules,
                     cache=None, cache_meta=None):
    """Self-attention block. With ``cache=(k_cache, v_cache)`` (decode path) the
    freshly projected k/v are written into the cache at ``cache_meta["write_idx"]``
    and attention runs against the whole cache (masked by ``cache_meta["valid"]``
    as kv segment ids + position-causal masking); returns ``(out, (k, v))``.
    Training path (cache=None) returns just ``out``."""
    from jax.ad_checkpoint import checkpoint_name

    lin = backend.linear
    q = checkpoint_name(project(x, lp["wq"], 1, lin), "attn_q")
    k = checkpoint_name(project(x, lp["wk"], 1, lin), "attn_k")
    v = checkpoint_name(project(x, lp["wv"], 1, lin), "attn_v")
    if cfg.attention_bias:
        q = q + lp["bq"]
        k = k + lp["bk"]
        v = v + lp["bv"]
    if getattr(cfg, "clip_qkv", None) is not None:
        c = cfg.clip_qkv  # olmo: clamp projection outputs (post-bias, like HF)
        q, k, v = (jnp.clip(t, -c, c) for t in (q, k, v))
    if cfg.qk_norm_whole:
        # olmo2: RMSNorm over the flattened projection — mean over (heads,
        # head_dim) jointly, weight (n, h) == the flat HF (n*h,) weight reshaped
        q = _rms_norm_2d(q, lp["q_norm"], cfg.rms_norm_eps)
        k = _rms_norm_2d(k, lp["k_norm"], cfg.rms_norm_eps)
    elif cfg.qk_norm and cfg.norm_type == "layernorm":
        # cohere: per-head mean-centered LN with per-head (n, h) weights
        q = _centered_norm(q, lp["q_norm"], cfg.rms_norm_eps)
        k = _centered_norm(k, lp["k_norm"], cfg.rms_norm_eps)
    elif cfg.qk_norm:
        q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
    rope = apply_rope_interleaved if cfg.rope_interleaved else apply_rope
    q = rope(q, positions, inv_freq, attn_scale)
    k = rope(k, positions, inv_freq, attn_scale)
    if cfg.llama4_attn_scale_beta is not None:
        orig = cfg.original_max_position_embeddings or cfg.max_position_embeddings
        scale = 1.0 + cfg.llama4_attn_scale_beta * jnp.log1p(
            jnp.floor(positions.astype(jnp.float32) / orig)
        )
        q = q * scale[..., None, None].astype(q.dtype)
    if cache is not None:
        k_cache = _cache_write(cache[0], k.astype(cache[0].dtype), cache_meta["write_idx"])
        v_cache = _cache_write(cache[1], v.astype(cache[1].dtype), cache_meta["write_idx"])
        out = dot_product_attention(
            q, k_cache.astype(q.dtype), v_cache.astype(q.dtype),
            causal=cfg.causal,
            segment_ids_q=segment_ids,
            segment_ids_kv=cache_meta["valid"],
            positions_q=positions,
            positions_kv=cache_meta["positions"],
            sliding_window=sliding,
            sinks=lp.get("sinks"),
            softmax_scale=cfg.attention_multiplier,
            backend="xla",  # q_len 1 / position-masked: the flash kernel doesn't apply
        )
        o = project(out, lp["wo"], 2, lin)
        if cfg.attention_out_bias:
            o = o + lp["bo"]
        return o, (k_cache, v_cache)
    q = _constrain(q, rules, ("batch", "act_attn_seq", "act_heads", None))
    k = _constrain(k, rules, ("batch", "act_attn_seq", "act_heads", None))
    mesh = rules.mesh if rules is not None else None
    use_ring = (
        backend.context_parallel == "ring"
        and mesh is not None
        and mesh.shape.get("cp", 1) > 1
        and lp.get("sinks") is None
        and sliding is None  # traced per-layer windows can't close over shard_map
    )
    if use_ring:
        from automodel_tpu.parallel.ring_attention import make_ring_attention

        ring = make_ring_attention(mesh, causal=cfg.causal,
                                   softmax_scale=cfg.attention_multiplier)
        out = checkpoint_name(ring(q, k, v, positions, segment_ids), "attn_out")
    else:
        # names ``attn_out`` itself, by the path that ran (ops/attention.py)
        out = sharded_attention(
            q, k, v,
            rules=rules,
            causal=cfg.causal,
            # attention_segments=False: right-padded-unpacked fast path — causal
            # masking alone isolates real tokens from trailing pads. The
            # argument needs causality: bidirectional stacks keep their masking
            segment_ids_q=(segment_ids if (backend.attention_segments or not cfg.causal)
                           else None),
            sliding_window=sliding,
            sinks=lp.get("sinks"),
            softmax_scale=cfg.attention_multiplier,
            backend=backend.attention,
        )
    o = project(out, lp["wo"], 2, lin)
    if cfg.attention_out_bias:
        o = o + lp["bo"]
    return o


_MLP_ACTS = {
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,  # tanh approximation (HF "gelu_pytorch_tanh")
    "gelu_exact": lambda x: jax.nn.gelu(x, approximate=False),  # HF bare "gelu"
    "relu2": lambda x: jnp.square(jax.nn.relu(x)),  # arcee
}


def _mlp_block(cfg: DenseDecoderConfig, backend: BackendConfig, lp: dict, x, rules):
    from jax.ad_checkpoint import checkpoint_name

    lin = backend.linear
    # getattr: family configs outside the dense lineage (MLA) reach this shared
    # MLP through the MoE dense prefix and carry no mlp_* fields (all gated silu)
    act_fn = _MLP_ACTS[getattr(cfg, "mlp_act", "silu")]
    # names feed the "mlp_*" remat policies (backend.py): these (tokens,
    # intermediate) tensors are the activation-memory peak of the layer
    up = checkpoint_name(project(x, lp["w_up"], 1, lin), "mlp_up")
    if getattr(cfg, "mlp_bias", False):
        up = up + lp["b_up"]
    if getattr(cfg, "mlp_gated", True):
        gate = checkpoint_name(project(x, lp["w_gate"], 1, lin), "mlp_gate")
        if getattr(cfg, "mlp_bias", False):
            gate = gate + lp["b_gate"]
        h = act_fn(gate) * up
    else:  # arcee/starcoder2: down(act(up(x)))
        h = act_fn(up)
    h = _constrain(h, rules, ("batch", "act_attn_seq", "act_mlp"))
    out = project(h, lp["w_down"], 1, lin)
    if getattr(cfg, "mlp_bias", False):
        out = out + lp["b_down"]
    return out


def make_layer_body(cfg: DenseDecoderConfig, backend: BackendConfig, rules=None):
    """Scan body over a carried state dict {"h", "positions", ["segment_ids"]}.

    The state-dict form lets the same body serve decoder_forward's layer scan and
    the pp pipeline (parallel/pipeline.py), where positions/segment ids ride along
    with the activation between stages.
    """
    dtype = backend.jnp_dtype
    inv_freq = rope_frequencies(
        cfg.head_dim, cfg.rope_theta, cfg.rope_scaling,
        partial_rotary_factor=cfg.partial_rotary_factor,
    )
    attn_scale = rope_attention_scaling(cfg.rope_scaling)
    any_sliding = any(cfg.sliding_flags)
    window = jnp.int32(cfg.sliding_window or 0)

    def layer_fn(state, layer_inputs):
        if len(layer_inputs) == 3:
            lp, is_sliding, kv = layer_inputs  # decode: per-layer kv cache rides as xs
        else:
            (lp, is_sliding), kv = layer_inputs, None
        lp = jax.tree.map(lambda a: a.astype(dtype), lp)
        h = state["h"]
        # "disabled" window must exceed every causal q-kv distance for the actual
        # (static at trace time) sequence length, even when S > max_position_embeddings
        kv_len = h.shape[1] if kv is None else kv[0].shape[1]
        big_window = jnp.int32(cfg.max_position_embeddings + kv_len)
        # traced per-layer window (scan-compatible); None disables the mask entirely
        eff_window = jnp.where(is_sliding & 1, window, big_window) if any_sliding else None
        # bit 1: NoPE layer (SmolLM3) — rope with zeroed frequencies is identity
        inv_freq_l = inv_freq
        if cfg.no_rope_layers is not None:
            inv_freq_l = inv_freq * (1 - ((is_sliding >> 1) & 1)).astype(inv_freq.dtype)
        def attn_call(x):
            """One copy of the cache/no-cache attention dispatch for every
            block style (sequential pre/post-norm AND cohere parallel)."""
            if kv is None:
                return _attention_block(
                    cfg, backend, lp, x, state["positions"], state.get("segment_ids"),
                    inv_freq_l, attn_scale, eff_window, rules), None
            cache_meta = {k_: state[k_] for k_ in ("write_idx", "valid")}
            cache_meta["positions"] = state["kv_positions"]
            return _attention_block(
                cfg, backend, lp, x, state["positions"], state.get("segment_ids"),
                inv_freq_l, attn_scale, eff_window, rules,
                cache=kv, cache_meta=cache_meta,
            )

        def parallel_sublayer(h):
            # cohere: ONE input norm feeds attention AND the MLP; both outputs
            # add to the residual together
            x = _block_norm(cfg, h, lp.get("attn_norm"), lp.get("attn_norm_b"))
            attn_out, kv_out = attn_call(x)
            h = h + attn_out + _mlp_block(cfg, backend, lp, x, rules)
            return _constrain(h, rules, ("batch", "act_seq", "act_embed")), kv_out

        post = cfg.norm_placement == "post"
        sandwich = cfg.norm_placement == "sandwich"

        def attention_sublayer(h):
            # post (olmo2): attention reads h RAW; attn_norm applies to the
            # sublayer OUTPUT before the residual add (post_attention_layernorm).
            # sandwich (glm4): input norm AND a post norm on the output.
            x = h if post else _block_norm(cfg, h, lp.get("attn_norm"), lp.get("attn_norm_b"))
            attn_out, kv_out = attn_call(x)
            if post:
                attn_out = _block_norm(cfg, attn_out, lp.get("attn_norm"), lp.get("attn_norm_b"))
            elif sandwich:  # post_self_attn_layernorm
                attn_out = _block_norm(cfg, attn_out, lp["attn_post_norm"])
            if cfg.residual_multiplier != 1.0:  # granite
                attn_out = attn_out * cfg.residual_multiplier
            h = h + attn_out
            return _constrain(h, rules, ("batch", "act_seq", "act_embed")), kv_out

        def mlp_sublayer(h):
            x = h if post else _block_norm(cfg, h, lp.get("mlp_norm"), lp.get("mlp_norm_b"))
            mlp_out = _mlp_block(cfg, backend, lp, x, rules)
            if post:  # post_feedforward_layernorm
                mlp_out = _block_norm(cfg, mlp_out, lp.get("mlp_norm"), lp.get("mlp_norm_b"))
            elif sandwich:  # post_mlp_layernorm
                mlp_out = _block_norm(cfg, mlp_out, lp["mlp_post_norm"])
            if cfg.residual_multiplier != 1.0:
                mlp_out = mlp_out * cfg.residual_multiplier
            h = h + mlp_out
            return _constrain(h, rules, ("batch", "act_seq", "act_embed"))

        # named scopes label the profiler trace per block (the reference gets the
        # same from autonvtx module hooks, autonvtx/__init__.py:33)
        blocks = scope_blocks({
            "parallel_block": parallel_sublayer,
            "attention": attention_sublayer,
            "mlp": mlp_sublayer,
        })
        if cfg.parallel_block:
            h, kv_out = blocks["parallel_block"](h)
            return dict(state, h=h), kv_out
        h, kv_out = blocks["attention"](h)
        h = blocks["mlp"](h)
        return dict(state, h=h), kv_out

    return layer_fn


@scoped("layer_stack")  # the scan's own slicing and stacking; blocks carry theirs inside
def apply_layer_stack(
    cfg: DenseDecoderConfig,
    backend: BackendConfig,
    lp_stack,  # pytree of (L, ...) stacked layer params
    sliding_flags: jnp.ndarray,  # (L,) int32
    state: dict,  # {"h": (B,S,D), "positions": (B,S), ["segment_ids": (B,S)]}
    rules=None,
    cache=None,  # decode: {"k"/"v": (L,B,S_max,KH,D), ...} -> returns (state, cache)
):
    body = backend.layer_remat(make_layer_body(cfg, backend, rules))
    if cache is not None:
        xs = (lp_stack, sliding_flags, (cache["k"], cache["v"]))
        if backend.scan_layers:
            state, (k_new, v_new) = jax.lax.scan(body, state, xs)
        else:
            num_layers = jax.tree.leaves(lp_stack)[0].shape[0]
            ks, vs = [], []
            for i in range(num_layers):
                sliced = jax.tree.map(lambda a: a[i], xs)
                state, (k_l, v_l) = body(state, sliced)
                ks.append(k_l)
                vs.append(v_l)
            k_new, v_new = jnp.stack(ks), jnp.stack(vs)
        return state, dict(cache, k=k_new, v=v_new)
    if backend.scan_layers:
        state, _ = jax.lax.scan(body, state, (lp_stack, sliding_flags))
    else:
        num_layers = jax.tree.leaves(lp_stack)[0].shape[0]
        for i in range(num_layers):
            lp = jax.tree.map(lambda a: a[i], lp_stack)
            state, _ = body(state, (lp, sliding_flags[i]))
    return state


def decoder_forward(
    cfg: DenseDecoderConfig,
    backend: BackendConfig,
    params: dict,
    input_ids: jnp.ndarray,  # (B, S) int32
    positions: jnp.ndarray | None = None,
    segment_ids: jnp.ndarray | None = None,
    rules=None,
    return_hidden: bool = False,
    inputs_embeds: jnp.ndarray | None = None,  # VLM path: pre-merged embeddings
    cache=None,  # generation.init_kv_cache dict -> returns (logits, cache)
):
    """Forward pass -> logits (B, S, V), or final hidden states for fused linear-CE.

    With ``cache`` (a :func:`automodel_tpu.generation.init_kv_cache` dict whose
    positions/valid/write_idx the generation loop has already advanced for this
    chunk) the pass serves prefill (S = prompt length) and decode (S = 1) and
    returns ``(logits, cache)``; ``segment_ids`` is then REQUIRED (it doubles as
    the q-validity mask against unfilled cache slots).
    """
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(input_ids.shape[1]), input_ids.shape)
    if cache is not None and segment_ids is None:
        raise ValueError("cache decoding requires segment_ids (1 = real token)")
    dtype = backend.jnp_dtype
    if inputs_embeds is not None:
        h = inputs_embeds
        if cfg.embedding_multiplier != 1.0:  # HF scales provided embeds too
            h = h * jnp.asarray(cfg.embedding_multiplier, h.dtype)
    else:
        h = embed_lookup(params["embed"], input_ids, dtype, rules,
                         scale=cfg.embedding_multiplier)
    h = _constrain(h, rules, ("batch", "act_seq", "act_embed"))

    state = {"h": h, "positions": positions}
    if segment_ids is not None:
        state["segment_ids"] = segment_ids
    if cache is not None:
        state["kv_positions"] = cache["positions"]
        state["valid"] = cache["valid"]
        state["write_idx"] = cache["write_idx"]
    sliding_flags = jnp.asarray(cfg.layer_flags, dtype=jnp.int32)
    out = apply_layer_stack(cfg, backend, params["layers"], sliding_flags, state, rules,
                            cache=cache)
    state, cache = out if cache is not None else (out, None)
    h = state["h"]

    # final norm and head are one layer kind in a device trace; the recipe opens the
    # same scope around its loss call
    with jax.named_scope("lm_head_loss"):
        h = apply_final_norm(cfg, params, h, dtype)
        if cache is not None:
            # next-token logits ONLY (B, 1, V): unembedding the whole prefill chunk
            # would materialize a (B, S_prompt, V) tensor — an HBM spike at exactly
            # the long-prompt scales the KV cache exists for. Right-padded contract:
            # each row's last valid position is segment_ids.sum()-1.
            last = jnp.maximum(segment_ids.sum(-1) - 1, 0).astype(jnp.int32)
            h = jnp.take_along_axis(h, last[:, None, None], axis=1)  # (B, 1, D)
            if return_hidden:
                return h, cache
            logits = jnp.einsum("bsd,dv->bsv", h, resolve_unembed(cfg, params, dtype))
            return logits, cache
        if return_hidden:
            return h
        logits = jnp.einsum("bsd,dv->bsv", h, resolve_unembed(cfg, params, dtype))
        return logits
