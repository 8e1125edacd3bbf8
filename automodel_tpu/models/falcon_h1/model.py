"""Falcon-H1 — a Mamba-2 mixer and a GQA mixer side by side in every block
(``model_type: falcon_h1``; equations of the public ``modeling_falcon_h1.py``).

One homogeneous stack. A block reads ONE RMSNorm and hands it to both mixers; their
outputs, each times a published scalar, join the stream together; a second norm and a
gated SiLU MLP follow::

    u = RMSNorm(x)
    x = x + ssm_out * Mamba2(ssm_in * u; in-projection x mup_vector) + attn_out * GQA(attn_in * u)
    w = RMSNorm(x)
    x = x + mlp[1] * (up(w) * silu(mlp[0] * gate(w))) @ W_down

with ``embedding_multiplier`` on the embedding and ``lm_head_multiplier`` on the logits
(twelve muP scalars in all, ``FalconH1Config``). Rotary is half-split over the whole head.

What is shared, not copied: the Mamba-2 mixer is ``ops.mamba2.mamba2_mixer`` (Nemotron-H's,
with the per-segment scale vector); the GQA mixer is ``common.transformer._attention_block``
(``project``, rotary, ``sharded_attention``, the ``attn_k`` / ``attn_v`` / ``attn_out``
checkpoint names the remat rungs look for); the embedding is ``embed_lookup(scale=)``.

Two scalars are applied where values and every gradient are the same and the work is
less: ``key_multiplier`` in the softmax scale (rotary is linear, so ``(m k) . q = m (k .
q)``), ``lm_head_multiplier`` on the normed hidden state before the head (``(m h) W = m (h
W)``; a (tokens, hidden) product, not a (tokens, vocabulary) one, and the fused linear CE
gets it too). No scalar is folded into a weight. Every scalar multiplies in float32 and
the product is rounded once, as PyTorch multiplies a bf16 tensor by a Python float.

Device-trace scopes: ``embed``, ``layer_stack`` (round the scan), ``mamba`` (the whole
mixer; ``mamba_proj`` and ``mamba_ssd`` inside it), ``attention``, ``mlp``,
``lm_head_loss``. Training only: there is no decode cache for two kinds of state a layer
yet (``cache=`` raises).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from automodel_tpu.models.common.backend import BackendConfig
from automodel_tpu.models.common.transformer import (
    DenseDecoderConfig, _attention_block, _constrain, embed_lookup, resolve_unembed,
)
from automodel_tpu.ops.fp8 import project
from automodel_tpu.ops.mamba2 import mamba2_mixer
from automodel_tpu.ops.norms import rms_norm
from automodel_tpu.ops.rope import rope_attention_scaling, rope_frequencies
from automodel_tpu.utils.tracing import scope_blocks

__all__ = ["FalconH1Config", "FalconH1ForCausalLM"]


@dataclasses.dataclass
class FalconH1Config:
    vocab_size: int = 1024
    hidden_size: int = 256
    intermediate_size: int = 512
    num_hidden_layers: int = 2
    rms_norm_eps: float = 1e-5
    # GQA mixer
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = 64
    rope_theta: float = 1e11
    rope_scaling: dict[str, Any] | None = None
    max_position_embeddings: int = 262144
    # Mamba-2 mixer
    mamba_d_ssm: int = 256
    mamba_n_heads: int = 8
    mamba_d_head: int = 32
    mamba_n_groups: int = 2
    mamba_d_state: int = 64
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    mamba_conv_bias: bool = True
    # the published muP multipliers
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    key_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)  # z | x | B | C | dt
    mlp_multipliers: tuple[float, float] = (1.0, 1.0)  # gate, down
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02

    def __post_init__(self):
        if self.mamba_n_heads * self.mamba_d_head != self.mamba_d_ssm:
            raise ValueError(f"mamba_d_ssm {self.mamba_d_ssm} is not mamba_n_heads "
                             f"{self.mamba_n_heads} x mamba_d_head {self.mamba_d_head}")
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError("ssm_multipliers has five entries (z, x, B, C, dt) and "
                             "mlp_multipliers two (gate, down)")

    @property
    def conv_dim(self) -> int:
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def in_proj_dim(self) -> int:
        return self.mamba_d_ssm + self.conv_dim + self.mamba_n_heads

    @property
    def attention(self) -> DenseDecoderConfig:
        """The GQA mixer as the shared attention block reads it; ``key_multiplier`` rides
        in the softmax scale."""
        return DenseDecoderConfig(
            hidden_size=self.hidden_size, num_attention_heads=self.num_attention_heads,
            num_key_value_heads=self.num_key_value_heads, head_dim=self.head_dim,
            rope_theta=self.rope_theta, rope_scaling=self.rope_scaling,
            max_position_embeddings=self.max_position_embeddings,
            rms_norm_eps=self.rms_norm_eps,
            attention_multiplier=self.key_multiplier * self.head_dim ** -0.5,
        )

    def mup_vector(self) -> jnp.ndarray:
        """``ssm_multipliers`` spread over the in-projection's segments z | x | B | C | dt."""
        gns = self.mamba_n_groups * self.mamba_d_state
        widths = (self.mamba_d_ssm, self.mamba_d_ssm, gns, gns, self.mamba_n_heads)
        return jnp.concatenate([jnp.full((w,), m, jnp.float32)
                                for w, m in zip(widths, self.ssm_multipliers)])

    @classmethod
    def from_hf(cls, hf: dict[str, Any]) -> "FalconH1Config":
        """From a published ``config.json``. Keys the published modeling code never reads
        (``mamba_use_mlp``, ``mlp_expansion_factor``, ``attn_layer_indices``,
        ``num_logits_to_keep``) are not read here either; ``mamba_expand`` gives the
        mixer's width only where ``mamba_d_ssm`` is absent, as there. A variant no
        published Falcon-H1 has, and this model does not compute, is refused by name."""
        served = {"attention_bias": False, "mlp_bias": False, "mamba_proj_bias": False,
                  "projectors_bias": False, "mamba_rms_norm": True,
                  "mamba_norm_before_gate": False, "hidden_act": "silu"}
        refused = {k: hf[k] for k, v in served.items() if hf.get(k, v) != v}
        if refused:
            raise ValueError(f"falcon_h1: not served: {refused}")
        d_ssm = hf.get("mamba_d_ssm") or hf.get("mamba_expand", 2) * hf["hidden_size"]
        heads = hf["mamba_n_heads"]
        return cls(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_hidden_layers=hf["num_hidden_layers"],
            rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
            num_attention_heads=hf["num_attention_heads"],
            num_key_value_heads=hf.get("num_key_value_heads", hf["num_attention_heads"]),
            head_dim=hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"],
            rope_theta=float(hf.get("rope_theta", 1e11)),
            rope_scaling=hf.get("rope_scaling"),
            max_position_embeddings=hf.get("max_position_embeddings", 262144),
            mamba_d_ssm=d_ssm,
            mamba_n_heads=heads,
            mamba_d_head=hf.get("mamba_d_head") or d_ssm // heads,
            mamba_n_groups=hf["mamba_n_groups"],
            mamba_d_state=hf["mamba_d_state"],
            mamba_d_conv=hf.get("mamba_d_conv", 4),
            mamba_chunk_size=hf.get("mamba_chunk_size", 128),
            mamba_conv_bias=hf.get("mamba_conv_bias", True),
            embedding_multiplier=hf.get("embedding_multiplier", 1.0),
            lm_head_multiplier=hf.get("lm_head_multiplier", 1.0),
            key_multiplier=hf.get("key_multiplier", 1.0),
            attention_in_multiplier=hf.get("attention_in_multiplier", 1.0),
            attention_out_multiplier=hf.get("attention_out_multiplier", 1.0),
            ssm_in_multiplier=hf.get("ssm_in_multiplier", 1.0),
            ssm_out_multiplier=hf.get("ssm_out_multiplier", 1.0),
            ssm_multipliers=tuple(hf.get("ssm_multipliers") or (1.0,) * 5),
            mlp_multipliers=tuple(hf.get("mlp_multipliers") or (1.0, 1.0)),
            tie_word_embeddings=hf.get("tie_word_embeddings", False),
            initializer_range=hf.get("initializer_range", 0.02),
        )


def _layer_shapes(cfg: FalconH1Config) -> dict[str, tuple[int, ...]]:
    d, n, k, h = cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    hm, inter = cfg.mamba_n_heads, cfg.mamba_d_ssm
    shapes = {
        "input_norm": (d,),
        "in_proj": (d, cfg.in_proj_dim),
        "conv_w": (cfg.conv_dim, cfg.mamba_d_conv),
        "dt_bias": (hm,),
        "a_log": (hm,),
        "d_skip": (hm,),
        "gated_norm": (inter,),
        "out_proj": (inter, d),
        "wq": (d, n, h),
        "wk": (d, k, h),
        "wv": (d, k, h),
        "wo": (n, h, d),
        "mlp_norm": (d,),
        "w_gate": (d, cfg.intermediate_size),
        "w_up": (d, cfg.intermediate_size),
        "w_down": (cfg.intermediate_size, d),
    }
    if cfg.mamba_conv_bias:
        shapes["b_conv"] = (cfg.conv_dim,)
    return shapes


_LAYER_AXES = {
    "input_norm": ("norm",),
    "in_proj": ("embed", "mlp"),
    "conv_w": (None, None),
    "b_conv": ("mlp",),
    "dt_bias": ("heads",),
    "a_log": ("heads",),
    "d_skip": ("heads",),
    "gated_norm": ("norm",),
    "out_proj": ("mlp", "embed"),
    "wq": ("embed", "heads", "head_dim"),
    "wk": ("embed", "kv_heads", "head_dim"),
    "wv": ("embed", "kv_heads", "head_dim"),
    "wo": ("heads", "head_dim", "embed"),
    "mlp_norm": ("norm",),
    "w_gate": ("embed", "mlp"),
    "w_up": ("embed", "mlp"),
    "w_down": ("mlp", "embed"),
}


def _times(x, m: float):
    """``x`` times a published scalar, in float32, rounded once to ``x``'s type."""
    return x if m == 1.0 else (x * jnp.float32(m)).astype(x.dtype)


class FalconH1ForCausalLM:
    """Functional model: holds config + backend, operates on param pytrees."""

    config_class = FalconH1Config
    hf_architectures = ("FalconH1ForCausalLM",)

    def __init__(self, config: FalconH1Config, backend: BackendConfig | None = None):
        self.config = config
        self.backend = backend or BackendConfig()

    # ---- params ----

    def init(self, key: jax.Array, dtype=jnp.float32) -> dict:
        """``normal(0, initializer_range)`` matrices; norms, ``dt_bias`` and ``D`` ones,
        ``A = 1 .. heads`` (its log stays float32), conv bias zeros: as the published
        modeling code sets them."""
        cfg = self.config
        std, L = cfg.initializer_range, cfg.num_hidden_layers
        shapes = _layer_shapes(cfg)
        keys = jax.random.split(key, len(shapes) + 2)
        layers = {}
        for idx, (name, shape) in enumerate(shapes.items()):
            if name.endswith("norm") or name in ("dt_bias", "d_skip"):
                layers[name] = jnp.ones((L, *shape), dtype)
            elif name == "a_log":
                a = jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32))
                layers[name] = jnp.broadcast_to(a, (L, *shape)).copy()
            elif name == "b_conv":
                layers[name] = jnp.zeros((L, *shape), dtype)
            else:
                layers[name] = (jax.random.normal(keys[idx], (L, *shape), jnp.float32)
                                * std).astype(dtype)
        vd = (cfg.vocab_size, cfg.hidden_size)
        params = {
            "embed": (jax.random.normal(keys[-2], vd, jnp.float32) * std).astype(dtype),
            "layers": layers,
            "final_norm": jnp.ones((cfg.hidden_size,), dtype),
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = (jax.random.normal(keys[-1], vd[::-1], jnp.float32)
                                 * std).astype(dtype)
        return params

    def abstract_params(self, dtype=jnp.bfloat16) -> dict:
        return jax.eval_shape(lambda k: self.init(k, dtype), jax.random.key(0))

    def logical_axes(self) -> dict:
        cfg = self.config
        axes = {
            "embed": ("vocab", "embed"),
            "layers": {name: ("layers",) + _LAYER_AXES[name] for name in _layer_shapes(cfg)},
            "final_norm": ("norm",),
        }
        if not cfg.tie_word_embeddings:
            axes["lm_head"] = ("embed", "vocab")
        return axes

    # ---- forward ----

    def __call__(self, params, input_ids, positions=None, segment_ids=None, rules=None,
                 return_hidden=False, cache=None):
        if cache is not None:
            raise NotImplementedError(
                "falcon_h1: no decode cache yet (a layer holds a KV cache AND conv taps with "
                "an SSD state); the training path is what this family serves")
        cfg, backend = self.config, self.backend
        dtype = backend.jnp_dtype
        B, S = input_ids.shape
        eps = cfg.rms_norm_eps
        lin = backend.linear
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        reset_mask = None
        if segment_ids is not None:
            reset_mask = jnp.concatenate(
                [jnp.zeros((B, 1), bool), segment_ids[:, 1:] != segment_ids[:, :-1]], axis=1)
        acfg = cfg.attention
        inv_freq = rope_frequencies(cfg.head_dim, cfg.rope_theta, cfg.rope_scaling)
        attn_scale = rope_attention_scaling(cfg.rope_scaling)
        mup_vector = cfg.mup_vector()
        gate_mult, down_mult = cfg.mlp_multipliers

        def mamba(lp, u):
            out = mamba2_mixer(
                lp, _times(u, cfg.ssm_in_multiplier), num_heads=cfg.mamba_n_heads,
                head_dim=cfg.mamba_d_head, n_groups=cfg.mamba_n_groups,
                state_size=cfg.mamba_d_state, chunk_size=cfg.mamba_chunk_size, eps=eps,
                linear=lin, segment_ids=segment_ids, reset_mask=reset_mask,
                mesh=None if rules is None else rules.mesh, segment_scale=mup_vector,
            )
            return _times(out, cfg.ssm_out_multiplier)

        def attention(lp, u):
            out = _attention_block(
                acfg, backend, lp, _times(u, cfg.attention_in_multiplier), positions,
                segment_ids, inv_freq, attn_scale, None, rules)
            return _times(out, cfg.attention_out_multiplier)

        def mlp(lp, w):
            # names as in the shared MLP: the ``mlp_*`` remat rungs keep these two
            up = checkpoint_name(project(w, lp["w_up"], 1, lin), "mlp_up")
            gate = checkpoint_name(project(w, lp["w_gate"], 1, lin), "mlp_gate")
            act = up * jax.nn.silu(_times(gate, gate_mult))
            act = _constrain(act, rules, ("batch", "act_attn_seq", "act_mlp"))
            return _times(project(act, lp["w_down"], 1, lin), down_mult)

        blocks = scope_blocks({"mamba": mamba, "attention": attention, "mlp": mlp})

        def layer(h, lp):
            # compute-dtype cast; the decay's log stays float32
            lp = {k: v if k == "a_log" else v.astype(dtype) for k, v in lp.items()}
            u = rms_norm(h, lp["input_norm"], eps).astype(dtype)
            h = h + blocks["mamba"](lp, u) + blocks["attention"](lp, u)
            h = _constrain(h, rules, ("batch", "act_seq", "act_embed"))
            h = h + blocks["mlp"](lp, rms_norm(h, lp["mlp_norm"], eps).astype(dtype))
            return _constrain(h, rules, ("batch", "act_seq", "act_embed")), None

        body = backend.layer_remat(layer)
        h = embed_lookup(params["embed"], input_ids, dtype, rules, scale=cfg.embedding_multiplier)
        h = _constrain(h, rules, ("batch", "act_seq", "act_embed"))
        # the scan's own slicing and stacking; blocks carry their labels inside
        with jax.named_scope("layer_stack"):
            if backend.scan_layers:
                h, _ = jax.lax.scan(body, h, params["layers"])
            else:
                for i in range(cfg.num_hidden_layers):
                    h, _ = body(h, jax.tree.map(lambda a: a[i], params["layers"]))

        # final norm and head are one layer kind in a device trace; the recipe opens the
        # same scope around its loss call
        with jax.named_scope("lm_head_loss"):
            h = _times(rms_norm(h, params["final_norm"].astype(dtype), eps),
                       cfg.lm_head_multiplier)
            if return_hidden:
                return h
            return jnp.einsum("bsd,dv->bsv", h, resolve_unembed(cfg, params, dtype))

    # ---- interop ----

    def state_dict_adapter(self):
        from automodel_tpu.models.falcon_h1.state_dict_adapter import FalconH1StateDictAdapter

        return FalconH1StateDictAdapter(self.config)

    @classmethod
    def from_config(cls, config, backend: BackendConfig | None = None):
        if isinstance(config, dict):
            config = FalconH1Config.from_hf(config)
        return cls(config, backend)
