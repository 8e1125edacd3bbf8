"""NemotronV3 / Nemotron-H — TPU-native hybrid Mamba2 + Attention + MLP + MoE
(reference models/nemotron_v3/model.py:36, layers.py:155 Mamba2 mixer,
layers.py:458 single-mixer pre-norm blocks).

Each layer is ONE mixer (norm -> mixer -> residual), the type given per layer by
``layers_block_type`` ("mamba" | "attention" | "mlp" | "moe"). Attention is GQA
*without* rope (NemotronH convention); MLP/experts use ReLU²; MoE routes with
DSv3-style sigmoid scores, group-limited top-k, a shared ReLU² expert and a forced
score-correction-bias buffer.

The MoE may be a LatentMoE (``moe_latent_size``: the routed experts work in a narrower
latent, ``moe/layers.py``) and may hold a share of the routed experts
(``router_n_experts`` / ``first_held_expert``, ``moe/config.py``).

TPU-first structure: params live in four stacked per-type streams and the forward is
ONE ``lax.scan``. An iteration runs the layer kinds in a fixed order, each at most once
(``MEMEMEMEM*E`` is five iterations of Mamba, [attention], MoE); a kind that every
iteration has is scanned over its whole stack, so neither its parameters nor their
gradients are sliced or copied; a kind that some iterations lack runs under ``lax.cond``
on its own index into its stack. Compile time is that of one layer of each kind.
Mamba2 uses the chunked SSD scan in ops/mamba2.py; packed sequences reset conv taps
and recurrence at document boundaries.

Device-trace scopes: ``embed``, ``layer_stack`` (round the scans only), one label a
block kind (``mamba``, ``attention``, ``mlp``, ``moe``), ``mamba_proj`` and ``mamba_ssd``
(the mixer's two projections and its scan alone, inside ``mamba``: ``ops.mamba2.mamba2_mixer``,
which Falcon-H1's block calls too), ``lm_head_loss``. Every projection goes through ``ops.fp8.project``,
so ``backend.linear`` reaches them all.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp

from automodel_tpu.models.common.backend import BackendConfig
from jax.ad_checkpoint import checkpoint_name

from automodel_tpu.models.common.transformer import _constrain, embed_lookup
from automodel_tpu.moe.config import MoEConfig
from automodel_tpu.moe.dispatch import make_moe_block_forward
from automodel_tpu.moe.layers import cast_moe_compute_params, init_moe_params, moe_logical_axes
from automodel_tpu.utils.tracing import scope_blocks
from automodel_tpu.ops.attention import dot_product_attention, sharded_attention
from automodel_tpu.ops.fp8 import project
from automodel_tpu.ops.gated_delta import causal_conv1d, conv_state_from_prefill, conv_step
from automodel_tpu.ops.mamba2 import (
    group_rms_norm_gated, mamba2_mixer, mamba_chunk_scan, softplus_dt,
)
from automodel_tpu.ops.norms import rms_norm

__all__ = ["NemotronV3Config", "NemotronHForCausalLM"]

BLOCK_TYPES = ("mamba", "attention", "mlp", "moe")
# the published config spells the layers as one character each (hybrid_override_pattern)
PATTERN_CHARS = {"M": "mamba", "*": "attention", "-": "mlp", "E": "moe"}


def _iterations(types: tuple[str, ...]) -> tuple[tuple[str, ...], np.ndarray]:
    """Cut the layer pattern into iterations of ONE scan: ``(order, present)``. An
    iteration runs the layer kinds in ``order``, each at most once; ``present[i, j]`` says
    whether iteration ``i`` has a layer of kind ``order[j]``. The order is the one that
    needs the fewest iterations (``MEMEMEMEM*E`` under ``(M, *, E)``: five, the attention
    layer in the last alone; a run of one kind: an iteration a layer)."""
    kinds = sorted(set(types), key=types.index)
    best = None
    for order in itertools.permutations(kinds):
        rank = {t: j for j, t in enumerate(order)}
        rows: list[set] = []
        for t in types:
            if not rows or rank[t] <= max(rank[u] for u in rows[-1]):
                rows.append(set())
            rows[-1].add(t)
        if best is None or len(rows) < len(best[1]):
            best = (order, rows)
    order, rows = best
    return order, np.asarray([[t in row for t in order] for row in rows], bool)


@dataclasses.dataclass
class NemotronV3Config:
    vocab_size: int = 1024
    hidden_size: int = 256
    intermediate_size: int = 512
    num_hidden_layers: int = 4
    layers_block_type: tuple[str, ...] = ("mamba", "attention", "mlp", "moe")
    layer_norm_epsilon: float = 1e-5
    # attention (no rope)
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = 64
    attention_bias: bool = False
    # mamba2
    mamba_num_heads: int = 8
    mamba_head_dim: int = 32
    ssm_state_size: int = 64
    n_groups: int = 2
    chunk_size: int = 128
    conv_kernel: int = 4
    use_conv_bias: bool = True
    use_bias: bool = False  # in_proj/out_proj bias
    time_step_limit: tuple[float, float] = (0.0, float("inf"))
    # mlp
    mlp_bias: bool = False
    residual_in_fp32: bool = False
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    moe: MoEConfig | None = None

    def __post_init__(self):
        bad = set(self.layers_block_type) - set(BLOCK_TYPES)
        if bad:
            raise ValueError(f"unknown layers_block_type entries {bad}")
        if "moe" in self.layers_block_type and self.moe is None:
            raise ValueError("moe layers present but no MoEConfig")

    @property
    def mamba_intermediate(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.mamba_intermediate + 2 * self.n_groups * self.ssm_state_size

    def type_indices(self, t: str) -> tuple[int, ...]:
        return tuple(i for i, bt in enumerate(self.layers_block_type) if bt == t)

    @property
    def iterations(self) -> tuple[tuple[str, ...], np.ndarray]:
        """``(order of layer kinds, present (iterations, kinds))``: what the forward scans."""
        return _iterations(tuple(self.layers_block_type))

    @classmethod
    def from_hf(cls, hf: dict[str, Any]) -> "NemotronV3Config":
        """From a published ``config.json``. The layer kinds come from
        ``hybrid_override_pattern`` (``M``, ``*``, ``-``, ``E``: Nemotron-H and Nemotron-3)
        or from ``layers_block_type``. Two keys no published config has state one chip's
        share of an expert-parallel layer: with ``router_n_experts`` the router scores that
        many experts while ``n_routed_experts`` of them, from ``first_held_expert`` on, are
        held here (default: the router's width is ``n_routed_experts``, all held)."""
        moe = None
        if "hybrid_override_pattern" in hf:
            bad = set(hf["hybrid_override_pattern"]) - set(PATTERN_CHARS)
            if bad:
                raise ValueError(f"hybrid_override_pattern has unknown layer kinds {sorted(bad)}")
            layer_types = tuple(PATTERN_CHARS[c] for c in hf["hybrid_override_pattern"])
        else:
            layer_types = tuple(hf["layers_block_type"])
        if len(layer_types) != hf["num_hidden_layers"]:
            raise ValueError(f"{len(layer_types)} layer kinds for num_hidden_layers "
                             f"{hf['num_hidden_layers']}")
        if "moe" in layer_types:
            router_width = hf.get("router_n_experts", hf["n_routed_experts"])
            moe = MoEConfig(
                n_routed_experts=router_width,
                n_held_experts=hf["n_routed_experts"] if "router_n_experts" in hf else None,
                first_held_expert=hf.get("first_held_expert", 0),
                latent_dim=hf.get("moe_latent_size") or None,
                n_activated_experts=hf["num_experts_per_tok"],
                dim=hf["hidden_size"],
                moe_inter_dim=hf["moe_intermediate_size"],
                n_shared_experts=hf.get("n_shared_experts", 1),
                n_expert_groups=max(hf.get("n_group") or 1, 1),
                n_limited_groups=max(hf.get("topk_group") or 1, 1),
                score_func="sigmoid",
                route_scale=hf.get("routed_scaling_factor", 1.0),
                norm_topk_prob=hf.get("norm_topk_prob", True),
                expert_bias=hf.get("mlp_bias", False),
                expert_activation="relu2",
                shared_expert_inter_dim=hf.get("moe_shared_expert_intermediate_size"),
                shared_expert_activation="relu2",
                force_score_correction_bias=True,
            )
        return cls(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_hidden_layers=hf["num_hidden_layers"],
            layers_block_type=layer_types,
            layer_norm_epsilon=hf.get("layer_norm_epsilon",
                                      hf.get("norm_eps", hf.get("rms_norm_eps", 1e-5))),
            num_attention_heads=hf["num_attention_heads"],
            num_key_value_heads=hf.get("num_key_value_heads", hf["num_attention_heads"]),
            head_dim=hf.get("head_dim", hf["hidden_size"] // hf["num_attention_heads"]),
            attention_bias=hf.get("attention_bias", False),
            mamba_num_heads=hf["mamba_num_heads"],
            mamba_head_dim=hf["mamba_head_dim"],
            ssm_state_size=hf["ssm_state_size"],
            n_groups=hf["n_groups"],
            chunk_size=hf.get("chunk_size", 128),
            conv_kernel=hf.get("conv_kernel", 4),
            use_conv_bias=hf.get("use_conv_bias", True),
            use_bias=hf.get("use_bias", hf.get("mamba_proj_bias", False)),
            time_step_limit=tuple(hf.get("time_step_limit", (0.0, float("inf")))),
            mlp_bias=hf.get("mlp_bias", False),
            residual_in_fp32=hf.get("residual_in_fp32", False),
            tie_word_embeddings=hf.get("tie_word_embeddings", False),
            initializer_range=hf.get("initializer_range", 0.02),
            moe=moe,
        )


def _stream_shapes(cfg: NemotronV3Config, t: str) -> dict[str, tuple[int, ...]]:
    d = cfg.hidden_size
    shapes: dict[str, tuple[int, ...]] = {"norm": (d,)}
    if t == "mamba":
        inter, hm = cfg.mamba_intermediate, cfg.mamba_num_heads
        proj = inter + cfg.conv_dim + hm
        shapes |= {
            "in_proj": (d, proj),
            "conv_w": (cfg.conv_dim, cfg.conv_kernel),
            "dt_bias": (hm,),
            "a_log": (hm,),
            "d_skip": (hm,),
            "gated_norm": (inter,),
            "out_proj": (inter, d),
        }
        if cfg.use_conv_bias:
            shapes["b_conv"] = (cfg.conv_dim,)
        if cfg.use_bias:
            shapes["b_in"] = (proj,)
            shapes["b_out"] = (d,)
    elif t == "attention":
        h, kv, dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        shapes |= {"wq": (d, h, dh), "wk": (d, kv, dh), "wv": (d, kv, dh), "wo": (h, dh, d)}
        if cfg.attention_bias:
            shapes |= {"bq": (h, dh), "bk": (kv, dh), "bv": (kv, dh), "bo": (d,)}
    elif t == "mlp":
        shapes |= {"w_up": (d, cfg.intermediate_size), "w_down": (cfg.intermediate_size, d)}
        if cfg.mlp_bias:
            shapes |= {"b_up": (cfg.intermediate_size,), "b_down": (d,)}
    return shapes  # moe: just the norm; expert params come from init_moe_params


_STREAM_AXES = {
    "norm": ("norm",),
    "in_proj": ("embed", "mlp"),
    "conv_w": (None, None),
    "b_conv": ("mlp",),
    "b_in": ("mlp",),
    "dt_bias": ("heads",),
    "a_log": ("heads",),
    "d_skip": ("heads",),
    "gated_norm": ("norm",),
    "out_proj": ("mlp", "embed"),
    "b_out": ("norm",),
    "wq": ("embed", "heads", "head_dim"),
    "wk": ("embed", "kv_heads", "head_dim"),
    "wv": ("embed", "kv_heads", "head_dim"),
    "wo": ("heads", "head_dim", "embed"),
    "bq": ("heads", "head_dim"),
    "bk": ("kv_heads", "head_dim"),
    "bv": ("kv_heads", "head_dim"),
    "bo": ("norm",),
    "w_up": ("embed", "mlp"),
    "b_up": ("mlp",),
    "w_down": ("mlp", "embed"),
    "b_down": ("norm",),
}

_STREAM_KEY = {"mamba": "mamba_layers", "attention": "attn_layers", "mlp": "mlp_layers", "moe": "moe_layers"}


class NemotronHForCausalLM:
    """Functional model: holds config + backend, operates on param pytrees."""

    config_class = NemotronV3Config
    hf_architectures = ("NemotronHForCausalLM", "NemotronV3ForCausalLM")

    def __init__(self, config: NemotronV3Config, backend: BackendConfig | None = None):
        self.config = config
        self.backend = backend or BackendConfig()

    # ---- params ----

    def init(self, key: jax.Array, dtype=jnp.float32) -> dict:
        cfg = self.config
        std = cfg.initializer_range
        keys = iter(jax.random.split(key, 8))
        params: dict = {
            "embed": (jax.random.normal(next(keys), (cfg.vocab_size, cfg.hidden_size), jnp.float32) * std).astype(dtype),
            "final_norm": jnp.ones((cfg.hidden_size,), dtype),
        }

        def init_stack(t: str, L: int, key) -> dict:
            shapes = _stream_shapes(cfg, t)
            ks = jax.random.split(key, len(shapes))
            out = {}
            for idx, (name, shape) in enumerate(shapes.items()):
                if name in ("norm", "gated_norm"):
                    out[name] = jnp.ones((L, *shape), dtype)
                elif name == "dt_bias" or name == "d_skip":
                    out[name] = jnp.ones((L, *shape), dtype)
                elif name == "a_log":
                    # A = arange(1..H) (reference layers.py:208): log stays fp32
                    a = jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32))
                    out[name] = jnp.broadcast_to(a, (L, *shape)).copy()
                elif name.startswith("b"):
                    out[name] = jnp.zeros((L, *shape), dtype)
                else:
                    out[name] = (jax.random.normal(ks[idx], (L, *shape), jnp.float32) * std).astype(dtype)
            return out

        for t in BLOCK_TYPES:
            idx = cfg.type_indices(t)
            if not idx:
                continue
            stack = init_stack(t, len(idx), next(keys))
            if t == "moe":
                stack["moe"] = jax.vmap(lambda k: init_moe_params(cfg.moe, k, dtype, std))(
                    jax.random.split(next(keys), len(idx))
                )
            params[_STREAM_KEY[t]] = stack
        if not cfg.tie_word_embeddings:
            params["lm_head"] = (
                jax.random.normal(next(keys), (cfg.hidden_size, cfg.vocab_size), jnp.float32) * std
            ).astype(dtype)
        return params

    def abstract_params(self, dtype=jnp.bfloat16) -> dict:
        return jax.eval_shape(lambda k: self.init(k, dtype), jax.random.key(0))

    def logical_axes(self) -> dict:
        cfg = self.config
        axes: dict = {"embed": ("vocab", "embed"), "final_norm": ("norm",)}
        for t in BLOCK_TYPES:
            idx = cfg.type_indices(t)
            if not idx:
                continue
            stream = {name: ("layers",) + _STREAM_AXES[name] for name in _stream_shapes(cfg, t)}
            if t == "moe":
                stream["moe"] = jax.tree.map(
                    lambda tp: ("layers",) + tp,
                    moe_logical_axes(cfg.moe),
                    is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x),
                )
            axes[_STREAM_KEY[t]] = stream
        if not cfg.tie_word_embeddings:
            axes["lm_head"] = ("embed", "vocab")
        return axes

    # ---- forward ----

    def __call__(self, params, input_ids, positions=None, segment_ids=None, token_mask=None,
                 rules=None, return_hidden=False, training=True, cache=None):
        cfg, backend = self.config, self.backend
        dtype = backend.jnp_dtype
        B, S = input_ids.shape
        eps = cfg.layer_norm_epsilon

        if cache is not None:
            if segment_ids is None:
                raise ValueError("cache decoding requires segment_ids (1 = real token)")
            if positions is None:
                positions = jnp.broadcast_to(jnp.arange(S), (B, S))
            return self._decode_forward(params, input_ids, positions, segment_ids, cache, dtype)

        reset_mask = None
        if segment_ids is not None:
            reset_mask = jnp.concatenate(
                [jnp.zeros((B, 1), bool), segment_ids[:, 1:] != segment_ids[:, :-1]], axis=1
            )

        lin = backend.linear

        def mamba_block(lp, h):
            x = rms_norm(h, lp["norm"], eps).astype(dtype)
            if token_mask is not None:
                x = x * token_mask[..., None].astype(x.dtype)
            out = mamba2_mixer(
                lp, x, num_heads=cfg.mamba_num_heads, head_dim=cfg.mamba_head_dim,
                n_groups=cfg.n_groups, state_size=cfg.ssm_state_size,
                chunk_size=cfg.chunk_size, eps=eps, time_step_limit=cfg.time_step_limit,
                linear=lin, segment_ids=segment_ids, reset_mask=reset_mask,
                mesh=None if rules is None else rules.mesh,
            )
            return h + out, _zero_stats()

        def attn_block(lp, h):
            x = rms_norm(h, lp["norm"], eps).astype(dtype)
            # names as in the shared decoder: the remat policies that save k, v and the
            # attention's output (``mlp_attn_dots``) find them here too; the output is
            # named inside ``sharded_attention``
            q = project(x, lp["wq"], 1, lin)
            k = checkpoint_name(project(x, lp["wk"], 1, lin), "attn_k")
            v = checkpoint_name(project(x, lp["wv"], 1, lin), "attn_v")
            if cfg.attention_bias:
                q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
            out = sharded_attention(
                q, k, v, rules=rules, causal=True, segment_ids_q=segment_ids,
                backend=backend.attention,
            )
            o = project(out, lp["wo"], 2, lin)
            if cfg.attention_bias:
                o = o + lp["bo"]
            return h + o, _zero_stats()

        def mlp_block(lp, h):
            x = rms_norm(h, lp["norm"], eps).astype(dtype)
            up = project(x, lp["w_up"], 1, lin)
            if "b_up" in lp:
                up = up + lp["b_up"]
            act = jnp.square(jax.nn.relu(up))
            out = project(act, lp["w_down"], 1, lin)
            if "b_down" in lp:
                out = out + lp["b_down"]
            return h + out, _zero_stats()

        moe_fwd = (
            make_moe_block_forward(cfg.moe, backend, rules, training=training)
            if cfg.moe is not None else None
        )

        def moe_block(lp, h):
            x = rms_norm(h, lp["norm"], eps).astype(dtype)
            moe_params = cast_moe_compute_params(lp["moe"], dtype)
            y, aux, load, dropped = moe_fwd(moe_params, x, token_mask)
            return h + y, (jnp.float32(0) if aux is None else aux, load, dropped)

        def _zero_stats():
            E = cfg.moe.n_routed_experts if cfg.moe else 1
            return jnp.float32(0), jnp.zeros((E,), jnp.float32), jnp.float32(0)

        # profiler labels per block kind (autonvtx parity): mamba runs vs
        # attention vs moe show as separate regions in the trace viewer
        block_fns = scope_blocks(
            {"mamba": mamba_block, "attention": attn_block, "mlp": mlp_block, "moe": moe_block}
        )

        def layer_fn(t):
            fn = block_fns[t]

            def one(hh, lp):
                # compute-dtype cast; decay logs stay fp32, moe casts in moe_block
                lp = {
                    k: v if k in ("moe", "a_log") else jax.tree.map(lambda a: a.astype(dtype), v)
                    for k, v in lp.items()
                }
                hh, stats = fn(lp, hh)
                hh = _constrain(hh, rules, ("batch", "act_seq", "act_embed"))
                return hh, stats

            return backend.layer_remat(one)

        layer_fns = {t: layer_fn(t) for t in BLOCK_TYPES}

        h = embed_lookup(params["embed"], input_ids, dtype)
        if cfg.residual_in_fp32:
            # reference keeps the residual stream fp32 (layers.py:555-557);
            # mixer outputs promote on add, norms read fp32 and cast back
            h = h.astype(jnp.float32)
        h = _constrain(h, rules, ("batch", "act_seq", "act_embed"))

        order, present = cfg.iterations
        n_iter = len(present)
        streams = {t: params[_STREAM_KEY[t]] for t in order}
        # a kind in every iteration is scanned over its stack; another is indexed at its
        # own count so far (``at[i, j]``) where ``present`` says the iteration has it
        in_all = {t: bool(present[:, j].all()) for j, t in enumerate(order)}
        at = np.cumsum(present, axis=0) - present

        def iteration(hh, flags, index, scanned):
            stats = []
            for j, t in enumerate(order):
                if in_all[t]:
                    hh, st = layer_fns[t](hh, scanned[t])
                elif isinstance(flags, np.ndarray):  # unrolled: the pattern is static
                    st = _zero_stats()
                    if flags[j]:
                        hh, st = layer_fns[t](hh, jax.tree.map(lambda a: a[index[j]], streams[t]))
                else:
                    def run(h_, stack, k, t=t):
                        lp = jax.tree.map(
                            lambda a: jax.lax.dynamic_index_in_dim(a, k, keepdims=False), stack)
                        return layer_fns[t](h_, lp)

                    hh, st = jax.lax.cond(flags[j], run, lambda h_, *_: (h_, _zero_stats()),
                                          hh, streams[t], index[j])
                stats.append(st)
            return hh, tuple(jnp.stack(x) for x in zip(*stats))  # (kinds, ...)

        scanned = {t: streams[t] for t in order if in_all[t]}
        if backend.scan_layers and n_iter > 1:
            # the scan's own slicing and stacking; blocks carry their labels inside
            with jax.named_scope("layer_stack"):
                h, stats = jax.lax.scan(
                    lambda hh, xs: iteration(hh, *xs), h,
                    (jnp.asarray(present), jnp.asarray(at, jnp.int32), scanned))
        else:
            per_iter = []
            for i in range(n_iter):
                h, st = iteration(h, present[i], at[i], jax.tree.map(lambda a: a[i], scanned))
                per_iter.append(st)
            stats = tuple(jnp.stack(x) for x in zip(*per_iter))
        # (iterations, kinds, ...) -> the layers that exist, in execution order
        ran = present.reshape(-1)
        aux_all, load_all, drop_all = (x.reshape(ran.size, *x.shape[2:])[ran] for x in stats)
        moe_sel = np.asarray([t == "moe" for row in present for t, p in zip(order, row) if p])

        emit_aux = (
            cfg.moe is not None and cfg.moe.aux_loss_coeff > 0 and training
            and not backend.fake_balanced_gate
        )
        stats = {
            "aux_loss": aux_all.sum() if emit_aux else None,
            "expert_load": load_all[moe_sel] if cfg.moe is not None else load_all[:0],
        }
        if backend.dispatcher == "a2a" and cfg.moe is not None:
            stats["dropped_token_frac"] = drop_all[moe_sel].mean()

        # final norm and head are one layer kind in a device trace; the recipe opens the
        # same scope around its loss call
        with jax.named_scope("lm_head_loss"):
            h = rms_norm(h, params["final_norm"].astype(dtype), eps)
            if return_hidden:
                return h, stats
            unembed = params.get("lm_head")
            if unembed is None:
                unembed = params["embed"].T
            logits = jnp.einsum("bsd,dv->bsv", h, unembed.astype(dtype))
        return logits, stats

    # ---- decode ----

    def init_decode_cache(self, batch_size: int, max_len: int, dtype=jnp.bfloat16) -> dict:
        """Hybrid decode cache: KV for attention layers, conv taps + SSD state
        (fp32) for mamba layers (mlp/moe layers are stateless)."""
        cfg = self.config
        La = len(cfg.type_indices("attention"))
        Lm = len(cfg.type_indices("mamba"))
        return {
            "k": jnp.zeros((La, batch_size, max_len, cfg.num_key_value_heads, cfg.head_dim), dtype),
            "v": jnp.zeros((La, batch_size, max_len, cfg.num_key_value_heads, cfg.head_dim), dtype),
            "conv": jnp.zeros((Lm, batch_size, cfg.conv_kernel - 1, cfg.conv_dim), dtype),
            "rec": jnp.zeros(
                (Lm, batch_size, cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size),
                jnp.float32,
            ),
            "positions": jnp.zeros((batch_size, max_len), jnp.int32),
            "valid": jnp.zeros((batch_size, max_len), jnp.int32),
            "write_idx": jnp.zeros((batch_size,), jnp.int32),
        }

    def _decode_forward(self, params, input_ids, positions, segment_ids, cache, dtype):
        """Unrolled cached forward (prefill S>1, decode S=1). Right-padding is
        neutralized in the recurrence by zeroing dt (decay exp(0·A)=1, write
        dt·B·x=0) and in the conv by gathering each row's trailing VALID inputs."""
        from automodel_tpu.models.common.transformer import _cache_write

        cfg = self.config
        eps = cfg.layer_norm_epsilon
        B, S = input_ids.shape
        token_mask = segment_ids != 0
        K = cfg.conv_kernel
        h = params["embed"].astype(dtype)[input_ids]
        if cfg.residual_in_fp32:
            h = h.astype(jnp.float32)
        k_all, v_all = cache["k"], cache["v"]
        conv_all, rec_all = cache["conv"], cache["rec"]
        moe_fwd = (
            make_moe_block_forward(cfg.moe, self.backend, None, training=False)
            if cfg.moe is not None else None
        )
        offsets = dict.fromkeys(BLOCK_TYPES, 0)
        a_i = m_i = 0
        for t in cfg.layers_block_type:
            o = offsets[t]
            lp = jax.tree.map(lambda a: a[o], params[_STREAM_KEY[t]])
            offsets[t] = o + 1
            lp = {
                k_: v_ if k_ in ("moe", "a_log") else jax.tree.map(lambda a: a.astype(dtype), v_)
                for k_, v_ in lp.items()
            }
            if t == "mamba":
                x = rms_norm(h, lp["norm"], eps).astype(dtype)
                x = x * token_mask[..., None].astype(x.dtype)
                inter, hm = cfg.mamba_intermediate, cfg.mamba_num_heads
                gns = cfg.n_groups * cfg.ssm_state_size
                proj = jnp.einsum("bsd,dp->bsp", x, lp["in_proj"])
                if "b_in" in lp:
                    proj = proj + lp["b_in"]
                gate, xbc, dt_raw = jnp.split(proj, [inter, inter + cfg.conv_dim], axis=-1)
                if S == 1:
                    xbc_c, new_conv = conv_step(
                        conv_all[m_i], xbc, lp["conv_w"], bias=lp.get("b_conv")
                    )
                else:
                    xbc_c = causal_conv1d(xbc, lp["conv_w"], bias=lp.get("b_conv"))
                    new_conv = conv_state_from_prefill(xbc, token_mask.sum(-1), K)
                xi, Bm, Cm = jnp.split(xbc_c, [inter, inter + gns], axis=-1)
                dt = softplus_dt(dt_raw, lp["dt_bias"], cfg.time_step_limit)
                dt = dt * token_mask[..., None].astype(dt.dtype)
                A = -jnp.exp(lp["a_log"].astype(jnp.float32))
                y, rec = mamba_chunk_scan(
                    xi.reshape(B, S, hm, cfg.mamba_head_dim), dt, A,
                    Bm.reshape(B, S, cfg.n_groups, cfg.ssm_state_size),
                    Cm.reshape(B, S, cfg.n_groups, cfg.ssm_state_size),
                    lp["d_skip"], chunk_size=min(cfg.chunk_size, S),
                    initial_state=rec_all[m_i], output_final_state=True,
                )
                conv_all = conv_all.at[m_i].set(new_conv.astype(conv_all.dtype))
                rec_all = rec_all.at[m_i].set(rec)
                y = group_rms_norm_gated(
                    y.reshape(B, S, inter), lp["gated_norm"], gate,
                    group_size=inter // cfg.n_groups, eps=eps,
                )
                out = jnp.einsum("bsi,id->bsd", y, lp["out_proj"])
                if "b_out" in lp:
                    out = out + lp["b_out"]
                h = h + out
                m_i += 1
            elif t == "attention":
                x = rms_norm(h, lp["norm"], eps).astype(dtype)
                q = jnp.einsum("bsd,dnh->bsnh", x, lp["wq"])
                k = jnp.einsum("bsd,dnh->bsnh", x, lp["wk"])
                v = jnp.einsum("bsd,dnh->bsnh", x, lp["wv"])
                if cfg.attention_bias:
                    q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
                k_cache = _cache_write(k_all[a_i], k.astype(k_all.dtype), cache["write_idx"])
                v_cache = _cache_write(v_all[a_i], v.astype(v_all.dtype), cache["write_idx"])
                out = dot_product_attention(
                    q, k_cache.astype(q.dtype), v_cache.astype(q.dtype),
                    causal=True, segment_ids_q=segment_ids,
                    segment_ids_kv=cache["valid"],
                    positions_q=positions,
                    positions_kv=cache["positions"],
                    backend="xla",
                )
                k_all = k_all.at[a_i].set(k_cache)
                v_all = v_all.at[a_i].set(v_cache)
                o = jnp.einsum("bsnh,nhd->bsd", out, lp["wo"])
                if cfg.attention_bias:
                    o = o + lp["bo"]
                h = h + o
                a_i += 1
            elif t == "mlp":
                x = rms_norm(h, lp["norm"], eps).astype(dtype)
                up = jnp.einsum("bsd,di->bsi", x, lp["w_up"])
                if "b_up" in lp:
                    up = up + lp["b_up"]
                act = jnp.square(jax.nn.relu(up))
                out = jnp.einsum("bsi,id->bsd", act, lp["w_down"])
                if "b_down" in lp:
                    out = out + lp["b_down"]
                h = h + out
            else:  # moe
                x = rms_norm(h, lp["norm"], eps).astype(dtype)
                moe_params = cast_moe_compute_params(lp["moe"], dtype)
                y, _, _, _ = moe_fwd(moe_params, x, token_mask)
                h = h + y
        h = rms_norm(h, params["final_norm"].astype(dtype), eps)
        last = jnp.maximum(segment_ids.sum(-1) - 1, 0).astype(jnp.int32)
        h = jnp.take_along_axis(h, last[:, None, None], axis=1)
        unembed = params.get("lm_head")
        if unembed is None:
            unembed = params["embed"].T
        logits = jnp.einsum("bsd,dv->bsv", h, unembed.astype(dtype))
        return logits, dict(cache, k=k_all, v=v_all, conv=conv_all, rec=rec_all)

    def generate(self, params, input_ids, **kw):
        """Sample with the hybrid conv+SSD+KV cache (automodel_tpu.generation)."""
        from automodel_tpu.generation import generate

        return generate(self, params, input_ids, **kw)

    # ---- interop ----

    def state_dict_adapter(self):
        from automodel_tpu.models.nemotron_v3.state_dict_adapter import NemotronV3StateDictAdapter

        return NemotronV3StateDictAdapter(self.config)

    @classmethod
    def from_config(cls, config, backend: BackendConfig | None = None):
        if isinstance(config, dict):
            config = NemotronV3Config.from_hf(config)
        return cls(config, backend)
