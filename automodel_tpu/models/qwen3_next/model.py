"""Qwen3-Next family — TPU-native (reference models/qwen3_next/model.py).

Hybrid decoder: most layers mix tokens with a *gated DeltaNet* linear-attention
recurrence (reference model.py:38-39 delegates to HF Qwen3NextGatedDeltaNet); every
``full_attention_interval``-th layer is gated full attention (q carries a sigmoid
output gate, reference layers.py:56-153); every layer's MLP is Qwen-style MoE with a
gated shared expert (reference model.py:117-139).

TPU-first structure: layers are stored as two stacked streams ("linear_layers",
"full_layers") in execution order. When the layer pattern is uniform — (P-1) linear +
1 full repeated, the shape of every released Qwen3-Next checkpoint — the forward scans
over *period groups*: params reshape to (G, P-1, ...) / (G, ...) and one
``lax.scan`` body traces P layers, so compile time stays flat in depth. Non-uniform
patterns fall back to an unrolled loop. A mixer and a MoE block are each a remat unit,
inside the scan too: one mixer's float32 delta-rule intermediates or one MoE block's
rows are alive in the backward pass, not a period's; a unit reads its layer out of the
group's slice inside itself, so the backward pass keeps no copy of the layer.

The MoE may hold a share of the routed experts (``router_n_experts`` /
``first_held_expert``, ``moe/config.py``). Every projection of both mixers goes through
``ops.fp8.project``, so ``backend.linear`` reaches them all.

Device-trace scopes: ``embed``, ``layer_stack`` (round the layer loop, scanned or not),
``delta_net`` (the DeltaNet mixer alone: norm, projections, conv, rule, gated norm,
out), ``delta_rule`` (the recurrence alone, inside ``delta_net``), ``attention`` (the
gated full-attention mixer), ``moe`` (every layer's MoE block, outside both mixers),
``lm_head_loss``.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from automodel_tpu.models.common.backend import BackendConfig
from automodel_tpu.models.common.transformer import _constrain, embed_lookup
from automodel_tpu.moe.config import MoEConfig
from automodel_tpu.moe.dispatch import make_moe_block_forward
from automodel_tpu.utils.tracing import scoped
from automodel_tpu.moe.layers import cast_moe_compute_params, init_moe_params, moe_logical_axes
from automodel_tpu.ops.attention import dot_product_attention, sharded_attention
from automodel_tpu.ops.fp8 import project
from automodel_tpu.ops.gated_delta import (
    causal_conv1d,
    chunk_gated_delta_rule,
    conv_state_from_prefill,
    conv_step,
    gated_rms_norm,
)
from automodel_tpu.ops.norms import rms_norm
from automodel_tpu.ops.rope import apply_rope, rope_attention_scaling, rope_frequencies

__all__ = ["Qwen3NextConfig", "Qwen3NextForCausalLM"]

LINEAR = "linear_attention"
FULL = "full_attention"


@dataclasses.dataclass
class Qwen3NextConfig:
    vocab_size: int = 1024
    hidden_size: int = 256
    intermediate_size: int = 512
    num_hidden_layers: int = 4
    layer_types: tuple[str, ...] = (LINEAR, LINEAR, LINEAR, FULL)
    # full attention
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = 64
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10000.0
    rope_scaling: dict[str, Any] | None = None
    # gated deltanet
    linear_num_value_heads: int = 4
    linear_num_key_heads: int = 2
    linear_key_head_dim: int = 32
    linear_value_head_dim: int = 32
    linear_conv_kernel_dim: int = 4
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 4096
    initializer_range: float = 0.02
    moe: MoEConfig | None = None

    def __post_init__(self):
        if self.moe is None:
            raise ValueError("Qwen3NextConfig requires a MoEConfig in .moe")
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError("layer_types length must equal num_hidden_layers")

    @classmethod
    def from_hf(cls, hf: dict[str, Any]) -> "Qwen3NextConfig":
        """From a published ``config.json``. Two keys no published config has state one
        chip's share of an expert-parallel layer, as in ``NemotronV3Config.from_hf``: with
        ``router_n_experts`` the router scores that many experts while ``num_experts`` of
        them, from ``first_held_expert`` on, are held here (default: all held)."""
        if hf.get("mlp_only_layers"):
            raise NotImplementedError("qwen3_next dense-MLP layers are not supported")
        rope = hf.get("rope_parameters") or {}
        # rope_parameters doubles as the scaling config when rope_type != default
        rope_scaling = hf.get("rope_scaling")
        if rope_scaling is None and rope.get("rope_type", "default") != "default":
            rope_scaling = rope
        layer_types = hf.get("layer_types")
        if layer_types is None:
            interval = hf.get("full_attention_interval", 4)
            layer_types = [
                FULL if (i + 1) % interval == 0 else LINEAR for i in range(hf["num_hidden_layers"])
            ]
        moe = MoEConfig(
            n_routed_experts=hf.get("router_n_experts", hf["num_experts"]),
            n_held_experts=hf["num_experts"] if "router_n_experts" in hf else None,
            first_held_expert=hf.get("first_held_expert", 0),
            n_activated_experts=hf["num_experts_per_tok"],
            dim=hf["hidden_size"],
            moe_inter_dim=hf["moe_intermediate_size"],
            n_shared_experts=1,
            shared_expert_inter_dim=hf.get("shared_expert_intermediate_size", hf["moe_intermediate_size"]),
            shared_expert_gate=True,
            score_func="softmax",
            softmax_before_topk=True,
            norm_topk_prob=hf.get("norm_topk_prob", True),
            aux_loss_coeff=hf.get("router_aux_loss_coef", 0.0),
        )
        return cls(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf.get("intermediate_size", 0),
            num_hidden_layers=hf["num_hidden_layers"],
            layer_types=tuple(layer_types),
            num_attention_heads=hf["num_attention_heads"],
            num_key_value_heads=hf["num_key_value_heads"],
            head_dim=hf.get("head_dim", hf["hidden_size"] // hf["num_attention_heads"]),
            partial_rotary_factor=rope.get(
                "partial_rotary_factor", hf.get("partial_rotary_factor", 0.25)
            ),
            rope_theta=rope.get("rope_theta", hf.get("rope_theta", 10000.0)),
            rope_scaling=rope_scaling,
            linear_num_value_heads=hf["linear_num_value_heads"],
            linear_num_key_heads=hf["linear_num_key_heads"],
            linear_key_head_dim=hf["linear_key_head_dim"],
            linear_value_head_dim=hf["linear_value_head_dim"],
            linear_conv_kernel_dim=hf["linear_conv_kernel_dim"],
            rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
            tie_word_embeddings=hf.get("tie_word_embeddings", False),
            max_position_embeddings=hf.get("max_position_embeddings", 4096),
            initializer_range=hf.get("initializer_range", 0.02),
            moe=moe,
        )

    @property
    def linear_layer_indices(self) -> tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types) if t == LINEAR)

    @property
    def full_layer_indices(self) -> tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types) if t == FULL)

    @property
    def conv_dim(self) -> int:
        return 2 * self.linear_num_key_heads * self.linear_key_head_dim + (
            self.linear_num_value_heads * self.linear_value_head_dim
        )

    @property
    def period(self) -> int | None:
        """Group size P when layer_types is ((P-1) x linear, full) repeated, else None."""
        full = self.full_layer_indices
        if not full or not self.linear_layer_indices:
            return None
        P = full[0] + 1
        uniform = self.num_hidden_layers % P == 0 and all(
            t == (FULL if (i + 1) % P == 0 else LINEAR) for i, t in enumerate(self.layer_types)
        )
        return P if uniform else None


def _linear_attn_shapes(cfg: Qwen3NextConfig) -> dict:
    """HF's fused projections stay fused as single leaves: one big MXU matmul each
    and a 1:1 state-dict mapping (in_proj_qkvz rows are per-key-head
    [q|k|v·r|z·r] — HF fix_query_key_value_ordering, modeling_qwen3_next.py:631)."""
    D = cfg.hidden_size
    Hk, dk = cfg.linear_num_key_heads, cfg.linear_key_head_dim
    Hv, dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
    r = Hv // Hk
    return {
        "attn_norm": (D,),
        "mlp_norm": (D,),
        "wqkvz": (D, Hk, 2 * dk + 2 * r * dv),
        "wba": (D, Hk, 2 * r),
        "conv_w": (cfg.conv_dim, cfg.linear_conv_kernel_dim),
        "dt_bias": (Hv,),
        "a_log": (Hv,),
        "norm": (dv,),
        "wo": (Hv, dv, D),
    }


def _full_attn_shapes(cfg: Qwen3NextConfig) -> dict:
    D, H, Hkv, dh = cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    return {
        "attn_norm": (D,),
        "mlp_norm": (D,),
        "wq": (D, H, 2 * dh),  # per-head [q | sigmoid output gate] (HF q_proj 2x width)
        "wk": (D, Hkv, dh),
        "wv": (D, Hkv, dh),
        "wo": (H, dh, D),
        "q_norm": (dh,),
        "k_norm": (dh,),
    }


_LINEAR_AXES = {
    "attn_norm": ("norm",),
    "mlp_norm": ("norm",),
    "wqkvz": ("embed", "kv_heads", "head_dim"),
    "wba": ("embed", "kv_heads", "head_dim"),
    "conv_w": (None, None),
    "dt_bias": ("heads",),
    "a_log": ("heads",),
    "norm": ("norm",),
    "wo": ("heads", "head_dim", "embed"),
}

_FULL_AXES = {
    "attn_norm": ("norm",),
    "mlp_norm": ("norm",),
    "wq": ("embed", "heads", "head_dim"),
    "wk": ("embed", "kv_heads", "head_dim"),
    "wv": ("embed", "kv_heads", "head_dim"),
    "wo": ("heads", "head_dim", "embed"),
    "q_norm": ("norm",),
    "k_norm": ("norm",),
}


class Qwen3NextForCausalLM:
    """Functional model: holds config + backend, operates on param pytrees."""

    config_class = Qwen3NextConfig
    hf_architectures = ("Qwen3NextForCausalLM",)

    def __init__(self, config: Qwen3NextConfig, backend: BackendConfig | None = None):
        self.config = config
        self.backend = backend or BackendConfig()

    # ---- params ----

    def init(self, key: jax.Array, dtype=jnp.float32) -> dict:
        cfg = self.config
        std = cfg.initializer_range
        k_embed, k_lin, k_full, k_moe, k_head = jax.random.split(key, 5)

        def init_stack(shapes: dict, L: int, key) -> dict:
            keys = jax.random.split(key, len(shapes))
            out = {}
            for idx, (name, shape) in enumerate(shapes.items()):
                if name.endswith("norm") or name == "norm":
                    # HF zero-centered RMSNorm for attn/mlp/q/k norms; gated norm is
                    # standard (ones) — both store the HF tensor verbatim: zeros here
                    # means "identity" for the zero-centered ones, so init gated norm
                    # weights to ones and the rest to zeros
                    fill = jnp.ones if name == "norm" else jnp.zeros
                    out[name] = fill((L, *shape), dtype)
                elif name == "dt_bias":
                    out[name] = jnp.ones((L, *shape), dtype)
                elif name == "a_log":
                    u = jax.random.uniform(keys[idx], (L, *shape), jnp.float32, 1e-4, 16.0)
                    out[name] = jnp.log(u).astype(jnp.float32)  # kept fp32 (HF casts too)
                else:
                    out[name] = (jax.random.normal(keys[idx], (L, *shape), jnp.float32) * std).astype(dtype)
            return out

        L_lin, L_full = len(cfg.linear_layer_indices), len(cfg.full_layer_indices)
        params: dict = {
            "embed": (jax.random.normal(k_embed, (cfg.vocab_size, cfg.hidden_size), jnp.float32) * std).astype(dtype),
            "final_norm": jnp.zeros((cfg.hidden_size,), dtype),
        }
        if L_lin:
            lin = init_stack(_linear_attn_shapes(cfg), L_lin, k_lin)
            lin["moe"] = jax.vmap(lambda k: init_moe_params(cfg.moe, k, dtype, std))(
                jax.random.split(jax.random.fold_in(k_moe, 0), L_lin)
            )
            params["linear_layers"] = lin
        if L_full:
            full = init_stack(_full_attn_shapes(cfg), L_full, k_full)
            full["moe"] = jax.vmap(lambda k: init_moe_params(cfg.moe, k, dtype, std))(
                jax.random.split(jax.random.fold_in(k_moe, 1), L_full)
            )
            params["full_layers"] = full
        if not cfg.tie_word_embeddings:
            params["lm_head"] = (
                jax.random.normal(k_head, (cfg.hidden_size, cfg.vocab_size), jnp.float32) * std
            ).astype(dtype)
        return params

    def abstract_params(self, dtype=jnp.bfloat16) -> dict:
        return jax.eval_shape(lambda k: self.init(k, dtype), jax.random.key(0))

    def logical_axes(self) -> dict:
        cfg = self.config
        axes: dict = {
            "embed": ("vocab", "embed"),
            "final_norm": ("norm",),
        }
        if cfg.linear_layer_indices:
            lin = {k: ("layers",) + v for k, v in _LINEAR_AXES.items()}
            lin["moe"] = jax.tree.map(
                lambda t: ("layers",) + t,
                moe_logical_axes(cfg.moe),
                is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x),
            )
            axes["linear_layers"] = lin
        if cfg.full_layer_indices:
            full = {k: ("layers",) + v for k, v in _FULL_AXES.items()}
            full["moe"] = jax.tree.map(
                lambda t: ("layers",) + t,
                moe_logical_axes(cfg.moe),
                is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x),
            )
            axes["full_layers"] = full
        if not cfg.tie_word_embeddings:
            axes["lm_head"] = ("embed", "vocab")
        return axes

    # ---- forward ----

    def __call__(self, params, input_ids, positions=None, segment_ids=None, token_mask=None,
                 rules=None, return_hidden=False, training=True, cache=None):
        cfg, backend = self.config, self.backend
        dtype = backend.jnp_dtype
        B, S = input_ids.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        emit_aux = cfg.moe.aux_loss_coeff > 0 and training and not backend.fake_balanced_gate

        inv_freq = rope_frequencies(
            cfg.head_dim, cfg.rope_theta, cfg.rope_scaling,
            partial_rotary_factor=cfg.partial_rotary_factor,
        )
        attn_scale = rope_attention_scaling(cfg.rope_scaling)

        moe_fwd = make_moe_block_forward(cfg.moe, backend, rules, training=training)

        if cache is not None:
            if segment_ids is None:
                raise ValueError("cache decoding requires segment_ids (1 = real token)")
            h = params["embed"].astype(dtype)[input_ids]
            return self._decode_forward(params, h, positions, segment_ids, cache,
                                        dtype, moe_fwd, inv_freq, attn_scale)

        def unit(fn):
            """A remat unit over one layer's leaves. Handed a stack and the layer's place in
            it, the unit reads the layer inside itself: what the backward pass keeps is the
            stack it was given (a slice the scan made anyway), not a copy of the layer."""
            def run(lp, h, k=None):
                def body(lp, h):
                    return fn(lp if k is None else jax.tree.map(lambda a: a[k], lp), h)

                return backend.layer_remat(body)(lp, h)

            return run

        # a mixer and a MoE block are each a remat unit, inside the scan too: the backward
        # pass holds one DeltaNet mixer's float32 rule OR one MoE block's rows. The mixers
        # carry their own labels and the MoE block stands outside both, so a device trace
        # gives each of the three its own time
        @unit
        @scoped("moe")
        def moe_block(lp, h):
            x = rms_norm(h, lp["mlp_norm"].astype(dtype), cfg.rms_norm_eps, offset=1.0)
            moe_params = cast_moe_compute_params(lp["moe"], dtype)
            y, aux, load, dropped = moe_fwd(moe_params, x, token_mask)
            h = _constrain(h + y, rules, ("batch", "act_seq", "act_embed"))
            return h, (aux if emit_aux else jnp.float32(0), load, dropped)

        @unit
        @scoped("delta_net")
        def delta_mixer(lp, h):
            x = rms_norm(h, lp["attn_norm"].astype(dtype), cfg.rms_norm_eps, offset=1.0)
            if token_mask is not None:
                # conv + recurrence leak across positions: zero padded tokens
                # (HF apply_mask_to_padding_states)
                x = x * token_mask[..., None].astype(x.dtype)
            h = h + self._gated_delta_attn(lp, x, dtype, segment_ids,
                                           mesh=None if rules is None else rules.mesh)
            return _constrain(h, rules, ("batch", "act_seq", "act_embed"))

        @unit
        @scoped("attention")
        def attn_mixer(lp, h):
            x = rms_norm(h, lp["attn_norm"].astype(dtype), cfg.rms_norm_eps, offset=1.0)
            h = h + self._gated_full_attn(lp, x, positions, segment_ids, inv_freq, attn_scale,
                                          dtype, rules=rules)
            return _constrain(h, rules, ("batch", "act_seq", "act_embed"))

        moe_leaves = ("mlp_norm", "moe")  # a unit is handed the leaves it reads, no others

        def layer_of(mixer):
            def block(lp, h, k=None):
                h = mixer({n: v for n, v in lp.items() if n not in moe_leaves}, h, k)
                return moe_block({n: lp[n] for n in moe_leaves}, h, k)

            return block

        linear_block, full_block = layer_of(delta_mixer), layer_of(attn_mixer)

        h = embed_lookup(params["embed"], input_ids, dtype, rules)
        h = _constrain(h, rules, ("batch", "act_seq", "act_embed"))

        P = cfg.period
        lin_params = params.get("linear_layers")
        full_params = params.get("full_layers")
        if P is not None and backend.scan_layers:
            G = cfg.num_hidden_layers // P
            glin = jax.tree.map(lambda a: a.reshape(G, P - 1, *a.shape[1:]), lin_params)
            gfull = jax.tree.map(lambda a: a.reshape(G, *a.shape[1:]), full_params)

            def group_body(h, lp_group):
                gl, gf = lp_group
                ys = []
                for j in range(P - 1):  # the unit reads layer j of the group's stack inside
                    h, y = linear_block(gl, h, j)
                    ys.append(y)
                h, y = full_block(gf, h)
                ys.append(y)
                return h, jax.tree.map(lambda *a: jnp.stack(a), *ys)

            # the scan's own slicing and stacking; blocks carry their labels inside
            with jax.named_scope("layer_stack"):
                h, (auxs, loads, droppeds) = jax.lax.scan(group_body, h, (glin, gfull))
            auxs = auxs.reshape(-1)
            loads = loads.reshape(-1, *loads.shape[2:])
            droppeds = droppeds.reshape(-1)
        else:
            lin_i, full_i = 0, 0
            ys = []
            with jax.named_scope("layer_stack"):  # as the scan's: what is no block's own
                for t in cfg.layer_types:
                    if t == LINEAR:
                        h, y = linear_block(jax.tree.map(lambda a: a[lin_i], lin_params), h)
                        lin_i += 1
                    else:
                        h, y = full_block(jax.tree.map(lambda a: a[full_i], full_params), h)
                        full_i += 1
                    ys.append(y)
            auxs, loads, droppeds = (jnp.stack(a) for a in zip(*ys))

        stats = {"aux_loss": auxs.sum() if emit_aux else None, "expert_load": loads}
        if backend.dispatcher == "a2a":
            stats["dropped_token_frac"] = droppeds.mean()

        # final norm and head are one layer kind in a device trace; the recipe opens the
        # same scope around its loss call
        with jax.named_scope("lm_head_loss"):
            h = rms_norm(h, params["final_norm"].astype(dtype), cfg.rms_norm_eps, offset=1.0)
            if return_hidden:
                return h, stats
            unembed = params.get("lm_head")
            if unembed is None:
                unembed = params["embed"].T
            logits = jnp.einsum("bsd,dv->bsv", h, unembed.astype(dtype))
        return logits, stats

    def _gated_delta_attn(self, lp, x, dtype, segment_ids=None, token_mask=None,
                          conv_state=None, rec_state=None, return_state=False, mesh=None):
        """Gated DeltaNet token mixer (HF Qwen3NextGatedDeltaNet.forward,
        modeling_qwen3_next.py:660-775).

        Packed sequences: the recurrence resets at document boundaries by injecting a
        large negative log-decay at each segment start — within-segment decays are
        differences of cumulative sums, so the injection cancels exactly there and
        zeroes every cross-segment path (state carry, intra-chunk attention, and the
        chunk-state write). The conv masks its cross-segment taps directly.

        Decode: ``conv_state`` ((B, K-1, C) trailing pre-conv inputs) and
        ``rec_state`` ((B, Hv, dk, dv) delta-rule state) continue the recurrence;
        ``return_state=True`` (prefill) extracts both from the prompt.
        ``token_mask`` neutralizes right-padding: pad tokens get decay 1 / write
        strength 0, so the state each row carries out of prefill is exactly its
        last VALID token's. Stateful calls return ``(out, (conv_state, rec_state))``.
        """
        cfg = self.config
        B, S, _ = x.shape
        Hk, dk = cfg.linear_num_key_heads, cfg.linear_key_head_dim
        Hv, dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
        r = Hv // Hk
        K = cfg.linear_conv_kernel_dim

        lin = self.backend.linear
        qkvz = project(x, lp["wqkvz"].astype(dtype), 1, lin)  # (B,S,Hk,2dk+2rdv)
        ba = project(x, lp["wba"].astype(dtype), 1, lin)  # (B,S,Hk,2r)
        q = qkvz[..., :dk]
        k = qkvz[..., dk : 2 * dk]
        v = qkvz[..., 2 * dk : 2 * dk + r * dv].reshape(B, S, Hv, dv)
        z = qkvz[..., 2 * dk + r * dv :].reshape(B, S, Hv, dv)
        b = ba[..., :r].reshape(B, S, Hv)
        a = ba[..., r:].reshape(B, S, Hv)

        beta = jax.nn.sigmoid(b.astype(jnp.float32))
        g = -jnp.exp(lp["a_log"].astype(jnp.float32)) * jax.nn.softplus(
            a.astype(jnp.float32) + lp["dt_bias"].astype(jnp.float32)
        )
        if token_mask is not None:
            valid = token_mask.astype(jnp.float32)[..., None]
            beta = beta * valid  # pad: no write
            g = g * valid  # pad: decay exp(0) = 1, state passes through
        if segment_ids is not None and token_mask is None:
            # -50 in log space ≈ exp(-50) ~ 2e-22: dead past, still fp32-cancellable
            seg_start = jnp.concatenate(
                [jnp.zeros((B, 1), bool), segment_ids[:, 1:] != segment_ids[:, :-1]], axis=1
            )
            g = g + jnp.where(seg_start, -50.0, 0.0)[..., None]

        mixed = jnp.concatenate(
            [q.reshape(B, S, Hk * dk), k.reshape(B, S, Hk * dk), v.reshape(B, S, Hv * dv)], axis=-1
        )
        new_conv = None
        if conv_state is not None:
            conv_out, new_conv = conv_step(conv_state, mixed, lp["conv_w"].astype(dtype))
        else:
            conv_out = causal_conv1d(
                mixed, lp["conv_w"].astype(dtype),
                segment_ids=segment_ids if token_mask is None else None,
            )
            if return_state:
                lens = (token_mask.sum(-1) if token_mask is not None
                        else jnp.full((B,), S, jnp.int32))
                new_conv = conv_state_from_prefill(mixed, lens, K)
        q, k, v = jnp.split(conv_out, [Hk * dk, 2 * Hk * dk], axis=-1)
        # key heads alone: the rule reads key head h // r under value head h
        q, k = q.reshape(B, S, Hk, dk), k.reshape(B, S, Hk, dk)
        v = v.reshape(B, S, Hv, dv)

        stateful = return_state or rec_state is not None
        with jax.named_scope("delta_rule"):  # the recurrence alone: what a kernel replaces
            core, final = chunk_gated_delta_rule(
                q, k, v, g, beta, chunk_size=min(64, S),
                initial_state=rec_state, output_final_state=stateful, mesh=mesh,
            )
        core = gated_rms_norm(core, lp["norm"].astype(dtype), z, cfg.rms_norm_eps)
        out = project(core, lp["wo"].astype(dtype), 2, lin)
        if stateful:
            return out, (new_conv, final)
        return out

    def _gated_full_attn(self, lp, x, positions, segment_ids, inv_freq, attn_scale, dtype,
                         kv=None, cache_meta=None, rules=None):
        """Full attention with per-head sigmoid output gate (reference
        qwen3_next/layers.py:95-153). With ``kv=(k_cache, v_cache)`` (decode) the
        fresh k/v write into the cache and attention runs position-masked against
        it; returns ``(out, (k_cache, v_cache))``."""
        cfg = self.config
        dh = cfg.head_dim
        lin = self.backend.linear
        qg = project(x, lp["wq"].astype(dtype), 1, lin)
        q, gate = qg[..., :dh], qg[..., dh:]
        k = project(x, lp["wk"].astype(dtype), 1, lin)
        v = project(x, lp["wv"].astype(dtype), 1, lin)
        q = rms_norm(q, lp["q_norm"].astype(dtype), cfg.rms_norm_eps, offset=1.0)
        k = rms_norm(k, lp["k_norm"].astype(dtype), cfg.rms_norm_eps, offset=1.0)
        q = apply_rope(q, positions, inv_freq, attn_scale)
        k = apply_rope(k, positions, inv_freq, attn_scale)
        if kv is not None:
            from automodel_tpu.models.common.transformer import _cache_write

            k_cache = _cache_write(kv[0], k.astype(kv[0].dtype), cache_meta["write_idx"])
            v_cache = _cache_write(kv[1], v.astype(kv[1].dtype), cache_meta["write_idx"])
            attn = dot_product_attention(
                q, k_cache.astype(q.dtype), v_cache.astype(q.dtype),
                causal=True,
                segment_ids_q=segment_ids,
                segment_ids_kv=cache_meta["valid"],
                positions_q=positions,
                positions_kv=cache_meta["positions"],
                backend="xla",
            )
            attn = attn * jax.nn.sigmoid(gate)
            return project(attn, lp["wo"].astype(dtype), 2, lin), (k_cache, v_cache)
        attn = sharded_attention(
            q, k, v,
            rules=rules,
            causal=True,
            segment_ids_q=segment_ids,
            backend=self.backend.attention,
        )
        attn = attn * jax.nn.sigmoid(gate)
        return project(attn, lp["wo"].astype(dtype), 2, lin)

    # ---- decode ----

    def init_decode_cache(self, batch_size: int, max_len: int, dtype=jnp.bfloat16) -> dict:
        """Hybrid decode cache: KV for the full-attention layers, conv taps +
        delta-rule state (fp32 — the recurrence compounds rounding) for the
        DeltaNet layers. positions/valid/write_idx follow the generation loop's
        shared-contract (generation.init_kv_cache)."""
        cfg = self.config
        Lf = len(cfg.full_layer_indices)
        Ll = len(cfg.linear_layer_indices)
        Hv, dk, dv = cfg.linear_num_value_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim
        return {
            "k": jnp.zeros((Lf, batch_size, max_len, cfg.num_key_value_heads, cfg.head_dim), dtype),
            "v": jnp.zeros((Lf, batch_size, max_len, cfg.num_key_value_heads, cfg.head_dim), dtype),
            "conv": jnp.zeros((Ll, batch_size, cfg.linear_conv_kernel_dim - 1, cfg.conv_dim), dtype),
            "rec": jnp.zeros((Ll, batch_size, Hv, dk, dv), jnp.float32),
            "positions": jnp.zeros((batch_size, max_len), jnp.int32),
            "valid": jnp.zeros((batch_size, max_len), jnp.int32),
            "write_idx": jnp.zeros((batch_size,), jnp.int32),
        }

    def _decode_forward(self, params, h, positions, segment_ids, cache, dtype,
                        moe_fwd, inv_freq, attn_scale):
        """Unrolled cached forward (prefill S>1, decode S=1). Layer scanning is
        skipped: decode shapes are tiny and the per-kind cache threading (kv vs
        conv+rec) is simplest unrolled."""
        cfg = self.config
        S = h.shape[1]
        token_mask = segment_ids != 0
        cache_meta = {"write_idx": cache["write_idx"], "valid": cache["valid"],
                      "positions": cache["positions"]}
        lin_params = params.get("linear_layers")
        full_params = params.get("full_layers")
        k_all, v_all = cache["k"], cache["v"]
        conv_all, rec_all = cache["conv"], cache["rec"]
        lin_i = full_i = 0
        for t in cfg.layer_types:
            if t == LINEAR:
                lp = jax.tree.map(lambda a, i=lin_i: a[i], lin_params)
                x = rms_norm(h, lp["attn_norm"].astype(dtype), cfg.rms_norm_eps, offset=1.0)
                x = x * token_mask[..., None].astype(x.dtype)
                out, (nc, nr) = self._gated_delta_attn(
                    lp, x, dtype, token_mask=token_mask,
                    conv_state=(conv_all[lin_i] if S == 1 else None),
                    rec_state=rec_all[lin_i], return_state=True,
                )
                conv_all = conv_all.at[lin_i].set(nc.astype(conv_all.dtype))
                rec_all = rec_all.at[lin_i].set(nr)
                h = h + out
                lin_i += 1
            else:
                lp = jax.tree.map(lambda a, i=full_i: a[i], full_params)
                x = rms_norm(h, lp["attn_norm"].astype(dtype), cfg.rms_norm_eps, offset=1.0)
                out, (kc, vc) = self._gated_full_attn(
                    lp, x, positions, segment_ids, inv_freq, attn_scale, dtype,
                    kv=(k_all[full_i], v_all[full_i]), cache_meta=cache_meta,
                )
                k_all = k_all.at[full_i].set(kc)
                v_all = v_all.at[full_i].set(vc)
                h = h + out
                full_i += 1
            x = rms_norm(h, lp["mlp_norm"].astype(dtype), cfg.rms_norm_eps, offset=1.0)
            moe_params = cast_moe_compute_params(lp["moe"], dtype)
            y, _, _, _ = moe_fwd(moe_params, x, token_mask)
            h = h + y
        h = rms_norm(h, params["final_norm"].astype(dtype), cfg.rms_norm_eps, offset=1.0)
        # next-token logits only (B, 1, V)
        last = jnp.maximum(segment_ids.sum(-1) - 1, 0).astype(jnp.int32)
        h = jnp.take_along_axis(h, last[:, None, None], axis=1)
        unembed = params.get("lm_head")
        if unembed is None:
            unembed = params["embed"].T
        logits = jnp.einsum("bsd,dv->bsv", h, unembed.astype(dtype))
        return logits, dict(cache, k=k_all, v=v_all, conv=conv_all, rec=rec_all)

    def generate(self, params, input_ids, **kw):
        """Sample with the hybrid conv+recurrence+KV cache (automodel_tpu.generation)."""
        from automodel_tpu.generation import generate

        return generate(self, params, input_ids, **kw)

    # ---- interop ----

    def state_dict_adapter(self):
        from automodel_tpu.models.qwen3_next.state_dict_adapter import Qwen3NextStateDictAdapter

        return Qwen3NextStateDictAdapter(self.config)

    @classmethod
    def from_config(cls, config, backend: BackendConfig | None = None):
        if isinstance(config, dict):
            config = Qwen3NextConfig.from_hf(config)
        return cls(config, backend)
