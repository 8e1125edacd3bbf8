"""Qwen3-Next HF key/layout mapping (reference models/qwen3_next/state_dict_adapter.py).

Hybrid layer streams: HF indexes layers 0..L-1 with interleaved linear/full attention;
ours stacks each stream separately, so every per-layer entry pins explicit
``layer_indices``. The fused HF projections (in_proj_qkvz, in_proj_ba, q_proj with its
output gate) stay fused as single leaves — transforms are pure transposes/reshapes.
"""

from __future__ import annotations

import numpy as np

from automodel_tpu.models.common.state_dict import Entry, MappingAdapter
from automodel_tpu.models.llama.state_dict_adapter import _o_in, _o_out, _proj_in, _proj_out, _t
from automodel_tpu.models.qwen3_moe.state_dict_adapter import moe_expert_entries

__all__ = ["Qwen3NextStateDictAdapter"]


def _fused_in(heads: int):
    """HF (heads*M, D) -> ours (D, heads, M)."""

    def f(w: np.ndarray) -> np.ndarray:
        return np.ascontiguousarray(w.T).reshape(w.shape[1], heads, -1)

    return f


def _fused_out(w: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(w.reshape(w.shape[0], -1).T)


def _conv_in(w: np.ndarray) -> np.ndarray:
    return w[:, 0, :]  # (C, 1, K) -> (C, K)


def _conv_out(w: np.ndarray) -> np.ndarray:
    return w[:, None, :]


class Qwen3NextStateDictAdapter(MappingAdapter):
    def __init__(self, cfg):
        self.cfg = cfg
        lin_idx, full_idx = cfg.linear_layer_indices, cfg.full_layer_indices
        Hk = cfg.linear_num_key_heads
        H, Hkv, dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        pre = "model.layers.{i}"

        entries = [
            Entry("model.embed_tokens.weight", "embed"),
            Entry("model.norm.weight", "final_norm"),
        ]
        if not cfg.tie_word_embeddings:
            entries.append(Entry("lm_head.weight", "lm_head", _t, _t))

        def stream(ours_prefix: str, idx: tuple[int, ...]) -> list[Entry]:
            out = [
                Entry(f"{pre}.input_layernorm.weight", f"{ours_prefix}.attn_norm", layer_indices=idx),
                Entry(f"{pre}.post_attention_layernorm.weight", f"{ours_prefix}.mlp_norm", layer_indices=idx),
                Entry(f"{pre}.mlp.gate.weight", f"{ours_prefix}.moe.gate.weight", layer_indices=idx),
                Entry(f"{pre}.mlp.shared_expert.gate_proj.weight",
                      f"{ours_prefix}.moe.shared_experts.w_gate", _t, _t, layer_indices=idx),
                Entry(f"{pre}.mlp.shared_expert.up_proj.weight",
                      f"{ours_prefix}.moe.shared_experts.w_up", _t, _t, layer_indices=idx),
                Entry(f"{pre}.mlp.shared_expert.down_proj.weight",
                      f"{ours_prefix}.moe.shared_experts.w_down", _t, _t, layer_indices=idx),
                Entry(f"{pre}.mlp.shared_expert_gate.weight",
                      f"{ours_prefix}.moe.shared_expert_gate", _t, _t, layer_indices=idx),
            ]
            for e in moe_expert_entries(f"{pre}.mlp", f"{ours_prefix}.moe"):
                out.append(Entry(e.hf, e.ours, e.to_ours, e.to_hf, layer_indices=idx))
            return out

        if lin_idx:
            entries += stream("linear_layers", lin_idx)
            entries += [
                Entry(f"{pre}.linear_attn.in_proj_qkvz.weight", "linear_layers.wqkvz",
                      _fused_in(Hk), _fused_out, layer_indices=lin_idx),
                Entry(f"{pre}.linear_attn.in_proj_ba.weight", "linear_layers.wba",
                      _fused_in(Hk), _fused_out, layer_indices=lin_idx),
                Entry(f"{pre}.linear_attn.conv1d.weight", "linear_layers.conv_w",
                      _conv_in, _conv_out, layer_indices=lin_idx),
                Entry(f"{pre}.linear_attn.dt_bias", "linear_layers.dt_bias", layer_indices=lin_idx),
                # decay logs stay fp32 like init() (bf16 rounding perturbs every step
                # of the recurrence; same precedent as DSv3's score_correction_bias)
                Entry(f"{pre}.linear_attn.A_log", "linear_layers.a_log",
                      to_ours=lambda x: x.astype(np.float32),
                      keep_dtype=True, layer_indices=lin_idx),
                Entry(f"{pre}.linear_attn.norm.weight", "linear_layers.norm", layer_indices=lin_idx),
                Entry(f"{pre}.linear_attn.out_proj.weight", "linear_layers.wo",
                      _o_in(cfg.linear_num_value_heads, cfg.linear_value_head_dim),
                      _o_out(cfg.linear_num_value_heads, cfg.linear_value_head_dim),
                      layer_indices=lin_idx),
            ]
        if full_idx:
            entries += stream("full_layers", full_idx)
            entries += [
                Entry(f"{pre}.self_attn.q_proj.weight", "full_layers.wq",
                      _fused_in(H), _fused_out, layer_indices=full_idx),
                Entry(f"{pre}.self_attn.k_proj.weight", "full_layers.wk",
                      _proj_in(Hkv, dh), _proj_out(Hkv, dh), layer_indices=full_idx),
                Entry(f"{pre}.self_attn.v_proj.weight", "full_layers.wv",
                      _proj_in(Hkv, dh), _proj_out(Hkv, dh), layer_indices=full_idx),
                Entry(f"{pre}.self_attn.o_proj.weight", "full_layers.wo",
                      _o_in(H, dh), _o_out(H, dh), layer_indices=full_idx),
                Entry(f"{pre}.self_attn.q_norm.weight", "full_layers.q_norm", layer_indices=full_idx),
                Entry(f"{pre}.self_attn.k_norm.weight", "full_layers.k_norm", layer_indices=full_idx),
            ]

        super().__init__(entries, cfg.num_hidden_layers, num_experts=cfg.moe.held_experts)
