"""NemotronV3 / Nemotron-H HF mapping (reference nemotron_v3/state_dict_adapter.py).

HF layout uses a ``backbone.`` prefix, ``norm_f`` for the final norm, ``mixer`` for
every block's single sub-module, and per-expert ReLU² weights
(``mixer.experts.{e}.up_proj`` — no gate_proj). Our four per-type streams pin
explicit ``layer_indices``. A LatentMoE's two projections are ``mixer.fc1_latent_proj``
(hidden -> latent) and ``mixer.fc2_latent_proj`` (latent -> hidden).
"""

from __future__ import annotations

import numpy as np

from automodel_tpu.models.common.state_dict import Entry, MappingAdapter
from automodel_tpu.models.llama.state_dict_adapter import (
    _bias_in,
    _bias_out,
    _o_in,
    _o_out,
    _proj_in,
    _proj_out,
    _t,
)

__all__ = ["NemotronV3StateDictAdapter"]


def _conv_in(w: np.ndarray) -> np.ndarray:
    return w[:, 0, :]


def _conv_out(w: np.ndarray) -> np.ndarray:
    return w[:, None, :]


class NemotronV3StateDictAdapter(MappingAdapter):
    def __init__(self, cfg):
        pre = "backbone.layers.{i}"
        entries = [
            Entry("backbone.embed_tokens.weight", "embed"),
            Entry("backbone.norm_f.weight", "final_norm"),
        ]
        if not cfg.tie_word_embeddings:
            entries.append(Entry("lm_head.weight", "lm_head", _t, _t))

        for t, stream in (("mamba", "mamba_layers"), ("attention", "attn_layers"),
                          ("mlp", "mlp_layers"), ("moe", "moe_layers")):
            idx = cfg.type_indices(t)
            if not idx:
                continue
            entries.append(Entry(f"{pre}.norm.weight", f"{stream}.norm", layer_indices=idx))
            if t == "mamba":
                entries += [
                    Entry(f"{pre}.mixer.in_proj.weight", f"{stream}.in_proj", _t, _t, layer_indices=idx),
                    Entry(f"{pre}.mixer.conv1d.weight", f"{stream}.conv_w", _conv_in, _conv_out, layer_indices=idx),
                    Entry(f"{pre}.mixer.dt_bias", f"{stream}.dt_bias", layer_indices=idx),
                    Entry(f"{pre}.mixer.A_log", f"{stream}.a_log",
                          to_ours=lambda x: x.astype(np.float32), keep_dtype=True, layer_indices=idx),
                    Entry(f"{pre}.mixer.D", f"{stream}.d_skip", layer_indices=idx),
                    Entry(f"{pre}.mixer.norm.weight", f"{stream}.gated_norm", layer_indices=idx),
                    Entry(f"{pre}.mixer.out_proj.weight", f"{stream}.out_proj", _t, _t, layer_indices=idx),
                ]
                if cfg.use_conv_bias:
                    entries.append(Entry(f"{pre}.mixer.conv1d.bias", f"{stream}.b_conv", layer_indices=idx))
                if cfg.use_bias:
                    entries += [
                        Entry(f"{pre}.mixer.in_proj.bias", f"{stream}.b_in", layer_indices=idx),
                        Entry(f"{pre}.mixer.out_proj.bias", f"{stream}.b_out", layer_indices=idx),
                    ]
            elif t == "attention":
                n, kv, dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
                entries += [
                    Entry(f"{pre}.mixer.q_proj.weight", f"{stream}.wq", _proj_in(n, dh), _proj_out(n, dh), layer_indices=idx),
                    Entry(f"{pre}.mixer.k_proj.weight", f"{stream}.wk", _proj_in(kv, dh), _proj_out(kv, dh), layer_indices=idx),
                    Entry(f"{pre}.mixer.v_proj.weight", f"{stream}.wv", _proj_in(kv, dh), _proj_out(kv, dh), layer_indices=idx),
                    Entry(f"{pre}.mixer.o_proj.weight", f"{stream}.wo", _o_in(n, dh), _o_out(n, dh), layer_indices=idx),
                ]
                if cfg.attention_bias:
                    entries += [
                        Entry(f"{pre}.mixer.q_proj.bias", f"{stream}.bq", _bias_in(n, dh), _bias_out(n, dh), layer_indices=idx),
                        Entry(f"{pre}.mixer.k_proj.bias", f"{stream}.bk", _bias_in(kv, dh), _bias_out(kv, dh), layer_indices=idx),
                        Entry(f"{pre}.mixer.v_proj.bias", f"{stream}.bv", _bias_in(kv, dh), _bias_out(kv, dh), layer_indices=idx),
                        Entry(f"{pre}.mixer.o_proj.bias", f"{stream}.bo", layer_indices=idx),
                    ]
            elif t == "mlp":
                entries += [
                    Entry(f"{pre}.mixer.up_proj.weight", f"{stream}.w_up", _t, _t, layer_indices=idx),
                    Entry(f"{pre}.mixer.down_proj.weight", f"{stream}.w_down", _t, _t, layer_indices=idx),
                ]
                if cfg.mlp_bias:
                    entries += [
                        Entry(f"{pre}.mixer.up_proj.bias", f"{stream}.b_up", layer_indices=idx),
                        Entry(f"{pre}.mixer.down_proj.bias", f"{stream}.b_down", layer_indices=idx),
                    ]
            else:  # moe
                entries += [
                    Entry(f"{pre}.mixer.gate.weight", f"{stream}.moe.gate.weight", layer_indices=idx),
                    Entry(f"{pre}.mixer.gate.e_score_correction_bias",
                          f"{stream}.moe.gate.score_correction_bias",
                          to_ours=lambda b: b.astype(np.float32),
                          optional=True, keep_dtype=True, layer_indices=idx),
                    # ReLU² experts: up only (E, D, I); HF stores (I, D) per expert
                    Entry(f"{pre}.mixer.experts.{{e}}.up_proj.weight",
                          f"{stream}.moe.experts.gate_up_proj", _t, _t, layer_indices=idx),
                    Entry(f"{pre}.mixer.experts.{{e}}.down_proj.weight",
                          f"{stream}.moe.experts.down_proj", _t, _t, layer_indices=idx),
                    Entry(f"{pre}.mixer.shared_experts.up_proj.weight",
                          f"{stream}.moe.shared_experts.w_up", _t, _t, layer_indices=idx),
                    Entry(f"{pre}.mixer.shared_experts.down_proj.weight",
                          f"{stream}.moe.shared_experts.w_down", _t, _t, layer_indices=idx),
                ]
                if cfg.moe.latent_dim:
                    # LatentMoE (Nemotron-3): hidden -> latent before the routed experts,
                    # latent -> hidden after the combine (HF fc1_latent_proj / fc2_latent_proj)
                    entries += [
                        Entry(f"{pre}.mixer.fc1_latent_proj.weight",
                              f"{stream}.moe.latent.w_down", _t, _t, layer_indices=idx),
                        Entry(f"{pre}.mixer.fc2_latent_proj.weight",
                              f"{stream}.moe.latent.w_up", _t, _t, layer_indices=idx),
                    ]
                if cfg.moe.expert_bias:
                    entries += [
                        Entry(f"{pre}.mixer.experts.{{e}}.up_proj.bias",
                              f"{stream}.moe.experts.gate_up_bias", layer_indices=idx),
                        Entry(f"{pre}.mixer.experts.{{e}}.down_proj.bias",
                              f"{stream}.moe.experts.down_bias", layer_indices=idx),
                    ]

        super().__init__(
            entries, cfg.num_hidden_layers,
            num_experts=cfg.moe.held_experts if cfg.moe else 0,
        )
