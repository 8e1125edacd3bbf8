"""Falcon-H1 HF mapping (``modeling_falcon_h1.py``'s module names): a block's norms are
``input_layernorm`` and ``pre_ff_layernorm``, its three sub-modules ``mamba``,
``self_attn`` and ``feed_forward``, the final norm ``model.final_layernorm``. HF linear
weights are (out, in); the depthwise conv is (channels, 1, taps)."""

from __future__ import annotations

import numpy as np

from automodel_tpu.models.common.state_dict import Entry, MappingAdapter
from automodel_tpu.models.llama.state_dict_adapter import _o_in, _o_out, _proj_in, _proj_out, _t
from automodel_tpu.models.nemotron_v3.state_dict_adapter import _conv_in, _conv_out

__all__ = ["FalconH1StateDictAdapter"]


class FalconH1StateDictAdapter(MappingAdapter):
    def __init__(self, cfg):
        pre = "model.layers.{i}"
        n, kv, dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        entries = [
            Entry("model.embed_tokens.weight", "embed"),
            Entry("model.final_layernorm.weight", "final_norm"),
            Entry(f"{pre}.input_layernorm.weight", "layers.input_norm"),
            Entry(f"{pre}.pre_ff_layernorm.weight", "layers.mlp_norm"),
            Entry(f"{pre}.mamba.in_proj.weight", "layers.in_proj", _t, _t),
            Entry(f"{pre}.mamba.conv1d.weight", "layers.conv_w", _conv_in, _conv_out),
            Entry(f"{pre}.mamba.dt_bias", "layers.dt_bias"),
            Entry(f"{pre}.mamba.A_log", "layers.a_log",
                  to_ours=lambda x: x.astype(np.float32), keep_dtype=True),
            Entry(f"{pre}.mamba.D", "layers.d_skip"),
            Entry(f"{pre}.mamba.norm.weight", "layers.gated_norm"),
            Entry(f"{pre}.mamba.out_proj.weight", "layers.out_proj", _t, _t),
            Entry(f"{pre}.self_attn.q_proj.weight", "layers.wq", _proj_in(n, dh), _proj_out(n, dh)),
            Entry(f"{pre}.self_attn.k_proj.weight", "layers.wk", _proj_in(kv, dh), _proj_out(kv, dh)),
            Entry(f"{pre}.self_attn.v_proj.weight", "layers.wv", _proj_in(kv, dh), _proj_out(kv, dh)),
            Entry(f"{pre}.self_attn.o_proj.weight", "layers.wo", _o_in(n, dh), _o_out(n, dh)),
            Entry(f"{pre}.feed_forward.gate_proj.weight", "layers.w_gate", _t, _t),
            Entry(f"{pre}.feed_forward.up_proj.weight", "layers.w_up", _t, _t),
            Entry(f"{pre}.feed_forward.down_proj.weight", "layers.w_down", _t, _t),
        ]
        if cfg.mamba_conv_bias:
            entries.append(Entry(f"{pre}.mamba.conv1d.bias", "layers.b_conv"))
        if not cfg.tie_word_embeddings:
            entries.append(Entry("lm_head.weight", "lm_head", _t, _t))
        super().__init__(entries, cfg.num_hidden_layers)
