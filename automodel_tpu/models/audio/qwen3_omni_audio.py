"""Qwen3-Omni audio encoder — TPU-native (HF Qwen3OmniMoeAudioEncoder,
transformers modeling_qwen3_omni_moe.py:636; the reference keeps HF's towers and
swaps only the thinker text stack, reference models/qwen3_omni_moe/model.py).

Whisper-style mel encoder: per-audio mel streams chunk into ``2*n_window``-frame
windows, three stride-2 Conv2d+GELU stages downsample 8x in time, a linear folds
(channels x mel/8) per frame, sinusoid positions add per within-chunk frame, then
pre-norm attention layers run with *windowed* bidirectional attention
(``n_window_infer`` frames per attention block) and a GELU head projects to the
text width.

TPU-first contract: chunk padding, the valid-frame gather and window segment ids
are host-side numpy (``prepare_audio_inputs``); the device function sees only
static-shaped arrays — the convs run on the padded (num_chunks, mel, chunk) block
and validity is a single gather.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp

from automodel_tpu.models.common.backend import BackendConfig
from automodel_tpu.ops.attention import sharded_attention
from automodel_tpu.ops.norms import layer_norm

__all__ = ["Qwen3OmniAudioConfig", "init_audio_params", "audio_logical_axes",
           "audio_forward", "prepare_audio_inputs", "audio_output_lengths"]


@dataclasses.dataclass
class Qwen3OmniAudioConfig:
    num_mel_bins: int = 128
    d_model: int = 1280
    encoder_layers: int = 32
    encoder_attention_heads: int = 20
    encoder_ffn_dim: int = 5120
    downsample_hidden_size: int = 480
    output_dim: int = 2048
    n_window: int = 50
    n_window_infer: int = 400
    max_source_positions: int = 1500
    activation_function: str = "gelu"
    initializer_range: float = 0.02

    @classmethod
    def from_hf(cls, hf: dict[str, Any]) -> "Qwen3OmniAudioConfig":
        keys = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in hf.items() if k in keys})

    @property
    def head_dim(self) -> int:
        return self.d_model // self.encoder_attention_heads

    @property
    def chunk_len(self) -> int:
        return 2 * self.n_window

    @property
    def conv_freq_out(self) -> int:
        f = self.num_mel_bins
        for _ in range(3):
            f = (f + 1) // 2
        return f


def _conv_out_len(n):
    """Elementwise 3x (k=3, s=2, p=1) conv output length: ceil-halving applied 3x."""
    for _ in range(3):
        n = (n + 1) // 2
    return n


def audio_output_lengths(input_lengths: np.ndarray, chunk_len: int = 100) -> np.ndarray:
    """Per-audio encoder output frame count: full chunks contribute
    conv_out(chunk_len) frames, the tail contributes conv_out(tail). Equals HF's
    _get_feat_extract_output_lengths (modeling_qwen3_omni_moe.py:79-87) for the
    shipped 100-frame chunking; computed from the actual conv math here so it stays
    consistent with prepare_audio_inputs for any chunk_len."""
    input_lengths = np.asarray(input_lengths)
    tail = input_lengths % chunk_len
    tail_out = np.where(tail > 0, _conv_out_len(tail), 0)
    return (input_lengths // chunk_len) * _conv_out_len(chunk_len) + tail_out


def init_audio_params(cfg: Qwen3OmniAudioConfig, key: jax.Array, dtype=jnp.float32) -> dict:
    std = cfg.initializer_range
    d, f, L = cfg.d_model, cfg.encoder_ffn_dim, cfg.encoder_layers
    ch = cfg.downsample_hidden_size
    keys = iter(jax.random.split(key, 12))

    def w(shape):
        return (jax.random.normal(next(keys), shape, jnp.float32) * std).astype(dtype)

    ks = jax.random.split(next(keys), 6)
    mk = lambda kk, shape: (jax.random.normal(kk, (L, *shape), jnp.float32) * std).astype(dtype)
    layers = {
        "attn_ln_w": jnp.ones((L, d), dtype), "b_attn_ln": jnp.zeros((L, d), dtype),
        "wq": mk(ks[0], (d, d)), "b_q": jnp.zeros((L, d), dtype),
        "wk": mk(ks[1], (d, d)), "b_k": jnp.zeros((L, d), dtype),
        "wv": mk(ks[2], (d, d)), "b_v": jnp.zeros((L, d), dtype),
        "wo": mk(ks[3], (d, d)), "b_o": jnp.zeros((L, d), dtype),
        "final_ln_w": jnp.ones((L, d), dtype), "b_final_ln": jnp.zeros((L, d), dtype),
        "fc1": mk(ks[4], (d, f)), "b_fc1": jnp.zeros((L, f), dtype),
        "fc2": mk(ks[5], (f, d)), "b_fc2": jnp.zeros((L, d), dtype),
    }
    return {
        # conv weights kept in HF Conv2d layout (out, in, 3, 3)
        "conv1_w": w((ch, 1, 3, 3)), "b_conv1": jnp.zeros((ch,), dtype),
        "conv2_w": w((ch, ch, 3, 3)), "b_conv2": jnp.zeros((ch,), dtype),
        "conv3_w": w((ch, ch, 3, 3)), "b_conv3": jnp.zeros((ch,), dtype),
        "conv_out_w": w((ch * cfg.conv_freq_out, d)),
        "layers": layers,
        "post_ln_w": jnp.ones((d,), dtype), "b_post_ln": jnp.zeros((d,), dtype),
        "proj1_w": w((d, d)), "b_proj1": jnp.zeros((d,), dtype),
        "proj2_w": w((d, cfg.output_dim)), "b_proj2": jnp.zeros((cfg.output_dim,), dtype),
    }


def audio_logical_axes(cfg: Qwen3OmniAudioConfig) -> dict:
    return {
        "conv1_w": (None, None, None, None), "b_conv1": ("norm",),
        "conv2_w": (None, None, None, None), "b_conv2": ("norm",),
        "conv3_w": (None, None, None, None), "b_conv3": ("norm",),
        "conv_out_w": (None, "embed"),
        "layers": {
            "attn_ln_w": ("layers", "norm"), "b_attn_ln": ("layers", "norm"),
            "wq": ("layers", "embed", "heads"), "b_q": ("layers", "heads"),
            "wk": ("layers", "embed", "heads"), "b_k": ("layers", "heads"),
            "wv": ("layers", "embed", "heads"), "b_v": ("layers", "heads"),
            "wo": ("layers", "heads", "embed"), "b_o": ("layers", "norm"),
            "final_ln_w": ("layers", "norm"), "b_final_ln": ("layers", "norm"),
            "fc1": ("layers", "embed", "mlp"), "b_fc1": ("layers", "mlp"),
            "fc2": ("layers", "mlp", "embed"), "b_fc2": ("layers", "norm"),
        },
        "post_ln_w": ("norm",), "b_post_ln": ("norm",),
        "proj1_w": ("embed", "mlp"), "b_proj1": ("norm",),
        "proj2_w": ("embed", "mlp"), "b_proj2": ("norm",),
    }


def prepare_audio_inputs(
    features: "list[np.ndarray]",  # per-audio mel (num_mel_bins, T)
    cfg: Qwen3OmniAudioConfig,
) -> dict[str, np.ndarray]:
    """Chunk + pad each audio's mel frames into (num_chunks, mel, chunk_len) and
    precompute the valid-frame gather and windowed-attention segment ids (HF
    cu_seqlens construction, modeling_qwen3_omni_moe.py:714-759)."""
    C = cfg.chunk_len
    if cfg.n_window_infer % C:
        raise ValueError(
            f"n_window_infer ({cfg.n_window_infer}) must be a multiple of the "
            f"chunk length 2*n_window ({C})"
        )
    chunks, gather, seg = [], [], []
    chunk_base = 0
    seg_id = 0
    t_out = _conv_out_len(C)
    win_frames = t_out * (cfg.n_window_infer // C)
    for mel in features:
        T = mel.shape[1]
        n_chunks = math.ceil(T / C)
        frames_this = 0
        for ci in range(n_chunks):
            part = mel[:, ci * C : (ci + 1) * C]
            valid = part.shape[1]
            if valid < C:
                part = np.pad(part, ((0, 0), (0, C - valid)))
            chunks.append(part)
            v_out = _conv_out_len(valid)
            gather.append((chunk_base + ci) * t_out + np.arange(v_out))
            frames_this += v_out
        chunk_base += n_chunks
        # windowed attention blocks of win_frames over this audio's frames
        n_full, rem = divmod(frames_this, win_frames)
        for _ in range(n_full):
            seg.append(np.full(win_frames, seg_id, np.int32))
            seg_id += 1
        if rem:
            seg.append(np.full(rem, seg_id, np.int32))
            seg_id += 1
    return {
        "chunks": np.stack(chunks).astype(np.float32),  # (N, mel, C)
        "gather_idx": np.concatenate(gather).astype(np.int32),  # (Ta,)
        "segment_ids": np.concatenate(seg),  # (Ta,)
    }


def audio_forward(
    cfg: Qwen3OmniAudioConfig,
    backend: BackendConfig,
    params: dict,
    chunks: jnp.ndarray,  # (N, mel, chunk_len)
    gather_idx: jnp.ndarray,  # (Ta,)
    segment_ids: jnp.ndarray,  # (Ta,)
    rules=None,
) -> jnp.ndarray:
    """Returns encoded audio tokens (Ta, output_dim)."""
    dtype = backend.jnp_dtype
    d, H, dh = cfg.d_model, cfg.encoder_attention_heads, cfg.head_dim
    p = jax.tree.map(lambda a: a.astype(dtype) if a.dtype != jnp.int32 else a, params)

    x = chunks.astype(dtype)[:, None]  # (N, 1, mel, C)
    for i in (1, 2, 3):
        x = jax.lax.conv_general_dilated(
            x, p[f"conv{i}_w"], window_strides=(2, 2), padding=[(1, 1), (1, 1)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
        ) + p[f"b_conv{i}"][None, :, None, None]
        x = jax.nn.gelu(x, approximate=False)
    N, ch, fr, t_out = x.shape
    x = x.transpose(0, 3, 1, 2).reshape(N, t_out, ch * fr) @ p["conv_out_w"]

    # sinusoid positions per within-chunk frame (HF SinusoidsPositionEmbedding)
    half = d // 2
    inv = jnp.exp(-math.log(10000) / (half - 1) * jnp.arange(half, dtype=jnp.float32))
    ang = jnp.arange(t_out, dtype=jnp.float32)[:, None] * inv[None, :]
    pos = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=1).astype(dtype)
    x = x + pos[None]

    h = x.reshape(N * t_out, d)[gather_idx]
    seg = segment_ids[None]

    def layer_fn(hh, lp):
        x_ = layer_norm(hh, lp["attn_ln_w"], lp["b_attn_ln"])
        q = (x_ @ lp["wq"] + lp["b_q"]).reshape(-1, H, dh)
        k = (x_ @ lp["wk"] + lp["b_k"]).reshape(-1, H, dh)
        v = (x_ @ lp["wv"] + lp["b_v"]).reshape(-1, H, dh)
        attn = sharded_attention(
            q[None], k[None], v[None], rules=rules, causal=False,
            segment_ids_q=seg, segment_ids_kv=seg, backend=backend.attention,
        )[0].reshape(-1, d)
        hh = hh + (attn @ lp["wo"] + lp["b_o"])
        x_ = layer_norm(hh, lp["final_ln_w"], lp["b_final_ln"])
        hh = hh + (jax.nn.gelu(x_ @ lp["fc1"] + lp["b_fc1"], approximate=False) @ lp["fc2"] + lp["b_fc2"])
        return hh, None

    h, _ = jax.lax.scan(backend.layer_remat(layer_fn), h, p["layers"])
    h = layer_norm(h, p["post_ln_w"], p["b_post_ln"])
    h = jax.nn.gelu(h @ p["proj1_w"] + p["b_proj1"], approximate=False)
    return h @ p["proj2_w"] + p["b_proj2"]
