"""Qwen3-VL vision tower — TPU-native (HF Qwen3VLMoeVisionModel,
transformers modeling_qwen3_vl_moe.py:617; the reference reuses the HF tower and
swaps only the text stack, reference models/qwen3_vl_moe/model.py:101).

TPU-first contract: all data-dependent bookkeeping — 2D rope position ids, bilinear
pos-embed interpolation indices/weights, per-frame attention segment ids — is computed
host-side by ``prepare_vision_inputs`` (numpy, from ``grid_thw``), so the device
function sees only static-shaped arrays. The Conv3D patch embed collapses to one
matmul (kernel == stride), and per-frame varlen attention becomes segment-id masking
in the shared ``dot_product_attention``.

Token order is the Qwen processor's merge-unit order: (t, block_row, block_col,
intra_row, intra_col), so the spatial mergers are plain reshapes.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp

from automodel_tpu.models.common.backend import BackendConfig
from automodel_tpu.ops.attention import sharded_attention
from automodel_tpu.ops.norms import layer_norm
from automodel_tpu.ops.rope import apply_rope_angles, rope_frequencies

__all__ = ["Qwen3VLVisionConfig", "init_vision_params", "vision_logical_axes",
           "vision_forward", "prepare_vision_inputs"]


@dataclasses.dataclass
class Qwen3VLVisionConfig:
    depth: int = 27
    hidden_size: int = 1152
    intermediate_size: int = 4304
    num_heads: int = 16
    in_channels: int = 3
    patch_size: int = 16
    spatial_merge_size: int = 2
    temporal_patch_size: int = 2
    out_hidden_size: int = 3584
    num_position_embeddings: int = 2304
    deepstack_visual_indexes: tuple[int, ...] = (8, 16, 24)
    hidden_act: str = "gelu_pytorch_tanh"
    initializer_range: float = 0.02

    def __post_init__(self):
        # the segmented forward scan taps deepstack features in index order
        if list(self.deepstack_visual_indexes) != sorted(self.deepstack_visual_indexes):
            raise ValueError("deepstack_visual_indexes must be sorted ascending")
        if self.deepstack_visual_indexes and self.deepstack_visual_indexes[-1] >= self.depth:
            raise ValueError("deepstack_visual_indexes out of range")

    @classmethod
    def from_hf(cls, hf: dict[str, Any]) -> "Qwen3VLVisionConfig":
        keys = {f.name for f in dataclasses.fields(cls)}
        kwargs = {k: v for k, v in hf.items() if k in keys}
        if "deepstack_visual_indexes" in kwargs:
            kwargs["deepstack_visual_indexes"] = tuple(kwargs["deepstack_visual_indexes"])
        return cls(**kwargs)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def patch_dim(self) -> int:
        return self.in_channels * self.temporal_patch_size * self.patch_size**2

    @property
    def merge_unit(self) -> int:
        return self.spatial_merge_size**2

    @property
    def num_grid_per_side(self) -> int:
        return int(self.num_position_embeddings**0.5)


def init_vision_params(cfg: Qwen3VLVisionConfig, key: jax.Array, dtype=jnp.float32) -> dict:
    std = cfg.initializer_range
    d, i = cfg.hidden_size, cfg.intermediate_size
    dm = d * cfg.merge_unit
    keys = iter(jax.random.split(key, 16))

    def w(shape):
        return (jax.random.normal(next(keys), shape, jnp.float32) * std).astype(dtype)

    def block_stack(L):
        ks = jax.random.split(next(keys), 4)
        mk = lambda kk, shape: (jax.random.normal(kk, (L, *shape), jnp.float32) * std).astype(dtype)
        return {
            "ln1_w": jnp.ones((L, d), dtype), "b_ln1": jnp.zeros((L, d), dtype),
            "ln2_w": jnp.ones((L, d), dtype), "b_ln2": jnp.zeros((L, d), dtype),
            "qkv_w": mk(ks[0], (d, 3 * d)), "b_qkv": jnp.zeros((L, 3 * d), dtype),
            "proj_w": mk(ks[1], (d, d)), "b_proj": jnp.zeros((L, d), dtype),
            "fc1_w": mk(ks[2], (d, i)), "b_fc1": jnp.zeros((L, i), dtype),
            "fc2_w": mk(ks[3], (i, d)), "b_fc2": jnp.zeros((L, d), dtype),
        }

    def merger(norm_dim):
        return {
            "norm_w": jnp.ones((norm_dim,), dtype), "b_norm": jnp.zeros((norm_dim,), dtype),
            "fc1_w": w((dm, dm)), "b_fc1": jnp.zeros((dm,), dtype),
            "fc2_w": w((dm, cfg.out_hidden_size)), "b_fc2": jnp.zeros((cfg.out_hidden_size,), dtype),
        }

    n_ds = len(cfg.deepstack_visual_indexes)
    return {
        "patch_w": w((cfg.patch_dim, d)),
        "b_patch": jnp.zeros((d,), dtype),
        "pos_embed": w((cfg.num_position_embeddings, d)),
        "blocks": block_stack(cfg.depth),
        "merger": merger(d),
        "ds_mergers": jax.tree.map(
            lambda *xs: jnp.stack(xs), *[merger(dm) for _ in range(n_ds)]
        ) if n_ds else {},
    }


def vision_logical_axes(cfg: Qwen3VLVisionConfig) -> dict:
    blocks = {
        "ln1_w": ("layers", "norm"), "b_ln1": ("layers", "norm"),
        "ln2_w": ("layers", "norm"), "b_ln2": ("layers", "norm"),
        "qkv_w": ("layers", "embed", "heads"), "b_qkv": ("layers", "heads"),
        "proj_w": ("layers", "heads", "embed"), "b_proj": ("layers", "norm"),
        "fc1_w": ("layers", "embed", "mlp"), "b_fc1": ("layers", "mlp"),
        "fc2_w": ("layers", "mlp", "embed"), "b_fc2": ("layers", "norm"),
    }
    merger = {"norm_w": ("norm",), "b_norm": ("norm",),
              "fc1_w": ("embed", "mlp"), "b_fc1": ("mlp",),
              "fc2_w": ("mlp", "embed"), "b_fc2": ("norm",)}
    axes = {
        "patch_w": (None, "embed"), "b_patch": ("norm",),
        "pos_embed": (None, "embed"),
        "blocks": blocks,
        "merger": merger,
    }
    if cfg.deepstack_visual_indexes:
        axes["ds_mergers"] = {k: ("layers",) + v for k, v in merger.items()}
    return axes


def prepare_vision_inputs(grid_thw: np.ndarray, cfg: Qwen3VLVisionConfig) -> dict[str, np.ndarray]:
    """Host-side bookkeeping from ``grid_thw (n_images, 3)``: rope angles' position
    pairs, bilinear pos-embed gather indices/weights, per-frame segment ids —
    everything data-dependent, so the device fn stays static-shaped.

    Mirrors HF rot_pos_emb (:656) and fast_pos_embed_interpolate (:695); all outputs
    follow the processor's merge-unit token order.
    """
    ms = cfg.spatial_merge_size
    side = cfg.num_grid_per_side
    pos_pairs, idx4, w4, seg = [], [[] for _ in range(4)], [[] for _ in range(4)], []
    seg_id = 0
    for t, h, w in np.asarray(grid_thw):
        t, h, w = int(t), int(h), int(w)
        # --- rope coords in merge-unit order ---
        bh, bw = h // ms, w // ms
        row = (np.arange(bh)[:, None, None, None] * ms + np.arange(ms)[None, None, :, None])
        col = (np.arange(bw)[None, :, None, None] * ms + np.arange(ms)[None, None, None, :])
        row = np.broadcast_to(row, (bh, bw, ms, ms)).reshape(-1)
        col = np.broadcast_to(col, (bh, bw, ms, ms)).reshape(-1)
        coords = np.stack([row, col], axis=-1)
        pos_pairs.append(np.tile(coords, (t, 1)))
        # --- bilinear pos-embed interpolation (row-major), then merge-unit permute ---
        h_idx = np.linspace(0, side - 1, h, dtype=np.float32)
        w_idx = np.linspace(0, side - 1, w, dtype=np.float32)
        hf_, wf_ = h_idx.astype(np.int32), w_idx.astype(np.int32)
        hc_, wc_ = np.clip(hf_ + 1, None, side - 1), np.clip(wf_ + 1, None, side - 1)
        dh, dw = h_idx - hf_, w_idx - wf_
        corner_idx = [
            (hf_[:, None] * side + wf_[None, :]),
            (hf_[:, None] * side + wc_[None, :]),
            (hc_[:, None] * side + wf_[None, :]),
            (hc_[:, None] * side + wc_[None, :]),
        ]
        corner_w = [
            (1 - dh)[:, None] * (1 - dw)[None, :],
            (1 - dh)[:, None] * dw[None, :],
            dh[:, None] * (1 - dw)[None, :],
            dh[:, None] * dw[None, :],
        ]
        # row-major (h, w) -> (t, bh, bw, ms, ms) merge-unit order
        perm = (
            np.arange(h * w)
            .reshape(bh, ms, bw, ms)
            .transpose(0, 2, 1, 3)
            .reshape(-1)
        )
        for j in range(4):
            flat_i = corner_idx[j].reshape(-1)[perm]
            flat_w = corner_w[j].reshape(-1)[perm]
            idx4[j].append(np.tile(flat_i, t))
            w4[j].append(np.tile(flat_w, t))
        # --- per-frame attention segments (HF cu_seqlens repeat_interleave h*w, t) ---
        for _ in range(t):
            seg.append(np.full((h * w,), seg_id, dtype=np.int32))
            seg_id += 1
    return {
        "pos_pairs": np.concatenate(pos_pairs).astype(np.int32),  # (Tv, 2)
        "pos_idx": np.stack([np.concatenate(x) for x in idx4]).astype(np.int32),  # (4, Tv)
        "pos_w": np.stack([np.concatenate(x) for x in w4]).astype(np.float32),  # (4, Tv)
        "segment_ids": np.concatenate(seg),  # (Tv,)
    }


def vision_forward(
    cfg: Qwen3VLVisionConfig,
    backend: BackendConfig,
    params: dict,
    patches: jnp.ndarray,  # (Tv, patch_dim) processor-flattened pixels
    pos_pairs: jnp.ndarray,  # (Tv, 2) from prepare_vision_inputs
    pos_idx: jnp.ndarray,  # (4, Tv)
    pos_w: jnp.ndarray,  # (4, Tv)
    segment_ids: jnp.ndarray,  # (Tv,)
    rules=None,
):
    """Returns ``(merged (Tv/merge_unit, out_hidden), deepstack (n_ds, Tv/mu, out))``."""
    dtype = backend.jnp_dtype
    d = cfg.hidden_size
    H, dh = cfg.num_heads, cfg.head_dim
    mu = cfg.merge_unit
    approx = cfg.hidden_act == "gelu_pytorch_tanh"

    p = jax.tree.map(lambda a: a.astype(dtype) if a.dtype != jnp.int32 else a, params)

    h = patches.astype(dtype) @ p["patch_w"] + p["b_patch"]
    pos = (p["pos_embed"][pos_idx] * pos_w[..., None].astype(dtype)).sum(0)
    h = h + pos

    # 2D rope: per-token angles [row*(inv_freq), col*(inv_freq)] over head_dim/2
    inv_freq = rope_frequencies(dh // 2)
    angles = (pos_pairs[:, :, None].astype(jnp.float32) * inv_freq).reshape(h.shape[0], -1)
    angles = angles[None]  # (1, Tv, dh/2)

    seg = segment_ids[None]

    def merger_apply(mp, x, post_shuffle):
        if post_shuffle:
            x = x.reshape(-1, d * mu)
            x = layer_norm(x, mp["norm_w"], mp["b_norm"], 1e-6)
        else:
            x = layer_norm(x, mp["norm_w"], mp["b_norm"], 1e-6).reshape(-1, d * mu)
        x = jax.nn.gelu(x @ mp["fc1_w"] + mp["b_fc1"], approximate=False)
        return x @ mp["fc2_w"] + mp["b_fc2"]

    def block_fn(hh, lp):
        x = layer_norm(hh, lp["ln1_w"], lp["b_ln1"], 1e-6)
        qkv = (x @ lp["qkv_w"] + lp["b_qkv"]).reshape(1, -1, 3, H, dh)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        q = apply_rope_angles(q, angles)
        k = apply_rope_angles(k, angles)
        attn = sharded_attention(
            q, k, v, rules=rules, causal=False, segment_ids_q=seg, segment_ids_kv=seg,
            backend=backend.attention,
        )[0].reshape(-1, d)
        hh = hh + (attn @ lp["proj_w"] + lp["b_proj"])
        x = layer_norm(hh, lp["ln2_w"], lp["b_ln2"], 1e-6)
        hh = hh + (jax.nn.gelu(x @ lp["fc1_w"] + lp["b_fc1"], approximate=approx) @ lp["fc2_w"] + lp["b_fc2"])
        return hh, None

    body = backend.layer_remat(block_fn)

    # scan the contiguous segments between deepstack taps (compile time ~ #taps)
    deepstack = []
    bounds = [i + 1 for i in cfg.deepstack_visual_indexes]
    start = 0
    for j, end in enumerate([*bounds, cfg.depth]):
        if end > start:
            seg_params = jax.tree.map(lambda a: a[start:end], p["blocks"])
            h, _ = jax.lax.scan(body, h, seg_params)
        if j < len(bounds):
            mp = jax.tree.map(lambda a: a[j], p["ds_mergers"])
            deepstack.append(merger_apply(mp, h, post_shuffle=True))
        start = end

    merged = merger_apply(p["merger"], h, post_shuffle=False)
    ds = jnp.stack(deepstack) if deepstack else jnp.zeros((0, merged.shape[0], cfg.out_hidden_size), dtype)
    return merged, ds
