"""MoonViT vision tower (Kimi-VL) — TPU-native (reference kimivl/model.py:163-377).

Native-resolution ViT: per-image (h, w) patch grids packed into one token stream,
2D complex-pair rope, a *learnable* position embedding bicubically resized to each
grid, LayerNorm pre-norm blocks with biased qkv, and a 2x2 patch merger feeding the
projector.

Also serves the MoonViT3d variant (Kimi-K2.5, reference kimi_k25_vl/model.py:228-490):
temporal frames add a fixed sincos time embedding, spatial rope repeats per frame,
and the merger mean-pools over frames — expressed here as a host-precomputed
scatter-mean (out_idx/out_w) that degenerates to a pure permutation for t=1.

TPU-first contract: all data-dependent bookkeeping is host-side numpy
(``prepare_moonvit_inputs``): rope angles, per-image segment ids, the row-major ->
merge-unit permutation, and — the interesting one — the bicubic resize expressed as
a precomputed 16-tap gather (indices + cubic-convolution weights) so the device-side
interpolation is a differentiable weighted gather over the learned table with
static shapes (no per-grid recompilation, exact torch F.interpolate semantics,
align_corners=False, a=-0.75).
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

__all__ = ["MoonViTConfig", "init_moonvit_params", "moonvit_logical_axes",
           "moonvit_forward", "prepare_moonvit_inputs"]


@dataclasses.dataclass
class MoonViTConfig:
    patch_size: int = 14
    init_pos_emb_height: int = 64
    init_pos_emb_width: int = 64
    num_attention_heads: int = 16
    num_hidden_layers: int = 27
    hidden_size: int = 1152
    intermediate_size: int = 4304
    merge_kernel_size: tuple[int, int] = (2, 2)
    in_channels: int = 3
    initializer_range: float = 0.02
    # >1 enables the MoonViT3d temporal path (Kimi-K2.5): fixed sincos time
    # embedding per frame + temporal mean-pooling in the merger
    pos_emb_time: int = 1

    @classmethod
    def from_hf(cls, hf: dict[str, Any]) -> "MoonViTConfig":
        keys = {f.name for f in dataclasses.fields(cls)}
        kwargs = {k: v for k, v in hf.items() if k in keys}
        if "merge_kernel_size" in kwargs:
            kwargs["merge_kernel_size"] = tuple(kwargs["merge_kernel_size"])
        return cls(**kwargs)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def patch_dim(self) -> int:
        return self.in_channels * self.patch_size**2


def init_moonvit_params(cfg: MoonViTConfig, key: jax.Array, dtype=jnp.float32) -> dict:
    std = cfg.initializer_range
    d, i, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_hidden_layers
    keys = iter(jax.random.split(key, 8))

    def w(shape, s=std):
        return (jax.random.normal(next(keys), shape, jnp.float32) * s).astype(dtype)

    ks = jax.random.split(next(keys), 4)
    mk = lambda kk, shape, s: (jax.random.normal(kk, (L, *shape), jnp.float32) * s).astype(dtype)
    blocks = {
        "ln0_w": jnp.ones((L, d), dtype), "b_ln0": jnp.zeros((L, d), dtype),
        "ln1_w": jnp.ones((L, d), dtype), "b_ln1": jnp.zeros((L, d), dtype),
        "wqkv": mk(ks[0], (d, 3 * d), std), "b_qkv": jnp.zeros((L, 3 * d), dtype),
        "wo": mk(ks[1], (d, d), std), "b_o": jnp.zeros((L, d), dtype),
        # reference MoonVitMLP trunc-normal init with std sqrt(2/fan_in)
        "fc0": mk(ks[2], (d, i), (2 / d) ** 0.5), "b_fc0": jnp.zeros((L, i), dtype),
        "fc1": mk(ks[3], (i, d), (2 / i) ** 0.5), "b_fc1": jnp.zeros((L, d), dtype),
    }
    return {
        "patch_w": w((cfg.patch_dim, d)),
        "b_patch": jnp.zeros((d,), dtype),
        # reference inits pos_emb with normal(0, 1)
        "pos_emb": (jax.random.normal(next(keys), (cfg.init_pos_emb_height, cfg.init_pos_emb_width, d), jnp.float32)).astype(dtype),
        "blocks": blocks,
        "final_ln_w": jnp.ones((d,), dtype),
        "b_final_ln": jnp.zeros((d,), dtype),
    }


def moonvit_logical_axes(cfg: MoonViTConfig) -> dict:
    return {
        "patch_w": (None, "embed"), "b_patch": ("norm",),
        "pos_emb": (None, None, "embed"),
        "blocks": {
            "ln0_w": ("layers", "norm"), "b_ln0": ("layers", "norm"),
            "ln1_w": ("layers", "norm"), "b_ln1": ("layers", "norm"),
            "wqkv": ("layers", "embed", "heads"), "b_qkv": ("layers", "heads"),
            "wo": ("layers", "heads", "embed"), "b_o": ("layers", "norm"),
            "fc0": ("layers", "embed", "mlp"), "b_fc0": ("layers", "mlp"),
            "fc1": ("layers", "mlp", "embed"), "b_fc1": ("layers", "norm"),
        },
        "final_ln_w": ("norm",), "b_final_ln": ("norm",),
    }


def _cubic_taps(dst: int, src: int) -> tuple[np.ndarray, np.ndarray]:
    """4-tap cubic-convolution (a=-0.75) indices/weights per output coordinate,
    torch F.interpolate bicubic semantics (align_corners=False, clamped borders)."""
    a = -0.75
    scale = src / dst
    x = (np.arange(dst) + 0.5) * scale - 0.5
    x0 = np.floor(x).astype(np.int64)
    t = x - x0

    def k(u):
        u = np.abs(u)
        return np.where(
            u <= 1, ((a + 2) * u - (a + 3)) * u * u + 1,
            np.where(u < 2, (((u - 5) * u + 8) * u - 4) * a, 0.0),
        )

    offs = np.array([-1, 0, 1, 2])
    idx = x0[:, None] + offs[None, :]
    wts = k(t[:, None] - offs[None, :])
    idx = np.clip(idx, 0, src - 1)
    return idx, wts


def _sincos_1d(dim: int, t_size: int) -> np.ndarray:
    """MAE-style [sin | cos] temporal embedding (reference kimi_k25_vl/model.py:169-190)."""
    omega = 1.0 / 10000 ** (np.arange(dim // 2, dtype=np.float32) / (dim / 2.0))
    out = np.arange(t_size, dtype=np.float32)[:, None] * omega[None, :]
    return np.concatenate([np.sin(out), np.cos(out)], axis=1)


def prepare_moonvit_inputs(grid_hws: np.ndarray, cfg: MoonViTConfig) -> dict[str, np.ndarray]:
    """Host-side bookkeeping per packed image: rope angles, segment ids, 16-tap
    bicubic gather for the learned pos-emb table, the fixed temporal embedding, and
    the merger scatter (mean over frames; pure permutation for t=1 grids).

    ``grid_hws`` rows are (h, w) or (t, h, w)."""
    dh = cfg.head_dim
    d = cfg.hidden_size
    Hp, Wp = cfg.init_pos_emb_height, cfg.init_pos_emb_width
    kh, kw = cfg.merge_kernel_size
    n_freq = dh // 4
    freqs = 1.0 / (10000.0 ** (np.arange(0, dh, 4)[:n_freq].astype(np.float64) / dh))
    time_table = _sincos_1d(d, max(cfg.pos_emb_time, 1))

    grids = np.asarray(grid_hws)
    if grids.shape[1] == 2:
        grids = np.concatenate([np.ones((len(grids), 1), grids.dtype), grids], axis=1)

    angles, seg, pos_idx, pos_w, time_emb, out_idx, out_w = [], [], [], [], [], [], []
    seg_id, merged_offset = 0, 0
    for t, h, w in grids:
        t, h, w = int(t), int(h), int(w)
        if h % kh or w % kw:
            raise ValueError(f"grid ({h}, {w}) not divisible by merge kernel ({kh}, {kw})")
        if t > max(cfg.pos_emb_time, 1):
            raise ValueError(f"t={t} exceeds pos_emb_time={cfg.pos_emb_time}")
        # 2D rope: interleave (x*f, y*f) per frequency, repeated over frames
        # (reference Rope2DPosEmb / Rope2DPosEmbRepeated)
        ys, xs = np.meshgrid(np.arange(h), np.arange(w), indexing="ij")
        xa = xs.reshape(-1, 1) * freqs[None, :]
        ya = ys.reshape(-1, 1) * freqs[None, :]
        ang = np.stack([xa, ya], axis=-1).reshape(h * w, -1)  # (h*w, dh/2)
        angles.append(np.tile(ang, (t, 1)))
        # one attention segment per image (all frames attend jointly,
        # reference cu_seqlens over t*h*w)
        seg.append(np.full((t * h * w,), seg_id, np.int32))
        seg_id += 1
        # bicubic taps: outer product of per-axis 4-tap kernels -> 16 taps
        iy, wy = _cubic_taps(h, Hp)
        ix, wx = _cubic_taps(w, Wp)
        flat_idx = (iy[:, None, :, None] * Wp + ix[None, :, None, :]).reshape(h * w, 16)
        flat_w = (wy[:, None, :, None] * wx[None, :, None, :]).reshape(h * w, 16)
        pos_idx.append(np.tile(flat_idx, (t, 1)))
        pos_w.append(np.tile(flat_w, (t, 1)))
        # fixed sincos time embedding per frame (zero for single-frame images,
        # reference Learnable2DInterpPosEmbDividedFixed: t==1 skips the add)
        if t > 1:
            time_emb.append((t, h * w))
        else:
            time_emb.append((1, h * w))
        # row-major -> merge-unit order, then mean over frames: token (f, y, x)
        # lands in merged slot (block, intra) with weight 1/t
        p = (
            np.arange(h * w)
            .reshape(h // kh, kh, w // kw, kw)
            .transpose(0, 2, 1, 3)
            .reshape(-1)
        )
        inv = np.empty_like(p)
        inv[p] = np.arange(h * w)  # row-major token -> merge-unit slot
        oi = np.tile(inv, t) + merged_offset
        out_idx.append(oi)
        out_w.append(np.full((t * h * w,), 1.0 / t, np.float32))
        merged_offset += h * w
    out = {
        "rope_angles": np.concatenate(angles).astype(np.float32),  # (T, dh/2)
        "segment_ids": np.concatenate(seg),  # (T,)
        "pos_idx": np.concatenate(pos_idx).astype(np.int32),  # (T, 16)
        "pos_w": np.concatenate(pos_w).astype(np.float32),  # (T, 16)
        "out_idx": np.concatenate(out_idx).astype(np.int32),  # (T,)
        "out_w": np.concatenate(out_w).astype(np.float32),  # (T,)
    }
    if any(int(t) > 1 for t, _, _ in grids):
        # only multi-frame batches carry the temporal embedding (zeros otherwise);
        # built lazily so all-image batches never allocate the (T, hidden) block
        out["time_emb"] = np.concatenate(
            [
                np.repeat(time_table[:t], hw, axis=0) if t > 1 else np.zeros((hw, d), np.float32)
                for t, hw in time_emb
            ]
        ).astype(np.float32)  # (T, hidden)
    return out


def _rope_interleaved_angles(x: jnp.ndarray, angles: jnp.ndarray) -> jnp.ndarray:
    """Complex-pair rotation with per-token angles; x (T, H, dh), angles (T, dh/2)."""
    dtype = x.dtype
    cos = jnp.cos(angles)[:, None, :]
    sin = jnp.sin(angles)[:, None, :]
    xf = x.astype(jnp.float32)
    x0, x1 = xf[..., 0::2], xf[..., 1::2]
    out = jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos], axis=-1)
    return out.reshape(x.shape).astype(dtype)


def moonvit_forward(
    cfg: MoonViTConfig,
    backend: BackendConfig,
    params: dict,
    patches: jnp.ndarray,  # (T, patch_dim)
    rope_angles: jnp.ndarray,  # (T, dh/2)
    segment_ids: jnp.ndarray,  # (T,)
    pos_idx: jnp.ndarray,  # (T, 16)
    pos_w: jnp.ndarray,  # (T, 16)
    out_idx: jnp.ndarray,  # (T,) merged-slot scatter indices
    out_w: jnp.ndarray,  # (T,) scatter weights (1/t per frame)
    num_merged_units: int,  # static: total merged slots (= sum h*w per image)
    time_emb: jnp.ndarray | None = None,  # (T, hidden) fixed temporal sincos (3d)
    rules=None,
) -> jnp.ndarray:
    """Returns merged features (num_merged_units // mu, mu, hidden) for the projector."""
    dtype = backend.jnp_dtype
    d, H, dh = cfg.hidden_size, cfg.num_attention_heads, cfg.head_dim
    mu = cfg.merge_kernel_size[0] * cfg.merge_kernel_size[1]
    p = jax.tree.map(lambda a: a.astype(dtype) if a.dtype not in (jnp.int32,) else a, params)

    h = patches.astype(dtype) @ p["patch_w"] + p["b_patch"]
    table = p["pos_emb"].reshape(-1, d)
    h = h + (table[pos_idx] * pos_w[..., None].astype(dtype)).sum(axis=1)
    if time_emb is not None:
        h = h + time_emb.astype(dtype)

    seg = segment_ids[None]

    def block_fn(hh, lp):
        x = layer_norm(hh, lp["ln0_w"], lp["b_ln0"])
        qkv = (x @ lp["wqkv"] + lp["b_qkv"]).reshape(-1, 3, H, dh)
        q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
        q = _rope_interleaved_angles(q, rope_angles)
        k = _rope_interleaved_angles(k, rope_angles)
        attn = sharded_attention(
            q[None], k[None], v[None], rules=rules, causal=False,
            segment_ids_q=seg, segment_ids_kv=seg, backend=backend.attention,
        )[0].reshape(-1, d)
        hh = hh + (attn @ lp["wo"] + lp["b_o"])
        x = layer_norm(hh, lp["ln1_w"], lp["b_ln1"])
        hh = hh + (jax.nn.gelu(x @ lp["fc0"] + lp["b_fc0"], approximate=True) @ lp["fc1"] + lp["b_fc1"])
        return hh, None

    h, _ = jax.lax.scan(backend.layer_remat(block_fn), h, p["blocks"])
    h = layer_norm(h, p["final_ln_w"], p["b_final_ln"])
    # merge-unit regroup + mean over frames as one scatter-add
    merged = jnp.zeros((int(num_merged_units), d), h.dtype)
    merged = merged.at[out_idx].add(h * out_w[:, None].astype(h.dtype))
    return merged.reshape(-1, mu, d)
