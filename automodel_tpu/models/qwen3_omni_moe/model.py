"""Qwen3-Omni-MoE thinker — TPU-native (reference models/qwen3_omni_moe/model.py:177;
the reference swaps only the thinker text stack and keeps HF towers — here the
audio tower (models/audio/qwen3_omni_audio.py) and vision tower
(models/vision/qwen3_vl_vit.py — identical math to the omni tower, only merger key
names differ) are native too).

Composition = Qwen3-VL-MoE (deepstack vision + interleaved mrope text) plus audio:
encoded audio tokens replace the embedding rows at ``audio_token_id`` positions.
Audio tokens take text-like (all-axes-equal) mrope positions, which the inherited
``get_mrope_positions`` walk already produces for non-vision tokens
(HF get_rope_index audio branch, modeling_qwen3_omni_moe.py:333-344).

Video spans use omni timestamp semantics: one contiguous placeholder run whose
t-indices are floor(frame * second_per_grid * position_id_per_seconds)
(HF-pinned). Interleaved audio-in-video position ids are not yet supported."""

from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

import jax.numpy as jnp

from automodel_tpu.models.audio.qwen3_omni_audio import (
    Qwen3OmniAudioConfig,
    audio_forward,
    audio_logical_axes,
    init_audio_params,
    prepare_audio_inputs,
)
from automodel_tpu.models.common.backend import BackendConfig
from automodel_tpu.models.qwen3_vl_moe.model import (
    Qwen3VLMoeConfig,
    Qwen3VLMoeForConditionalGeneration,
)

__all__ = ["Qwen3OmniMoeThinkerConfig", "Qwen3OmniMoeThinkerForConditionalGeneration"]


@dataclasses.dataclass
class Qwen3OmniMoeThinkerConfig(Qwen3VLMoeConfig):
    audio: Qwen3OmniAudioConfig = None
    audio_token_id: int = 151646
    position_id_per_seconds: int = 25

    @classmethod
    def from_hf(cls, hf: dict[str, Any]) -> "Qwen3OmniMoeThinkerConfig":
        hf = hf.get("thinker_config", hf)
        base = Qwen3VLMoeConfig.from_hf(hf)
        return cls(
            **{f.name: getattr(base, f.name) for f in dataclasses.fields(Qwen3VLMoeConfig)},
            audio=Qwen3OmniAudioConfig.from_hf(hf.get("audio_config", {})),
            audio_token_id=hf.get("audio_token_id", 151646),
            position_id_per_seconds=hf.get("position_id_per_seconds", 25),
        )


class Qwen3OmniMoeThinkerForConditionalGeneration(Qwen3VLMoeForConditionalGeneration):
    config_class = Qwen3OmniMoeThinkerConfig
    hf_architectures = (
        "Qwen3OmniMoeThinkerForConditionalGeneration",
        "Qwen3OmniMoeForConditionalGeneration",
    )
    # the layer walk is inherited from Qwen3VLMoe, so the pipelined hidden path
    # works as-is once the audio embeds ride the per-microbatch prologue:
    def _pp_extra_embeds(self, params, mb, rules=None):
        if "audio_chunks" not in mb:
            return None
        ai = mb["audio_inputs"]
        tokens = audio_forward(
            self.config.audio, self.backend, params["audio"],
            mb["audio_chunks"], ai["gather_idx"], ai["segment_ids"], rules=rules,
        )
        return ((mb["audio_coords_b"], mb["audio_coords_s"]), tokens)

    # ---- params ----

    def init(self, key, dtype=jnp.float32):
        import jax

        k_base, k_audio = jax.random.split(jax.random.fold_in(key, 0))
        params = super().init(k_base, dtype)
        params["audio"] = init_audio_params(self.config.audio, k_audio, dtype)
        return params

    def logical_axes(self):
        axes = super().logical_axes()
        axes["audio"] = audio_logical_axes(self.config.audio)
        return axes

    # ---- host-side helpers ----

    def prepare_audio_inputs(self, features) -> dict[str, np.ndarray]:
        return prepare_audio_inputs(features, self.config.audio)

    def audio_token_coords(self, input_ids: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        b, s = np.where(input_ids == self.config.audio_token_id)
        return b.astype(np.int32), s.astype(np.int32)

    def get_mrope_positions(
        self,
        input_ids,
        grid_thw,
        attention_mask=None,
        video_grid_thw=None,
        second_per_grids=None,  # (n_videos,) seconds per temporal grid (default 1.0)
    ):
        """Omni mrope: audio spans take text-like positions (inherited walk);

        NOTE: this forks the Qwen3VLMoe walk (qwen3_vl_moe/model.py
        get_mrope_positions) because omni videos are ONE contiguous t*gh*gw span
        with timestamp t-indices while VL splits them into per-frame t=1 spans —
        fixes to the parent walk's cursor/mask handling must be mirrored here.
        video spans are ONE contiguous run of t*gh*gw placeholders whose t-index is
        timestamp-scaled — floor(frame * second_per_grid * position_id_per_seconds)
        (HF get_rope_index video branch + get_llm_pos_ids_for_vision). Interleaved
        audio-in-video is not supported."""
        cfg = self.config
        vids = None if video_grid_thw is None else np.asarray(video_grid_thw)
        if vids is None or not (vids[:, 0] > 1).any():
            return super().get_mrope_positions(
                input_ids, grid_thw, attention_mask=attention_mask, video_grid_thw=video_grid_thw
            )
        if second_per_grids is None:
            second_per_grids = np.ones((len(vids),), np.float32)
        ms = cfg.vision.spatial_merge_size
        B, S = input_ids.shape
        pos = np.zeros((3, B, S), dtype=np.int64)
        img_idx, vid_idx = 0, 0
        for b in range(B):
            valid = np.ones((S,), bool) if attention_mask is None else attention_mask[b].astype(bool)
            ids = input_ids[b][valid]
            out = np.zeros((3, len(ids)), dtype=np.int64)
            st, cursor = 0, 0
            is_img = ids == cfg.image_token_id
            is_vid = ids == cfg.video_token_id
            while st < len(ids):
                if not (is_img[st] or is_vid[st]):
                    out[:, st] = cursor
                    cursor += 1
                    st += 1
                    continue
                if is_vid[st]:
                    t, h, w = (int(x) for x in vids[vid_idx])
                    spg = float(second_per_grids[vid_idx])
                    vid_idx += 1
                    t_index = np.floor(
                        np.arange(t) * spg * cfg.position_id_per_seconds
                    ).astype(np.int64)
                else:
                    t, h, w = (int(x) for x in grid_thw[img_idx])
                    img_idx += 1
                    t_index = np.arange(t)
                gh, gw = h // ms, w // ms
                n = t * gh * gw
                span = is_vid[st : st + n] if is_vid[st] else is_img[st : st + n]
                if len(span) < n:
                    raise ValueError(
                        f"vision span truncated: expected {n} placeholder tokens for "
                        f"grid ({t},{h},{w}) but the sequence ends after {len(span)}"
                    )
                if not span.all():
                    # use_audio_in_video interleaves audio tokens per frame inside
                    # the video span — those position ids are not implemented, and
                    # assigning grid coordinates blindly would silently desync
                    raise NotImplementedError(
                        "non-contiguous vision span (audio-in-video interleaving is "
                        "not supported; check grid/token alignment otherwise)"
                    )
                out[0, st : st + n] = np.repeat(t_index, gh * gw) + cursor
                out[1, st : st + n] = np.tile(np.repeat(np.arange(gh), gw), t) + cursor
                out[2, st : st + n] = np.tile(np.arange(gw), t * gh) + cursor
                cursor = int(out[:, st : st + n].max()) + 1
                st += n
            pos[:, b, valid] = out
        return pos

    # ---- forward ----

    def __call__(
        self,
        params,
        input_ids,
        pixel_values=None,
        vision_inputs=None,
        visual_coords=None,
        audio_chunks=None,  # (N, mel, chunk_len)
        audio_inputs=None,  # dict from prepare_audio_inputs
        audio_coords=None,  # (b_idx, s_idx) of audio placeholder tokens
        positions3=None,
        segment_ids=None,
        token_mask=None,
        rules=None,
        return_hidden=False,
        training=True,
    ):
        extra_embeds = None
        if audio_chunks is not None:
            ai = audio_inputs
            audio_tokens = audio_forward(
                self.config.audio, self.backend, params["audio"],
                audio_chunks, ai["gather_idx"], ai["segment_ids"], rules=rules,
            )
            extra_embeds = (audio_coords, audio_tokens)
        return super().__call__(
            params, input_ids,
            pixel_values=pixel_values, vision_inputs=vision_inputs,
            visual_coords=visual_coords, positions3=positions3,
            segment_ids=segment_ids, token_mask=token_mask, rules=rules,
            return_hidden=return_hidden, training=training,
            extra_embeds=extra_embeds,
        )

    # ---- interop ----

    def state_dict_adapter(self):
        from automodel_tpu.models.qwen3_omni_moe.state_dict_adapter import (
            Qwen3OmniMoeThinkerStateDictAdapter,
        )

        return Qwen3OmniMoeThinkerStateDictAdapter(self.config)

    @classmethod
    def from_config(cls, config, backend: BackendConfig | None = None):
        if isinstance(config, dict):
            config = Qwen3OmniMoeThinkerConfig.from_hf(config)
        return cls(config, backend)
