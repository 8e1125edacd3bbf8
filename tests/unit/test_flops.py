"""Per-arch FLOPs formulas (reference utils/flops_utils.py:18-830): each family's
forward FLOPs/token must track ~2x its ACTIVE non-embedding params (the
parameter-counting identity), which the old dense-only formula violated for
MLA / DeltaNet / Mamba hybrids."""

import numpy as np

import jax
import jax.numpy as jnp

from automodel_tpu.models.auto import AutoModelForCausalLM
from automodel_tpu.models.common.backend import BackendConfig
import pytest

from automodel_tpu.utils.flops import flops_per_token, mfu, vision_tower_flops


def _param_count(model, exclude=("embed", "lm_head", "wte")):
    params = model.abstract_params(jnp.float32)
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        keys = [getattr(p, "key", "") for p in path]
        if any(k in exclude for k in keys):
            continue
        total += int(np.prod(leaf.shape))
    return total


def _check(hf, lo=1.2, hi=3.2, seq=64, active_frac=1.0):
    model = AutoModelForCausalLM.from_config(hf, BackendConfig(dtype="float32"))
    fwd = flops_per_token(hf, seq, training=False)
    active = _param_count(model) * active_frac
    ratio = fwd / (2 * active)
    assert lo < ratio < hi, f"{hf['architectures']}: fwd/2P ratio {ratio:.2f}"
    return fwd


class TestFlopsPerArch:
    def test_dense_llama(self):
        hf = {
            "architectures": ["LlamaForCausalLM"], "vocab_size": 256,
            "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "max_position_embeddings": 128,
        }
        _check(hf, lo=0.9, hi=2.5)

    def test_mla_counts_low_rank_projections(self):
        hf = {
            "architectures": ["DeepseekV3ForCausalLM"], "vocab_size": 256,
            "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
            "num_hidden_layers": 3, "num_attention_heads": 4, "q_lora_rank": 24,
            "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
            "v_head_dim": 16, "n_routed_experts": 8, "num_experts_per_tok": 2,
            "n_shared_experts": 1, "norm_topk_prob": True, "first_k_dense_replace": 1,
            "max_position_embeddings": 128,
        }
        # active params: experts are 8x but only 2+1 active -> scale expert block
        model = AutoModelForCausalLM.from_config(hf, BackendConfig(dtype="float32"))
        params = model.abstract_params(jnp.float32)
        total = 0
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
            keys = [getattr(p, "key", "") for p in path]
            if any(k in ("embed", "lm_head") for k in keys):
                continue
            n = int(np.prod(leaf.shape))
            if any(k in ("gate_up_proj", "down_proj") for k in keys):
                n = n * 2 // 8  # top-2 of 8 routed
            total += n
        fwd = flops_per_token(hf, 64, training=False)
        ratio = fwd / (2 * total)
        assert 0.8 < ratio < 2.8, f"MLA ratio {ratio:.2f}"

    def test_deltanet_hybrid_ignores_seq_quadratic_on_linear_layers(self):
        hf = {
            "architectures": ["Qwen3NextForCausalLM"], "vocab_size": 256,
            "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
            "num_hidden_layers": 4, "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 16, "num_experts": 8, "num_experts_per_tok": 2,
            "shared_expert_intermediate_size": 32, "linear_num_key_heads": 2,
            "linear_key_head_dim": 16, "linear_num_value_heads": 4,
            "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4,
            "full_attention_interval": 4, "max_position_embeddings": 128,
        }
        f_short = flops_per_token(hf, 64, training=False)
        f_long = flops_per_token(hf, 4096, training=False)
        # only 1 of 4 layers is full attention: the quadratic term must be ~1/4
        # of a dense model's growth
        dense = dict(hf)
        dense.pop("linear_num_key_heads"); dense.pop("full_attention_interval")
        d_short = flops_per_token(dense, 64, training=False)
        d_long = flops_per_token(dense, 4096, training=False)
        assert (f_long - f_short) < 0.3 * (d_long - d_short)

    def test_mamba_hybrid_layer_kinds(self):
        hf = {
            "architectures": ["NemotronHForCausalLM"], "vocab_size": 256,
            "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 4,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "mamba_num_heads": 4, "mamba_head_dim": 16, "ssm_state_size": 32,
            "n_groups": 1, "conv_kernel": 4,
            "hybrid_override_pattern": "M*M-",
            "max_position_embeddings": 128,
        }
        f = flops_per_token(hf, 64, training=False)
        assert f > 0
        # mamba layers cost no seq-quadratic term: growth comes from 1 attn layer
        f_long = flops_per_token(hf, 2048, training=False)
        per_layer_growth = (f_long - f) / (2048 - 64)
        n, h = 4, 16
        assert abs(per_layer_growth - 2 * 2 * n * h) / (2 * 2 * n * h) < 0.05

    def test_nemotron3_latent_moe_counts_latent_experts_and_projections_once(self):
        """Pattern ``ME*E``: the E layers carry the MoE (relu2: two matrices an FFN). A
        routed expert is 2 x latent x width, the latent projections are met once a token,
        router and shared expert read the full width; with ``router_n_experts`` a token
        meets ``top_k x held / all`` of the held experts."""
        d, latent, width, shared, router, k = 64, 16, 24, 40, 8, 3
        hf = {
            "architectures": ["NemotronHForCausalLM"], "vocab_size": 256, "hidden_size": d,
            "intermediate_size": width, "num_hidden_layers": 4, "hybrid_override_pattern": "ME*E",
            "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
            "mamba_num_heads": 4, "mamba_head_dim": 16, "ssm_state_size": 32, "n_groups": 1,
            "conv_kernel": 4, "mlp_hidden_act": "relu2", "n_routed_experts": router,
            "num_experts_per_tok": k, "moe_intermediate_size": width, "moe_latent_size": latent,
            "n_shared_experts": 1, "moe_shared_expert_intermediate_size": shared,
        }
        no_moe = dict(hf, hybrid_override_pattern="M-*-", n_routed_experts=0)
        dense = 2 * 3 * 2 * d * width  # two '-' layers as a pattern without E counts them
        base = flops_per_token(no_moe, 64, training=False) - dense
        moe_layer = (2 * d * router + 2 * 2 * d * latent + k * 2 * 2 * latent * width
                     + 2 * 2 * d * shared)
        assert flops_per_token(hf, 64, training=False) == pytest.approx(base + 2 * moe_layer)
        held = dict(hf, n_routed_experts=2, router_n_experts=router)
        moe_layer_held = moe_layer - k * (1 - 2 / router) * 2 * 2 * latent * width
        assert flops_per_token(held, 64, training=False) == pytest.approx(base + 2 * moe_layer_held)

    def test_mfu_device_table(self):
        assert 0.49 < mfu(12_000, 8.2e9, "TPU v5 lite") < 0.51
        assert mfu(1000, 1e9, "cpu") is None  # no peak: CPU rows carry no mfu
        with pytest.raises(ValueError, match="unknown accelerator"):
            mfu(1000, 1e9, "unknown accelerator")


class TestVisionTowerFlops:
    # tiny tower, every term hand-computable: 8x8 image, 4x4 patches ->
    # 4 patches + CLS = 5 positions
    VCFG = {
        "hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 2,
        "num_attention_heads": 2, "image_size": 8, "patch_size": 4,
    }

    def test_pins_hand_computed_count(self):
        d, inter, L, patch = 8, 16, 2, 4
        num_patches = (8 // 4) ** 2          # 4
        n_pos = num_patches + 1              # 5
        patch_embed = num_patches * 2 * (3 * patch * patch) * d   # 4*2*48*8 = 3072
        attn = 2 * d * 3 * d + 2 * d * d + 2 * 2 * n_pos * d      # 384+128+160 = 672
        mlp = 2 * 2 * d * inter                                   # 512
        expected = patch_embed + n_pos * L * (attn + mlp)         # 3072+5*2*1184 = 14912
        assert expected == 14912
        assert vision_tower_flops(self.VCFG) == expected

    def test_accepts_config_objects(self):
        from automodel_tpu.models.vision.clip_vit import CLIPVisionConfig

        cfg = CLIPVisionConfig(**{k: v for k, v in self.VCFG.items()
                                  if k != "num_attention_heads"},
                               num_attention_heads=2)
        assert vision_tower_flops(cfg) == vision_tower_flops(self.VCFG)

    def test_vlm_config_amortizes_vision_over_seq(self):
        text = {
            "vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
            "num_hidden_layers": 2, "num_attention_heads": 4,
            "num_key_value_heads": 2,
        }
        vlm = {"architectures": ["LlavaForConditionalGeneration"],
               "vision_config": self.VCFG, "text_config": text}
        seq = 64
        text_only = flops_per_token(text, seq, training=False)
        with_vision = flops_per_token(vlm, seq, training=False, num_images=2)
        expected_extra = vision_tower_flops(self.VCFG) * 2 / seq
        assert with_vision - text_only == expected_extra
        # training keeps the 3x fwd multiplier over the combined count
        assert flops_per_token(vlm, seq, training=True, num_images=2) == (
            3.0 * with_vision)
