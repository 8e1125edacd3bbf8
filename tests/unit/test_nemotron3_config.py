"""Nemotron-3-Super as published: ``from_hf`` on the catalog row's ``config`` verbatim,
the parameter arithmetic of its layers, the single scan its pattern becomes, the cut to
one chip's share, and the LatentMoE round trip through the HF names."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from automodel_tpu.models.common.backend import BackendConfig
from automodel_tpu.models.nemotron_v3.model import (
    NemotronHForCausalLM,
    NemotronV3Config,
    _iterations,
)

# the `config` of the catalog row NVIDIA-Nemotron-3-Super-120B-A12B-BF16, verbatim
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128,
    "hidden_size": 4096,
    "hybrid_override_pattern": "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 2688, "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_hidden_act": "silu", "mamba_num_heads": 128, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "mlp_bias": False, "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h", "moe_intermediate_size": 2688, "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376, "moe_shared_expert_overlap": False,
    "mtp_hybrid_override_pattern": "*E", "n_group": 1, "n_groups": 8, "n_routed_experts": 512,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 22, "num_hidden_layers": 88, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
    "rescale_prenorm_residual": True, "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 5, "sliding_window": None, "ssm_state_size": 128,
    "tie_word_embeddings": False, "time_step_floor": 0.0001, "time_step_max": 0.1,
    "time_step_min": 0.001, "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072,
}


def _count(tree) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


def test_from_hf_reads_the_published_config_verbatim():
    cfg = NemotronV3Config.from_hf(PUBLISHED)
    kinds = cfg.layers_block_type
    assert (kinds.count("mamba"), kinds.count("moe"), kinds.count("attention")) == (40, 40, 8)
    assert kinds[27:38] == ("mamba", "moe") * 4 + ("mamba", "attention", "moe")
    assert (cfg.mamba_intermediate, cfg.conv_dim) == (8192, 10240)
    moe = cfg.moe
    assert (moe.n_routed_experts, moe.held_experts, moe.n_activated_experts) == (512, 512, 22)
    assert (moe.latent_dim, moe.expert_dim, moe.dim, moe.moe_inter_dim) == (1024, 1024, 4096, 2688)
    assert (moe.shared_inter_dim, moe.route_scale, moe.score_func) == (5376, 5, "sigmoid")
    assert moe.norm_topk_prob and moe.expert_activation == "relu2" and not moe.gated
    assert moe.n_expert_groups == 1 and moe.holds_all_experts and not cfg.use_bias


def test_the_cut_holds_a_share_and_counts_as_the_issue_reckons():
    """One period, 16 of 512 experts, an eighth of the vocabulary: the parameters per layer
    kind (ISSUE 29's arithmetic) and in all, from the shapes the model would allocate."""
    cut = dict(PUBLISHED, num_hidden_layers=11, hybrid_override_pattern="MEMEMEMEM*E",
               n_routed_experts=16, router_n_experts=512, first_held_expert=0,
               vocab_size=16384, num_nextn_predict_layers=0)
    cfg = NemotronV3Config.from_hf(cut)
    assert (cfg.moe.n_routed_experts, cfg.moe.held_experts, cfg.moe.first_held_expert) == (512, 16, 0)
    shapes = NemotronHForCausalLM(cfg, BackendConfig()).abstract_params()
    per = lambda tree, n: _count(tree) / n / 1e6  # noqa: E731
    assert per(shapes["mamba_layers"], 5) == pytest.approx(109.64, abs=0.01)
    assert per(shapes["attn_layers"], 1) == pytest.approx(35.66, abs=0.01)
    moe = shapes["moe_layers"]["moe"]
    assert moe["gate"]["weight"].shape == (5, 512, 4096)
    assert moe["experts"]["gate_up_proj"].shape == (5, 16, 1024, 2688)
    assert moe["experts"]["down_proj"].shape == (5, 16, 2688, 1024)
    assert per(moe["experts"], 5 * 16) == pytest.approx(5.505, abs=0.001)
    assert per(shapes["moe_layers"], 5) - 16 * 5.505 == pytest.approx(54.53, abs=0.01)
    assert _count(shapes["embed"]) / 1e6 == _count(shapes["lm_head"]) / 1e6 == pytest.approx(67.11, abs=0.01)
    assert _count(shapes) / 1e9 == pytest.approx(1.431, abs=0.001)
    whole = NemotronHForCausalLM(NemotronV3Config.from_hf(dict(cut, vocab_size=131072)),
                                 BackendConfig()).abstract_params()
    assert _count(whole["embed"]) / 1e6 == pytest.approx(536.9, abs=0.1)
    assert shapes["mamba_layers"]["a_log"].dtype == jnp.float32
    assert moe["gate"]["score_correction_bias"].dtype == jnp.float32


@pytest.mark.parametrize("pattern,order,present", [
    ("MEMEMEMEM*E", "M*E", ["M.E"] * 4 + ["M*E"]),
    ("MMMM", "M", ["M"] * 4),
    ("MEME", "ME", ["ME", "ME"]),
    ("MM*-", "M*-", ["M..", "M*-"]),
    ("M", "M", ["M"]),
])
def test_the_pattern_becomes_one_scan(pattern, order, present):
    chars = {"M": "mamba", "*": "attention", "-": "mlp", "E": "moe"}
    got_order, got = _iterations(tuple(chars[c] for c in pattern))
    assert got_order == tuple(chars[c] for c in order)
    assert ["".join(c if p else "." for c, p in zip(order, row)) for row in got] == present


def test_unknown_layer_kinds_and_wrong_lengths_are_refused():
    with pytest.raises(ValueError, match="unknown layer kinds"):
        NemotronV3Config.from_hf(dict(PUBLISHED, hybrid_override_pattern="MX" * 44))
    with pytest.raises(ValueError, match="num_hidden_layers"):
        NemotronV3Config.from_hf(dict(PUBLISHED, num_hidden_layers=87))


def _tiny(**kw):
    hf = dict(PUBLISHED, vocab_size=128, hidden_size=64, num_hidden_layers=4,
              hybrid_override_pattern="ME*E", num_attention_heads=4, num_key_value_heads=2,
              head_dim=16, mamba_num_heads=4, mamba_head_dim=32, ssm_state_size=16, n_groups=2,
              chunk_size=16, intermediate_size=32, moe_intermediate_size=32, moe_latent_size=24,
              moe_shared_expert_intermediate_size=48, n_routed_experts=8, num_experts_per_tok=3)
    hf.update(kw)
    return NemotronV3Config.from_hf(hf)


def test_the_latent_projections_round_trip_through_the_hf_names():
    model = NemotronHForCausalLM(_tiny(), BackendConfig(dtype="float32"))
    params = model.init(jax.random.key(4), jnp.float32)
    adapter = model.state_dict_adapter()
    hf = adapter.to_hf(params)
    down = np.asarray(params["moe_layers"]["moe"]["latent"]["w_down"])
    np.testing.assert_array_equal(hf["backbone.layers.1.mixer.fc1_latent_proj.weight"], down[0].T)
    np.testing.assert_array_equal(hf["backbone.layers.3.mixer.fc1_latent_proj.weight"], down[1].T)
    assert hf["backbone.layers.3.mixer.fc2_latent_proj.weight"].shape == (64, 24)
    assert hf["backbone.layers.1.mixer.experts.7.up_proj.weight"].shape == (32, 24)
    back = adapter.from_hf(hf)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_scan_cond_and_unrolled_agree_and_the_share_leaves_out_the_rest():
    cfg = _tiny()
    scanned = NemotronHForCausalLM(cfg, BackendConfig(dtype="float32", remat_policy="none"))
    unrolled = NemotronHForCausalLM(cfg, BackendConfig(dtype="float32", remat_policy="full",
                                                      scan_layers=False))
    params = scanned.init(jax.random.key(1), jnp.float32)
    ids = jnp.asarray(np.random.RandomState(1).randint(0, 128, (2, 32)))

    def loss(model, p):
        logits, stats = model(p, ids, training=True)
        return jnp.mean(jnp.square(logits)), stats

    (a, stats), ga = jax.value_and_grad(lambda p: loss(scanned, p), has_aux=True)(params)
    (b, _), gb = jax.value_and_grad(lambda p: loss(unrolled, p), has_aux=True)(params)
    assert stats["expert_load"].shape == (2, 8)
    np.testing.assert_allclose(a, b, rtol=1e-5)
    for x, y in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        np.testing.assert_allclose(x, y, atol=1e-5, rtol=1e-4)
    # a share of the experts: same router, fewer parameters, another (partial) output
    held_cfg = _tiny(n_routed_experts=2, router_n_experts=8, first_held_expert=2)
    held = NemotronHForCausalLM(held_cfg, BackendConfig(dtype="float32"))
    part = jax.tree.map(lambda x: x, params)
    part["moe_layers"]["moe"]["experts"] = jax.tree.map(
        lambda x: x[:, 2:4], params["moe_layers"]["moe"]["experts"])
    want = jax.tree.map(lambda x: (x.shape, x.dtype), held.abstract_params(jnp.float32))
    assert jax.tree.map(lambda x: (x.shape, x.dtype), part) == want
    logits, stats = held(part, ids, training=False)
    assert stats["expert_load"].shape == (2, 8) and np.isfinite(np.asarray(logits)).all()


def test_the_example_recipe_is_the_benchmarks_configuration():
    """``examples/llm_pretrain/nemotron3_super_ep32_share.yaml`` states in YAML, as a user
    would, what the benchmark's configuration file states: the same model comes out."""
    import json

    from automodel_tpu.config.loader import load_config

    example = load_config("examples/llm_pretrain/nemotron3_super_ep32_share.yaml")
    with open("benchmarks/configs/nemotron-3-super-120b-a12b-p11-ep32.json") as f:
        bench = json.load(f)
    a = NemotronV3Config.from_hf(dict(example.get("model.config")))
    b = NemotronV3Config.from_hf(bench)
    assert a == b
    for key in ("dtype", "attention", "remat_policy", "dispatcher", "experts_backend"):
        assert example.get(f"backend.{key}") == bench["recipe"]["backend"][key]
    for key in ("optimizer", "lr", "max_grad_norm"):
        assert example.get(f"optimizer.{key}") == bench["recipe"]["optimizer"][key]
    assert list(example.get("optimizer.betas")) == bench["recipe"]["optimizer"]["betas"]
    assert example.get("loss") is None and "loss" not in bench["recipe"]
    assert example.get("dataset.vocab_size") == bench["vocab_size"] == 16384
