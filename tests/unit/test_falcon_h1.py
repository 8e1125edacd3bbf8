"""Falcon-H1 (``models/falcon_h1``) on the CPU at a tiny size, seeded weights, every muP
multiplier set away from 1 and from each other: logits, loss and every leaf's gradient
against the plain float32 reference (``benchmarks/reference/falcon_h1.py``); a case a
multiplier in which the program without it would fail; ``from_hf`` on the catalog's
published config; the state-dict adapter's names; and the Nemotron-H block, whose mixer
moved into ``ops.mamba2.mamba2_mixer`` with this family, bit for bit against the block as
it was written before the move (float32 with every gradient under jit, bfloat16 eagerly)."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from automodel_tpu.models.common.backend import BackendConfig
from automodel_tpu.models.falcon_h1 import FalconH1Config, FalconH1ForCausalLM
from automodel_tpu.models.registry import resolve_model_class
from benchmarks.adapters import falcon_h1 as adapter
from benchmarks.harness import weights
from benchmarks.reference import falcon_h1 as reference

# the catalog row's ``config`` (huggingface.co/tiiuae/Falcon-H1-34B-Instruct config.json)
PUBLISHED = {
    "attention_bias": False, "attention_in_multiplier": 1, "attention_out_multiplier": 0.0375,
    "attn_layer_indices": None, "embedding_multiplier": 5.656854249492381, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 21504,
    "key_multiplier": 0.011048543456039804, "lm_head_multiplier": 0.0078125,
    "mamba_chunk_size": 128, "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 128,
    "mamba_d_ssm": 4096, "mamba_d_state": 256, "mamba_expand": 2, "mamba_n_groups": 2,
    "mamba_n_heads": 32, "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_use_mlp": True, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_expansion_factor": 8,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284], "model_type": "falcon_h1",
    "num_attention_heads": 20, "num_hidden_layers": 72, "num_key_value_heads": 4,
    "num_logits_to_keep": 1, "projectors_bias": False, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 100000000000, "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5, 0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845, "tie_word_embeddings": False, "vocab_size": 261120,
}

# every multiplier away from 1 and from every other
TINY = dict(
    PUBLISHED, vocab_size=128, hidden_size=64, intermediate_size=96, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    mamba_d_ssm=64, mamba_n_heads=4, mamba_d_head=16, mamba_n_groups=2, mamba_d_state=16,
    mamba_chunk_size=32, initializer_range=0.2, rope_theta=10000.0,
    embedding_multiplier=1.7, lm_head_multiplier=0.6, key_multiplier=0.45,
    attention_in_multiplier=1.3, attention_out_multiplier=0.8, ssm_in_multiplier=0.7,
    ssm_out_multiplier=1.4, ssm_multipliers=[0.55, 1.25, 0.75, 1.5, 0.65],
    mlp_multipliers=[1.6, 0.35],
)
MULTIPLIERS = [(k, None) for k in TINY if k.endswith("_multiplier")] + [
    ("ssm_multipliers", i) for i in range(5)] + [("mlp_multipliers", i) for i in range(2)]
ROWS, SEQ = 2, 64


def _blocks(seed=3):
    blocks = weights.make_blocks(reference, TINY, seed, "float32")
    # spread the constant leaves too, or a wrong bias, skip or norm would not show
    key = jax.random.key(seed + 1)
    for i, leaves in enumerate(blocks.values()):
        for j, leaf in enumerate(sorted(leaves)):
            if leaves[leaf].ndim == 1:
                noise = jax.random.normal(jax.random.fold_in(key, 100 * i + j), leaves[leaf].shape)
                leaves[leaf] = leaves[leaf] + 0.3 * noise
    return blocks


def _tokens(seed=3):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randint(0, TINY["vocab_size"], (ROWS, SEQ))),
            jnp.asarray(rng.randint(0, TINY["vocab_size"], (ROWS, SEQ))))


def _program(hf: dict):
    model = FalconH1ForCausalLM(FalconH1Config.from_hf(hf),
                                BackendConfig(dtype="float32", remat_policy="none"))
    groups = reference.layer_groups(TINY)
    params = adapter.from_reference(weights.stack_layers(_blocks(), groups))
    want = jax.tree.map(lambda x: (x.shape, x.dtype), model.abstract_params(jnp.float32))
    assert jax.tree.map(lambda x: (x.shape, x.dtype), params) == want
    return model, params


def _reference_logits():
    blocks = _blocks()
    ids, _ = _tokens()
    x = reference.embed_block(blocks["embed"], ids, m=TINY)
    for i in range(TINY["num_hidden_layers"]):
        x = reference.layer_block(blocks[f"layer_{i}"], x, m=TINY)
    return reference.logits_block(blocks["head"], x, m=TINY)


def test_logits_loss_and_every_gradient_match_the_plain_reference():
    ids, labels = _tokens()
    groups = reference.layer_groups(TINY)
    with jax.default_matmul_precision("highest"):
        grads = {}
        ref_loss = reference.loss_and_grads(_blocks(), ids, labels, m=TINY,
                                            on_grad=lambda block, g: grads.__setitem__(block, g))
        ref_grads = weights.stack_layers(grads, groups)
        ref_logits = _reference_logits()
        model, params = _program(TINY)

        def loss_fn(p):
            logits = model(p, ids, segment_ids=jnp.ones_like(ids))
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
            return -jnp.take_along_axis(logp, labels[..., None], -1).mean(), logits

        (loss, logits), got = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(params)
    np.testing.assert_allclose(logits, ref_logits, atol=2e-5, rtol=1e-4)
    assert float(loss) == pytest.approx(float(ref_loss), abs=2e-5)
    got = adapter.to_reference(got)
    assert set(got) == set(ref_grads)
    for leaf, want in ref_grads.items():
        scale = float(np.abs(np.asarray(want)).max())
        assert scale > 0, leaf  # every leaf takes a gradient
        np.testing.assert_allclose(got[leaf], want, atol=2e-4 * scale, rtol=2e-3, err_msg=leaf)


@pytest.mark.parametrize("key,index", MULTIPLIERS,
                         ids=[k if i is None else f"{k}_{i}" for k, i in MULTIPLIERS])
def test_a_program_without_one_multiplier_does_not_match(key, index):
    """The comparison above is tight enough for each scalar: with that one alone taken out
    of the PROGRAM's configuration (set to 1) the logits leave the tolerance it passes by."""
    without = dict(TINY)
    if index is None:
        without[key] = 1.0
    else:
        without[key] = [1.0 if i == index else v for i, v in enumerate(TINY[key])]
    ids, _ = _tokens()
    with jax.default_matmul_precision("highest"):
        ref_logits = np.asarray(_reference_logits())
        model, params = _program(without)
        logits = np.asarray(jax.jit(model)(params, ids, segment_ids=jnp.ones_like(ids)))
    assert not np.allclose(logits, ref_logits, atol=2e-5, rtol=1e-4)
    assert np.abs(logits - ref_logits).max() > 1e-3


def test_the_mup_vector_lies_over_the_in_projections_five_segments():
    cfg = FalconH1Config.from_hf(PUBLISHED)
    vec = np.asarray(cfg.mup_vector())
    assert vec.shape == (cfg.in_proj_dim,) == (4096 + 5120 + 32,)
    edges = np.cumsum([0, 4096, 4096, 512, 512, 32])
    for (a, b), m in zip(zip(edges, edges[1:]), PUBLISHED["ssm_multipliers"]):
        assert (vec[a:b] == np.float32(m)).all()


def test_from_hf_on_the_published_config_gives_the_published_sizes():
    cfg = FalconH1Config.from_hf(PUBLISHED)
    assert (cfg.num_hidden_layers, cfg.hidden_size, cfg.intermediate_size) == (72, 5120, 21504)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim) == (20, 4, 128)
    assert (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_ssm) == (32, 128, 4096)
    assert (cfg.mamba_n_groups, cfg.mamba_d_state, cfg.mamba_d_conv, cfg.mamba_chunk_size) == (
        2, 256, 4, 128)
    assert (cfg.conv_dim, cfg.in_proj_dim, cfg.vocab_size) == (5120, 9248, 261120)
    assert cfg.rope_theta == 1e11 and cfg.rms_norm_eps == 1e-5
    assert cfg.attention.attention_multiplier == pytest.approx(0.011048543456039804 / 128 ** 0.5)
    shapes = FalconH1ForCausalLM(cfg).abstract_params()
    block = sum(int(np.prod(x.shape[1:])) for x in jax.tree.leaves(shapes["layers"]))
    assert block == 430_120_032  # attention 31.46 M + mixer 68.35 M + MLP 330.30 M + two norms
    assert resolve_model_class("FalconH1ForCausalLM") is FalconH1ForCausalLM
    # a variant the model does not compute is refused by name, not run wrongly
    with pytest.raises(ValueError, match="mamba_norm_before_gate"):
        FalconH1Config.from_hf(dict(PUBLISHED, mamba_norm_before_gate=True))
    # mamba_expand is read only where mamba_d_ssm is absent, as the published code reads it
    assert FalconH1Config.from_hf(dict(PUBLISHED, mamba_d_ssm=None, mamba_n_heads=80,
                                       mamba_d_head=None)).mamba_d_ssm == 10240


def test_adapter_names_round_trip_to_the_published_module_names():
    cfg = FalconH1Config.from_hf(TINY)
    model = FalconH1ForCausalLM(cfg)
    params = jax.tree.map(np.asarray, model.init(jax.random.key(0), jnp.float32))
    sd_adapter = model.state_dict_adapter()
    hf = sd_adapter.to_hf(params)
    for name, shape in {
        "model.embed_tokens.weight": (128, 64),
        "model.layers.0.input_layernorm.weight": (64,),
        "model.layers.1.pre_ff_layernorm.weight": (64,),
        "model.layers.1.mamba.in_proj.weight": (64 + 64 + 2 * 2 * 16 + 4, 64),
        "model.layers.0.mamba.conv1d.weight": (128, 1, 4),
        "model.layers.0.mamba.conv1d.bias": (128,),
        "model.layers.0.mamba.A_log": (4,), "model.layers.0.mamba.D": (4,),
        "model.layers.0.mamba.dt_bias": (4,), "model.layers.0.mamba.norm.weight": (64,),
        "model.layers.0.mamba.out_proj.weight": (64, 64),
        "model.layers.0.self_attn.q_proj.weight": (64, 64),
        "model.layers.1.self_attn.k_proj.weight": (32, 64),
        "model.layers.0.self_attn.v_proj.weight": (32, 64),
        "model.layers.0.self_attn.o_proj.weight": (64, 64),
        "model.layers.1.feed_forward.gate_proj.weight": (96, 64),
        "model.layers.0.feed_forward.up_proj.weight": (96, 64),
        "model.layers.0.feed_forward.down_proj.weight": (64, 96),
        "model.final_layernorm.weight": (64,), "lm_head.weight": (128, 64),
    }.items():
        assert hf[name].shape == shape, name
    assert len(hf) == 3 + 2 * 17
    back = sd_adapter.from_hf(hf)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
        np.testing.assert_array_equal(a, b)
    assert set(model.logical_axes()["layers"]) == set(params["layers"])


def test_no_decode_cache_yet_is_said_not_guessed():
    model = FalconH1ForCausalLM(FalconH1Config.from_hf(TINY))
    with pytest.raises(NotImplementedError, match="decode cache"):
        model({}, _tokens()[0], cache={})


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_nemotron_block_is_bit_identical_across_the_mixers_move(dtype):
    """``nemotron_v3``'s ``mamba_block`` as it stood before its body became
    ``ops.mamba2.mamba2_mixer`` (the closure, written out here), against the model: logits
    and every gradient, packed documents and the optional biases included."""
    from automodel_tpu.models.nemotron_v3.model import NemotronHForCausalLM, NemotronV3Config
    from automodel_tpu.ops.fp8 import project
    from automodel_tpu.ops.gated_delta import causal_conv1d
    from automodel_tpu.ops.mamba2 import group_rms_norm_gated, mamba_chunk_scan, softplus_dt
    from automodel_tpu.ops.norms import rms_norm

    cfg = NemotronV3Config(vocab_size=128, hidden_size=64, num_hidden_layers=2,
                           layers_block_type=("mamba", "mamba"), mamba_num_heads=4,
                           mamba_head_dim=16, ssm_state_size=16, n_groups=2, chunk_size=32,
                           use_bias=True)
    model = NemotronHForCausalLM(cfg, BackendConfig(dtype=dtype, remat_policy="none",
                                                    scan_layers=False))
    dt = jnp.dtype(dtype)
    params = model.init(jax.random.key(5), dt)
    ids = jax.random.randint(jax.random.key(6), (1, 64), 0, 128)
    seg = jnp.concatenate([jnp.ones((1, 24), jnp.int32), 2 * jnp.ones((1, 40), jnp.int32)], 1)
    B, S = ids.shape
    eps = cfg.layer_norm_epsilon
    reset = jnp.concatenate([jnp.zeros((B, 1), bool), seg[:, 1:] != seg[:, :-1]], axis=1)

    def block_before_the_move(lp, h):
        x = rms_norm(h, lp["norm"], eps).astype(dt)
        inter, hm = cfg.mamba_intermediate, cfg.mamba_num_heads
        gns = cfg.n_groups * cfg.ssm_state_size
        proj = project(x, lp["in_proj"], 1, "default") + lp["b_in"]
        gate, xbc, dt_raw = jnp.split(proj, [inter, inter + cfg.conv_dim], axis=-1)
        xbc = causal_conv1d(xbc, lp["conv_w"], segment_ids=seg, bias=lp.get("b_conv"))
        xi, Bm, Cm = jnp.split(xbc, [inter, inter + gns], axis=-1)
        step = softplus_dt(dt_raw, lp["dt_bias"], cfg.time_step_limit)
        A = -jnp.exp(lp["a_log"].astype(jnp.float32))
        y, _ = mamba_chunk_scan(
            xi.reshape(B, S, hm, cfg.mamba_head_dim), step, A,
            Bm.reshape(B, S, cfg.n_groups, cfg.ssm_state_size),
            Cm.reshape(B, S, cfg.n_groups, cfg.ssm_state_size),
            lp["d_skip"], chunk_size=cfg.chunk_size, reset_mask=reset, mesh=None)
        y = group_rms_norm_gated(y.reshape(B, S, inter), lp["gated_norm"], gate,
                                 group_size=inter // cfg.n_groups, eps=eps)
        return h + project(y, lp["out_proj"], 1, "default") + lp["b_out"]

    def before(p):
        h = p["embed"].astype(dt)[ids]
        for i in range(2):
            lp = {k: v[i] if k == "a_log" else v[i].astype(dt)
                  for k, v in p["mamba_layers"].items()}
            h = jax.checkpoint(block_before_the_move)(lp, h)
        h = rms_norm(h, p["final_norm"].astype(dt), eps)
        return jnp.einsum("bsd,dv->bsv", h, p["lm_head"].astype(dt))

    def loss(forward, p):
        logits = forward(p)
        return jnp.mean(jax.nn.logsumexp(logits.astype(jnp.float32), -1)), logits

    after = lambda q: model(q, ids, segment_ids=seg)[0]  # noqa: E731
    if dtype == "bfloat16":
        # operation by operation: under jit the two spellings fuse differently, and a fused
        # bf16 chain keeps float32 between its steps, so only the eager values can be equal
        np.testing.assert_array_equal(np.asarray(after(params), np.float32),
                                      np.asarray(before(params), np.float32))
        return
    (_, want), want_g = jax.jit(jax.value_and_grad(
        lambda p: loss(before, p), has_aux=True))(params)
    (_, got), got_g = jax.jit(jax.value_and_grad(
        lambda p: loss(after, p), has_aux=True))(params)
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
    for a, b in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
