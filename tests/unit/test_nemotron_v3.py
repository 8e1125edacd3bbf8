"""NemotronV3 hybrid (Mamba2/Attention/MLP/MoE): SSD kernel vs naive recurrence,
run-grouped scan vs unrolled, packing isolation, adapter round-trip, training grads.
(No HF implementation in this transformers version; reference nemotron_v3/ is the
spec, so model checks are semantic self-consistency.)"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from automodel_tpu.models.common.backend import BackendConfig
from automodel_tpu.models.nemotron_v3.model import NemotronHForCausalLM, NemotronV3Config
from automodel_tpu.moe.config import MoEConfig
from automodel_tpu.ops.mamba2 import group_rms_norm_gated, mamba_chunk_scan, softplus_dt


def _naive_ssd(x, dt, A, B, C, D):
    b, S, H, dh = x.shape
    G, N = B.shape[2], B.shape[3]
    r = H // G
    h = np.zeros((b, H, dh, N), np.float64)
    y = np.zeros(x.shape, np.float64)
    for t in range(S):
        for hd in range(H):
            g = hd // r
            decay = np.exp(dt[:, t, hd, None, None] * A[hd])
            h[:, hd] = h[:, hd] * decay + dt[:, t, hd, None, None] * np.einsum(
                "bd,bn->bdn", x[:, t, hd], B[:, t, g]
            )
            y[:, t, hd] = np.einsum("bdn,bn->bd", h[:, hd], C[:, t, g]) + D[hd] * x[:, t, hd]
    return y


class TestMamba2Kernel:
    def test_matches_naive_recurrence(self):
        rng = np.random.RandomState(0)
        b, S, H, dh, G, N = 2, 37, 4, 8, 2, 6
        x = rng.randn(b, S, H, dh).astype(np.float32)
        dt = (np.abs(rng.randn(b, S, H)) * 0.5).astype(np.float32)
        A = -np.abs(rng.randn(H)).astype(np.float32)
        B = rng.randn(b, S, G, N).astype(np.float32)
        C = rng.randn(b, S, G, N).astype(np.float32)
        D = rng.randn(H).astype(np.float32)
        ref = _naive_ssd(x, dt, A, B, C, D)
        for cs in (16, 64):
            ours, _ = mamba_chunk_scan(
                jnp.array(x), jnp.array(dt), jnp.array(A), jnp.array(B), jnp.array(C),
                jnp.array(D), chunk_size=cs,
            )
            np.testing.assert_allclose(np.asarray(ours), ref, atol=1e-4)

    def test_reset_mask_isolates_segments(self):
        rng = np.random.RandomState(1)
        b, S, H, dh, G, N = 1, 24, 2, 4, 1, 4
        mk = lambda *s: rng.randn(*s).astype(np.float32)
        x, B, C = mk(b, S, H, dh), mk(b, S, G, N), mk(b, S, G, N)
        dt = (np.abs(mk(b, S, H)) * 0.5).astype(np.float32)
        A = -np.abs(mk(H)).astype(np.float32)
        D = mk(H)
        reset = np.zeros((b, S), bool)
        reset[0, 10] = True  # doc boundary at t=10
        out, _ = mamba_chunk_scan(
            jnp.array(x), jnp.array(dt), jnp.array(A), jnp.array(B), jnp.array(C),
            jnp.array(D), chunk_size=8, reset_mask=jnp.array(reset),
        )
        # second doc alone must reproduce out[10:]
        out2, _ = mamba_chunk_scan(
            jnp.array(x[:, 10:]), jnp.array(dt[:, 10:]), jnp.array(A),
            jnp.array(B[:, 10:]), jnp.array(C[:, 10:]), jnp.array(D), chunk_size=8,
        )
        np.testing.assert_allclose(np.asarray(out[:, 10:]), np.asarray(out2), atol=1e-4)

    def test_gated_group_norm(self):
        rng = np.random.RandomState(2)
        x = jnp.array(rng.randn(2, 5, 8).astype(np.float32))
        w = jnp.array(rng.randn(8).astype(np.float32))
        z = jnp.array(rng.randn(2, 5, 8).astype(np.float32))
        # norm_before_gate=False: gate multiplies before normalization
        got = group_rms_norm_gated(x, w, z, group_size=4, eps=1e-5)
        xg = np.asarray(x) * (np.asarray(z) * (1 / (1 + np.exp(-np.asarray(z)))))
        xg = xg.reshape(2, 5, 2, 4)
        ref = xg / np.sqrt((xg**2).mean(-1, keepdims=True) + 1e-5)
        ref = ref.reshape(2, 5, 8) * np.asarray(w)
        np.testing.assert_allclose(np.asarray(got), ref, atol=1e-5)


def _cfg(**kw):
    base = dict(
        vocab_size=128, hidden_size=64, intermediate_size=96, num_hidden_layers=6,
        layers_block_type=("mamba", "mamba", "attention", "mlp", "moe", "mamba"),
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        mamba_num_heads=4, mamba_head_dim=8, ssm_state_size=16, n_groups=2,
        chunk_size=16, conv_kernel=4,
        moe=MoEConfig(
            n_routed_experts=8, n_activated_experts=2, dim=64, moe_inter_dim=32,
            n_shared_experts=1, n_expert_groups=2, n_limited_groups=1,
            score_func="sigmoid", route_scale=2.5, norm_topk_prob=True,
            expert_activation="relu2", shared_expert_activation="relu2",
            shared_expert_inter_dim=48, force_score_correction_bias=True,
        ),
    )
    base.update(kw)
    return NemotronV3Config(**base)


def _fp32_backend(**kw):
    return BackendConfig(dtype="float32", remat_policy="full", **kw)


class TestNemotronV3:
    def test_forward_shapes_and_finite(self):
        model = NemotronHForCausalLM(_cfg(), _fp32_backend())
        params = model.init(jax.random.key(0), jnp.float32)
        ids = jnp.asarray(np.random.RandomState(0).randint(0, 128, (2, 16)))
        logits, stats = model(params, ids, training=False)
        assert logits.shape == (2, 16, 128)
        assert np.all(np.isfinite(np.asarray(logits)))
        assert stats["expert_load"].shape == (1, 8)

    def test_scan_matches_unrolled(self):
        cfg = _cfg()
        model = NemotronHForCausalLM(cfg, _fp32_backend())
        params = model.init(jax.random.key(1), jnp.float32)
        model_u = NemotronHForCausalLM(cfg, _fp32_backend(scan_layers=False))
        ids = jnp.asarray(np.random.RandomState(1).randint(0, 128, (1, 20)))
        a, _ = model(params, ids, training=False)
        b, _ = model_u(params, ids, training=False)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)

    def test_causality(self):
        model = NemotronHForCausalLM(_cfg(), _fp32_backend())
        params = model.init(jax.random.key(2), jnp.float32)
        ids = jnp.asarray(np.random.RandomState(2).randint(0, 128, (1, 16)))
        a, _ = model(params, ids, training=False)
        ids2 = ids.at[0, 12:].set((ids[0, 12:] + 1) % 128)
        b, _ = model(params, ids2, training=False)
        np.testing.assert_allclose(np.asarray(a[0, :12]), np.asarray(b[0, :12]), atol=1e-5)

    def test_packed_segments_isolated(self):
        model = NemotronHForCausalLM(_cfg(), _fp32_backend())
        params = model.init(jax.random.key(3), jnp.float32)
        rng = np.random.RandomState(3)
        ids = jnp.asarray(rng.randint(0, 128, (1, 16)))
        seg = jnp.asarray(np.array([[0] * 8 + [1] * 8]))
        a, _ = model(params, ids, segment_ids=seg, training=False)
        ids2 = ids.at[0, :8].set((ids[0, :8] + 3) % 128)  # perturb doc 0 only
        b, _ = model(params, ids2, segment_ids=seg, training=False)
        np.testing.assert_allclose(np.asarray(a[0, 8:]), np.asarray(b[0, 8:]), atol=1e-5)

    def test_adapter_roundtrip(self):
        model = NemotronHForCausalLM(_cfg(), _fp32_backend())
        params = model.init(jax.random.key(4), jnp.float32)
        adapter = model.state_dict_adapter()
        hf = adapter.to_hf(params)
        for k in (
            "backbone.embed_tokens.weight",
            "backbone.norm_f.weight",
            "backbone.layers.0.mixer.in_proj.weight",
            "backbone.layers.0.mixer.A_log",
            "backbone.layers.2.mixer.q_proj.weight",
            "backbone.layers.3.mixer.up_proj.weight",
            "backbone.layers.4.mixer.gate.weight",
            "backbone.layers.4.mixer.experts.0.up_proj.weight",
            "backbone.layers.4.mixer.shared_experts.down_proj.weight",
        ):
            assert k in hf, k
        back = adapter.from_hf(hf)
        flat_a = jax.tree.leaves(params)
        flat_b = jax.tree.leaves(back)
        assert len(flat_a) == len(flat_b)
        for a, b in zip(flat_a, flat_b):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)

    def test_grads_finite(self):
        model = NemotronHForCausalLM(_cfg(), _fp32_backend())
        params = model.init(jax.random.key(5), jnp.float32)
        ids = jnp.asarray(np.random.RandomState(5).randint(0, 128, (2, 16)))

        def loss_fn(p):
            logits, _ = model(p, ids[:, :-1], training=True)
            ll = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
            return -jnp.take_along_axis(ll, ids[:, 1:, None], -1).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        assert np.isfinite(float(loss))
        assert all(np.all(np.isfinite(np.asarray(g))) for g in jax.tree.leaves(grads))

    def test_from_hf(self):
        hf = dict(
            vocab_size=128, hidden_size=64, intermediate_size=96, num_hidden_layers=4,
            layers_block_type=["mamba", "attention", "mlp", "moe"],
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            mamba_num_heads=4, mamba_head_dim=8, ssm_state_size=16, n_groups=2,
            n_routed_experts=8, num_experts_per_tok=2, n_group=2, topk_group=1,
            moe_intermediate_size=32, moe_shared_expert_intermediate_size=48,
            routed_scaling_factor=2.5, norm_topk_prob=True,
        )
        cfg = NemotronV3Config.from_hf(hf)
        assert cfg.moe.expert_activation == "relu2"
        order, present = cfg.iterations
        assert order == ("mamba", "attention", "mlp", "moe") and present.tolist() == [[True] * 4]
