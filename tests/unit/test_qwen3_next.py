"""Qwen3-Next hybrid family: gated delta rule parity, logits parity vs HF, interop."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from automodel_tpu.models.auto import AutoModelForCausalLM
from automodel_tpu.models.common.backend import BackendConfig
from automodel_tpu.models.qwen3_next.model import Qwen3NextConfig, Qwen3NextForCausalLM
from automodel_tpu.ops.gated_delta import causal_conv1d, chunk_gated_delta_rule

transformers = pytest.importorskip("transformers")
torch = pytest.importorskip("torch")


def _fp32_backend(**kw):
    return BackendConfig(dtype="float32", remat_policy="full", **kw)


def tiny_cfg(**kw):
    base = dict(
        vocab_size=128, hidden_size=64, intermediate_size=0, moe_intermediate_size=32,
        shared_expert_intermediate_size=48, num_hidden_layers=4,
        layer_types=["linear_attention", "linear_attention", "linear_attention", "full_attention"],
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        linear_num_value_heads=4, linear_num_key_heads=2, linear_key_head_dim=16,
        linear_value_head_dim=16, linear_conv_kernel_dim=4,
        num_experts=8, num_experts_per_tok=2, decoder_sparse_step=1, mlp_only_layers=[],
        norm_topk_prob=True, max_position_embeddings=128, partial_rotary_factor=0.25,
    )
    base.update(kw)
    return transformers.Qwen3NextConfig(**base)


class TestGatedDeltaRule:
    def test_matches_torch_reference(self):
        from transformers.models.qwen3_next.modeling_qwen3_next import (
            torch_chunk_gated_delta_rule,
        )

        rng = np.random.RandomState(0)
        B, S, H, dk, dv = 2, 133, 3, 16, 24
        q = rng.randn(B, S, H, dk).astype(np.float32)
        k = rng.randn(B, S, H, dk).astype(np.float32)
        v = rng.randn(B, S, H, dv).astype(np.float32)
        g = -np.abs(rng.randn(B, S, H)).astype(np.float32)
        beta = (1 / (1 + np.exp(-rng.randn(B, S, H)))).astype(np.float32)

        ref, ref_state = torch_chunk_gated_delta_rule(
            torch.tensor(q), torch.tensor(k), torch.tensor(v), torch.tensor(g),
            torch.tensor(beta), chunk_size=64, initial_state=None,
            output_final_state=True, use_qk_l2norm_in_kernel=True,
        )
        ours, state = chunk_gated_delta_rule(
            jnp.array(q), jnp.array(k), jnp.array(v), jnp.array(g), jnp.array(beta),
            chunk_size=64, output_final_state=True,
        )
        np.testing.assert_allclose(np.asarray(ours), ref.numpy(), atol=2e-5)
        np.testing.assert_allclose(np.asarray(state), ref_state.numpy(), atol=2e-5)

    def test_chunk_size_invariance(self):
        rng = np.random.RandomState(1)
        B, S, H, dk, dv = 1, 50, 2, 8, 8
        args = [
            jnp.array(rng.randn(B, S, H, d).astype(np.float32)) for d in (dk, dk, dv)
        ]
        g = jnp.array(-np.abs(rng.randn(B, S, H)).astype(np.float32))
        beta = jnp.array((1 / (1 + np.exp(-rng.randn(B, S, H)))).astype(np.float32))
        out16, _ = chunk_gated_delta_rule(*args, g, beta, chunk_size=16)
        out64, _ = chunk_gated_delta_rule(*args, g, beta, chunk_size=64)
        np.testing.assert_allclose(np.asarray(out16), np.asarray(out64), atol=1e-5)

    def test_causal_conv1d_is_causal(self):
        rng = np.random.RandomState(2)
        x = jnp.array(rng.randn(1, 10, 6).astype(np.float32))
        w = jnp.array(rng.randn(6, 4).astype(np.float32))
        y1 = causal_conv1d(x, w)
        x2 = x.at[0, 5:].set(123.0)  # future perturbation
        y2 = causal_conv1d(x2, w)
        np.testing.assert_allclose(np.asarray(y1[0, :5]), np.asarray(y2[0, :5]), atol=1e-6)


def _save_hf(model, tmp_path):
    d = str(tmp_path / "hf")
    model.save_pretrained(d, safe_serialization=True)
    return d


class TestQwen3NextParity:
    def test_logits_match_hf(self, tmp_path):
        torch.manual_seed(0)
        hf = transformers.Qwen3NextForCausalLM(tiny_cfg()).eval()
        d = _save_hf(hf, tmp_path)
        model, params = AutoModelForCausalLM.from_pretrained(
            d, dtype=jnp.float32, backend=_fp32_backend()
        )
        rng = np.random.RandomState(0)
        ids = rng.randint(0, 128, (2, 16))
        ours, stats = model(params, jnp.asarray(ids), training=False)
        with torch.no_grad():
            theirs = hf(torch.tensor(ids)).logits.float().numpy()
        np.testing.assert_allclose(np.asarray(ours), theirs, atol=5e-4, rtol=1e-3)
        assert stats["expert_load"].shape == (4, 8)

    def test_grouped_scan_matches_unrolled(self, tmp_path):
        torch.manual_seed(1)
        hf = transformers.Qwen3NextForCausalLM(tiny_cfg(num_hidden_layers=8, layer_types=None))
        d = _save_hf(hf, tmp_path)
        model, params = AutoModelForCausalLM.from_pretrained(
            d, dtype=jnp.float32, backend=_fp32_backend()
        )
        assert model.config.period == 4
        model_unrolled = Qwen3NextForCausalLM(
            model.config, _fp32_backend(scan_layers=False)
        )
        ids = jnp.asarray(np.random.RandomState(1).randint(0, 128, (1, 24)))
        a, _ = model(params, ids, training=False)
        b, _ = model_unrolled(params, ids, training=False)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)

    def test_roundtrip_and_key_parity(self, tmp_path):
        torch.manual_seed(2)
        hf = transformers.Qwen3NextForCausalLM(tiny_cfg())
        d = _save_hf(hf, tmp_path)
        model, params = AutoModelForCausalLM.from_pretrained(
            d, dtype=jnp.float32, backend=_fp32_backend()
        )
        adapter = model.state_dict_adapter()
        hf_dict = adapter.to_hf(params)
        theirs = {k for k in hf.state_dict() if "rotary_emb" not in k}
        assert set(hf_dict) == theirs
        for k_, v in hf.state_dict().items():
            if k_ in hf_dict:
                np.testing.assert_allclose(
                    hf_dict[k_], v.numpy(), atol=1e-6, err_msg=k_
                )

    def test_padded_batch_masks_leakage(self, tmp_path):
        torch.manual_seed(3)
        hf = transformers.Qwen3NextForCausalLM(tiny_cfg())
        d = _save_hf(hf, tmp_path)
        model, params = AutoModelForCausalLM.from_pretrained(
            d, dtype=jnp.float32, backend=_fp32_backend()
        )
        ids = jnp.asarray(np.random.RandomState(3).randint(0, 128, (1, 12)))
        mask = jnp.ones((1, 12), bool).at[0, 8:].set(False)
        out_masked, _ = model(params, ids, token_mask=mask, training=False)
        ids2 = ids.at[0, 8:].set(7)  # different padding content
        out_masked2, _ = model(params, ids2, token_mask=mask, training=False)
        np.testing.assert_allclose(
            np.asarray(out_masked[0, :8]), np.asarray(out_masked2[0, :8]), atol=1e-5
        )

    def test_training_grads_finite(self, tmp_path):
        torch.manual_seed(4)
        hf = transformers.Qwen3NextForCausalLM(tiny_cfg(router_aux_loss_coef=0.01))
        d = _save_hf(hf, tmp_path)
        model, params = AutoModelForCausalLM.from_pretrained(
            d, dtype=jnp.float32, backend=_fp32_backend()
        )
        ids = jnp.asarray(np.random.RandomState(4).randint(0, 128, (2, 16)))

        def loss_fn(p):
            logits, stats = model(p, ids[:, :-1], training=True)
            labels = ids[:, 1:]
            ll = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
            ce = -jnp.take_along_axis(ll, labels[..., None], -1).mean()
            return ce + 0.01 * stats["aux_loss"]

        loss, grads = jax.value_and_grad(loss_fn)(params)
        assert np.isfinite(float(loss))
        flat = jax.tree.leaves(grads)
        assert all(np.all(np.isfinite(np.asarray(g))) for g in flat)


class TestHeldShareAndLinearBackend:
    HF = dict(
        vocab_size=128, hidden_size=64, moe_intermediate_size=32,
        shared_expert_intermediate_size=48, num_hidden_layers=4, full_attention_interval=4,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        linear_num_value_heads=4, linear_num_key_heads=2, linear_key_head_dim=16,
        linear_value_head_dim=16, linear_conv_kernel_dim=4,
        num_experts=8, num_experts_per_tok=2, norm_topk_prob=True,
    )

    def test_from_hf_without_the_held_keys_holds_every_expert(self):
        cfg = Qwen3NextConfig.from_hf(self.HF)
        assert cfg.moe.n_routed_experts == 8 and cfg.moe.holds_all_experts
        assert cfg.moe.n_held_experts is None and cfg.moe.first_held_expert == 0

    def test_from_hf_reads_a_held_share_and_the_stack_holds_it(self):
        hf = dict(self.HF, num_experts=2, router_n_experts=8, first_held_expert=4)
        model = Qwen3NextForCausalLM(Qwen3NextConfig.from_hf(hf), _fp32_backend())
        moe = model.config.moe
        assert (moe.n_routed_experts, moe.held_experts, moe.first_held_expert) == (8, 2, 4)
        assert moe.shared_expert_gate and moe.norm_topk_prob and moe.score_func == "softmax"
        shapes = model.abstract_params(jnp.float32)
        assert shapes["linear_layers"]["moe"]["gate"]["weight"].shape == (3, 8, 64)
        assert shapes["linear_layers"]["moe"]["experts"]["gate_up_proj"].shape == (3, 2, 64, 64)
        assert shapes["full_layers"]["moe"]["experts"]["down_proj"].shape == (1, 2, 32, 64)
        # the checkpoint mapping counts the experts held here, not the router's width
        assert model.state_dict_adapter().num_experts == 2
        with pytest.raises(ValueError, match="held experts"):
            Qwen3NextConfig.from_hf(dict(hf, first_held_expert=7))

    @pytest.mark.parametrize("mixer", ["delta_net", "gated_attention"])
    def test_backend_linear_fp8_reaches_a_mixers_projections(self, mixer):
        """The benchmark's control (``backend.linear: fp8``) has to bite in both mixers:
        with every projection through ``ops.fp8.project`` a mixer's output under fp8
        differs from bf16 by some percent, where bare einsums would leave it identical."""
        from automodel_tpu.ops.rope import rope_frequencies

        cfg = Qwen3NextConfig.from_hf(self.HF)
        params = Qwen3NextForCausalLM(cfg).init(jax.random.key(0), jnp.bfloat16)
        x = jax.random.normal(jax.random.key(1), (2, 32, 64), jnp.bfloat16)

        def run(linear):
            model = Qwen3NextForCausalLM(cfg, BackendConfig(dtype="bfloat16", linear=linear,
                                                            attention="xla"))
            if mixer == "delta_net":
                lp = jax.tree.map(lambda a: a[0], params["linear_layers"])
                return model._gated_delta_attn(lp, x, jnp.bfloat16)
            lp = jax.tree.map(lambda a: a[0], params["full_layers"])
            inv_freq = rope_frequencies(cfg.head_dim, cfg.rope_theta, None,
                                        partial_rotary_factor=cfg.partial_rotary_factor)
            positions = jnp.broadcast_to(jnp.arange(32), (2, 32))
            return model._gated_full_attn(lp, x, positions, None, inv_freq, 1.0, jnp.bfloat16)

        plain, fp8 = (np.asarray(run(b), np.float32) for b in ("default", "fp8"))
        gap = np.linalg.norm(fp8 - plain) / np.linalg.norm(plain)
        assert 5e-3 < gap < 0.3, gap
