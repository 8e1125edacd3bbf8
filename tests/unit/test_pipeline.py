"""Pipeline parallelism: pp-sharded layer scan + ppermute ticks vs the plain decoder."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from automodel_tpu.models.common.backend import BackendConfig
from automodel_tpu.models.llama.model import LlamaConfig, LlamaForCausalLM
from automodel_tpu.ops.losses import masked_cross_entropy
from automodel_tpu.parallel.mesh import MeshContext
from automodel_tpu.parallel.pipeline import make_dense_decoder_pp_loss



@pytest.fixture(scope="module")
def pp_mesh():
    devs = jax.devices()
    assert len(devs) == 8
    return MeshContext(pp=2, dp_shard=2, tp=2, world_size=8).build_mesh(devs)


def _setup(n_layers=4):
    cfg = LlamaConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=n_layers, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64,
    )
    backend = BackendConfig(dtype="float32")
    model = LlamaForCausalLM(cfg, backend)
    params = model.init(jax.random.key(0), jnp.float32)
    return cfg, backend, model, params


def _batch_stack(cfg, n_micro=4, b=4, s=16, seed=0):
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, cfg.vocab_size, (n_micro, b, s)).astype(np.int32)
    return {
        "input_ids": jnp.asarray(ids),
        "labels": jnp.asarray(ids.copy()),
        "positions": jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), ids.shape),
        "segment_ids": jnp.ones((n_micro, b, s), jnp.int32),
    }


def _pp_loss_fn(cfg, backend, mesh):
    model = LlamaForCausalLM(cfg, backend)
    return make_dense_decoder_pp_loss(model, mesh)


def _ref_loss(cfg, backend, model, params, batch_stack, n):
    losses = []
    for i in range(batch_stack["input_ids"].shape[0]):
        mb = jax.tree.map(lambda a: a[i], batch_stack)
        logits = model(params, mb["input_ids"], positions=mb["positions"],
                       segment_ids=mb["segment_ids"])
        losses.append(masked_cross_entropy(logits, mb["labels"], n))
    return sum(losses)


class TestPipeline:
    def test_loss_matches_reference(self, pp_mesh):
        cfg, backend, model, params = _setup()
        batch = _batch_stack(cfg)
        n = float((batch["labels"] != -100).sum())
        pp_loss = _pp_loss_fn(cfg, backend, pp_mesh)
        with jax.sharding.set_mesh(pp_mesh):
            got = jax.jit(pp_loss)(params, batch, n)
        want = _ref_loss(cfg, backend, model, params, batch, n)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-5)

    def test_grads_match_reference(self, pp_mesh):
        cfg, backend, model, params = _setup()
        batch = _batch_stack(cfg, seed=1)
        n = float((batch["labels"] != -100).sum())
        pp_loss = _pp_loss_fn(cfg, backend, pp_mesh)
        with jax.sharding.set_mesh(pp_mesh):
            g_pp = jax.jit(jax.grad(pp_loss))(params, batch, n)
        g_ref = jax.grad(lambda p: _ref_loss(cfg, backend, model, p, batch, n))(params)
        flat_pp = jax.tree.leaves_with_path(g_pp)
        flat_ref = dict(jax.tree.leaves_with_path(g_ref))
        for path, leaf in flat_pp:
            np.testing.assert_allclose(
                np.asarray(leaf), np.asarray(flat_ref[path]), atol=1e-5,
                err_msg=f"grad mismatch at {jax.tree_util.keystr(path)}",
            )

    def test_circular_virtual_stages_match_reference(self, pp_mesh):
        """Interleaved schedule (V=2 rounds over pp=2, 8 layers -> 4 blocks of 2,
        round-major) reproduces the plain decoder loss exactly."""
        cfg, backend, model, params = _setup(n_layers=8)
        batch = _batch_stack(cfg, n_micro=4, seed=3)
        n = float((batch["labels"] != -100).sum())
        pp_loss = make_dense_decoder_pp_loss(model, pp_mesh, circular_repeats=2)
        with jax.sharding.set_mesh(pp_mesh):
            got = jax.jit(pp_loss)(params, batch, n)
        want = _ref_loss(cfg, backend, model, params, batch, n)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-5)

    def test_circular_grads_match(self, pp_mesh):
        cfg, backend, model, params = _setup(n_layers=8)
        batch = _batch_stack(cfg, n_micro=4, seed=4)
        n = float((batch["labels"] != -100).sum())
        pp_loss = make_dense_decoder_pp_loss(model, pp_mesh, circular_repeats=2)
        with jax.sharding.set_mesh(pp_mesh):
            g_pp = jax.jit(jax.grad(pp_loss))(params, batch, n)
        g_ref = jax.grad(lambda p: _ref_loss(cfg, backend, model, p, batch, n))(params)
        flat_ref = dict(jax.tree.leaves_with_path(g_ref))
        for path, leaf in jax.tree.leaves_with_path(g_pp):
            np.testing.assert_allclose(
                np.asarray(leaf), np.asarray(flat_ref[path]), atol=1e-5,
                err_msg=f"grad mismatch at {jax.tree_util.keystr(path)}",
            )

    def test_pp_linear_ce_matches(self, pp_mesh):
        """linear_ce head under PP (no full logits) equals the masked_ce reference."""
        cfg, backend, model, params = _setup()
        batch = _batch_stack(cfg, seed=5)
        n = float((batch["labels"] != -100).sum())
        pp_loss = make_dense_decoder_pp_loss(model, pp_mesh, loss_name="linear_ce")
        with jax.sharding.set_mesh(pp_mesh):
            got = jax.jit(pp_loss)(params, batch, n)
        want = _ref_loss(cfg, backend, model, params, batch, n)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-5)

    def test_tick_counts_and_bubble(self):
        from automodel_tpu.parallel.pipeline import pipeline_ticks

        assert pipeline_ticks(8, 4) == 11
        assert pipeline_ticks(8, 4, circular_repeats=2) == 19
        # bubble fraction shrinks ~V-fold: (pp-1)/(V*n + pp - 1)
        bubble_v1 = (11 - 8) / 11
        bubble_v2 = (19 - 16) / 19
        assert bubble_v2 < bubble_v1 / 1.7

    def test_uneven_micro_count(self, pp_mesh):
        # n_micro not a multiple of pp still schedules correctly
        cfg, backend, model, params = _setup()
        batch = _batch_stack(cfg, n_micro=3, seed=2)
        n = float((batch["labels"] != -100).sum())
        pp_loss = _pp_loss_fn(cfg, backend, pp_mesh)
        with jax.sharding.set_mesh(pp_mesh):
            got = jax.jit(pp_loss)(params, batch, n)
        want = _ref_loss(cfg, backend, model, params, batch, n)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-5)


class TestMoEPPAuxExactWeighting:
    def test_aux_matches_nonpp_with_uneven_labels(self):
        """Per-microbatch aux terms are weighted by each microbatch's OWN
        label-token fraction (riding the ring with the activation), matching the
        non-pp objective exactly even when label counts are uneven — the r2
        design divided by n_micro, exact only for equal counts."""
        from automodel_tpu.models.auto import AutoModelForCausalLM
        from automodel_tpu.parallel.pipeline import make_moe_pp_loss

        mesh = MeshContext(pp=2, dp_shard=2, ep=2, world_size=8).build_mesh(jax.devices())
        hf_cfg = {
            "architectures": ["Qwen3MoeForCausalLM"],
            "vocab_size": 128, "hidden_size": 64, "intermediate_size": 96,
            "moe_intermediate_size": 32, "num_hidden_layers": 2,
            "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
            "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
            "router_aux_loss_coef": 0.05, "max_position_embeddings": 64,
        }
        model = AutoModelForCausalLM.from_config(hf_cfg, BackendConfig(dtype="float32"))
        params = model.init(jax.random.key(1), jnp.float32)

        rng = np.random.RandomState(3)
        n_micro, b, s = 2, 2, 16
        ids = rng.randint(0, 128, (n_micro, b, s)).astype(np.int32)
        labels = ids.copy()
        # sharply uneven label counts: microbatch 0 keeps 4 labels, 1 keeps all
        labels[0, :, :-2] = -100
        batch_stack = {
            "input_ids": jnp.asarray(ids),
            "labels": jnp.asarray(labels),
            "positions": jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), ids.shape),
            "segment_ids": jnp.ones((n_micro, b, s), jnp.int32),
        }
        n = float((labels != -100).sum())

        with mesh:
            pp_loss = make_moe_pp_loss(model, mesh)
            got, aux = jax.jit(lambda p, bs: pp_loss(p, bs, jnp.float32(n)))(
                params, batch_stack
            )

        # non-pp reference: per-microbatch CE + aux * (mb_tokens / n)
        want = 0.0
        coeff = model.config.moe.aux_loss_coeff
        for i in range(n_micro):
            mb = jax.tree.map(lambda a: a[i], batch_stack)
            logits, stats = model(
                params, mb["input_ids"], positions=mb["positions"],
                segment_ids=mb["segment_ids"], training=True,
            )
            mb_tokens = float((np.asarray(mb["labels"]) != -100).sum())
            want += float(masked_cross_entropy(logits, mb["labels"], n))
            want += coeff * float(stats["aux_loss"]) * (mb_tokens / n)
        np.testing.assert_allclose(float(got), want, rtol=2e-5)
        assert aux["expert_load"].shape == (2, 8)


class TestMoEPPA2AComposition:
    """a2a x PP: the pipeline's manual region is flattened to one manual mesh
    over {pp, ep}, so the explicit EP dispatch runs INSIDE the pp stage body
    (no nested shard_map). A pp2 x ep4 world-8 mesh is fully manual — every
    axis of size > 1 is manual — which the shimmed CPU shard_map compiles, so
    unlike the partial-manual pp meshes above these tests need no skip."""

    HF_CFG = {
        "architectures": ["Qwen3MoeForCausalLM"],
        "vocab_size": 128, "hidden_size": 64, "intermediate_size": 96,
        "moe_intermediate_size": 32, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
        "router_aux_loss_coef": 0.0, "max_position_embeddings": 64,
    }

    def _build(self, **backend_kw):
        from automodel_tpu.models.auto import AutoModelForCausalLM

        return AutoModelForCausalLM.from_config(
            self.HF_CFG,
            BackendConfig(dtype="float32", dispatcher="a2a", **backend_kw))

    def _batch(self, n_micro=2, b=4, s=16):
        rng = np.random.RandomState(3)
        ids = rng.randint(0, 128, (n_micro, b, s)).astype(np.int32)
        stack = {
            "input_ids": jnp.asarray(ids), "labels": jnp.asarray(ids.copy()),
            "positions": jnp.broadcast_to(
                jnp.arange(s, dtype=jnp.int32), ids.shape),
            "segment_ids": jnp.ones((n_micro, b, s), jnp.int32),
        }
        return stack, jnp.float32(n_micro * b * s)

    def _mesh(self):
        return MeshContext(pp=2, ep=4, world_size=8).build_mesh(jax.devices())

    def test_steps_and_trains_with_drop_accounting(self):
        from automodel_tpu.parallel.pipeline import make_moe_pp_loss

        mesh = self._mesh()
        model = self._build(ep_capacity_factor=8.0)
        params = model.init(jax.random.key(1), jnp.float32)
        batch_stack, n = self._batch()
        with mesh:
            pp_loss = make_moe_pp_loss(model, mesh)
            loss, aux = jax.jit(lambda p, bs: pp_loss(p, bs, n))(
                params, batch_stack)
            g = jax.jit(jax.grad(lambda p, bs: pp_loss(p, bs, n)[0]))(
                params, batch_stack)
        assert np.isfinite(float(loss))
        # ample capacity: the exact drop accounting reports zero
        assert float(aux["dropped_token_frac"]) == 0.0
        # the a2a path actually trained the experts on both pp stages
        eg = np.asarray(g["moe_layers"]["moe"]["experts"]["gate_up_proj"])
        assert np.isfinite(eg).all() and np.abs(eg).max() > 0

    def test_ce_matches_dense_dispatcher_reference(self):
        """With ample capacity (no drops) and aux coeff 0, pp+a2a reproduces
        the non-pp dense-dispatcher CE. (The a2a aux term is pmean'd over ep
        shards — per-shard load stats, not the global-batch aux — so CE is
        the exact cross-dispatcher contract; see moe/dispatch.py.)"""
        from automodel_tpu.models.auto import AutoModelForCausalLM
        from automodel_tpu.parallel.pipeline import make_moe_pp_loss

        mesh = self._mesh()
        model = self._build(ep_capacity_factor=8.0)
        params = model.init(jax.random.key(1), jnp.float32)
        batch_stack, n = self._batch()
        with mesh:
            got, _ = jax.jit(
                lambda p, bs: make_moe_pp_loss(model, mesh)(p, bs, n))(
                params, batch_stack)

        ref_model = AutoModelForCausalLM.from_config(
            self.HF_CFG, BackendConfig(dtype="float32"))
        want = 0.0
        for i in range(batch_stack["input_ids"].shape[0]):
            mb = jax.tree.map(lambda a: a[i], batch_stack)
            logits, _ = ref_model(
                params, mb["input_ids"], positions=mb["positions"],
                segment_ids=mb["segment_ids"], training=True)
            want += float(masked_cross_entropy(logits, mb["labels"], n))
        np.testing.assert_allclose(float(got), want, rtol=2e-5)

    def test_tight_capacity_reports_drops(self):
        from automodel_tpu.parallel.pipeline import make_moe_pp_loss

        mesh = self._mesh()
        model = self._build(ep_capacity_factor=0.5)
        params = model.init(jax.random.key(1), jnp.float32)
        batch_stack, n = self._batch()
        with mesh:
            _, aux = jax.jit(
                lambda p, bs: make_moe_pp_loss(model, mesh)(p, bs, n))(
                params, batch_stack)
        assert 0.0 < float(aux["dropped_token_frac"]) <= 1.0

    def test_chunked_dispatch_under_pp_bit_identical(self):
        from automodel_tpu.parallel.pipeline import make_moe_pp_loss

        mesh = self._mesh()
        params = self._build(ep_capacity_factor=8.0).init(
            jax.random.key(1), jnp.float32)
        batch_stack, n = self._batch()
        losses = {}
        with mesh:
            for nch in (1, 3):
                model = self._build(ep_capacity_factor=8.0, a2a_chunks=nch)
                losses[nch] = float(jax.jit(
                    lambda p, bs, m=model: make_moe_pp_loss(m, mesh)(p, bs, n))(
                    params, batch_stack)[0])
        assert losses[1] == losses[3]

    def test_pallas_experts_under_pp_a2a(self):
        from automodel_tpu.parallel.pipeline import make_moe_pp_loss

        mesh = self._mesh()
        params = self._build(ep_capacity_factor=8.0).init(
            jax.random.key(1), jnp.float32)
        batch_stack, n = self._batch()
        losses = {}
        with mesh:
            for eb in ("ragged_dot", "pallas"):
                model = self._build(ep_capacity_factor=8.0, experts_backend=eb)
                losses[eb] = float(jax.jit(
                    lambda p, bs, m=model: make_moe_pp_loss(m, mesh)(p, bs, n))(
                    params, batch_stack)[0])
        np.testing.assert_allclose(losses["pallas"], losses["ragged_dot"],
                                   rtol=1e-5)
