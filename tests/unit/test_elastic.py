"""Elastic topology unit coverage (docs/resilience.md "Elastic restore & warm
restart"): reshard topology metadata + restore classification, deterministic
dataloader-state re-partitioning, joiner-aware pod agreement, the hardened
latest pointer, chaos topology injection, and the multi-variant AOT executor.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from automodel_tpu.checkpoint.checkpointing import (
    Checkpointer, CheckpointingConfig, ModelSignatureMismatch, _ABSTAIN,
)
from automodel_tpu.checkpoint.reshard import (
    TOPOLOGY_KEY, build_topology, describe_delta, mesh_delta, read_topology,
    strip_topology,
)
from automodel_tpu.parallel.mesh import MeshContext
from automodel_tpu.resilience.elastic import (
    ElasticTopologyChange, merge_host_states, plan_warmup_micro_counts,
    repartition_dataloader_state,
)


def _params(seed=0, d=8):
    rng = np.random.RandomState(seed)
    return {
        "embed": jnp.asarray(rng.randn(16, d), jnp.float32),
        "layers": {"wq": jnp.asarray(rng.randn(2, d, d), jnp.float32)},
    }


def _topo(**axes):
    return build_topology(MeshContext(world_size=8, **axes), process_count=1)


class TestReshardMetadata:
    def test_build_topology_records_axes_and_pod(self):
        t = build_topology(MeshContext(dp_shard=4, tp=2, world_size=8),
                           process_count=3)
        assert t["mesh"]["dp_shard"] == 4 and t["mesh"]["tp"] == 2
        assert t["process_count"] == 3 and t["world_size"] == 8

    def test_strip_topology_roundtrip(self):
        sig = {"a": "f32/(2, 2)", TOPOLOGY_KEY: _topo(dp_shard=8)}
        clean, topo = strip_topology(sig)
        assert TOPOLOGY_KEY not in clean and clean == {"a": "f32/(2, 2)"}
        assert topo["mesh"]["dp_shard"] == 8
        # legacy signature: no topology key
        clean2, topo2 = strip_topology({"a": "f32/(2, 2)"})
        assert topo2 is None and clean2 == {"a": "f32/(2, 2)"}

    def test_mesh_delta_names_only_changed_axes(self):
        delta = mesh_delta(_topo(dp_shard=8), _topo(dp_shard=4, tp=2))
        assert delta == {"dp_shard": (8, 4), "tp": (1, 2)}
        assert "dp_shard 8->4" in describe_delta(delta)
        assert "tp 1->2" in describe_delta(delta)

    def test_mesh_delta_same_mesh_is_empty(self):
        assert mesh_delta(_topo(dp_shard=8), _topo(dp_shard=8)) == {}
        # either side unknown (legacy checkpoint / unwired recipe) -> same-mesh
        assert mesh_delta(None, _topo(dp_shard=8)) == {}
        assert mesh_delta(_topo(dp_shard=8), None) == {}

    def test_mesh_delta_process_count_change(self):
        a = build_topology(MeshContext(dp_shard=8, world_size=8), process_count=4)
        b = build_topology(MeshContext(dp_shard=8, world_size=8), process_count=2)
        assert mesh_delta(a, b) == {"process_count": (4, 2)}

    def test_read_topology_missing_dir(self, tmp_path):
        assert read_topology(str(tmp_path / "nope")) is None


class TestRepartition:
    def _state(self, cursor=10, bs=16):
        return {"epoch": 1, "cursor": cursor, "seed": 5, "batch_size": bs,
                "process_count": 2}

    def test_exact_shrink(self):
        out, info = repartition_dataloader_state(self._state(), 8)
        assert out["cursor"] == 20 and out["batch_size"] == 8
        assert out["epoch"] == 1 and out["seed"] == 5
        assert info["consumed_examples"] == 160
        assert "refed_examples" not in info

    def test_exact_grow(self):
        out, info = repartition_dataloader_state(self._state(), 32)
        assert out["cursor"] == 5
        assert "refed_examples" not in info

    def test_nondivisible_refeeds_never_drops(self):
        out, info = repartition_dataloader_state(self._state(), 12)
        # 160 consumed -> cursor 13 (156 examples) + 4 re-fed, none dropped
        assert out["cursor"] == 13
        assert info["refed_examples"] == 4
        assert out["cursor"] * 12 + info["refed_examples"] == 160

    def test_legacy_state_without_batch_size(self):
        out, info = repartition_dataloader_state({"epoch": 0, "cursor": 7}, 8)
        assert out["cursor"] == 7  # assumed same-size: cursor passes through
        assert info["old_batch_size"] == 8

    def test_bad_batch_size_raises(self):
        with pytest.raises(ValueError, match="new_batch_size"):
            repartition_dataloader_state(self._state(), 0)

    def test_merge_host_states_consistent_rows(self):
        rows = [{"process_index": i, "epoch": 1, "cursor": 10, "batch_size": 16}
                for i in range(4)]
        merged, info = merge_host_states(rows, {"epoch": 9, "cursor": 9})
        assert merged["cursor"] == 10 and merged["epoch"] == 1
        assert "host_cursor_skew" not in info

    def test_merge_host_states_divergent_takes_minimum(self):
        rows = [
            {"process_index": 0, "epoch": 1, "cursor": 12},
            {"process_index": 1, "epoch": 1, "cursor": 10},  # stale host wins
            {"process_index": 2, "epoch": 1, "cursor": 12},
        ]
        merged, info = merge_host_states(rows, {"epoch": 0, "cursor": 0})
        assert merged["cursor"] == 10
        assert info["host_cursor_skew"] == 2

    def test_merge_orders_by_epoch_then_cursor(self):
        rows = [
            {"process_index": 0, "epoch": 2, "cursor": 1},
            {"process_index": 1, "epoch": 1, "cursor": 30},  # earlier epoch wins
        ]
        merged, _ = merge_host_states(rows, {})
        assert (merged["epoch"], merged["cursor"]) == (1, 30)

    def test_merge_empty_rows_keeps_fallback(self):
        merged, info = merge_host_states(None, {"epoch": 3, "cursor": 4})
        assert merged == {"epoch": 3, "cursor": 4} and info == {}

    def test_repartition_uses_host_rows(self):
        rows = [{"process_index": 0, "epoch": 1, "cursor": 10, "batch_size": 16},
                {"process_index": 1, "epoch": 1, "cursor": 9, "batch_size": 16}]
        out, info = repartition_dataloader_state(self._state(cursor=10), 8,
                                                 host_rows=rows)
        assert out["cursor"] == 18  # min cursor 9 * 16 / 8
        assert info["host_cursor_skew"] == 1


class TestWarmupPlan:
    def test_trailing_partial_shape(self):
        assert plan_warmup_micro_counts(10, 4) == [2]

    def test_divisible_epoch_has_no_extra_shape(self):
        assert plan_warmup_micro_counts(12, 4) == []

    def test_no_accumulation_or_unsized(self):
        assert plan_warmup_micro_counts(10, 1) == []
        assert plan_warmup_micro_counts(None, 4) == []


class TestDataLoaderElasticState:
    def _loader(self, bs=8):
        from automodel_tpu.data.loader import DataLoader

        return DataLoader(list(range(64)), batch_size=bs, seed=3)

    def test_state_dict_carries_geometry(self):
        dl = self._loader()
        next(iter(dl))
        s = dl.state_dict()
        assert s["batch_size"] == 8 and s["process_count"] == 1
        assert dl.consumed_examples == 8

    def test_load_rejects_mismatched_batch_size(self):
        dl = self._loader(bs=8)
        state = dict(dl.state_dict(), batch_size=16)
        with pytest.raises(ValueError, match="repartition"):
            dl.load_state_dict(state)

    def test_load_tolerates_legacy_state(self):
        dl = self._loader()
        dl.load_state_dict({"epoch": 2, "cursor": 3})  # pre-elastic checkpoint
        assert dl.epoch == 2 and dl._cursor == 3

    def test_repartitioned_state_consumes_same_examples(self):
        # the invariant the whole elastic path rests on: the consumed set is
        # the first cursor*batch_size permutation entries, so after an exact
        # reshape the new loader resumes at the identical example boundary
        dl = self._loader(bs=16)
        it = iter(dl)
        next(it), next(it)
        new_state, _ = repartition_dataloader_state(dl.state_dict(), 8)
        dl2 = self._loader(bs=8)
        dl2.load_state_dict(new_state)
        assert dl2.consumed_examples == dl.consumed_examples == 32


class TestTopologyAwareCheckpoint:
    def _ck(self, tmp_path, topo=None, events=None):
        ck = Checkpointer(CheckpointingConfig(checkpoint_dir=str(tmp_path / "ck")))
        ck.topology = topo
        if events is not None:
            ck.event_sink = lambda step, event, **f: events.append((event, f))
        return ck

    def test_save_embeds_topology_in_signature(self, tmp_path):
        ck = self._ck(tmp_path, topo=_topo(dp_shard=8))
        ck.save(1, _params())
        sig = json.load(open(os.path.join(ck.step_dir(1), "signature.json")))
        assert sig[TOPOLOGY_KEY]["mesh"]["dp_shard"] == 8
        assert read_topology(ck.step_dir(1))["mesh"]["dp_shard"] == 8

    def test_same_mesh_restore_is_not_elastic(self, tmp_path):
        events = []
        ck = self._ck(tmp_path, topo=_topo(dp_shard=8), events=events)
        p = _params()
        ck.save(1, p)
        _, _, client = ck.load(jax.tree.map(jnp.zeros_like, p), step=1)
        assert "__elastic__" not in client
        assert not any(e == "elastic_restore" for e, _ in events)

    def test_mesh_change_classified_elastic_and_bitwise_equal(self, tmp_path):
        events = []
        ck = self._ck(tmp_path, topo=_topo(dp_shard=8))
        p = _params()
        ck.save(2, p, client_states={"step": 2})
        ck2 = self._ck(tmp_path, topo=_topo(dp_shard=4, tp=2), events=events)
        restored, _, client = ck2.load(jax.tree.map(jnp.zeros_like, p), step=2)
        marker = client["__elastic__"]
        assert marker["delta"]["dp_shard"] == [8, 4]
        assert marker["from"]["mesh"]["dp_shard"] == 8
        assert [e for e, _ in events] == ["elastic_restore"]
        assert "dp_shard 8->4" in events[0][1]["delta"]
        np.testing.assert_array_equal(np.asarray(restored["layers"]["wq"]),
                                      np.asarray(p["layers"]["wq"]))

    def test_model_change_still_hard_fails(self, tmp_path):
        ck = self._ck(tmp_path, topo=_topo(dp_shard=8))
        ck.save(1, _params(d=8))
        ck2 = self._ck(tmp_path, topo=_topo(dp_shard=4, tp=2))
        # a changed MODEL must never be mistaken for a changed mesh
        with pytest.raises(ValueError, match="different model signature"):
            ck2.load(_params(d=16), step=1)
        with pytest.raises(ModelSignatureMismatch):
            ck2.load(_params(d=16), step=1)

    def test_legacy_checkpoint_without_topology(self, tmp_path):
        ck = self._ck(tmp_path, topo=None)  # pre-elastic writer
        p = _params()
        ck.save(1, p)
        sig = json.load(open(os.path.join(ck.step_dir(1), "signature.json")))
        assert TOPOLOGY_KEY not in sig
        ck2 = self._ck(tmp_path, topo=_topo(dp_shard=4, tp=2))
        _, _, client = ck2.load(jax.tree.map(jnp.zeros_like, p), step=1)
        assert "__elastic__" not in client  # unknown saved mesh -> not elastic

    def test_missing_manifest_emits_unverified_restore(self, tmp_path):
        events = []
        ck = self._ck(tmp_path, events=events)
        p = _params()
        ck.save(1, p)
        manifest = os.path.join(ck.step_dir(1), "manifest.json")
        if os.path.exists(manifest):
            os.remove(manifest)
        ck.load(jax.tree.map(jnp.zeros_like, p), step=1)
        assert "unverified_restore" in [e for e, _ in events]

    def test_save_records_host_rows_in_client(self, tmp_path):
        ck = self._ck(tmp_path)
        dl_state = {"epoch": 0, "cursor": 3, "seed": 1, "batch_size": 8,
                    "process_count": 1}
        ck.save(1, _params(), client_states={"dataloader": dl_state})
        client = json.load(open(os.path.join(ck.step_dir(1), "client.json")))
        rows = client["__hosts__"]["dataloader"]
        assert rows == [{"process_index": 0, "epoch": 0, "cursor": 3,
                         "batch_size": 8}]


class TestLatestPointerHardening:
    def test_dangling_symlink_falls_back_to_scan(self, tmp_path):
        cfg = CheckpointingConfig(checkpoint_dir=str(tmp_path / "ck"))
        ck = Checkpointer(cfg)
        ck.save(2, _params())
        latest = tmp_path / "ck" / "latest"
        os.remove(latest)
        os.symlink("step_9", latest)  # points at a pruned/never-written step
        assert Checkpointer(cfg).latest_step() == 2

    def test_symlink_to_incomplete_dir_falls_back(self, tmp_path):
        cfg = CheckpointingConfig(checkpoint_dir=str(tmp_path / "ck"))
        ck = Checkpointer(cfg)
        ck.save(2, _params())
        d9 = ck.step_dir(9)
        os.makedirs(os.path.join(d9, "model.orbax-checkpoint-tmp-42"))
        latest = tmp_path / "ck" / "latest"
        os.remove(latest)
        os.symlink("step_9", latest)  # crashed save that somehow won the swap
        assert Checkpointer(cfg).latest_step() == 2

    def test_healthy_symlink_stays_authoritative(self, tmp_path):
        cfg = CheckpointingConfig(checkpoint_dir=str(tmp_path / "ck"))
        ck = Checkpointer(cfg)
        ck.save(2, _params())
        ck.save(5, _params())
        assert Checkpointer(cfg).latest_step() == 5


class TestPodAgreement:
    """Divergent per-host views of agreed_restore_step/newest_verifiable_step:
    the collective is simulated by monkeypatching agreed_min_int with another
    host's (possibly lagging or abstaining) local answer."""

    def _ck_with_steps(self, tmp_path, steps=(2, 4, 6)):
        ck = Checkpointer(CheckpointingConfig(checkpoint_dir=str(tmp_path / "ck")))
        for s in steps:
            ck.save(s, _params())
        return ck

    def _pod(self, monkeypatch, remote_values):
        """agreed_min_int = min(local, *remote_values)."""
        import automodel_tpu.parallel.init as pinit

        monkeypatch.setattr(
            pinit, "agreed_min_int",
            lambda v: int(min(int(v), *[int(r) for r in remote_values])),
        )

    def test_newest_verifiable_with_overlapping_excludes(self, tmp_path):
        ck = self._ck_with_steps(tmp_path)
        assert ck.newest_verifiable_step() == 6
        assert ck.newest_verifiable_step({6}) == 4
        assert ck.newest_verifiable_step({4, 6}) == 2
        # overlapping sets excluding already-gone steps change nothing
        assert ck.newest_verifiable_step({4, 6, 99}) == 2
        assert ck.newest_verifiable_step({2, 4, 6}) is None

    def test_agreed_takes_min_over_divergent_hosts(self, tmp_path, monkeypatch):
        ck = self._ck_with_steps(tmp_path)
        self._pod(monkeypatch, [4])  # remote host's filesystem view lags at 4
        assert ck.agreed_restore_step() == 4
        # excluding the remote's answer locally still yields the pod minimum
        self._pod(monkeypatch, [6])
        assert ck.agreed_restore_step({6}) == 4

    def test_joiner_abstains_instead_of_forcing_fresh(self, tmp_path, monkeypatch):
        ck = Checkpointer(CheckpointingConfig(checkpoint_dir=str(tmp_path / "ck")))
        assert ck.newest_verifiable_step() is None  # empty local view
        self._pod(monkeypatch, [6])  # veterans agree on 6
        # legacy semantics: one empty host drags the pod to None
        assert ck.agreed_restore_step() is None
        # elastic join: the joiner abstains and restores what veterans agree on
        assert ck.agreed_restore_step(allow_joiners=True) == 6

    def test_all_hosts_abstaining_is_a_fresh_run(self, tmp_path, monkeypatch):
        ck = Checkpointer(CheckpointingConfig(checkpoint_dir=str(tmp_path / "ck")))
        self._pod(monkeypatch, [_ABSTAIN])
        assert ck.agreed_restore_step(allow_joiners=True) is None

    def test_veteran_with_joiners_present(self, tmp_path, monkeypatch):
        ck = self._ck_with_steps(tmp_path, steps=(3,))
        self._pod(monkeypatch, [_ABSTAIN])  # the other host just joined
        assert ck.agreed_restore_step(allow_joiners=True) == 3


class TestChaosElastic:
    def test_config_parses_elastic_fields(self):
        from automodel_tpu.resilience.chaos import ChaosConfig

        cfg = ChaosConfig.from_dict({
            "enabled": True, "elastic_steps": [3, 7],
            "elastic_mesh": {"dp_shard": 4, "tp": 2},
        })
        assert cfg.elastic_steps == (3, 7)
        assert cfg.elastic_mesh == {"dp_shard": 4, "tp": 2}
        assert ChaosConfig.from_dict({"enabled": True}).elastic_steps == ()

    def test_injector_fires_once_per_step(self):
        from automodel_tpu.resilience.chaos import ChaosConfig, ChaosInjector

        inj = ChaosInjector(ChaosConfig(
            enabled=True, elastic_steps=(3,), elastic_mesh={"dp_shard": 2}))
        assert not inj.should_elastic(2)
        assert inj.should_elastic(3)
        assert inj.elastic_change(3) == {"dp_shard": 2}
        assert not inj.should_elastic(3)  # fired

    def test_no_mesh_means_no_injection(self):
        from automodel_tpu.resilience.chaos import ChaosConfig, ChaosInjector

        inj = ChaosInjector(ChaosConfig(enabled=True, elastic_steps=(3,)))
        assert not inj.should_elastic(3)

    def test_exception_carries_step_and_mesh(self):
        exc = ElasticTopologyChange(7, {"dp_shard": 4})
        assert exc.step == 7 and exc.new_mesh == {"dp_shard": 4}
        assert "step 7" in str(exc)


class TestElasticConfig:
    def test_defaults_and_parsing(self):
        from automodel_tpu.resilience.config import ResilienceConfig

        cfg = ResilienceConfig.from_dict(None)
        assert cfg.elastic.enabled and cfg.elastic.allow_joiners
        cfg = ResilienceConfig.from_dict(
            {"elastic": {"enabled": False, "allow_joiners": False}})
        assert not cfg.elastic.enabled and not cfg.elastic.allow_joiners


class TestGuardedCompiledVariants:
    def _executor(self, fn, args, counters):
        from automodel_tpu.observability.manager import _GuardedCompiled

        compiled = fn.lower(*args).compile()
        return _GuardedCompiled(
            compiled, fn, args,
            on_shape_fallback=lambda: counters.__setitem__(
                "shape", counters["shape"] + 1),
        )

    def test_known_shape_runs_variant(self):
        counters = {"shape": 0}
        fn = jax.jit(lambda x: x * 2)
        g = self._executor(fn, (jnp.arange(8.0),), counters)
        np.testing.assert_array_equal(np.asarray(g(jnp.arange(8.0))),
                                      np.arange(8.0) * 2)
        assert counters == {"shape": 0}
        assert g.num_variants == 1

    def test_unseen_shape_counts_fallback(self):
        counters = {"shape": 0}
        fn = jax.jit(lambda x: x * 2)
        g = self._executor(fn, (jnp.arange(8.0),), counters)
        out = g(jnp.arange(4.0))  # trailing partial shape: no variant yet
        np.testing.assert_array_equal(np.asarray(out), np.arange(4.0) * 2)
        assert counters["shape"] == 1

    def test_add_variant_silences_fallback(self):
        counters = {"shape": 0}
        fn = jax.jit(lambda x: x * 2)
        g = self._executor(fn, (jnp.arange(8.0),), counters)
        small = (jnp.arange(4.0),)
        g.add_variant(small, fn.lower(*small).compile())
        assert g.num_variants == 2
        g(*small)
        g(jnp.arange(8.0))
        assert counters == {"shape": 0}

    def test_train_step_outputs_carry_the_input_shardings(self):
        """jit_train_step pins params/opt_state outputs to the shardings they
        came in with — what lets an AOT-compiled step be fed its own outputs
        every step (the compiler alone may hand a replicated leaf back split)."""
        import optax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from automodel_tpu.training.train_step import jit_train_step, make_train_step

        mesh = jax.make_mesh((8,), ("x",))
        params = {
            "split": jax.device_put(jnp.ones((16, 4)), NamedSharding(mesh, P("x"))),
            "whole": jax.device_put(jnp.ones((4,)), NamedSharding(mesh, P())),
        }
        opt = optax.sgd(0.1)
        opt_state = jax.jit(opt.init)(params)
        batch = {"labels": jnp.zeros((1, 8), jnp.int32),
                 "x": jax.device_put(jnp.ones((1, 8, 16)), NamedSharding(mesh, P(None, "x")))}

        def loss(p, b, n):
            return ((b["x"] @ p["split"]) * p["whole"]).sum() / n

        step = jit_train_step(make_train_step(loss, opt), params, opt_state)
        compiled = step.lower(params, opt_state, batch).compile()
        want = jax.tree.map(lambda x: x.sharding, params)
        for _ in range(3):  # the AOT object accepts its own outputs
            params, opt_state, _m = compiled(params, opt_state, batch)
            assert jax.tree.map(lambda x: x.sharding, params) == want

    def test_compiled_variant_error_propagates(self):
        """Whatever an AOT variant raises reaches the caller: there is no
        exception prose that turns a failure into a jit retry."""
        from automodel_tpu.observability.manager import _GuardedCompiled

        fn = jax.jit(lambda x: x * 2)
        counters = {"shape": 0}

        def exploding(*a):
            raise ValueError(
                "Computation was compiled for input shardings that disagree "
                "with the shardings of arguments passed to it")

        g = _GuardedCompiled(
            exploding, fn, (jnp.arange(8.0),),
            on_shape_fallback=lambda: counters.__setitem__(
                "shape", counters["shape"] + 1))
        with pytest.raises(ValueError, match="input shardings that disagree"):
            g(jnp.arange(8.0))
        g(jnp.arange(4.0))  # an unplanned shape is still counted, and answered
        assert counters == {"shape": 1}


class TestCompileCacheConfigure:
    """Where the persistent cache lives: the machine's JAX_COMPILATION_CACHE_DIR
    wins and nothing is set in code; else the YAML's dir; else <checkout>/.jax_cache.
    (The test process keeps the cache itself switched off — tests/conftest.py.)"""

    @pytest.fixture
    def restore_cache_config(self):
        opts = ("jax_compilation_cache_dir",
                "jax_persistent_cache_min_entry_size_bytes",
                "jax_persistent_cache_min_compile_time_secs")
        old = {o: getattr(jax.config, o) for o in opts}
        yield
        for o, v in old.items():
            jax.config.update(o, v)

    def test_env_set_means_code_sets_nothing(self, tmp_path, monkeypatch, restore_cache_config):
        from automodel_tpu.observability import compile_cache

        jax.config.update("jax_compilation_cache_dir", "sentinel-left-alone")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "from_env"))
        applied = compile_cache.configure({"dir": str(tmp_path / "from_yaml")})
        assert applied["dir"] == str(tmp_path / "from_env") and applied["dir_from"] == "env"
        assert jax.config.jax_compilation_cache_dir == "sentinel-left-alone"

    def test_yaml_dir_then_checkout_default(self, tmp_path, monkeypatch, restore_cache_config):
        from automodel_tpu.observability import compile_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        applied = compile_cache.configure({
            "dir": str(tmp_path / "xla_cache"),
            "min_entry_size_bytes": 0,
            "min_compile_time_secs": 0,
        })
        assert applied["dir"] == str(tmp_path / "xla_cache") and applied["dir_from"] == "config"
        assert compile_cache.snapshot()["dir"] == str(tmp_path / "xla_cache")
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0

        floor = 1.5
        jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)
        for raw in (None, {"warmup": True}):
            applied = compile_cache.configure(raw)
            assert applied == {"dir": compile_cache.default_dir(), "dir_from": "default"}
        checkout = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        assert compile_cache.default_dir() == os.path.join(checkout, ".jax_cache")
        # the default-on cache keeps whatever floors are in force
        assert jax.config.jax_persistent_cache_min_compile_time_secs == floor


