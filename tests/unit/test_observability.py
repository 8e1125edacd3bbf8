"""Observability subsystem tests: goodput accounting, stall watchdog, HBM
telemetry, on-demand profiling — plus the timers + experiment-logger tests
(reference tests for training/timers.py and loggers/)."""

import json
import os
import signal
import time

import jax.numpy as jnp
import pytest

from automodel_tpu.loggers.experiment_loggers import (
    MLflowLogger,
    WandbLogger,
    build_experiment_loggers,
)
from automodel_tpu.training.timers import Timer, Timers


class TestTimers:
    def test_basic_timing(self):
        timers = Timers()
        with timers("work"):
            time.sleep(0.01)
        s = timers.summary()
        assert 0.005 < s["work"] < 1.0

    def test_mean_over_calls(self):
        timers = Timers()
        for _ in range(3):
            with timers("x"):
                time.sleep(0.002)
        assert timers("x").count == 3
        assert timers("x").mean < timers("x").elapsed_total

    def test_sync_blocks_on_result(self):
        t = Timer("d", sync=True)
        t.start()
        out = jnp.ones((256, 256)) @ jnp.ones((256, 256))
        dt = t.stop(out)
        assert dt > 0

    def test_double_start_raises(self):
        t = Timer("x")
        t.start()
        with pytest.raises(RuntimeError, match="already started"):
            t.start()

    def test_summary_reset(self):
        timers = Timers()
        with timers("a"):
            pass
        timers.summary(reset=True)
        assert timers.summary() == {}


class TestExperimentLoggers:
    def test_missing_packages_degrade_gracefully(self):
        # wandb/mlflow are not installed in this image: loggers become no-ops
        w = WandbLogger(project="x", mode="offline")
        w.log(1, loss=1.0)
        w.close()
        m = MLflowLogger(tracking_uri="file:/tmp/nope")
        m.log(1, loss=1.0)
        m.close()

    def test_build_from_config(self):
        from automodel_tpu.config.loader import ConfigNode

        cfg = ConfigNode({"wandb": {"project": "p", "mode": "offline"}})
        loggers = build_experiment_loggers(cfg)
        assert len(loggers) == 1
        cfg2 = ConfigNode({})
        assert build_experiment_loggers(cfg2) == []


class TestNamedScopes:
    """Profiler scope labels (autonvtx parity): block/region names must survive
    into the lowered program's metadata so trace viewers can group ops."""

    def test_moe_block_scopes_in_lowered_text(self):
        import jax

        from automodel_tpu.moe.config import MoEConfig
        from automodel_tpu.moe.layers import init_moe_params, moe_forward
        from automodel_tpu.utils.tracing import lowered_text_with_scopes

        cfg = MoEConfig(n_routed_experts=4, n_activated_experts=2, dim=16,
                        moe_inter_dim=32, n_shared_experts=1)
        p = init_moe_params(cfg, jax.random.key(0))
        x = jnp.ones((4, 16))
        txt = lowered_text_with_scopes(
            jax.jit(lambda p, x: moe_forward(cfg, p, x)[0]).lower(p, x)
        )
        for scope in ("moe_gate", "moe_experts", "moe_shared_experts"):
            assert scope in txt, scope

    def test_hybrid_family_block_scopes(self):
        import jax
        import numpy as np

        from automodel_tpu.models.common.backend import BackendConfig
        from automodel_tpu.models.nemotron_v3.model import NemotronHForCausalLM, NemotronV3Config
        from automodel_tpu.moe.config import MoEConfig
        from automodel_tpu.utils.tracing import lowered_text_with_scopes

        cfg = NemotronV3Config(
            vocab_size=128, hidden_size=64, intermediate_size=96, num_hidden_layers=4,
            layers_block_type=("mamba", "attention", "mlp", "moe"),
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            mamba_num_heads=4, mamba_head_dim=8, ssm_state_size=16, n_groups=2,
            chunk_size=16, conv_kernel=4,
            moe=MoEConfig(
                n_routed_experts=4, n_activated_experts=2, dim=64, moe_inter_dim=32,
                score_func="sigmoid", expert_activation="relu2",
            ),
        )
        model = NemotronHForCausalLM(cfg, BackendConfig(dtype="float32", remat_policy="full"))
        params = model.init(jax.random.key(0), jnp.float32)
        ids = jnp.asarray(np.zeros((1, 8), np.int32))
        txt = lowered_text_with_scopes(
            jax.jit(lambda p, i: model(p, i)[0]).lower(params, ids)
        )
        for scope in ("mamba", "attention", "mlp"):
            assert scope in txt, scope

    def test_scoped_wrapper_preserves_fn(self):
        from automodel_tpu.utils.tracing import scope_blocks, scoped

        f = scoped("thing", lambda a, b: a + b)
        assert f(1, 2) == 3
        table = scope_blocks({"x": lambda v: v * 2})
        assert table["x"](4) == 8

    def test_shared_dense_path_scopes_in_lowered_text(self):
        """The common transformer path carries attention/mlp scope labels so
        EVERY dense family's trace is legible, not just the 3 that annotate
        per-family."""
        import jax

        from automodel_tpu.models.common.backend import BackendConfig
        from automodel_tpu.models.common.transformer import (
            DenseDecoderConfig, decoder_forward, init_dense_decoder_params,
        )
        from automodel_tpu.utils.tracing import lowered_text_with_scopes

        cfg = DenseDecoderConfig(
            vocab_size=64, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=2,
        )
        backend = BackendConfig(dtype="float32")
        params = init_dense_decoder_params(cfg, jax.random.key(0))
        ids = jnp.zeros((1, 8), jnp.int32)
        txt = lowered_text_with_scopes(
            jax.jit(lambda p, i: decoder_forward(cfg, backend, p, i)).lower(params, ids)
        )
        for scope in ("attention", "mlp"):
            assert scope in txt, scope

    def test_shared_moe_path_scopes_in_lowered_text(self):
        import jax

        from automodel_tpu.models.common.backend import BackendConfig
        from automodel_tpu.models.common.moe_transformer import (
            MoEDecoderConfig, init_moe_decoder_params, moe_decoder_forward,
        )
        from automodel_tpu.moe.config import MoEConfig
        from automodel_tpu.utils.tracing import lowered_text_with_scopes

        cfg = MoEDecoderConfig(
            vocab_size=64, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=2,
            first_k_dense_replace=1,
            moe=MoEConfig(n_routed_experts=4, n_activated_experts=2, dim=32,
                          moe_inter_dim=32),
        )
        backend = BackendConfig(dtype="float32")
        params = init_moe_decoder_params(cfg, jax.random.key(0))
        ids = jnp.zeros((1, 8), jnp.int32)
        txt = lowered_text_with_scopes(
            jax.jit(lambda p, i: moe_decoder_forward(cfg, backend, p, i)[0]).lower(params, ids)
        )
        for scope in ("attention", "mlp", "moe"):
            assert scope in txt, scope


class TestNonFiniteJson:
    """MetricLogger must emit VALID json for NaN/Inf metrics: bare NaN/Infinity
    from json.dumps breaks every json.loads consumer of training.jsonl."""

    def test_nonfinite_roundtrips_through_json_loads(self):
        from automodel_tpu.loggers.metric_logger import MetricsSample

        line = MetricsSample(
            step=3, metrics={"loss": float("nan"), "grad_norm": float("inf"), "ok": 1.5}
        ).to_json()
        rec = json.loads(line)  # bare NaN/Infinity would raise here
        assert rec["loss"] is None
        assert rec["loss_nonfinite"] is True
        assert rec["grad_norm"] is None
        assert rec["grad_norm_nonfinite"] is True
        assert rec["ok"] == 1.5
        assert "ok_nonfinite" not in rec

    def test_nonfinite_inside_arrays_and_lists(self):
        import numpy as np

        from automodel_tpu.loggers.metric_logger import MetricsSample

        line = MetricsSample(
            step=1,
            metrics={"load": np.asarray([1.0, float("nan")]),
                     "scalar": jnp.float32(2.0)},
        ).to_json()
        rec = json.loads(line)
        assert rec["load"] == [1.0, None]
        assert rec["load_nonfinite"] is True
        assert rec["scalar"] == 2.0

    def test_logger_writes_parseable_lines(self, tmp_path):
        from automodel_tpu.loggers.metric_logger import MetricLogger

        path = tmp_path / "training.jsonl"
        with MetricLogger(path) as ml:
            ml.log(1, loss=float("nan"), tps=None, mfu=0.31)
        rows = [json.loads(line) for line in open(path)]
        assert rows[0]["loss"] is None and rows[0]["loss_nonfinite"] is True
        assert rows[0]["tps"] is None
        assert rows[0]["mfu"] == 0.31


class TestGoodputTracker:
    def test_buckets_sum_to_wall_time(self):
        from automodel_tpu.observability import GoodputTracker

        now = [0.0]
        tracker = GoodputTracker(clock=lambda: now[0])

        def spend(bucket, s):
            with tracker.track(bucket):
                now[0] += s

        spend("compile", 30.0)
        spend("data_wait", 5.0)
        for _ in range(4):
            spend("device_step", 10.0)
        spend("eval", 15.0)
        spend("checkpoint", 5.0)
        now[0] += 5.0  # unaccounted -> idle

        totals = tracker.totals()
        assert sum(totals.values()) == pytest.approx(tracker.wall_s)
        assert totals["idle"] == pytest.approx(5.0)

        snap = tracker.snapshot()
        fracs = [v for k, v in snap.items() if k.startswith("goodput/")]
        assert sum(fracs) == pytest.approx(1.0, abs=1e-3)
        assert snap["goodput"] == pytest.approx(40.0 / 100.0, abs=1e-3)
        assert snap["goodput/compile"] == pytest.approx(0.3, abs=1e-3)

    def test_add_and_unknown_bucket(self):
        from automodel_tpu.observability import GoodputTracker

        tracker = GoodputTracker()
        tracker.add("device_step", 1.0)
        tracker.add("custom", 2.0)  # ad-hoc buckets allowed
        assert tracker.totals()["custom"] == 2.0
        assert "goodput/custom" in tracker.snapshot()


class TestStallWatchdog:
    def test_fires_on_simulated_stall_and_dumps_stacks(self, tmp_path):
        from automodel_tpu.observability import StallWatchdog

        events = []
        wd = StallWatchdog(threshold_s=0.05, dump_dir=str(tmp_path),
                           on_stall=events.append, poll_interval_s=0.01)
        wd.start()
        wd.heartbeat(step=7)
        deadline = time.monotonic() + 5.0
        while not events and time.monotonic() < deadline:
            time.sleep(0.01)  # the loop is "hung": no heartbeats arrive
        wd.stop()
        assert len(events) == 1, "stall must fire exactly once per silence window"
        ev = events[0]
        assert ev["event"] == "stall"
        assert ev["step"] == 7
        assert ev["stall_s"] >= 0.0
        assert os.path.exists(ev["stack_dump"])
        dump = open(ev["stack_dump"]).read()
        # the dump must contain THIS (stalled) thread's stack
        assert "test_fires_on_simulated_stall_and_dumps_stacks" in dump
        assert "last step 7" in dump

    def test_heartbeats_rearm_and_suppress(self, tmp_path):
        from automodel_tpu.observability import StallWatchdog

        events = []
        wd = StallWatchdog(threshold_s=0.2, dump_dir=str(tmp_path),
                           on_stall=events.append, poll_interval_s=0.01)
        wd.start()
        for _ in range(10):  # steady heartbeats: never fires
            wd.heartbeat(step=1)
            time.sleep(0.01)
        assert events == []
        time.sleep(0.4)  # silence: fires once
        assert len(events) == 1
        wd.heartbeat(step=2)  # recovery re-arms
        time.sleep(0.4)  # second stall fires again
        wd.stop()
        assert len(events) == 2
        assert not wd.running

    def test_bad_threshold_raises(self, tmp_path):
        from automodel_tpu.observability import StallWatchdog

        with pytest.raises(ValueError, match="threshold_s"):
            StallWatchdog(threshold_s=0.0, dump_dir=str(tmp_path))

    def test_context_fn_merges_goodput_snapshot_into_event(self, tmp_path):
        """A stall event must carry the run's goodput snapshot + last step so
        the incident row is diagnosable without cross-referencing other rows."""
        from automodel_tpu.observability import StallWatchdog

        events = []
        wd = StallWatchdog(threshold_s=0.05, dump_dir=str(tmp_path),
                           on_stall=events.append, poll_interval_s=0.01,
                           context_fn=lambda: {"goodput": 0.42, "goodput/compile": 0.3})
        wd.start()
        wd.heartbeat(step=9)
        deadline = time.monotonic() + 5.0
        while not events and time.monotonic() < deadline:
            time.sleep(0.01)
        wd.stop()
        assert events and events[0]["event"] == "stall"
        assert events[0]["step"] == 9  # last completed step
        assert events[0]["goodput"] == 0.42
        assert events[0]["goodput/compile"] == 0.3

    def test_context_fn_failure_does_not_eat_the_event(self, tmp_path):
        from automodel_tpu.observability import StallWatchdog

        def boom():
            raise RuntimeError("snapshot failed")

        events = []
        wd = StallWatchdog(threshold_s=0.05, dump_dir=str(tmp_path),
                           on_stall=events.append, poll_interval_s=0.01,
                           context_fn=boom)
        wd.start()
        wd.heartbeat(step=1)
        deadline = time.monotonic() + 5.0
        while not events and time.monotonic() < deadline:
            time.sleep(0.01)
        wd.stop()
        assert events and events[0]["event"] == "stall"


class TestMemoryTelemetry:
    def test_cpu_noops_cleanly(self):
        """CPU devices return None from memory_stats(): telemetry degrades to
        an empty dict, never a crash (JAX_PLATFORMS=cpu in the suite)."""
        from automodel_tpu.observability import device_memory_stats

        out = device_memory_stats()
        assert isinstance(out, dict)
        for v in out.values():  # if a backend DOES report, values are numeric GiB
            assert isinstance(v, float)

    def test_fake_device_stats(self):
        from automodel_tpu.observability import device_memory_stats

        class Dev:
            def __init__(self, in_use, peak):
                self._s = {"bytes_in_use": in_use, "peak_bytes_in_use": peak}

            def memory_stats(self):
                return self._s

        class NoneDev:
            def memory_stats(self):
                return None

        out = device_memory_stats([Dev(2**30, 2 * 2**30), Dev(2**29, 3 * 2**30), NoneDev()])
        assert out["hbm_gib_in_use"] == 1.0  # max over devices
        assert out["hbm_gib_peak"] == 3.0


class TestOnDemandProfiler:
    def test_sigusr1_arms_and_close_disarms_without_server(self, tmp_path):
        """The signal handler must arm a trace request (and restore the prior
        handler on close) with NO profiler server running."""
        from automodel_tpu.observability import OnDemandProfiler

        prev = signal.getsignal(signal.SIGUSR1)
        p = OnDemandProfiler(str(tmp_path), trace_steps=2, server_port=0)
        p.start()
        assert not p.armed
        os.kill(os.getpid(), signal.SIGUSR1)
        assert p.armed
        assert not p.tracing  # arming alone must not touch the profiler
        p.close()
        assert not p.armed
        assert signal.getsignal(signal.SIGUSR1) == prev
        # after close, SIGUSR1 no longer arms this profiler
        assert not p.armed

    def test_request_trace_programmatic(self, tmp_path):
        from automodel_tpu.observability import OnDemandProfiler

        p = OnDemandProfiler(str(tmp_path), trace_steps=3, server_port=0, signum=None)
        p.start()  # signum=None: no handler installed, no server started
        p.request_trace()
        assert p.armed
        p.close()

    def test_closed_window_records_exact_step_coverage(self, tmp_path):
        """A window closed at its step boundary knows exactly how many steps
        it covered (the analyzer's steps_hint); a window cut short by run end
        does not, and must report None."""
        from automodel_tpu.observability import OnDemandProfiler

        p = OnDemandProfiler(str(tmp_path), trace_steps=2, server_port=0,
                             signum=None)
        p.start()
        p.request_trace()
        p.on_step_start(5)  # opens: window spans steps 5..6
        assert p.tracing and p.last_window_steps is None
        p.on_step_end(5)
        assert p.tracing  # still inside the window
        p.on_step_end(6)
        assert not p.tracing
        assert p.last_window_steps == 2
        assert p.take_completed_trace() is not None
        # second window cut short by close(): coverage unknown
        p.request_trace()
        p.on_step_start(9)
        p.close()
        assert p.take_completed_trace() is not None
        assert p.last_window_steps is None


class TestObservabilityManager:
    def test_from_config_nested_sections(self):
        from automodel_tpu.observability import Observability, ObservabilityConfig

        cfg = ObservabilityConfig.from_dict({
            "goodput": True,
            "watchdog": {"enabled": True, "threshold_s": 120},
            "profiling": {"server_port": 0, "trace_steps": 7, "signal": "SIGUSR1"},
        })
        assert cfg.watchdog and cfg.watchdog_threshold_s == 120.0
        assert cfg.trace_steps == 7
        assert cfg.resolve_signal() == signal.SIGUSR1
        assert ObservabilityConfig.from_dict(None) == ObservabilityConfig()
        assert ObservabilityConfig.from_dict({"watchdog": False}).watchdog is False

        obs = Observability(cfg, out_dir="/tmp/obs-test")
        assert obs.watchdog is not None and obs.profiler is not None
        obs.close()

    def test_from_config_perf_observability_sections(self, tmp_path):
        from automodel_tpu.observability import Observability, ObservabilityConfig

        cfg = ObservabilityConfig.from_dict({
            "hlo_costs": False,
            "timeline": {"enabled": True, "max_events": 500},
            "aggregate": {"enabled": True, "straggler_factor": 3.5},
        })
        assert cfg.hlo_costs is False
        assert cfg.timeline is True and cfg.timeline_max_events == 500
        assert cfg.aggregate is True and cfg.straggler_factor == 3.5
        # bool shorthands
        off = ObservabilityConfig.from_dict({"timeline": False, "aggregate": False})
        assert off.timeline is False and off.aggregate is False

        obs = Observability(cfg, out_dir=str(tmp_path))
        assert obs.timeline is not None and obs.timeline.max_events == 500
        assert obs.aggregator is not None and obs.aggregator.straggler_factor == 3.5
        assert not obs.aggregator.active  # single-process suite: no gathers
        # hlo_costs disabled: compile_step hands the fn back untouched
        fn = object()
        assert obs.compile_step(fn, ()) is fn
        obs.close()

    def test_guarded_compiled_raises_on_a_sharding_mismatch(self):
        """The step hands params/opt_state back in the shardings they came in
        with, so an AOT variant is never fed anything else; if it is, that is a
        bug and the error reaches the caller — no quiet demotion to jit."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from automodel_tpu.observability.manager import _GuardedCompiled

        mesh = jax.make_mesh((8,), ("x",))
        split = NamedSharding(mesh, P("x"))
        whole = NamedSharding(mesh, P())
        x = jax.device_put(jnp.arange(16.0), split)
        step = jax.jit(lambda v: v * 2, out_shardings=split)
        fn = _GuardedCompiled(step.lower(x).compile(), step, (x,))
        out = fn(x)
        assert out.sharding == x.sharding  # outputs carry the input shardings
        assert fn(out).sharding == x.sharding  # ...so the step accepts its own output
        with pytest.raises(ValueError, match="sharding"):
            fn(jax.device_put(jnp.arange(16.0), whole))

    def test_timeline_written_on_close_with_compile_and_step_spans(self, tmp_path):
        from automodel_tpu.observability import Observability

        obs = Observability.from_config({"watchdog": False, "memory": False},
                                        str(tmp_path))
        with obs.track("compile", step=1):
            pass
        obs.on_step_start(1)
        obs.on_step_end(1)
        with obs.track("checkpoint"):
            pass
        obs.note_event(1, {"resilience/event": "rollback", "resilience/from_step": 1})
        obs.close()
        doc = json.load(open(os.path.join(str(tmp_path), "timeline.json")))
        names = {e["name"] for e in doc["traceEvents"]}
        assert {"compile", "step", "checkpoint", "rollback"} <= names

    def test_track_is_the_one_span_entry_point(self, tmp_path):
        """One context manager: the goodput bucket is billed, the Chrome event lands on
        timeline.json with its step, and with no profiler trace open the
        TraceAnnotation leaves nothing behind (no file, no exception)."""
        from automodel_tpu.observability import Observability

        obs = Observability.from_config({"watchdog": False, "memory": False},
                                        str(tmp_path))
        before = obs.goodput.totals()
        with obs.track("train_step", step=3, bucket="device_step"):
            time.sleep(0.01)
        with obs.track("log_row", step=3):  # no bucket: billed to none
            with obs.track("lr_schedule", step=3):
                pass
        with obs.track("data_wait"):  # a span named after a bucket bills it
            pass
        after = obs.goodput.totals()
        assert after["device_step"] - before["device_step"] >= 0.01
        assert after["data_wait"] >= before["data_wait"]
        assert set(after) == set(before)  # `log_row` opened no bucket of its own
        obs.close()
        doc = json.load(open(os.path.join(str(tmp_path), "timeline.json")))
        spans = {e["name"]: e for e in doc["traceEvents"] if e.get("cat") == "span"}
        assert set(spans) == {"train_step", "log_row", "lr_schedule", "data_wait"}
        assert spans["train_step"]["args"] == {"step": 3} and spans["train_step"]["dur"] >= 1e4
        assert spans["data_wait"]["args"] == {}
        assert spans["log_row"]["ts"] <= spans["lr_schedule"]["ts"]
        assert abs(doc["t0_unix_s"] - time.time()) < 600
        # nothing but the manager's own artifacts: no trace was open, none was written
        assert not [f for f in os.listdir(tmp_path) if "plugins" in f or f.endswith(".pb")]

    def test_disabled_manager_noops(self, tmp_path):
        from automodel_tpu.observability import Observability

        obs = Observability.from_config({"enabled": False}, str(tmp_path))
        obs.start()
        with obs.track("device_step"):
            pass
        obs.heartbeat(1)
        obs.on_step_start(1)
        obs.on_step_end(1)
        assert obs.step_metrics() == {}
        # the log rows' compile seconds are the one thing a disabled manager keeps
        with obs.track("compile", step=1):
            time.sleep(0.01)
        assert set(obs.step_metrics()) == {"compile_time_s"}
        assert obs.step_metrics()["compile_time_s"] >= 0.01
        obs.close()
        assert not os.path.exists(os.path.join(str(tmp_path), "timeline.json"))

    def test_step_metrics_carries_compile_and_goodput(self, tmp_path):
        from automodel_tpu.observability import Observability

        obs = Observability.from_config({"watchdog": False, "memory": False},
                                        str(tmp_path))
        assert "compile_time_s" not in obs.step_metrics()  # nothing compiled yet
        with obs.track("compile", step=1):  # the span is what the row's seconds add up
            time.sleep(0.02)
        first = obs.step_metrics()["compile_time_s"]
        with obs.track("compile", step=7):  # delayed-QAT second compile accumulates
            time.sleep(0.02)
        with obs.track("device_step"):
            pass
        m = obs.step_metrics()
        assert first >= 0.02 and m["compile_time_s"] >= first + 0.02
        assert m["compile_time_s"] == pytest.approx(
            obs.goodput.totals()["compile"], abs=5e-3)  # the bucket's seconds, no other clock
        assert "goodput" in m and "goodput/idle" in m
        obs.close()

    def test_stall_event_reaches_metric_sink(self, tmp_path):
        from automodel_tpu.observability import Observability

        rows = []
        obs = Observability.from_config(
            {"watchdog": {"threshold_s": 0.05, "poll_interval_s": 0.01},
             "goodput": False, "memory": False},
            str(tmp_path),
            metric_sink=lambda step, **kw: rows.append((step, kw)),
        )
        obs.start()
        obs.heartbeat(4)
        deadline = time.monotonic() + 5.0
        while not rows and time.monotonic() < deadline:
            time.sleep(0.01)
        obs.close()
        assert rows, "stall event must flow through the metric sink"
        step, fields = rows[0]
        assert step == 4 and fields["event"] == "stall"
        assert os.path.exists(fields["stack_dump"])

    @pytest.mark.parametrize("policy,fwd_calls", [("none", 2), ("mlp_attn_dots", 1)])
    def test_compile_row_counts_the_flash_kernels_calls(self, tmp_path, policy, fwd_calls):
        """``attention_fwd_calls`` / ``attention_bwd_calls`` of the ``compile_costs`` row,
        read off the compiled step: a remat policy that replays the forward kernel
        reads 2 : 1, one that keeps the kernel's output and log-sum-exp 1 : 1, and a
        layer scan's body counts once whatever the depth."""
        import jax

        from automodel_tpu.models.common.backend import BackendConfig
        from automodel_tpu.observability import Observability
        from automodel_tpu.ops.attention import sharded_attention

        def layer(h, w):
            q = (h @ w["q"]).reshape(1, 32, 2, 8)
            kv = (h @ w["kv"]).reshape(1, 32, 1, 8)
            out = sharded_attention(q, kv, kv, rules=None, backend="flash_interpret")
            return h + out.reshape(1, 32, 16) @ w["o"]

        remat = BackendConfig(remat_policy=policy).layer_remat(layer)

        def step(w, x):
            loss = lambda w: jax.lax.scan(lambda h, lw: (remat(h, lw), None), x, w)[0].sum()
            return jax.value_and_grad(loss)(w)

        key = jax.random.key(0)
        w = {"q": jax.random.normal(key, (3, 16, 16)), "kv": jax.random.normal(key, (3, 16, 8)),
             "o": jax.random.normal(key, (3, 16, 16))}
        rows = []
        obs = Observability.from_config({"watchdog": False, "memory": False}, str(tmp_path),
                                        metric_sink=lambda step, **kw: rows.append(kw))
        obs.compile_step(jax.jit(step), (w, jnp.ones((1, 32, 16))))
        obs.close()
        (row,) = [r for r in rows if r.get("event") == "compile_costs"]
        assert (row["attention_fwd_calls"], row["attention_bwd_calls"]) == (fwd_calls, 1)

    @pytest.mark.parametrize("held", [None, 2], ids=["all_experts", "a_share"])
    def test_compile_row_counts_the_moe_blocks_row_scatters(self, tmp_path, held):
        """``moe_row_scatters`` of the ``compile_costs`` row: a layer that holds all its
        experts moves rows by gathers both ways and reads 0; a held share keeps its
        scatter-adds (the combine in both of its loops, the transposes of its gathers)."""
        import jax

        from automodel_tpu.moe import MoEConfig, grouped_experts_apply, init_expert_params
        from automodel_tpu.observability import Observability

        cfg = MoEConfig(n_routed_experts=4, n_activated_experts=2, dim=16, moe_inter_dim=8,
                        **({} if held is None else {"n_held_experts": held}))
        params = init_expert_params(cfg, jax.random.key(0))
        idx = jnp.arange(24, dtype=jnp.int32).reshape(12, 2) % 4

        def step(params, x, w):
            loss = lambda p, x, w: grouped_experts_apply(cfg, p, x, w, idx).sum()
            return jax.value_and_grad(loss, argnums=(0, 1, 2))(params, x, w)

        rows = []
        obs = Observability.from_config({"watchdog": False, "memory": False}, str(tmp_path),
                                        metric_sink=lambda step, **kw: rows.append(kw))
        obs.compile_step(jax.jit(step), (params, jnp.ones((12, 16)), jnp.full((12, 2), 0.5)))
        obs.close()
        (row,) = [r for r in rows if r.get("event") == "compile_costs"]
        assert (row["moe_row_scatters"] == 0) if held is None else (row["moe_row_scatters"] > 0)
