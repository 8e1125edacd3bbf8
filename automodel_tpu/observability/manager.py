"""Config-driven observability manager wired into the training recipes.

One object owns the pillars — goodput accounting, HBM/compile telemetry, the
stall watchdog, on-demand profiling, per-compile HLO cost/roofline
accounting, the unified trace timeline, and cross-host metric aggregation —
so a recipe integrates with a handful of hooks: built first thing in
``setup()`` (``bind_process()`` once the distributed runtime is up,
``setup_done()`` at its end), ``start()``,
``track(name, step, bucket)`` (the one way to open a span), ``heartbeat(step)``,
``on_step_start/end(step)``, ``compile_step(fn, args)`` at the first call of a
jitted step, ``write_setup_summary(step)`` when that step has finished, and
``step_metrics()`` / ``roofline_row()`` / ``host_metrics()`` merged into each
log row. Everything flows through the existing MetricLogger/experiment-logger
fan-out plus one new artifact, ``out_dir/timeline.json``.

YAML (all keys optional; the subsystem is on by default and every pillar
no-ops cleanly where its backing API is unavailable):

.. code-block:: yaml

    observability:
      enabled: true
      goodput: true
      memory: true
      hlo_costs: true
      timeline: {enabled: true, max_events: 20000}
      aggregate: {enabled: true, straggler_factor: 2.0}
      watchdog: {enabled: true, threshold_s: 600}
      profiling: {server_port: 0, trace_steps: 5, signal: SIGUSR1}
      dynamics: {enabled: true, every_n_steps: 10, spike_zscore: 6.0}
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import logging
import os
import signal as _signal
import time
from typing import Any, Callable

from automodel_tpu.observability import compile_cache
from automodel_tpu.observability.aggregate import CrossHostAggregator, host_keys
from automodel_tpu.observability.dynamics import DynamicsConfig, DynamicsTracker
from automodel_tpu.observability.events import TraceTimeline
from automodel_tpu.observability.goodput import BUCKETS, GoodputTracker
from automodel_tpu.observability.hlo_costs import (
    compiled_cost_metrics,
    device_specs,
    diagnose_bound,
    roofline_metrics,
)
from automodel_tpu.observability.memory import device_memory_stats
from automodel_tpu.observability.memory_plan import (
    MemoryPlan,
    compiled_memory_attribution,
    reconcile,
)
from automodel_tpu.observability.oom import OOMFlightRecorder, is_oom_error
from automodel_tpu.observability.profiling import OnDemandProfiler
from automodel_tpu.observability.trace_analysis import (
    instruction_op_names,
    kernel_call_counts,
    moe_row_scatter_count,
)
from automodel_tpu.observability.watchdog import StallWatchdog

logger = logging.getLogger(__name__)

__all__ = ["ObservabilityConfig", "Observability"]

# the span around the call of the compiled step: the profiler's step marker
# (``StepTraceAnnotation``), so device runs of the step can be matched to steps
_STEP_SPAN = "train_step"


@dataclasses.dataclass
class ObservabilityConfig:
    enabled: bool = True
    goodput: bool = True
    memory: bool = True
    oom_report: bool = True  # OOM flight recorder (needs memory pillar on)
    oom_keep_rows: int = 20  # metric rows kept for the crash artifact
    hbm_limit_gib: float | None = None  # per-chip capacity override (mem plan)
    hlo_costs: bool = True
    timeline: bool = True
    timeline_max_events: int = 20000
    aggregate: bool = True
    straggler_factor: float = 2.0
    oom_risk_gib: float = 1.0  # flag a host when its headroom drops below this
    divergence_rtol: float = 1e-4  # replicated-scalar disagreement = desync
    dynamics: DynamicsConfig = dataclasses.field(default_factory=DynamicsConfig)
    watchdog: bool = True
    watchdog_threshold_s: float = 600.0
    watchdog_poll_interval_s: float | None = None
    profiler_port: int = 0  # 0 = no profiler server
    trace_steps: int = 5
    trace_signal: str | None = "SIGUSR1"  # None/"none" = no signal handler
    auto_trace: bool = True  # stall/excursion anomalies arm the profiler
    auto_trace_max: int = 1  # per-run budget of anomaly-triggered traces
    excursion_factor: float = 3.0  # step_time > factor x rolling median fires
    excursion_min_samples: int = 5  # dt samples before excursions are judged

    @classmethod
    def from_dict(cls, raw: Any) -> "ObservabilityConfig":
        """Build from the ``observability:`` YAML section (ConfigNode or dict)."""
        if raw is None:
            return cls()
        if hasattr(raw, "to_dict"):
            raw = raw.to_dict()
        raw = dict(raw)
        kw: dict[str, Any] = {
            k: raw[k] for k in ("enabled", "goodput", "hlo_costs") if k in raw
        }
        mem = raw.get("memory")
        if isinstance(mem, bool):
            kw["memory"] = mem
        elif isinstance(mem, dict):
            kw["memory"] = bool(mem.get("enabled", True))
            if "oom_report" in mem:
                kw["oom_report"] = bool(mem["oom_report"])
            if mem.get("oom_keep_rows") is not None:
                kw["oom_keep_rows"] = int(mem["oom_keep_rows"])
            if mem.get("hbm_limit_gib") is not None:
                kw["hbm_limit_gib"] = float(mem["hbm_limit_gib"])
        tl = raw.get("timeline")
        if isinstance(tl, bool):
            kw["timeline"] = tl
        elif isinstance(tl, dict):
            kw["timeline"] = bool(tl.get("enabled", True))
            if tl.get("max_events") is not None:
                kw["timeline_max_events"] = int(tl["max_events"])
        agg = raw.get("aggregate")
        if isinstance(agg, bool):
            kw["aggregate"] = agg
        elif isinstance(agg, dict):
            kw["aggregate"] = bool(agg.get("enabled", True))
            if agg.get("straggler_factor") is not None:
                kw["straggler_factor"] = float(agg["straggler_factor"])
            if agg.get("oom_risk_gib") is not None:
                kw["oom_risk_gib"] = float(agg["oom_risk_gib"])
            if agg.get("divergence_rtol") is not None:
                kw["divergence_rtol"] = float(agg["divergence_rtol"])
        if "dynamics" in raw:
            kw["dynamics"] = DynamicsConfig.from_dict(raw["dynamics"])
        wd = raw.get("watchdog")
        if isinstance(wd, bool):
            kw["watchdog"] = wd
        elif isinstance(wd, dict):
            kw["watchdog"] = bool(wd.get("enabled", True))
            if wd.get("threshold_s") is not None:
                kw["watchdog_threshold_s"] = float(wd["threshold_s"])
            if wd.get("poll_interval_s") is not None:
                kw["watchdog_poll_interval_s"] = float(wd["poll_interval_s"])
        prof = raw.get("profiling")
        if isinstance(prof, dict):
            kw["profiler_port"] = int(prof.get("server_port", 0))
            kw["trace_steps"] = int(prof.get("trace_steps", 5))
            kw["trace_signal"] = prof.get("signal", "SIGUSR1")
            if "auto_trace" in prof:
                kw["auto_trace"] = bool(prof["auto_trace"])
            if prof.get("auto_trace_max") is not None:
                kw["auto_trace_max"] = int(prof["auto_trace_max"])
            if prof.get("excursion_factor") is not None:
                kw["excursion_factor"] = float(prof["excursion_factor"])
            if prof.get("excursion_min_samples") is not None:
                kw["excursion_min_samples"] = int(prof["excursion_min_samples"])
        return cls(**kw)

    def resolve_signal(self) -> int | None:
        name = self.trace_signal
        if not name or str(name).lower() == "none":
            return None
        return getattr(_signal, str(name).upper())


def _process_age_s() -> float | None:
    """Seconds since the OS started this process: interpreter, imports and the
    accelerator runtime's start lie before any span the program can open.
    Linux ``/proc``; None ("not reported") elsewhere."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime_s = float(f.read().split()[0])
        return uptime_s - start_ticks / os.sysconf("SC_CLK_TCK")
    except Exception:
        return None


def _rounded(seconds: Any) -> float | None:
    return None if seconds is None else round(float(seconds), 4)


def _tree_avals(args: Any) -> Any:
    """Shape/dtype fingerprint of an argument tree — the executor dispatch key."""
    import jax

    return jax.tree.map(
        lambda x: (getattr(x, "shape", None), str(getattr(x, "dtype", type(x).__name__))),
        args,
    )


def _avals_key(args: Any) -> Any:
    """Hashable form of :func:`_tree_avals` — the variant-dict key."""
    import jax

    leaves, treedef = jax.tree.flatten(_tree_avals(args))
    return (treedef, tuple(leaves))


class _GuardedCompiled:
    """Dispatch steps to AOT-compiled variants by shape; jit is the last resort.

    The jit dispatch cache does NOT share entries with an AOT compile of the
    same function, so after extracting costs from ``lowered.compile()`` the
    loop must execute through that same compiled object or it would pay the
    full compile twice. The executor keys compiled variants by the argument
    shape/dtype fingerprint because the step scheduler can emit more than one
    step shape — the steady accumulation stack plus a trailing partial stack
    at the epoch tail. Warm restart (docs/resilience.md) pre-compiles the
    trailing shape via :meth:`add_variant`, so every shape the scheduler emits
    runs AOT; an *unplanned* shape falls back to jit and is counted
    (``aot_shape_fallback``) so the compile_summary row exposes it.

    A variant accepts only the input shardings it was lowered for. The step
    hands params/opt_state back in the shardings they came in with
    (``training.train_step.jit_train_step``), so feeding a step its own
    outputs always matches; any other sharding is a bug and raises.
    """

    def __init__(self, compiled: Any, fallback: Callable, args: Any,
                 on_shape_fallback: Callable[[], None] | None = None):
        self._variants: dict[Any, Any] = {_avals_key(args): compiled}
        self._fallback = fallback
        self._on_shape_fallback = on_shape_fallback
        self._warned_shapes: set[Any] = set()

    def add_variant(self, args: Any, compiled: Any) -> None:
        """Register an AOT-compiled variant for this argument shape."""
        self._variants[_avals_key(args)] = compiled

    @property
    def num_variants(self) -> int:
        return len(self._variants)

    def __call__(self, *args: Any) -> Any:
        key = _avals_key(args)
        compiled = self._variants.get(key)
        if compiled is not None:
            return compiled(*args)
        # unseen shape: no variant was pre-compiled for it — jit picks it
        # up, but the miss is counted so warm-restart coverage is auditable
        if key not in self._warned_shapes:
            self._warned_shapes.add(key)
            logger.info("step shape has no AOT variant; running through jit")
        if self._on_shape_fallback is not None:
            self._on_shape_fallback()
        return self._fallback(*args)


class Observability:
    """The manager a recipe holds; disabled pillars degrade to no-ops."""

    def __init__(
        self,
        config: ObservabilityConfig,
        out_dir: str,
        metric_sink: Callable[..., None] | None = None,
    ):
        self.config = config
        self.out_dir = str(out_dir)
        # a start, until the first step has finished (``write_setup_summary``):
        # the recipe builds the manager first thing in ``setup()``, so these
        # stamps ARE set-up's entry; every span opened meanwhile is kept by name
        self._before_setup_s = _process_age_s()
        self._t_setup = time.perf_counter()
        self._t_setup_done: float | None = None
        self._t_loop: float | None = None
        self._setup_spans: dict[str, float] | None = {}
        self._setup_spanned_s = 0.0  # of the outermost spans alone
        self._span_depth = 0
        self._step_request: dict[str, Any] | None = None  # the step's own compile request
        self._requests_on_timeline = 0
        self.compile_time_s: float | None = None
        self.roofline: dict[str, Any] | None = None
        # set by the recipe before compile_step ({axis: size}) so collective
        # bytes get attributed to ep/dp/tp/pp in the cost row
        self.mesh_axes: dict[str, int] | None = None
        # set by the recipe ({"model": ..., "seq_len": ...}) to identify the
        # (model, mesh, seq) cell in the signals.json bundle
        self.cell_info: dict[str, Any] | None = None
        # compile_step keeps the module text + analytic costs so completed
        # traces can be classified against named scopes (trace_analysis.py)
        self._hlo_text: str | None = None
        self._costs: dict[str, Any] | None = None
        # summary_row + reconciliation of the most recent analyzed trace
        self.trace_summary: dict[str, Any] | None = None
        # AOT-vs-jit accounting across every compile_step of the run:
        # aot = primary AOT compiles, aot_variant = extra shapes pre-compiled
        # by warmup, aot_shape_fallback = steps whose shape had no variant
        # (ran via jit), jit_fallback = step fns that never got an AOT
        # executor at all
        self.compile_counts = {"aot": 0, "jit_fallback": 0,
                               "aot_variant": 0, "aot_shape_fallback": 0}
        self._metric_sink = metric_sink
        self._step_t0: float | None = None
        # analytic HBM plan (set by the recipe once params/opt_state exist);
        # compile_step reconciles it against memory_analysis()
        self.memory_plan: MemoryPlan | None = None
        # anomaly-triggered profiling: per-run budget + step-time history
        self._auto_traces = 0
        self._dt_history: list[float] = []
        on = config.enabled
        self.goodput: GoodputTracker | None = GoodputTracker() if on and config.goodput else None
        self._memory = on and config.memory
        self.oom: OOMFlightRecorder | None = None
        if on and config.memory and config.oom_report:
            self.oom = OOMFlightRecorder(self.out_dir, keep_rows=config.oom_keep_rows)
        self.timeline: TraceTimeline | None = None
        if on and config.timeline:
            # no process index yet: the manager is built before the distributed
            # runtime is (``bind_process`` settles who keeps the file)
            self.timeline = TraceTimeline(os.path.join(self.out_dir, "timeline.json"),
                                          max_events=config.timeline_max_events)
        self.aggregator: CrossHostAggregator | None = None
        if on and config.aggregate:
            self.aggregator = CrossHostAggregator(
                config.straggler_factor, oom_risk_gib=config.oom_risk_gib,
                divergence_rtol=config.divergence_rtol)
        self.dynamics: DynamicsTracker | None = None
        if on and config.dynamics.enabled:
            self.dynamics = DynamicsTracker(config.dynamics, self.out_dir,
                                            metric_sink=metric_sink)
        self.watchdog: StallWatchdog | None = None
        if on and config.watchdog:
            def on_stall(event: dict, _sink=metric_sink):
                step = int(event.get("step") or 0)
                if _sink is not None:
                    _sink(step, **{k: v for k, v in event.items() if k != "step"})
                # a stalled run is exactly when a trace is worth its cost:
                # arm the profiler so the NEXT step (if the run unwedges)
                # captures what the device was doing
                self.auto_trace("stall", step, stall_s=event.get("stall_s"))
            self.watchdog = StallWatchdog(
                threshold_s=config.watchdog_threshold_s,
                dump_dir=self.out_dir,
                on_stall=on_stall,
                poll_interval_s=config.watchdog_poll_interval_s,
                # a stack dump alone says where the run is stuck; the goodput
                # snapshot says what it was doing with its time until then
                context_fn=lambda: self.goodput.snapshot() if self.goodput else {},
            )
        self.profiler: OnDemandProfiler | None = None
        if on:
            self.profiler = OnDemandProfiler(
                self.out_dir,
                trace_steps=config.trace_steps,
                server_port=config.profiler_port,
                signum=config.resolve_signal(),
            )
        # external liveness: when a supervisor (resilience/supervisor.py) set
        # AUTOMODEL_HEARTBEAT_FILE, every heartbeat() also beats that file —
        # the hang detector outside this process keys off its mtime/step
        from automodel_tpu.resilience.supervisor import HeartbeatWriter

        self.heartbeat_writer: HeartbeatWriter | None = HeartbeatWriter.from_env()

    @classmethod
    def from_config(cls, cfg: Any, out_dir: str,
                    metric_sink: Callable[..., None] | None = None) -> "Observability":
        return cls(ObservabilityConfig.from_dict(cfg), out_dir, metric_sink)

    # ------------------------------------------------------------------ lifecycle
    def bind_process(self) -> None:
        """Once ``jax.distributed`` is up (asking earlier would start the
        backend ahead of it): process 0 keeps ``timeline.json``, the others
        keep no file, as every artifact writer does."""
        import jax

        proc = jax.process_index()
        if self.timeline is not None:
            self.timeline.pid = proc
            if proc != 0:
                self.timeline.path = None

    def setup_done(self) -> None:
        """End of the recipe's ``setup()``. The goodput wall opens here, less
        what set-up already billed (the ``restore`` span on resume): the
        fractions keep summing to 1 and a resume's cost does not read as idle,
        while model and data building stay what the run ledger calls re-init."""
        self._t_setup_done = time.perf_counter()
        if self.goodput is not None:
            self.goodput.open_wall()

    def start(self) -> "Observability":
        if self._t_loop is None:
            self._t_loop = time.perf_counter()
        if self.watchdog is not None:
            self.watchdog.start()
        if self.profiler is not None:
            self.profiler.start()
        if self.dynamics is not None:
            self.dynamics.start()
        return self

    def close(self) -> None:
        if self.watchdog is not None:
            self.watchdog.stop()
        if self.profiler is not None:
            self.profiler.close()
            # a window the run end cut short still gets its analysis
            trace = self.profiler.take_completed_trace()
            if trace is not None:
                self.analyze_trace(trace, step=-1,
                                   steps_hint=self.profiler.last_window_steps)
        self.write_signals()
        if self.dynamics is not None:
            self.dynamics.close()
        self._compile_requests_to_timeline()
        if self.timeline is not None:
            self.timeline.close()

    @property
    def dynamics_enabled(self) -> bool:
        """True when the train step should be built with ``dynamics=True``."""
        return self.dynamics is not None

    def compile_summary(self) -> dict[str, Any]:
        """Run-total AOT/jit-fallback/demotion counts, compile-cache hits and
        the compile requests of the whole process summed (``compile_cache.totals``).

        The run_header is written before the first compile, so the per-run
        totals land here instead — the recipe logs this as a
        ``compile_summary`` event row at teardown.
        """
        out = {f"compile_{k}": v for k, v in self.compile_counts.items()}
        cache = compile_cache.counts()
        out["compile_cache_hits"] = cache["hits"]
        out["compile_cache_misses"] = cache["misses"]
        out.update(compile_cache.totals())
        return out

    def write_setup_summary(self, step: int) -> None:
        """The ``setup_summary`` row, once, when the first step has finished
        (docs/observability.md "Compile time and throughput"): where a start's
        seconds went, by the spans ``track`` kept since the manager was built,
        and what the compile requests of the start did with the cache. After
        it ``track`` keeps no span by name: a step pays nothing for this."""
        spans, self._setup_spans = self._setup_spans, None
        if spans is None or not self.config.enabled:
            return
        now = time.perf_counter()
        setup_s = (self._t_setup_done or now) - self._t_setup
        loop_s = now - self._t_loop if self._t_loop is not None else 0.0
        requests = [r for r in compile_cache.requests() if r["backend_s"] is not None]
        totals, seconds = compile_cache.totals(), compile_cache.seconds
        own = self._step_request or {}
        row = {
            "before_setup_s": _rounded(self._before_setup_s),
            "setup_s_inside": round(setup_s, 3),
            "loop_start_s": round(loop_s, 3),
            "spans": {name: round(s, 4) for name, s in spans.items()},
            "unspanned_s": round(max(setup_s + loop_s - self._setup_spanned_s, 0.0), 3),
            "step_trace_s": _rounded(own.get("trace_s")),
            "step_mlir_s": _rounded(own.get("lower_s")),
            "compile_requests": totals["compile_requests"],
            "cache_hits": sum(r["cache"] == "hit" for r in requests),
            "cache_misses": sum(r["cache"] == "miss" for r in requests),
            "missed": totals["missed"],
            "slowest_jits": [[str(r["fun_name"]), round(seconds(r), 3), r["cache"]]
                             for r in sorted(requests, key=seconds, reverse=True)[:8]],
        }
        self._compile_requests_to_timeline()
        if self._metric_sink is not None:
            self._metric_sink(step, event="setup_summary", **row)

    def _compile_requests_to_timeline(self) -> None:
        """Every compile request not yet there, as a complete event (cat
        ``compile``) on the timeline's clock: the small jits of a start show
        between the spans."""
        if self.timeline is None:
            return
        records = compile_cache.requests()
        new, self._requests_on_timeline = records[self._requests_on_timeline:], len(records)
        for r in new:
            start_s = float(r["start"]) - self.timeline.t0_unix_s
            self.timeline.complete(
                str(r["fun_name"]), "compile", start_s, float(r["end"]) - float(r["start"]),
                fun_name=r["fun_name"], cache=r["cache"], key=r["key"],
                trace_s=_rounded(r["trace_s"]), lower_s=_rounded(r["lower_s"]),
                backend_s=_rounded(r["backend_s"]), retrieval_s=_rounded(r["retrieval_s"]))

    # ------------------------------------------------------------------ hooks
    def track(self, name: str, step: int | None = None, bucket: str | None = None):
        """The one way to open a span (docs/observability.md "Spans").

        In one context manager the span bills its goodput ``bucket`` (a span
        named after a bucket bills that bucket), lands on ``timeline.json``
        with its ``step``, and enters a ``jax.profiler.TraceAnnotation`` — so
        whenever a profiler trace is open (the SIGUSR1 window, an auto-trace,
        a caller's own ``start_trace``) it lies on the trace's host plane, on
        the device trace's clock. With no trace open the annotation is one
        flag test.
        """
        stack = contextlib.ExitStack()
        if bucket is None and name in BUCKETS:
            bucket = name
        if bucket == "compile":  # the log rows' ``compile_time_s``: disabled or not
            stack.enter_context(self._compile_clock())
        if not self.config.enabled:
            return stack
        import jax

        if self.goodput is not None and bucket is not None:
            stack.enter_context(self.goodput.track(bucket))
        if self._setup_spans is not None:
            stack.enter_context(self._setup_span(name))
        args = {} if step is None else {"step": step}
        if self.timeline is not None:
            stack.enter_context(self.timeline.span(name, cat="span", **args))
        if name == _STEP_SPAN and step is not None:
            stack.enter_context(jax.profiler.StepTraceAnnotation(name, step_num=step))
        else:
            stack.enter_context(jax.profiler.TraceAnnotation(name, **args))
        return stack

    @contextlib.contextmanager
    def _compile_clock(self):
        """Cumulative ``compile_time_s`` of the log rows: what the ``compile``
        spans took (the first step's, a delayed-QAT switch's second)."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            seconds = time.perf_counter() - t0
            self.compile_time_s = round((self.compile_time_s or 0.0) + seconds, 3)
            logger.info("jit compile + first execute: %.1fs (cumulative %.1fs)",
                        seconds, self.compile_time_s)

    @contextlib.contextmanager
    def _setup_span(self, name: str):
        """A span of a start, kept by name for the ``setup_summary`` row; the
        outermost ones also count towards what the row calls spanned."""
        t0 = time.perf_counter()
        self._span_depth += 1
        try:
            yield
        finally:
            self._span_depth -= 1
            dt = time.perf_counter() - t0
            if self._setup_spans is not None:
                self._setup_spans[name] = self._setup_spans.get(name, 0.0) + dt
                # between ``setup()`` and the loop the program is not running
                in_walls = self._t_setup_done is None or self._t_loop is not None
                if self._span_depth == 0 and in_walls:
                    self._setup_spanned_s += dt

    def compile_step(self, step_fn: Callable, args: tuple, step: int = 0,
                     on_traced: Callable[[], None] | None = None) -> Callable:
        """First call of a jitted step: AOT-compile, log analytic costs +
        roofline once, and return the executor the loop should run from now on.

        Must run BEFORE the first execution — the step donates its params, so
        lowering afterwards would trace over deleted buffers. On any failure
        (backend without cost analysis, non-jit callable) the jit fn comes
        back unchanged and the run proceeds with one log line of warning.
        ``on_traced`` runs once the step has been traced, before it compiles.
        """
        if not (self.config.enabled and self.config.hlo_costs):
            return step_fn
        if not hasattr(step_fn, "lower"):  # plain-function executor (e.g. pp wrapper)
            logger.info("step executor is not a jit callable; no HLO cost row")
            self.compile_counts["jit_fallback"] += 1
            return step_fn
        import jax

        # outside the try below: a chip that is not in the peak table is an
        # error to repair, not a reason to step through jit
        spec = device_specs(jax.devices()[0].device_kind)
        try:
            with self.track("step_lower", step=step):
                seen = len(compile_cache.requests())
                lowered = step_fn.lower(*args)
                # the step's own request: the outermost lowering ends last
                self._step_request = (compile_cache.requests()[seen:] or [None])[-1]
                if on_traced is not None:
                    on_traced()  # before any row of this step reaches the sink
            with self.track("step_compile", step=step):
                compiled = lowered.compile()
            # what observing costs at every start: from here to the executor
            with self.track("step_analysis", step=step):
                row = self._analyze_compiled(compiled, spec)
                self.compile_counts["aot"] += 1
                row["compile_aot_total"] = self.compile_counts["aot"]
                if self._metric_sink is not None:
                    self._metric_sink(step, **row)
                if self.timeline is not None:
                    self.timeline.instant(
                        "compile_costs", cat="compile", step=step,
                        hlo_flops=row.get("hlo_flops"),
                        comm_bytes_total=row.get("comm_bytes_total"),
                    )
            def _shape_fallback():
                self.compile_counts["aot_shape_fallback"] += 1
            return _GuardedCompiled(compiled, step_fn, args,
                                    on_shape_fallback=_shape_fallback)
        except Exception:
            logger.warning("HLO cost extraction failed; step runs through jit",
                           exc_info=True)
            self.compile_counts["jit_fallback"] += 1
            return step_fn

    def _analyze_compiled(self, compiled: Any, spec: Any) -> dict[str, Any]:
        """The ``compile_costs`` row of one compiled step: the HLO text parsed
        for costs, ``step_scopes.json``, the roofline, XLA's memory attribution
        reconciled against the analytic plan."""
        try:
            hlo = compiled.as_text()  # fetched once; as_text() is not free
        except Exception:
            hlo = None
        costs = compiled_cost_metrics(compiled, mesh_axes=self.mesh_axes, hlo_text=hlo)
        self._hlo_text = hlo
        self._costs = costs
        self._write_step_scopes(hlo)
        # a CPU has no peak: its rows carry no roofline
        roof = roofline_metrics(costs, spec) if spec is not None else {}
        self.roofline = roof or None
        row: dict[str, Any] = {"event": "compile_costs", **costs}
        if hlo:
            # forward against backward calls of the flash kernel: 2 : 1 where the
            # remat policy replays the forward, 1 : 1 where it keeps the kernel's
            # output and log-sum-exp (``mlp_attn_dots``)
            calls = kernel_call_counts(hlo)
            row["attention_fwd_calls"] = calls.get("flash_attention_fwd", 0)
            row["attention_bwd_calls"] = (calls.get("flash_attention_bwd", 0)
                                          + calls.get("flash_attention_bwd_dq", 0))
            # scatters under moe_dispatch / moe_combine: 0 where the dropless block
            # moves its rows by gathers both ways, positive under a held share
            row["moe_row_scatters"] = moe_row_scatter_count(hlo)
        if roof:
            for key in ("roofline_t_compute_s", "roofline_t_memory_s",
                        "roofline_t_comm_s", "roofline_step_time_s"):
                row[key] = round(roof[key], 6)
            if "roofline_t_moe_a2a_s" in roof:
                row["roofline_t_moe_a2a_s"] = round(roof["roofline_t_moe_a2a_s"], 6)
            row["roofline_bound"] = roof["roofline_bound"]
            row["roofline_spec"] = roof["roofline_spec"]
        if self._memory:
            # the memory pillar's compile-time half: XLA's own byte
            # attribution, reconciled against the analytic plan when the
            # recipe provided one (mem_plan/recon_rel_err)
            attribution = compiled_memory_attribution(compiled)
            if attribution:
                if self.memory_plan is not None:
                    row.update(reconcile(self.memory_plan, attribution))
                    if self.oom is not None:
                        self.oom.set_plan_row(self.memory_plan.header_row())
                else:
                    row.update({f"mem/{k}_gib": round(v / 2**30, 4)
                                for k, v in attribution.items()})
            if self.timeline is not None and self.memory_plan is not None:
                plan = self.memory_plan
                self.timeline.counter(
                    "hbm_plan_gib",
                    params=round(plan.params_bytes / 2**30, 6),
                    opt=round(plan.opt_bytes / 2**30, 6),
                    batch=round(plan.batch_bytes / 2**30, 6),
                    act_est=round(plan.act_est_bytes / 2**30, 6),
                )
        return row

    def _write_step_scopes(self, hlo: str | None) -> None:
        """``out_dir/step_scopes.json``: the compiled step's instruction ->
        ``op_name`` table. A TPU trace names each device event by its
        instruction and carries no ``op_name``, so whoever reads a trace of
        this run joins the two through this file (docs/observability.md
        "Device names"). Proc 0; the latest compiled step wins."""
        import jax

        if not hlo or jax.process_index() != 0:
            return
        os.makedirs(self.out_dir, exist_ok=True)
        path = os.path.join(self.out_dir, "step_scopes.json")
        with open(f"{path}.tmp", "w") as f:
            json.dump(instruction_op_names(hlo), f)
        os.replace(f"{path}.tmp", path)

    def precompile_variant(self, executor: Callable, step_fn: Callable,
                           args: tuple, step: int = 0) -> bool:
        """AOT-compile one extra step shape into an existing executor.

        The warm-restart half of elastic resume (docs/resilience.md): the
        recipe calls this for every step shape the scheduler can emit beyond
        the steady one — e.g. the trailing partial accumulation — so no shape
        demotes to a mid-run jit compile. With a persistent compilation cache
        configured (observability/compile_cache.py) the lowering hits the
        cache and the "compile" is a deserialization. No-op (False) when the
        executor is not an AOT dispatcher or the compile fails.
        """
        if not isinstance(executor, _GuardedCompiled) or not hasattr(step_fn, "lower"):
            return False
        try:
            seen = len(compile_cache.requests())
            compiled = step_fn.lower(*args).compile()
            executor.add_variant(args, compiled)
            self.compile_counts["aot_variant"] += 1
            if self._metric_sink is not None:
                # what JAX reports of the variant's own request: trace, lowering, backend
                own = (compile_cache.requests()[seen:] or [{}])[-1]
                self._metric_sink(step, event="compile_variant",
                                  compile_s=round(compile_cache.seconds(own), 3),
                                  variants=executor.num_variants)
            return True
        except Exception:
            logger.warning("AOT warmup variant compile failed; that shape will "
                           "run through jit", exc_info=True)
            return False

    def heartbeat(self, step: int | None = None) -> None:
        if self.watchdog is not None:
            self.watchdog.heartbeat(step)
        if self.heartbeat_writer is not None:
            self.heartbeat_writer.beat(step)

    def on_step_start(self, step: int) -> None:
        if self.profiler is not None:
            self.profiler.on_step_start(step)
        if self.timeline is not None:
            self._step_t0 = self.timeline.now()

    def on_step_end(self, step: int, sync: Any = None) -> None:
        if self.profiler is not None:
            self.profiler.on_step_end(step, sync)
            trace = self.profiler.take_completed_trace()
            if trace is not None:
                self.analyze_trace(trace, step,
                                   steps_hint=self.profiler.last_window_steps)
        if self.dynamics is not None:
            self.dynamics.maybe_snapshot(step)
        if self.timeline is not None and self._step_t0 is not None:
            self.timeline.complete("step", "step", self._step_t0,
                                   self.timeline.now() - self._step_t0, step=step)
            self._step_t0 = None

    def dynamics_row(self, step: int, dyn_tree: Any) -> dict[str, float]:
        """One cadence sample: fold the device dynamics pytree into the
        tracker (EMAs, amax history, flight-recorder ring) and mirror the
        per-layer series onto Chrome counter tracks. Returns the flat
        ``dynamics/*`` keys the recipe merges into its log row."""
        if self.dynamics is None:
            return {}
        flat = self.dynamics.row(step, dyn_tree)
        if self.timeline is not None:
            self.timeline.counters_from_flat(flat)
        return flat

    def note_event(self, step: int, fields: dict[str, Any]) -> None:
        """Route structured events (stalls, resilience rollbacks/preemptions)
        onto the timeline; the metric fan-out already carries them as rows."""
        if self.timeline is None:
            return
        name = fields.get("event") or fields.get("resilience/event")
        if not name or name == "compile_costs":
            return
        args = {
            k.split("/")[-1]: v for k, v in fields.items()
            if isinstance(v, (int, float, str, bool)) and k.split("/")[-1]
            not in ("event", "step")
        }
        self.timeline.instant(str(name), cat="event", step=step, **args)

    # -------------------------------------------------------------- auto-trace
    def auto_trace(self, reason: str, step: int, **info: Any) -> bool:
        """Arm a throttled anomaly-triggered trace; True when actually armed.

        The throttle is a hard per-run budget (``auto_trace_max``): one
        anomaly explains itself with one trace, and a run degenerating every
        step must not fill the disk with xprof dumps. Requests while a trace
        is open or already armed coalesce (the profiler handles that); a
        manual SIGUSR1 is never budgeted — only anomaly triggers are.
        """
        if (self.profiler is None or not self.config.auto_trace
                or self._auto_traces >= self.config.auto_trace_max):
            return False
        if self.profiler.tracing or self.profiler.armed:
            return False
        self._auto_traces += 1
        self.profiler.request_trace()
        logger.warning("anomaly (%s) armed an auto-trace at step %d (%d/%d this run)",
                       reason, step, self._auto_traces, self.config.auto_trace_max)
        if self.timeline is not None:
            self.timeline.instant("auto_trace", cat="event", step=step,
                                  reason=reason, **info)
        if self._metric_sink is not None:
            self._metric_sink(step, event="auto_trace", auto_trace_reason=reason)
        return True

    def note_step_time(self, step: int, step_time_s: float | None) -> None:
        """The in-run regression detector: a step-time excursion beyond
        ``excursion_factor`` x the rolling median arms an auto-trace. Fed by
        the recipe at every log step with the same dt the row carries."""
        if step_time_s is None or step_time_s <= 0:
            return
        hist = self._dt_history
        if len(hist) >= self.config.excursion_min_samples:
            med = sorted(hist)[len(hist) // 2]
            if med > 0 and step_time_s > self.config.excursion_factor * med:
                self.auto_trace("step_time_excursion", step,
                                step_time_s=round(step_time_s, 4),
                                median_s=round(med, 4))
        hist.append(float(step_time_s))
        if len(hist) > 64:  # rolling window; excursions are vs recent history
            del hist[0]

    # ----------------------------------------------------------- trace analysis
    def analyze_trace(self, trace_dir: str, step: int = 0,
                      steps_hint: int | None = None) -> Any:
        """Machine-read one completed profiler trace (docs/observability.md
        "Measured trace attribution & signals").

        Runs automatically after every closed trace window — anomaly-triggered
        or on-demand — and on explicit call. Produces, guarded so analysis can
        never take the run down: an atomic ``out_dir/trace_report.json``, a
        ``trace_summary`` metric row carrying the ``measured_*`` /
        ``overlap_frac`` keys + the analytic-vs-measured verdict, and a
        refreshed ``signals.json``.
        Returns the TraceReport (None when the trace is empty or analysis
        failed). Proc 0 only on multi-host — the trace is host-local and the
        artifacts belong to the coordinator.
        """
        import jax

        if jax.process_index() != 0:
            return None
        try:
            from automodel_tpu.observability import trace_analysis as ta

            report = ta.analyze_trace(trace_dir, hlo_text=self._hlo_text,
                                      mesh_axes=self.mesh_axes,
                                      steps_hint=steps_hint)
            if report is None:
                return None
            row = report.summary_row()
            row.update(ta.reconcile_with_roofline(report, self.roofline))
            self.trace_summary = row
            self._write_trace_report(report, row)
            if self._metric_sink is not None:
                self._metric_sink(max(step, 0), event="trace_summary", **row)
            self.write_signals()
            return report
        except Exception:
            logger.warning("trace analysis failed for %s", trace_dir,
                           exc_info=True)
            return None

    def _write_trace_report(self, report: Any, row: dict[str, Any]) -> None:
        import tempfile

        doc = report.to_dict()
        doc["reconciliation"] = {
            k.split("/", 1)[-1]: v for k, v in row.items()
            if k.startswith("trace/") and not k.startswith("trace/scope/")
            and k not in ("trace/steps", "trace/events", "trace/window_s")
        }
        doc["roofline"] = self.roofline
        path = os.path.join(self.out_dir, "trace_report.json")
        os.makedirs(self.out_dir, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=self.out_dir, suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(doc, f, indent=1, sort_keys=True)
            os.replace(tmp, path)
        except BaseException:
            with contextlib.suppress(OSError):
                os.unlink(tmp)
            raise

    def write_signals(self) -> str | None:
        """Assemble + atomically write ``out_dir/signals.json`` (signals.py)
        from whatever sources exist right now; refreshed after every trace
        analysis and once more at close. Proc 0 only; never raises."""
        import jax

        if not self.config.enabled:
            return None
        try:
            if jax.process_index() != 0:
                return None
        except Exception:
            return None
        try:
            from automodel_tpu.observability import signals as sig

            doc = sig.build_signals(
                cell=self.cell_info,
                mesh_axes=self.mesh_axes,
                roofline=self.roofline,
                costs=self._costs,
                trace_summary=self.trace_summary,
                memory_plan=self.memory_plan,
                compile_summary=self.compile_summary(),
            )
            path = os.path.join(self.out_dir, "signals.json")
            sig.write_signals(path, doc)
            return path
        except Exception:
            logger.warning("signals.json write failed", exc_info=True)
            return None

    # ------------------------------------------------------------------- OOM
    def record_row(self, step: int, row: dict[str, Any]) -> None:
        """Feed the flight recorders' rings of recent metric rows (the OOM
        one and the loss-spike one share the "context for a future crash
        artifact" contract)."""
        if self.oom is not None:
            self.oom.record_row(step, row)
        if self.dynamics is not None:
            self.dynamics.recorder.record_row(step, row)

    def maybe_dump_oom(self, exc: BaseException, step: int | None = None) -> str | None:
        """Write ``oom_report.json`` when ``exc`` is an allocator exhaustion;
        returns the report path (the caller re-raises either way)."""
        if self.oom is None or not is_oom_error(exc):
            return None
        if self.oom._plan_row is None and self.memory_plan is not None:
            self.oom.set_plan_row(self.memory_plan.header_row())
        return self.oom.dump(exc, step=step)

    # ------------------------------------------------------------------ log rows
    def step_metrics(self) -> dict[str, Any]:
        """The per-log-row contribution: compile time, goodput fractions, HBM."""
        out: dict[str, Any] = {}
        if self.compile_time_s is not None:
            out["compile_time_s"] = self.compile_time_s
        if self.goodput is not None:
            out.update(self.goodput.snapshot())
        if self._memory:
            stats = device_memory_stats()
            out.update(stats)
            if stats and self.timeline is not None:
                # Perfetto counter track: HBM over the run's wall clock
                self.timeline.counter(
                    "hbm_gib",
                    in_use=stats.get("hbm_gib_in_use"),
                    peak=stats.get("hbm_gib_peak"),
                )
        return out

    def roofline_row(self, step_time_s: float | None) -> dict[str, Any]:
        """Per-row bound diagnosis + achieved fraction of the roofline."""
        if self.roofline is None:
            return {}
        data_wait_frac = 0.0
        if self.goodput is not None:
            data_wait_frac = self.goodput.snapshot().get("goodput/data_wait", 0.0)
        out: dict[str, Any] = {}
        bound = diagnose_bound(step_time_s, self.roofline, data_wait_frac)
        if bound is not None:
            out["bound"] = bound
        if step_time_s:
            # 6 digits: a test-sized model on a fast host can legitimately
            # achieve < 1e-4 of the analytic roofline — don't round it to 0
            out["roofline_frac"] = round(
                self.roofline["roofline_step_time_s"] / step_time_s, 6
            )
        return out

    def host_metrics(self, step_time_s: float | None,
                     moe_max_util: float | None = None,
                     grad_norm: float | None = None) -> dict[str, Any]:
        """Cross-host min/median/max + straggler flag for one log step.

        Collective on multi-host: every process must reach this call (the log
        step is deterministic across hosts); only proc 0 uses the result.
        MoE recipes pass their host-local max expert utilization — the wire
        format then grows the ``moe_max_util`` key (on every host, since the
        recipe config is identical pod-wide) and a ``hot_expert_host`` flag
        joins the straggler one. Dynamics runs pass the step's replicated
        ``grad_norm``, growing the wire identically; disagreement across
        hosts raises the ``divergent_host`` flag (replica desync).
        """
        if self.aggregator is None or not self.aggregator.active:
            return {}
        wanted = host_keys(
            moe=moe_max_util is not None or "moe_max_util" in self.aggregator.keys,
            dynamics=grad_norm is not None or "grad_norm" in self.aggregator.keys)
        if wanted != self.aggregator.keys:
            # first MoE/dynamics sample: widen the wire format once,
            # identically on every host (the flags derive from config shared
            # pod-wide, so every process rebuilds at the same log step)
            self.aggregator = CrossHostAggregator(
                self.aggregator.straggler_factor, keys=wanted,
                allgather_fn=self.aggregator._allgather,
                process_count=self.aggregator.process_count,
                oom_risk_gib=self.aggregator.oom_risk_gib,
                divergence_rtol=self.aggregator.divergence_rtol)
        sample: dict[str, Any] = {"step_time_s": step_time_s}
        if self.goodput is not None:
            sample["data_wait_s"] = round(self.goodput.totals().get("data_wait", 0.0), 4)
        if self._memory:
            stats = device_memory_stats()
            sample["hbm_gib_peak"] = stats.get("hbm_gib_peak")
            # allocator headroom when the platform reports it; the analytic
            # plan's otherwise — either way the pod's worst host is what the
            # oom_risk flag needs, and NaN travels where neither is known
            headroom = stats.get("hbm_headroom_gib")
            if headroom is None and self.memory_plan is not None:
                hb = self.memory_plan.headroom_bytes
                headroom = round(hb / 2**30, 4) if hb is not None else None
            sample["hbm_headroom_gib"] = headroom
        if moe_max_util is not None:
            sample["moe_max_util"] = float(moe_max_util)
        if grad_norm is not None:
            sample["grad_norm"] = float(grad_norm)
        out = self.aggregator.aggregate(sample)
        if self.timeline is not None and "straggler_host" in out:
            self.timeline.instant("straggler", cat="event",
                                  host=out["straggler_host"],
                                  ratio=out.get("straggler_ratio"))
        if self.timeline is not None and "hot_expert_host" in out:
            self.timeline.instant("hot_expert", cat="event",
                                  host=out["hot_expert_host"],
                                  ratio=out.get("hot_expert_ratio"))
        if self.timeline is not None and "divergent_host" in out:
            self.timeline.instant("divergent_replica", cat="event",
                                  host=out["divergent_host"],
                                  rel=out.get("divergence_rel"))
        return out
