"""Unified training observability: goodput accounting, HBM + compile telemetry,
a stall watchdog, on-demand profiling, HLO cost/roofline accounting, MoE
routing/dispatch telemetry, cross-host metric aggregation, a unified trace
timeline, measured trace attribution + the signals bundle, and a
perf-regression gate (docs/observability.md)."""

from automodel_tpu.observability import compile_cache
from automodel_tpu.observability.aggregate import CrossHostAggregator, host_keys
from automodel_tpu.observability.dynamics import (
    DynamicsConfig,
    DynamicsStats,
    DynamicsTracker,
    SpikeFlightRecorder,
    bucket_for_path,
    dynamics_tree,
    first_nonfinite_bucket,
    flatten_dynamics,
    nonfinite_provenance,
)
from automodel_tpu.observability.events import TraceTimeline
from automodel_tpu.observability.goodput import BUCKETS, GoodputTracker
from automodel_tpu.observability.hlo_costs import (
    collective_bytes,
    collective_bytes_by_axis,
    compiled_cost_metrics,
    device_specs,
    diagnose_bound,
    roofline_metrics,
)
from automodel_tpu.observability.manager import Observability, ObservabilityConfig
from automodel_tpu.observability.memory import device_memory_stats
from automodel_tpu.observability.memory_plan import (
    MemoryPlan,
    build_memory_plan,
    compiled_memory_attribution,
    reconcile,
    resolve_hbm_limit_bytes,
    tree_shard_bytes,
)
from automodel_tpu.observability.moe_stats import MoEStats, moe_step_metrics, routing_entropy
from automodel_tpu.observability.oom import (
    OOMFlightRecorder,
    is_oom_error,
    live_buffer_inventory,
)
from automodel_tpu.observability.profiling import OnDemandProfiler
from automodel_tpu.observability.runledger import (
    BADPUT_CLASSES,
    build_ledger,
    update_run_ledger,
    validate_ledger,
)
from automodel_tpu.observability.signals import (
    build_signals,
    validate_signals,
    write_signals,
)
from automodel_tpu.observability.trace_analysis import (
    TraceReport,
    analyze_trace,
    reconcile_with_roofline,
)
from automodel_tpu.observability.watchdog import StallWatchdog

# start counting compilation-cache traffic before the recipe's first compile
compile_cache.install()

__all__ = [
    "BADPUT_CLASSES",
    "BUCKETS",
    "CrossHostAggregator",
    "DynamicsConfig",
    "DynamicsStats",
    "DynamicsTracker",
    "GoodputTracker",
    "MemoryPlan",
    "MoEStats",
    "OOMFlightRecorder",
    "Observability",
    "ObservabilityConfig",
    "OnDemandProfiler",
    "SpikeFlightRecorder",
    "StallWatchdog",
    "TraceReport",
    "TraceTimeline",
    "analyze_trace",
    "bucket_for_path",
    "build_ledger",
    "build_memory_plan",
    "build_signals",
    "dynamics_tree",
    "first_nonfinite_bucket",
    "flatten_dynamics",
    "host_keys",
    "nonfinite_provenance",
    "collective_bytes",
    "collective_bytes_by_axis",
    "compile_cache",
    "compiled_cost_metrics",
    "compiled_memory_attribution",
    "device_memory_stats",
    "device_specs",
    "diagnose_bound",
    "is_oom_error",
    "live_buffer_inventory",
    "moe_step_metrics",
    "reconcile",
    "reconcile_with_roofline",
    "resolve_hbm_limit_bytes",
    "roofline_metrics",
    "routing_entropy",
    "tree_shard_bytes",
    "update_run_ledger",
    "validate_ledger",
    "validate_signals",
    "write_signals",
]
