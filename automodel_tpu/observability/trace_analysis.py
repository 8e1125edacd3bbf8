"""Measured-profile attribution: read the XPlane traces jax.profiler writes.

Every performance number the rest of this package reasons about is *analytic*
(hlo_costs.py derives rooflines from ``cost_analysis()`` and assumes zero
compute/comms overlap). This module machine-reads the profiler's traces: the
``*.xplane.pb`` file is read with ``jax.profiler.ProfileData`` (nothing but
JAX), device op events are classified against the compiled module's named
scopes (docs/observability.md "Device names": embed / layer_stack / attention /
mlp / moe* / lm_head_loss / optimizer) and collective-kind patterns, and interval-union math
turns them into measured per-category time per step — compute, ``moe_a2a``,
per-mesh-axis collectives, host/input gaps — plus an **overlap fraction**
(collective time concurrent with compute), the one number the analytic
roofline cannot produce.

Classification correlates trace event names with the compiled HLO text the
manager already fetched at compile_step. A CPU trace names an op event by its
instruction (``fusion.3``, ``all-reduce.5``); a TPU trace names it by the whole
instruction text, ``%fusion.3 = bf16[...] fusion(...)``, which
:func:`instruction_name` cuts back to ``fusion.3`` (a Pallas kernel's is its
``name=``: ``flash_attention_fwd.1``, ``linear_ce_bwd.2``). Instruction
names match the HLO's, their ``op_name`` metadata carries the named-scope path
(inside ``jvp(...)`` / ``transpose(...)`` / ``checkpoint`` wrappers: matched by
path component), and replica-group sizes attribute collectives to mesh axes
(same rules as hlo_costs.collective_bytes_by_axis). With no HLO text the
classifier degrades to event-name prefix patterns (collective kinds are still
separated from compute; scopes and axes go unattributed).

Category accounting is exact by construction: ``compute_s`` and ``comm_s`` are
interval *unions* (concurrent executor threads don't double-count),
``overlap_s = |union(comm) ∩ union(compute)|``, and the host/input gap is the
analysis window minus the union of all device-op intervals — so
``compute + comm - overlap + host == window`` identically and the per-step
categories always sum to the measured wall step time.
"""

from __future__ import annotations

import collections
import dataclasses
import logging
import os
import re
from typing import Any, Iterable

from automodel_tpu.observability.hlo_costs import (
    COLLECTIVE_OPS,
    MOE_DISPATCH_SCOPES,
    _group_size,
    _OP_RE,
    _OPNAME_RE,
)

logger = logging.getLogger(__name__)

__all__ = [
    "DEFAULT_SCOPES",
    "KERNEL_NAMES",
    "InstrInfo",
    "TraceEvent",
    "TraceLine",
    "TracePlane",
    "TraceReport",
    "analyze_trace",
    "build_instruction_index",
    "find_xplane_files",
    "innermost_scope",
    "instruction_name",
    "instruction_op_names",
    "kernel_call_counts",
    "moe_row_scatter_count",
    "intersection_total",
    "merge_intervals",
    "read_xspace",
    "reconcile_with_roofline",
    "union_total",
]

# the named-scope labels of the compiled step (docs/observability.md "Device
# names"): the block tables of utils/tracing.scope_blocks, the explicit
# named_scope sites in moe/, the embedding, the head and loss, the optimizer
DEFAULT_SCOPES = (
    "embed", "layer_stack", "attention", "mla_attention", "mlp", "moe_gate",
    "moe_shared_experts", "moe_experts", "ep_experts", "moe", "lm_head_loss", "optimizer",
) + MOE_DISPATCH_SCOPES

# the ``name=`` of every Pallas kernel (ops/pallas/): a kernel's instruction is
# ``<name>.<n>``, so its device time can be summed without the HLO text
KERNEL_NAMES = (
    "flash_attention_fwd", "flash_attention_bwd", "flash_attention_bwd_dq",
    "flash_attention_bwd_dkv", "linear_ce_fwd", "linear_ce_bwd", "grouped_gemm_fwd",
    "grouped_gemm_bwd_dw", "ring_attention_fwd", "ring_attention_bwd",
    "ssd_scan_fwd", "ssd_scan_bwd", "gated_delta_fwd", "gated_delta_bwd",
)


# ------------------------------------------------------------------- reading
@dataclasses.dataclass
class TraceEvent:
    name: str
    start_ps: int
    dur_ps: int
    stats: dict[str, Any]

    @property
    def end_ps(self) -> int:
        return self.start_ps + self.dur_ps


@dataclasses.dataclass
class TraceLine:
    name: str
    events: list[TraceEvent]


@dataclasses.dataclass
class TracePlane:
    name: str
    lines: list[TraceLine]


def instruction_name(event_name: str) -> str:
    """``%fusion.3 = bf16[8]{0} fusion(...)`` (how a TPU trace names an op
    event) -> ``fusion.3``; a bare instruction name comes back unchanged."""
    return event_name.partition(" = ")[0].lstrip("%")


def read_xspace(source: str | bytes) -> list[TracePlane]:
    """One serialized XSpace (path or bytes) as planes/lines/events, op events
    named by :func:`instruction_name`."""
    from jax.profiler import ProfileData

    data = (ProfileData.from_serialized_xspace(source) if isinstance(source, bytes)
            else ProfileData.from_file(source))
    return [
        TracePlane(plane.name, [
            TraceLine(line.name, [
                TraceEvent(instruction_name(ev.name), round(ev.start_ns * 1000),
                           max(round(ev.duration_ns * 1000), 0), dict(ev.stats))
                for ev in line.events])
            for line in plane.lines])
        for plane in data.planes]


def find_xplane_files(trace_dir: str) -> list[str]:
    """The ``<host>.xplane.pb`` files under one jax.profiler trace directory."""
    out = []
    for root, _dirs, files in os.walk(trace_dir):
        out.extend(os.path.join(root, f) for f in files
                   if f.endswith(".xplane.pb"))
    return sorted(out)


# -------------------------------------------------------------- interval math
def merge_intervals(intervals: Iterable[tuple[int, int]]) -> list[tuple[int, int]]:
    """Sorted disjoint union of half-open intervals (the canonical form)."""
    ivs = sorted((s, e) for s, e in intervals if e > s)
    out: list[tuple[int, int]] = []
    for s, e in ivs:
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def union_total(intervals: Iterable[tuple[int, int]]) -> int:
    """Total covered length of a set of (possibly overlapping) intervals."""
    return sum(e - s for s, e in merge_intervals(intervals))


def intersection_total(a: Iterable[tuple[int, int]],
                       b: Iterable[tuple[int, int]]) -> int:
    """Length of the intersection of two interval sets (merged two-pointer)."""
    ma, mb = merge_intervals(a), merge_intervals(b)
    i = j = total = 0
    while i < len(ma) and j < len(mb):
        lo = max(ma[i][0], mb[j][0])
        hi = min(ma[i][1], mb[j][1])
        if hi > lo:
            total += hi - lo
        if ma[i][1] <= mb[j][1]:
            i += 1
        else:
            j += 1
    return total


# ------------------------------------------------------------ classification
@dataclasses.dataclass
class InstrInfo:
    """What the compiled HLO says about one instruction name."""

    collective: str | None = None  # collective kind, None for compute
    axis: str | None = None  # mesh axis the collective runs over
    moe: bool = False  # MoE dispatch/combine traffic
    scope: str | None = None  # innermost named-scope label


_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_LABEL_RE = re.compile(r"[A-Za-z_]\w*")


def instruction_op_names(hlo_text: str) -> dict[str, str]:
    """instruction name -> ``op_name`` metadata, for every instruction of the
    module text that carries one. This table is what joins a device trace's op
    events to the program's named scopes: a TPU trace's events carry no
    ``op_name`` of their own, so the manager writes it beside the run's files
    at compile (``step_scopes.json``)."""
    table: dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        m_name = _OPNAME_RE.search(line) if m else None
        if m_name:
            table[m.group(1)] = m_name.group(1)
    return table


# a kernel's ``name=`` as one component of an ``op_name`` path
_KERNEL_CALL_RE = re.compile(
    r"(?:^|/)(%s)(?:/|$)" % "|".join(sorted(KERNEL_NAMES, key=len, reverse=True)))
_MOSAIC_CALL = 'custom_call_target="tpu_custom_call"'


def kernel_call_counts(hlo_text: str) -> dict[str, int]:
    """Pallas kernel ``name=`` -> calls in the compiled module's text.

    A compiled kernel is one Mosaic custom call whose ``op_name`` path holds the
    kernel's name: each such instruction is a call (one inside a layer scan's
    body counts once, whatever the depth; the bitcasts that share its ``op_name``
    are not calls). With no Mosaic call in the text (interpret mode) a call is
    many instructions that share the path up to ``<name>``: distinct paths are
    counted, less those that are the tail of another (an outlined computation's
    instructions lose the head of theirs). Forward against backward calls says
    whether a remat policy replays the forward kernel."""
    compiled: collections.Counter[str] = collections.Counter()
    for line in hlo_text.splitlines():
        if _MOSAIC_CALL in line:
            m_name = _OPNAME_RE.search(line)
            m = _KERNEL_CALL_RE.search(m_name.group(1)) if m_name else None
            if m:
                compiled[m.group(1)] += 1
    if compiled:
        return dict(compiled)
    interpreted: dict[str, set[str]] = {}
    for op_name in _OPNAME_RE.findall(hlo_text):
        m = _KERNEL_CALL_RE.search(op_name)
        if m:
            interpreted.setdefault(m.group(1), set()).add(op_name[:m.end(1)])
    return {name: sum(not any(other.endswith("/" + p) for other in paths) for p in paths)
            for name, paths in interpreted.items()}


_SCATTER_RE = re.compile(r"\sscatter\(")


def moe_row_scatter_count(hlo_text: str) -> int:
    """``scatter`` instructions of the compiled module whose ``op_name`` lies under
    ``moe_dispatch`` or ``moe_combine``: the dropless MoE block's row moves that the
    chip runs row after row. 0 where a layer that holds all its experts moves its rows
    by gathers over the inverse of its sort (``moe/experts.py``); positive where a held
    share, or a tree from before PR 49, scatter-adds them. One inside a layer scan's
    body counts once, whatever the depth."""
    return sum(1 for line in hlo_text.splitlines()
               if _SCATTER_RE.search(line) and (m := _OPNAME_RE.search(line))
               and {"moe_dispatch", "moe_combine"} & set(_LABEL_RE.findall(m.group(1))))


def innermost_scope(op_name: str, scopes: tuple[str, ...] = DEFAULT_SCOPES) -> str | None:
    """The last label of ``scopes`` on an ``op_name`` path, matched by path
    component: backward and recomputed operations keep their label inside
    ``transpose(jvp(attention))`` / ``checkpoint`` wrappers, and a longer word
    (``embed_lookup``) is not its prefix (``embed``)."""
    for label in reversed(_LABEL_RE.findall(op_name)):
        if label in scopes:
            return label
    return None


def build_instruction_index(hlo_text: str, mesh_axes: dict | None = None,
                            scopes: tuple[str, ...] = DEFAULT_SCOPES,
                            ) -> dict[str, InstrInfo]:
    """instruction name -> InstrInfo for every instruction in the module text.

    Trace event names on the device op lines are HLO instruction names, so
    this index is the whole correlation: collective kind + replica-group ->
    mesh axis (hlo_costs rules), ``op_name`` metadata -> innermost named
    scope, MOE_DISPATCH_SCOPES membership -> the ``moe_a2a`` flag.
    """
    axes = {str(k): int(v) for k, v in (mesh_axes or {}).items()}
    index: dict[str, InstrInfo] = {}
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if not m:
            continue
        info = InstrInfo()
        m_name = _OPNAME_RE.search(line)
        op_name = m_name.group(1) if m_name else ""
        info.scope = innermost_scope(op_name, scopes)
        cm = _OP_RE.search(line)
        if cm:
            info.collective = cm.group(2)
            info.moe = any(s in op_name for s in MOE_DISPATCH_SCOPES)
            g = _group_size(line)
            candidates = [ax for ax, size in axes.items() if size == g and size > 1]
            if len(candidates) == 1:
                info.axis = candidates[0]
                if info.axis == "ep" and info.collective == "all-to-all":
                    info.moe = True
            elif info.moe and "ep" in axes:
                info.axis = "ep"
        index[m.group(1)] = info
    return index


def _classify(name: str, index: dict[str, InstrInfo] | None) -> InstrInfo:
    """Event name -> InstrInfo, degrading to name-prefix patterns."""
    if index:
        info = index.get(name)
        if info is None and "." in name:
            # async halves land as `all-reduce-start.5` / `-done.5` events
            # while the index holds the `-start` instruction; retry the stem
            info = index.get(name.replace("-done.", "-start."))
        if info is not None:
            return info
    for kind in COLLECTIVE_OPS:
        if name.startswith(kind):
            return InstrInfo(collective=kind, moe=(kind == "all-to-all"))
    return InstrInfo()


def _is_op_line(line: TraceLine) -> bool:
    """Device-op timing lines: TPU planes call theirs "XLA Ops"; the CPU
    thunk executor's per-op events ride ``tf_XLATfrtCpuClient/...`` threads
    and are recognized by their hlo stats instead (see _is_op_event)."""
    return line.name.strip() == "XLA Ops"


_OP_EVENT_STATS = ("hlo_op", "hlo_category", "hlo_module", "program_id")


def _is_op_event(ev: TraceEvent) -> bool:
    return any(k in ev.stats for k in _OP_EVENT_STATS)


def _op_events(planes: list[TracePlane]) -> list[TraceEvent]:
    out: list[TraceEvent] = []
    for plane in planes:
        for line in plane.lines:
            if _is_op_line(line):
                out.extend(ev for ev in line.events if ev.dur_ps > 0)
            else:
                out.extend(ev for ev in line.events
                           if ev.dur_ps > 0 and _is_op_event(ev))
    return out


# ------------------------------------------------------------------ analysis
_PS = 1e-12  # picoseconds -> seconds


@dataclasses.dataclass
class TraceReport:
    """Measured per-step category attribution for one captured trace.

    All ``*_s`` category fields are **per step** (window totals divided by the
    estimated step count); ``window_s`` is the whole analysis window. The
    identity ``compute_s + comm_s - overlap_s + host_s == step_time_s`` holds
    exactly (see module docstring).
    """

    trace_path: str
    num_events: int
    module: str  # dominant hlo_module (most device time)
    steps: int  # estimated executions inside the window
    steps_hint: int | None  # caller-provided count, when given
    window_s: float
    step_time_s: float  # window_s / steps
    compute_s: float
    comm_s: float
    moe_a2a_s: float
    host_s: float
    overlap_s: float
    overlap_frac: float  # overlap_s / comm_s; 0.0 when no collectives ran
    comm_axis_s: dict[str, float]
    scope_s: dict[str, float]  # summed device-op time per named scope
    measured_bound: str  # compute | comms | moe_a2a | input
    # summed device time per Pallas kernel (KERNEL_NAMES), no HLO text needed
    kernel_s: dict[str, float] = dataclasses.field(default_factory=dict)

    def summary_row(self) -> dict[str, Any]:
        """Flat metric-row keys (the ``trace_summary`` event row contract)."""
        row: dict[str, Any] = {
            "trace/steps": self.steps,
            "trace/events": self.num_events,
            "trace/window_s": round(self.window_s, 6),
            "measured_step_time_s": round(self.step_time_s, 6),
            "measured_t_compute_s": round(self.compute_s, 6),
            "measured_t_comm_s": round(self.comm_s, 6),
            "measured_t_moe_a2a_s": round(self.moe_a2a_s, 6),
            "measured_t_host_s": round(self.host_s, 6),
            "measured_t_overlap_s": round(self.overlap_s, 6),
            "overlap_frac": round(self.overlap_frac, 4),
            "measured_bound": self.measured_bound,
        }
        denom = self.step_time_s or 1.0
        for cat, val in (("compute", self.compute_s), ("comm", self.comm_s),
                         ("moe_a2a", self.moe_a2a_s), ("host", self.host_s)):
            row[f"measured_frac_{cat}"] = round(val / denom, 4)
        for ax, s in sorted(self.comm_axis_s.items()):
            row[f"measured_comm_axis_{ax}_s"] = round(s, 6)
        for scope, s in sorted(self.scope_s.items()):
            row[f"trace/scope/{scope}_s"] = round(s, 6)
        for kernel, s in sorted(self.kernel_s.items()):
            row[f"trace/kernel/{kernel}_s"] = round(s, 6)
        return row

    def to_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)


def _estimate_steps(events: list[TraceEvent]) -> int:
    """Executions of the dominant module inside the window.

    Each execution replays every instruction once (scan/while bodies replay
    more, rare one-shot ops less), so the *median* multiplicity over distinct
    event names is a robust execution count.
    """
    counts: dict[str, int] = {}
    for ev in events:
        counts[ev.name] = counts.get(ev.name, 0) + 1
    if not counts:
        return 1
    mult = sorted(counts.values())
    return max(int(mult[len(mult) // 2]), 1)


def _measured_bound(compute_s: float, comm_s: float, moe_a2a_s: float,
                    host_frac: float, input_bound_frac: float = 0.25) -> str:
    """Mirror of hlo_costs.diagnose_bound on measured numbers. The trace
    cannot split compute-bound from memory-bound (both are device-busy), so
    "memory" never appears here; reconciliation maps the analytic "memory"
    onto measured "compute" for the agree/disagree verdict."""
    if host_frac > input_bound_frac:
        return "input"
    if comm_s > compute_s:
        if comm_s > 0 and moe_a2a_s > 0.5 * comm_s:
            return "moe_a2a"
        return "comms"
    return "compute"


def analyze_trace(trace: str, hlo_text: str | None = None,
                  mesh_axes: dict | None = None,
                  scopes: tuple[str, ...] = DEFAULT_SCOPES,
                  steps_hint: int | None = None) -> TraceReport | None:
    """One trace directory (or ``.xplane.pb`` path) -> a :class:`TraceReport`.

    Returns None when the trace holds no device op events (e.g. an empty
    window); raises only on unreadable/corrupt input. Multi-host traces
    contain one xplane file per host — this host's view is the first sorted
    file, which is the right one for per-host diagnosis under SPMD.
    """
    if os.path.isdir(trace):
        files = find_xplane_files(trace)
        if not files:
            logger.warning("no .xplane.pb under %s", trace)
            return None
        path = files[0]
    else:
        path = trace
    planes = read_xspace(path)
    events = _op_events(planes)
    if not events:
        logger.warning("trace %s has no device op events", path)
        return None

    index = (build_instruction_index(hlo_text, mesh_axes, scopes)
             if hlo_text else None)

    # dominant module = the step program; auxiliary executables (metric
    # pulls, eval helpers) stay in the category accounting but not in the
    # window/step estimation. A TPU plane lists the runs of each compiled
    # program on its "XLA Modules" line (its op events carry no module stat);
    # a CPU trace names the module in each op event's stats.
    runs: dict[str, list[TraceEvent]] = {}
    for plane in planes:
        for line in plane.lines:
            if line.name.strip() == "XLA Modules":
                for ev in line.events:
                    runs.setdefault(ev.name.partition("(")[0], []).append(ev)
    if runs:
        module = max(runs, key=lambda k: sum(e.dur_ps for e in runs[k]))
        step_events = runs[module]
        steps = steps_hint or len(step_events)
    else:
        by_module: dict[str, list[TraceEvent]] = {}
        for ev in events:
            key = str(ev.stats.get("hlo_module") or ev.stats.get("program_id")
                      or "unknown")
            by_module.setdefault(key, []).append(ev)
        module = max(by_module, key=lambda k: sum(e.dur_ps for e in by_module[k]))
        step_events = by_module[module]
        steps = steps_hint or _estimate_steps(step_events)
    w0 = min(e.start_ps for e in step_events)
    w1 = max(e.end_ps for e in step_events)
    if w1 <= w0:
        return None

    compute_iv: list[tuple[int, int]] = []
    comm_iv: list[tuple[int, int]] = []
    moe_iv: list[tuple[int, int]] = []
    axis_iv: dict[str, list[tuple[int, int]]] = {}
    scope_ps: dict[str, int] = {}
    kernel_ps: dict[str, int] = {}
    for ev in events:
        s, e = max(ev.start_ps, w0), min(ev.end_ps, w1)
        if e <= s:
            continue
        info = _classify(ev.name, index)
        if info.collective:
            comm_iv.append((s, e))
            if info.moe:
                moe_iv.append((s, e))
            if info.axis:
                axis_iv.setdefault(info.axis, []).append((s, e))
        else:
            compute_iv.append((s, e))
        if info.scope:
            scope_ps[info.scope] = scope_ps.get(info.scope, 0) + (e - s)
        # a backward kernel called under no scope reads `transpose_jvp_<name>__.1`
        kernel = max((k for k in KERNEL_NAMES if k in ev.name), key=len, default=None)
        if kernel is not None:
            kernel_ps[kernel] = kernel_ps.get(kernel, 0) + (e - s)

    window_ps = w1 - w0
    compute_ps = union_total(compute_iv)
    comm_ps = union_total(comm_iv)
    overlap_ps = intersection_total(compute_iv, comm_iv)
    busy_ps = union_total(compute_iv + comm_iv)
    host_ps = window_ps - busy_ps
    moe_ps = union_total(moe_iv)
    per_step = _PS / steps
    host_frac = host_ps / window_ps

    return TraceReport(
        trace_path=str(path),
        num_events=len(events),
        module=module,
        steps=steps,
        steps_hint=steps_hint,
        window_s=window_ps * _PS,
        step_time_s=window_ps * per_step,
        compute_s=compute_ps * per_step,
        comm_s=comm_ps * per_step,
        moe_a2a_s=moe_ps * per_step,
        host_s=host_ps * per_step,
        overlap_s=overlap_ps * per_step,
        overlap_frac=(overlap_ps / comm_ps) if comm_ps else 0.0,
        comm_axis_s={ax: union_total(iv) * per_step
                     for ax, iv in sorted(axis_iv.items())},
        scope_s={sc: ps * per_step for sc, ps in sorted(scope_ps.items())},
        kernel_s={k: ps * per_step for k, ps in sorted(kernel_ps.items())},
        measured_bound=_measured_bound(
            compute_ps, comm_ps, moe_ps, host_frac),
    )


# -------------------------------------------------------------- reconciliation
# the trace can't separate compute-bound from memory-bound (both are
# device-busy time), and the measured "input" diagnosis corresponds to the
# analytic data-wait one
_ANALYTIC_TO_MEASURED = {"compute": "compute", "memory": "compute",
                         "comms": "comms", "moe_a2a": "moe_a2a",
                         "input": "input"}


def reconcile_with_roofline(report: TraceReport,
                            roofline: dict[str, Any] | None) -> dict[str, Any]:
    """Measured-vs-analytic verdict keys for the ``trace_summary`` row.

    ``trace/bound_agrees`` is the headline: False means the analytic roofline
    is diagnosing the wrong resource and should not be trusted for this
    config.
    """
    out: dict[str, Any] = {}
    if not roofline:
        return out
    analytic = roofline.get("roofline_bound")
    if not analytic:
        return out
    mapped = _ANALYTIC_TO_MEASURED.get(str(analytic), str(analytic))
    agrees = mapped == report.measured_bound
    out["trace/analytic_bound"] = str(analytic)
    out["trace/bound_agrees"] = agrees
    out["trace/verdict"] = (
        "agree" if agrees
        else f"disagree analytic={analytic} measured={report.measured_bound}")
    expected = roofline.get("roofline_step_time_s")
    if expected and report.step_time_s > 0:
        # >1 would mean the device beat its own roofline — a modeling error
        out["trace/roofline_vs_measured"] = round(
            float(expected) / report.step_time_s, 6)
    return out
