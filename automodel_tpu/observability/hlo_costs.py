"""Analytic cost extraction from a compiled step + roofline accounting.

One XLA compile already knows almost everything a performance investigation
needs: the model FLOPs per step, the bytes the program touches, and — after
GSPMD partitioning — the exact collective instructions and their shapes. This
module pulls those numbers out of a ``jax.stages.Compiled`` once per compile
and turns them, together with the attached chip's peak specs, into a
roofline-expected step time and a per-row ``bound`` diagnosis
(compute/memory/comms/input-bound).

The per-collective byte accounting here is the single source of truth: the
driver's MULTICHIP dryrun (``__graft_entry__.py``) imports
:func:`collective_bytes` rather than carrying its own copy.

Convention: "bytes" = sum of each collective instruction's OUTPUT shape in the
per-device program (all-gather counts the gathered tensor, reduce-scatter the
scattered shard). Costs are per-device-program numbers — under SPMD every
device runs the same module, so per-chip rates compare directly.
"""

from __future__ import annotations

import dataclasses
import logging
import re
from typing import Any

logger = logging.getLogger(__name__)

__all__ = [
    "COLLECTIVE_OPS",
    "DTYPE_BYTES",
    "MOE_DISPATCH_SCOPES",
    "DeviceSpec",
    "collective_bytes",
    "collective_bytes_by_axis",
    "device_specs",
    "UnknownDeviceError",
    "compiled_cost_metrics",
    "roofline_metrics",
    "diagnose_bound",
]

COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                  "collective-permute")
DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_OP_RE = re.compile(
    r"=\s+((?:\([^)]*\))|(?:\S+))\s+(" + "|".join(COLLECTIVE_OPS) + r")(-start)?\("
)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')
# `replica_groups={{0,1},{2,3}}` — explicit groups; group size = first group len
_GROUPS_RE = re.compile(r"replica_groups=\{\{([\d,\s]*)\}")
# `replica_groups=[4,2]<=[8]` — iota form: 4 groups of size 2
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")

# named scopes that mark MoE dispatch/combine comms in the optimized HLO:
# the explicit-EP a2a path (moe/dispatch.py) and the GSPMD dense path
# (moe/experts.py) both label their reshard/exchange regions with these
MOE_DISPATCH_SCOPES = ("ep_dispatch", "ep_combine", "moe_dispatch", "moe_combine")


def _shapes_total_bytes(shapes_token: str, is_start: str | None = None) -> int:
    found = _SHAPE_RE.findall(shapes_token)
    if is_start and len(found) > 1:
        # async form: the -start tuple is (operand alias, ..., result) —
        # count only the result or the operand would double the volume
        found = found[-1:]
    total = 0
    for dt, dims in found:
        nbytes = DTYPE_BYTES.get(dt)
        if nbytes is None:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * nbytes
    return total


def collective_bytes(hlo: str) -> dict:
    """Sum output bytes per collective op kind in an optimized HLO module."""
    out = {}
    for line in hlo.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        shapes, op, is_start = m.group(1), m.group(2), m.group(3)
        total = _shapes_total_bytes(shapes, is_start)
        out[op] = out.get(op, 0) + total
    return out


def _group_size(line: str) -> int | None:
    """Participant count of a collective's replica groups, if parseable."""
    m = _GROUPS_RE.search(line)
    if m:
        ids = [x for x in m.group(1).split(",") if x.strip()]
        return len(ids) or None
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2)) or None
    return None


def collective_bytes_by_axis(hlo: str, mesh_axes: dict | None = None) -> dict:
    """Attribute collective output bytes to mesh axes (ep vs dp vs tp vs pp).

    Two signals, in priority order:

    1. **Scope**: a collective whose ``op_name`` metadata lies inside one of
       the :data:`MOE_DISPATCH_SCOPES` is MoE dispatch/combine traffic — it
       counts toward the ``ep`` axis AND the ``moe_a2a`` bucket (the category
       the roofline ``bound`` diagnosis reports when expert exchange dominates;
       ``moe_a2a`` is a subset view, not an extra axis).
    2. **Group size**: a collective over groups of size g belongs to the
       unique mesh axis of size g (> 1). Equal-sized axes are genuinely
       ambiguous from the HLO alone and land in ``unattributed`` — honest
       beats guessed for a diagnosis people act on.

    Returns ``{axis: bytes, ..., "moe_a2a": bytes, "unattributed": bytes}``
    with zero-byte axes omitted (``moe_a2a`` is always present when any MoE
    dispatch scope appears in the module, even at 0 bytes, so its absence
    means "not an MoE program" rather than "no traffic").
    """
    axes = {str(k): int(v) for k, v in (mesh_axes or {}).items()}
    out: dict[str, int] = {}
    saw_moe_scope = any(scope in hlo for scope in MOE_DISPATCH_SCOPES)
    for line in hlo.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        shapes, op, is_start = m.group(1), m.group(2), m.group(3)
        nbytes = _shapes_total_bytes(shapes, is_start)
        if not nbytes:
            continue
        m_name = _OPNAME_RE.search(line)
        op_name = m_name.group(1) if m_name else ""
        in_moe_scope = any(scope in op_name for scope in MOE_DISPATCH_SCOPES)
        if in_moe_scope:
            out["moe_a2a"] = out.get("moe_a2a", 0) + nbytes
            if "ep" in axes:
                out["ep"] = out.get("ep", 0) + nbytes
                continue
        g = _group_size(line)
        candidates = [ax for ax, size in axes.items() if size == g and size > 1]
        if len(candidates) == 1:
            ax = candidates[0]
            out[ax] = out.get(ax, 0) + nbytes
            if ax == "ep" and op == "all-to-all" and not in_moe_scope:
                out["moe_a2a"] = out.get("moe_a2a", 0) + nbytes
        elif not in_moe_scope:
            out["unattributed"] = out.get("unattributed", 0) + nbytes
    if saw_moe_scope:
        out.setdefault("moe_a2a", 0)
    return out


# ---------------------------------------------------------------------- specs
@dataclasses.dataclass(frozen=True)
class DeviceSpec:
    """Peak numbers for roofline and MFU math, per chip."""

    name: str
    peak_bf16_tflops: float
    hbm_gbps: float  # HBM bandwidth, GB/s
    ici_gbps: float  # aggregate interchip-interconnect bandwidth, GB/s
    hbm_gib: float = 0.0  # per-chip HBM capacity, GiB (0 = unknown)


class UnknownDeviceError(ValueError):
    """A device kind with no row in the peak table: add the row, with its source."""


# THE peak table (utils/flops.mfu and the roofline both read it).
# Source: Google Cloud TPU documentation, the "System architecture" page of each
# generation (v5e: 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s; ICI figures are the
# aggregate per-chip interconnect bandwidth in GB/s). Matched by substring against
# the lowercased device kind, first hit wins; "v5 lite" (what a v5e reports as
# its device_kind) before "v5p" keeps it from matching v5p.
_DEVICE_SPECS = (
    ("v5 lite", DeviceSpec("v5e", 197.0, 819.0, 200.0, hbm_gib=16.0)),
    ("v5e", DeviceSpec("v5e", 197.0, 819.0, 200.0, hbm_gib=16.0)),
    ("v5p", DeviceSpec("v5p", 459.0, 2765.0, 600.0, hbm_gib=95.0)),
    ("v4", DeviceSpec("v4", 275.0, 1228.0, 300.0, hbm_gib=32.0)),
    ("v6", DeviceSpec("v6e", 918.0, 1640.0, 448.0, hbm_gib=32.0)),
)


def device_specs(device_kind: str) -> DeviceSpec | None:
    """Peak-table lookup. ``None`` for a CPU: a host has no peak to hold a run
    against, so its rows carry no roofline and no MFU. Any other kind that is
    not in the table is an error, not a default."""
    kind = str(device_kind).lower()
    if kind == "cpu":
        return None
    for key, spec in _DEVICE_SPECS:
        if key in kind:
            return spec
    raise UnknownDeviceError(
        f"no peak numbers for device kind {device_kind!r}; add it to "
        "observability/hlo_costs._DEVICE_SPECS with its source")


# ------------------------------------------------------------------ extraction
def compiled_cost_metrics(compiled: Any, mesh_axes: dict | None = None,
                          hlo_text: str | None = None) -> dict[str, int]:
    """Analytic costs of one compiled step, as flat log-row-ready ints.

    Returns ``hlo_flops`` / ``hlo_bytes_accessed`` (XLA's own cost analysis of
    the optimized module) plus ``comm_bytes_<kind>`` per collective kind and
    ``comm_bytes_total`` (regex accounting over the optimized HLO text). With
    ``mesh_axes`` (``{axis: size}``), collective bytes are also attributed per
    mesh axis as ``comm_bytes_axis_<axis>`` with the MoE dispatch/combine
    subset surfaced as ``comm_bytes_moe_a2a`` (see
    :func:`collective_bytes_by_axis`). Any unavailable source contributes
    nothing rather than raising — diagnostics must never take the run down.
    ``hlo_text``: pass the module text if the caller already extracted it
    (``as_text()`` is not free on big programs).
    """
    out: dict[str, int] = {}
    try:
        cost = compiled.cost_analysis()
        # list-of-dicts on some backends (one per computation), dict on others
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        if cost:
            if cost.get("flops") is not None:
                out["hlo_flops"] = int(cost["flops"])
            if cost.get("bytes accessed") is not None:
                out["hlo_bytes_accessed"] = int(cost["bytes accessed"])
    except Exception:
        logger.debug("cost_analysis unavailable on this backend", exc_info=True)
    try:
        hlo = hlo_text if hlo_text is not None else compiled.as_text()
        comm = collective_bytes(hlo)
        for op, nbytes in sorted(comm.items()):
            out[f"comm_bytes_{op.replace('-', '_')}"] = int(nbytes)
        out["comm_bytes_total"] = int(sum(comm.values()))
        by_axis = collective_bytes_by_axis(hlo, mesh_axes)
        moe_a2a = by_axis.pop("moe_a2a", None)
        for ax, nbytes in sorted(by_axis.items()):
            out[f"comm_bytes_axis_{ax}"] = int(nbytes)
        if moe_a2a is not None:
            out["comm_bytes_moe_a2a"] = int(moe_a2a)
    except Exception:
        logger.debug("optimized HLO text unavailable", exc_info=True)
    return out


# -------------------------------------------------------------------- roofline
def roofline_metrics(costs: dict[str, int], spec: DeviceSpec) -> dict[str, float]:
    """Roofline-expected step time from analytic costs + chip peaks.

    Each resource is an independent floor: the step can go no faster than its
    FLOPs at peak compute, its bytes at peak HBM bandwidth, or its collective
    bytes at peak ICI bandwidth. The expected time is the max of the three and
    ``roofline_bound`` names the binding resource.
    """
    t_compute = costs.get("hlo_flops", 0) / (spec.peak_bf16_tflops * 1e12)
    t_memory = costs.get("hlo_bytes_accessed", 0) / (spec.hbm_gbps * 1e9)
    comm_total = costs.get("comm_bytes_total", 0)
    t_comm = comm_total / (spec.ici_gbps * 1e9)
    components = {"compute": t_compute, "memory": t_memory, "comms": t_comm}
    if max(components.values()) <= 0:
        return {}  # no analytic costs -> no roofline (an all-zero one misleads)
    bound = max(components, key=components.get)
    out = {
        "roofline_t_compute_s": t_compute,
        "roofline_t_memory_s": t_memory,
        "roofline_t_comm_s": t_comm,
        "roofline_step_time_s": max(components.values()),
        "roofline_bound": bound,
        "roofline_spec": spec.name,
    }
    moe_a2a = costs.get("comm_bytes_moe_a2a")
    if moe_a2a is not None:
        t_moe = moe_a2a / (spec.ici_gbps * 1e9)
        out["roofline_t_moe_a2a_s"] = t_moe
        # comms-bound and mostly dispatch/combine traffic -> the MoE a2a is the
        # wall, not generic gradient/activation collectives.
        if bound == "comms" and comm_total > 0 and moe_a2a > 0.5 * comm_total:
            out["roofline_bound"] = "moe_a2a"
    return out


def diagnose_bound(step_time_s: float | None, roofline: dict[str, Any],
                   data_wait_frac: float = 0.0,
                   input_bound_frac: float = 0.25) -> str | None:
    """Per-row bound diagnosis: achieved step time vs the roofline expectation.

    When the host spends more than ``input_bound_frac`` of wall time waiting on
    data, the step is input-bound regardless of what the device program looks
    like; otherwise the binding roofline resource is the diagnosis.
    """
    if not roofline or step_time_s is None:
        return None
    if data_wait_frac > input_bound_frac:
        return "input"
    return roofline.get("roofline_bound")
