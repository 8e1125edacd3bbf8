"""From a profiler trace (``*.xplane.pb``) to device busy time, kernel time, the
operations that took most time and the longest idle gaps.

Read with ``jax.profiler.ProfileData`` alone. What a TPU v5e trace holds (looked at by
hand, PR 24): one plane per chip, ``/device:TPU:<n>``, whose line ``XLA Modules`` has
one event per run of a compiled program and whose line ``XLA Ops`` has one event per
operation of it, start and duration in nanoseconds on one clock with the host planes
(``/host:CPU``: one line per thread, TraceMe and TraceAnnotation spans).

The traced window runs from the start of the first to the start of the last run of the
step's program (the module that took most time), so it holds whole steps with the gaps
between them. Busy time is the union of the operations' intervals inside it.
"""

from __future__ import annotations

import glob
import os

_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"


def newest_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def short_name(name: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO instruction, ``%lhs = type op(...)``,
    thousands of characters for a loop. Kept: ``%lhs op``."""
    lhs, sep, rhs = name.partition(" = ")
    if not sep:
        return name
    if rhs.startswith("("):  # a tuple type: skip to its closing parenthesis
        depth = 0
        for i, ch in enumerate(rhs):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rhs = rhs[i + 1:]
                break
    else:
        rhs = rhs.partition(" ")[2]
    return f"{lhs} {rhs.strip().partition('(')[0]}"


def load(path: str) -> dict:
    """``{plane: {line: [(name, start_ns, duration_ns), ...]}}``, lines with the same
    name in one plane merged, names cut by :func:`short_name`."""
    from jax.profiler import ProfileData

    planes: dict = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for ev in line.events:
                events.append((short_name(ev.name), float(ev.start_ns), float(ev.duration_ns)))
    return planes


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


_CONTAINERS = (" while", " conditional", " call")


def is_flash(name: str) -> bool:
    """The flash attention kernels: Mosaic custom calls that carry the kernel function's
    name, ``%attention.<n> custom-call`` (two forward calls and one fused backward call a
    layer under ``remat_policy: mlp_attn_dots``, which recomputes the forward)."""
    return name.endswith(" custom-call") and name.lstrip("%").startswith(("attention", "flash"))


def reduce_planes(planes: dict) -> dict:
    """Seconds throughout. ``steps`` whole steps lie in ``window_s``."""
    devices = sorted(p for p in planes if p.startswith("/device:TPU:")
                     and _OPS_LINE in planes[p] and planes[p][_OPS_LINE])
    if not devices:
        raise ValueError(f"no device plane with an '{_OPS_LINE}' line: {sorted(planes)}")
    busy = window = flash = 0.0
    steps = 0
    op_totals: dict[str, float] = {}
    gaps: list[tuple[float, float]] = []
    for dev in devices:
        modules = planes[dev].get(_MODULES_LINE, [])
        totals: dict[str, float] = {}
        for name, _, dur in modules:
            totals[name] = totals.get(name, 0.0) + dur
        step_module = max(totals, key=totals.get)
        runs = sorted(s for name, s, _ in modules if name == step_module)
        if len(runs) < 2:
            raise ValueError(f"{dev}: {len(runs)} runs of {step_module!r}; need two or more")
        lo, hi = runs[0], runs[-1]
        clipped = []
        for name, start, dur in planes[dev][_OPS_LINE]:
            a, b = max(start, lo), min(start + dur, hi)
            if b <= a:
                continue
            clipped.append((a, b))
            if name.endswith(_CONTAINERS):
                continue  # a loop's event spans its body's: busy, but not an operation
            op_totals[name] = op_totals.get(name, 0.0) + (b - a)
            if is_flash(name):
                flash += b - a
        merged = _union(clipped)
        busy += sum(b - a for a, b in merged)
        window += hi - lo
        steps = len(runs) - 1
        if dev == devices[0]:
            edges = [lo, *[x for ab in merged for x in ab], hi]
            gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    n = len(devices)
    host = [(name, s, s + d) for p in planes if p.startswith("/host:")
            for events in planes[p].values() for name, s, d in events]
    idle = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:5]:
        best, best_overlap = "unattributed", 0.0
        for name, s, e in host:
            overlap = min(b, e) - max(a, s)
            if overlap > best_overlap:
                best, best_overlap = name, overlap
        idle.append([best, (b - a) * 1e-9])
    top = sorted(op_totals.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": busy / n * 1e-9, "window_s": window / n * 1e-9, "steps": steps,
        "flash_s": flash / n * 1e-9,
        "breakdown": {"device_ops": [[name, t / n * 1e-9] for name, t in top],
                      "idle_gaps": idle},
    }


def summary(planes: dict, top: int = 40) -> str:
    """What a trace holds, for a reader: planes, lines, and each line's heaviest names."""
    out = []
    for plane, lines in planes.items():
        out.append(f"plane {plane}")
        for line, events in lines.items():
            totals: dict[str, list[float]] = {}
            for name, _, dur in events:
                entry = totals.setdefault(name, [0, 0.0])
                entry[0] += 1
                entry[1] += dur
            out.append(f"  line {line!r}: {len(events)} events, {len(totals)} names")
            for name, (count, dur) in sorted(totals.items(), key=lambda kv: -kv[1][1])[:top]:
                out.append(f"    {dur * 1e-6:10.3f} ms  x{count:<5d} {name[:160]}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    print(summary(load(newest_xplane(sys.argv[1]) if os.path.isdir(sys.argv[1]) else sys.argv[1])))
