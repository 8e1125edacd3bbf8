"""The signals bundle: one artifact per run (its reader, the autotuner, left
the tree in PR 32; ROADMAP D5 decides whether the writer stays).

Per (model, mesh, seq) cell: the analytic roofline, the
measured trace breakdown (trace_analysis.py), whether the two agree, the HBM
headroom (memory_plan.py), and the compile-cache state — scattered otherwise
across the compile_costs row, trace_report.json, the run_header, and the
compile_summary row. ``build_signals`` assembles them into one
``signals.json`` document with a machine-checkable schema (documented in
docs/observability.md "Measured trace attribution & signals"); absent sources
produce explicit ``null`` sections, never missing keys, so a consumer can
distinguish "not captured" from "captured as zero".

Schema (version 1)::

    {"version": 1, "cells": [{
        "cell":           {"model": str|null, "mesh": {axis: int}|null,
                           "seq_len": int|null},
        "analytic":       {"roofline_bound": str, "roofline_step_time_s": num,
                           "roofline_t_compute_s": num, "roofline_t_memory_s": num,
                           "roofline_t_comm_s": num, "hlo_flops": num|null,
                           "comm_bytes_total": num|null,
                           "comm_bytes_moe_a2a": num|null} | null,
        "measured":       {"measured_bound": str, "measured_step_time_s": num,
                           "overlap_frac": num, "measured_frac_compute": num,
                           "measured_frac_comm": num, "measured_frac_moe_a2a": num,
                           "measured_frac_host": num} | null,
        "reconciliation": {"analytic_bound": str, "measured_bound": str,
                           "agrees": bool, "verdict": str} | null,
        "memory":         {"hbm_headroom_gib": num|null, "hbm_limit_gib": num|null,
                           "total_gib": num, "fits": bool|null} | null,
        "compile_cache":  {"hits": num, "misses": num, "aot": num,
                           "jit_fallback": num} | null}]}
"""

from __future__ import annotations

import json
import logging
import os
import tempfile
from typing import Any

logger = logging.getLogger(__name__)

__all__ = ["SIGNALS_VERSION", "build_signals", "validate_signals",
           "write_signals"]

SIGNALS_VERSION = 1

# section -> {field: (types, required)}; numbers accept int or float, and a
# field marked optional may be null (absent sources stay explicit)
_NUM = (int, float)
_SECTIONS: dict[str, dict[str, tuple[tuple, bool]]] = {
    "analytic": {
        "roofline_bound": ((str,), True),
        "roofline_step_time_s": (_NUM, True),
        "roofline_t_compute_s": (_NUM, True),
        "roofline_t_memory_s": (_NUM, True),
        "roofline_t_comm_s": (_NUM, True),
        "hlo_flops": (_NUM, False),
        "comm_bytes_total": (_NUM, False),
        "comm_bytes_moe_a2a": (_NUM, False),
    },
    "measured": {
        "measured_bound": ((str,), True),
        "measured_step_time_s": (_NUM, True),
        "overlap_frac": (_NUM, True),
        "measured_frac_compute": (_NUM, True),
        "measured_frac_comm": (_NUM, True),
        "measured_frac_moe_a2a": (_NUM, True),
        "measured_frac_host": (_NUM, True),
    },
    "reconciliation": {
        "analytic_bound": ((str,), True),
        "measured_bound": ((str,), True),
        "agrees": ((bool,), True),
        "verdict": ((str,), True),
    },
    "memory": {
        "hbm_headroom_gib": (_NUM, False),
        "hbm_limit_gib": (_NUM, False),
        "total_gib": (_NUM, True),
        "fits": ((bool,), False),
    },
    "compile_cache": {
        "hits": (_NUM, True),
        "misses": (_NUM, True),
        "aot": (_NUM, True),
        "jit_fallback": (_NUM, True),
    },
}


def _analytic_section(roofline: dict | None, costs: dict | None) -> dict | None:
    if not roofline:
        return None
    out = {k: roofline.get(k) for k in
           ("roofline_bound", "roofline_step_time_s", "roofline_t_compute_s",
            "roofline_t_memory_s", "roofline_t_comm_s")}
    if any(v is None for v in out.values()):
        return None
    costs = costs or {}
    out["hlo_flops"] = costs.get("hlo_flops")
    out["comm_bytes_total"] = costs.get("comm_bytes_total")
    out["comm_bytes_moe_a2a"] = costs.get("comm_bytes_moe_a2a")
    return out


def _measured_section(trace_summary: dict | None) -> dict | None:
    if not trace_summary:
        return None
    out = {k: trace_summary.get(k) for k in _SECTIONS["measured"]}
    if out["measured_bound"] is None:
        return None
    return out


def _reconciliation_section(trace_summary: dict | None) -> dict | None:
    if not trace_summary or "trace/bound_agrees" not in trace_summary:
        return None
    return {
        "analytic_bound": trace_summary.get("trace/analytic_bound"),
        "measured_bound": trace_summary.get("measured_bound"),
        "agrees": bool(trace_summary["trace/bound_agrees"]),
        "verdict": trace_summary.get("trace/verdict"),
    }


def _memory_section(plan: Any) -> dict | None:
    if plan is None:
        return None
    head = plan.headroom_bytes
    limit = plan.hbm_limit_bytes
    return {
        "hbm_headroom_gib": round(head / 2**30, 4) if head is not None else None,
        "hbm_limit_gib": round(limit / 2**30, 4) if limit is not None else None,
        "total_gib": round(plan.total_bytes / 2**30, 4),
        "fits": plan.fits,
    }


def _compile_cache_section(compile_summary: dict | None) -> dict | None:
    if not compile_summary:
        return None
    return {
        "hits": int(compile_summary.get("compile_cache_hits", 0)),
        "misses": int(compile_summary.get("compile_cache_misses", 0)),
        "aot": int(compile_summary.get("compile_aot", 0)),
        "jit_fallback": int(compile_summary.get("compile_jit_fallback", 0)),
    }


def build_cell(cell: dict | None = None, mesh_axes: dict | None = None,
               roofline: dict | None = None, costs: dict | None = None,
               trace_summary: dict | None = None, memory_plan: Any = None,
               compile_summary: dict | None = None) -> dict[str, Any]:
    """One schema-shaped cell from whatever sources exist right now."""
    cell = dict(cell or {})
    return {
        "cell": {
            "model": cell.get("model"),
            "mesh": ({str(k): int(v) for k, v in mesh_axes.items()}
                     if mesh_axes else cell.get("mesh")),
            "seq_len": cell.get("seq_len"),
        },
        "analytic": _analytic_section(roofline, costs),
        "measured": _measured_section(trace_summary),
        "reconciliation": _reconciliation_section(trace_summary),
        "memory": _memory_section(memory_plan),
        "compile_cache": _compile_cache_section(compile_summary),
    }


def build_signals(cells: list[dict] | dict | None = None,
                  **one_cell_kwargs: Any) -> dict[str, Any]:
    """The signals.json document. Either pass pre-built cells (a list, or one
    dict) or the :func:`build_cell` kwargs for a single-cell document."""
    if one_cell_kwargs:
        assert not cells, "pass cells OR build_cell kwargs, not both"
        cells = [build_cell(**one_cell_kwargs)]
    elif isinstance(cells, dict):
        cells = [cells]
    return {"version": SIGNALS_VERSION, "cells": list(cells or [])}


def validate_signals(doc: Any) -> list[str]:
    """Schema-check a signals document; returns problems ([] = valid)."""
    problems: list[str] = []
    if not isinstance(doc, dict):
        return [f"document is {type(doc).__name__}, expected object"]
    if doc.get("version") != SIGNALS_VERSION:
        problems.append(f"version is {doc.get('version')!r}, "
                        f"expected {SIGNALS_VERSION}")
    cells = doc.get("cells")
    if not isinstance(cells, list):
        return problems + ["cells is not a list"]
    for i, cell in enumerate(cells):
        where = f"cells[{i}]"
        if not isinstance(cell, dict):
            problems.append(f"{where} is not an object")
            continue
        ident = cell.get("cell")
        if not isinstance(ident, dict):
            problems.append(f"{where}.cell missing or not an object")
        for section, fields in _SECTIONS.items():
            if section not in cell:
                problems.append(f"{where}.{section} key missing "
                                "(null it explicitly when not captured)")
                continue
            val = cell[section]
            if val is None:
                continue
            if not isinstance(val, dict):
                problems.append(f"{where}.{section} is not an object or null")
                continue
            for field, (types, required) in fields.items():
                if field not in val:
                    problems.append(f"{where}.{section}.{field} missing")
                    continue
                v = val[field]
                if v is None:
                    if required:
                        problems.append(f"{where}.{section}.{field} is null "
                                        "but required")
                    continue
                # bool is an int subclass; keep booleans out of numeric fields
                if isinstance(v, bool) and bool not in types:
                    problems.append(f"{where}.{section}.{field} is bool, "
                                    f"expected {'/'.join(t.__name__ for t in types)}")
                elif not isinstance(v, types):
                    problems.append(f"{where}.{section}.{field} is "
                                    f"{type(v).__name__}, expected "
                                    f"{'/'.join(t.__name__ for t in types)}")
        measured = cell.get("measured")
        if isinstance(measured, dict):
            frac = measured.get("overlap_frac")
            if isinstance(frac, (int, float)) and not 0.0 <= float(frac) <= 1.0:
                problems.append(f"{where}.measured.overlap_frac={frac} "
                                "outside [0, 1]")
    return problems


def write_signals(path: str, doc: dict[str, Any]) -> None:
    """Atomic write (tmp + rename): a crash mid-write must not leave a torn
    artifact for a reader to parse."""
    problems = validate_signals(doc)
    if problems:  # never ship an artifact the schema check would reject
        raise ValueError("signals document fails its own schema: "
                         + "; ".join(problems[:5]))
    directory = os.path.dirname(os.path.abspath(path)) or "."
    os.makedirs(directory, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=directory, suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
