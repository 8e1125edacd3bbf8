"""Perf-regression gate: compare a run against a committed baseline.

A perf regression that lands silently costs every future run; this module
turns "did this change make my run slower?" into an exit code. A run artifact —
a recipe ``training.jsonl``, a ``benchmark.json`` from the benchmark recipe or a
supervised run's ``run_ledger.json`` — is reduced to gate metrics (tps, mfu,
step_time_s, goodput) and compared per-metric against a baseline file with
direction-aware tolerances: throughput-like metrics regress by dropping, step
time by rising. The speeds of this repo itself are not gated here: the driver
measures ``BENCHMARK.json``'s cells (``benchmarks/run.py``).

The parsers for the one-line, matrix-row and search-summary formats of the perf
lab that left the tree in PR 32 (``_from_bench_line``, ``_matrix_key``,
``_from_matrix_rows``, ``_from_tuner_doc``, ``_from_ledger_section``,
``incomplete_cells``, ``write_baseline(merge=True)``) have no producer left;
ROADMAP D2b decides them with the run ledger's gate.

CLI (also exposed as ``tools/bench_gate.py``)::

    python tools/bench_gate.py --run out/training.jsonl --baseline baselines/v5e.json
    python tools/bench_gate.py --run out/run_ledger.json --baseline slo.json
    python tools/bench_gate.py --run out/training.jsonl --baseline b.json --write-baseline

Exit codes: 0 = within tolerance, 1 = regression, 2 = usage/artifact error.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Iterable

__all__ = [
    "DEFAULT_TOLERANCES",
    "HIGHER_IS_BETTER",
    "Comparison",
    "summarize_rows",
    "load_run_metrics",
    "load_baseline",
    "write_baseline",
    "compare",
    "main",
]

# run-ledger families (observability/runledger.py): the badput taxonomy and
# the supervisor's failure classes, spelled out as FULL keys below because
# their basenames ("restore", "idle", "crash", ...) are not gate metrics on
# their own and the basename fallback would guess the wrong direction.
_BADPUT_CLASSES = ("restart_backoff", "reinit", "restore", "recompile",
                   "wasted_steps", "data_stall", "eval", "checkpoint", "idle")
_FAILURE_CLASSES = ("oom", "numerics", "compile", "backend-init", "preemption",
                    "data", "watchdog", "crash", "unknown")

DEFAULT_TOLERANCES = {"tps": 0.05, "mfu": 0.05, "step_time_s": 0.05, "goodput": 0.05,
                      "hbm_gib_peak": 0.05, "hbm_headroom_gib": 0.05,
                      # measured-profile keys: a single traced step
                      # jitters more than a 10-step average
                      "measured_step_time_s": 0.15, "overlap_frac": 0.1,
                      "measured_frac_compute": 0.1, "measured_frac_comm": 0.1,
                      "measured_frac_moe_a2a": 0.1, "measured_frac_host": 0.1,
                      # static HLO share of collective bytes on the ep a2a
                      # axis: deterministic for a given (model, seq, batch)
                      # but compiler-version sensitive, so measured-sized slack
                      "a2a_byte_share": 0.1,
                      # run-ledger keys: goodput_e2e gates like throughput;
                      # the badput/recovery families are chaos-amplified (one
                      # extra retrained step doubles a small count), so they
                      # get SLO-sized slack rather than perf-sized
                      "goodput_e2e": 0.05, "wasted_steps": 0.25, "recovery_s": 0.25,
                      **{f"badput/{c}": 0.25 for c in _BADPUT_CLASSES},
                      **{f"recovery_s/{c}": 0.25 for c in _FAILURE_CLASSES}}
# regression direction: True = lower is a regression, False = higher is.
# Memory gates both ways: peak HBM regresses by RISING (a model change that
# quietly grows the footprint eats the retry margin long before it OOMs),
# headroom regresses by DROPPING. Measured-profile directions: overlap and
# the compute share of the step regress by dropping (less hidden comms, more
# exposed); the comm/moe_a2a/host shares regress by rising. Run-ledger
# directions: goodput_e2e regresses by dropping; every badput fraction, the
# wasted-step count, and time-to-recovery regress by RISING.
HIGHER_IS_BETTER = {"tps": True, "mfu": True, "goodput": True, "step_time_s": False,
                    "hbm_gib_peak": False, "hbm_headroom_gib": True,
                    "measured_step_time_s": False, "overlap_frac": True,
                    "measured_frac_compute": True, "measured_frac_comm": False,
                    "measured_frac_moe_a2a": False, "measured_frac_host": False,
                    "a2a_byte_share": False,
                    "goodput_e2e": True, "wasted_steps": False, "recovery_s": False,
                    **{f"badput/{c}": False for c in _BADPUT_CLASSES},
                    **{f"recovery_s/{c}": False for c in _FAILURE_CLASSES}}


def _metric_basename(metric: str) -> str:
    """Direction/tolerance lookup key for namespaced metrics: the last path
    segment, so ``matrix/gpt_s1024_pfon/hbm_gib_peak`` gates with the same
    direction and default tolerance as a bare ``hbm_gib_peak``."""
    return metric.rsplit("/", 1)[-1]


def _median(vals: list[float]) -> float:
    s = sorted(vals)
    n = len(s)
    return s[n // 2] if n % 2 else (s[n // 2 - 1] + s[n // 2]) / 2.0


def summarize_rows(rows: Iterable[dict[str, Any]]) -> dict[str, float]:
    """Reduce training.jsonl rows to gate metrics.

    Rate metrics take the median over steady-state rows (rows with a real
    ``tps`` — the compile window logs null) so one GC hiccup or the warmup row
    can't decide the gate; ``goodput`` takes the last row (it is cumulative).
    """
    rows = list(rows)
    metric_rows = [r for r in rows if "loss" in r]
    out: dict[str, float] = {}
    for key in ("tps", "mfu", "step_time_s"):
        vals = [float(r[key]) for r in metric_rows if r.get(key) is not None]
        if vals:
            out[key] = _median(vals)
    goodputs = [r["goodput"] for r in metric_rows if r.get("goodput") is not None]
    if goodputs:
        out["goodput"] = float(goodputs[-1])
    # memory gates: peak is the run's high-water (max, not median — a single
    # eval-step spike IS the number the allocator has to survive); planned
    # headroom rides the run_header row, so scan all rows for it
    peaks = [float(r["hbm_gib_peak"]) for r in metric_rows
             if r.get("hbm_gib_peak") is not None]
    if peaks:
        out["hbm_gib_peak"] = max(peaks)
    for r in rows:
        if r.get("mem_plan/hbm_headroom_gib") is not None:
            out["hbm_headroom_gib"] = float(r["mem_plan/hbm_headroom_gib"])
    return out


def _from_bench_line(doc: dict[str, Any]) -> dict[str, float]:
    """A one-line ``{"metric", "value", "extra"}`` document: value is
    tokens/s/chip, mfu rides in extra."""
    out: dict[str, float] = {}
    if doc.get("value") is not None:
        out["tps"] = float(doc["value"])
    extra = doc.get("extra") or {}
    if extra.get("mfu") is not None:
        out["mfu"] = float(extra["mfu"])
    return out


def _matrix_key(row: dict[str, Any]) -> str:
    """Stable gate key for one bench-matrix row: matrix/<model>_s<seq>_pf<on|off>.

    Rows measured with the dynamics telemetry in-graph get a ``_dyn`` suffix: a
    different measurement condition must never gate against the plain baseline
    cell by accident — it gets its own cells (and its own baseline via
    ``--write-baseline``).
    """
    pf = "on" if row.get("prefetch") else "off"
    dyn = "_dyn" if row.get("dynamics") else ""
    return f"matrix/{row.get('model')}_s{row.get('seq_len')}_pf{pf}{dyn}"


def _from_matrix_rows(rows: Iterable[dict[str, Any]]) -> dict[str, float]:
    """Flatten ``matrix_row`` rows into per-cell gate metrics.

    Each cell contributes ``<key>/tps`` (and ``<key>/moe_tps`` for MoE rows;
    ``cpu_tps`` / ``cpu_moe_tps`` for the rows of a ``--cpu`` rehearsal) so
    a regression in one cell — say moe s8192 with prefetch — fails the gate by
    name instead of hiding inside an average. Profiled rows add
    the measured-profile keys (``<key>/measured_*`` + ``<key>/overlap_frac``,
    every basename in HIGHER_IS_BETTER) so compute/comms overlap is gated,
    not just throughput. MoE rows add ``<key>/a2a_byte_share`` — the static
    HLO share of collective bytes on the ep all_to_all axis, which regresses
    by RISING (a dispatch change that bloats a2a traffic shows up here before
    the trace does). Remaining decoration fields (``steps``,
    ``measured_seq_len``, ``dropped_token_frac``, the ``measured_bound``
    string) stay out: they are diagnostics, not directional performance
    metrics.
    """
    out: dict[str, float] = {}
    for row in rows:
        key = _matrix_key(row)
        # a --cpu rehearsal row carries its rate under its own name and gates
        # under its own keys: it never shares a baseline cell with a chip row
        for field, gate in (("tokens_per_sec_per_chip", "tps"),
                            ("moe/tokens_per_sec_per_chip", "moe_tps"),
                            ("cpu_tokens_per_sec_per_device", "cpu_tps"),
                            ("moe/cpu_tokens_per_sec_per_device", "cpu_moe_tps")):
            if row.get(field) is not None:
                out[f"{key}/{gate}"] = float(row[field])
        if row.get("hbm_gib_peak") is not None:
            out[f"{key}/hbm_gib_peak"] = float(row["hbm_gib_peak"])
        if row.get("a2a_byte_share") is not None:
            out[f"{key}/a2a_byte_share"] = float(row["a2a_byte_share"])
        for k, v in row.items():
            if (k in ("measured_step_time_s", "overlap_frac")
                    or k.startswith("measured_frac_")) \
                    and isinstance(v, (int, float)):
                out[f"{key}/{k}"] = float(v)
    return out


def _from_benchmark_json(doc: dict[str, Any]) -> dict[str, float]:
    """The benchmark recipe's benchmark.json (recipes/llm/benchmark.py)."""
    out: dict[str, float] = {}
    mapping = {"tokens_per_sec": "tps", "mfu": "mfu", "step_time_s": "step_time_s"}
    for src, dst in mapping.items():
        if doc.get(src) is not None:
            out[dst] = float(doc[src])
    return out


def _from_run_ledger(doc: dict[str, Any]) -> dict[str, float]:
    """A ``run_ledger.json`` document (observability/runledger.py) gates
    directly: ``goodput_e2e``, ``wasted_steps``, the ``badput/<class>``
    fractions, and per-failure-class ``recovery_s/<class>`` means."""
    from automodel_tpu.observability.runledger import gate_metrics

    return gate_metrics(doc)


def _from_ledger_section(doc: dict[str, Any]) -> dict[str, float]:
    """Flattened run-ledger metrics under ``ledger`` in a summary doc merge
    into the cell metrics, so one capture gates throughput AND recovery cost."""
    section = doc.get("ledger")
    if not isinstance(section, dict):
        return {}
    return {k: float(v) for k, v in section.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


def _from_tuner_doc(doc: dict[str, Any]) -> dict[str, float]:
    """A search's summary doc: the winner's gate-ready metrics ride under
    ``tuner.metrics`` as ``tuned/<cell>/<basename>`` keys."""
    metrics = (doc.get("tuner") or {}).get("metrics") or {}
    return {k: float(v) for k, v in metrics.items()
            if isinstance(v, (int, float))}


def load_run_metrics(path: str) -> dict[str, float]:
    """Dispatch on content, not extension: JSONL rows, a bench line, or
    benchmark.json all reduce to the same gate-metric dict."""
    with open(path) as f:
        text = f.read()
    if not text.strip():
        raise ValueError(f"{path}: empty run artifact")
    try:  # one JSON document (possibly pretty-printed benchmark.json)
        doc = json.loads(text)
    except json.JSONDecodeError:
        doc = None
    if isinstance(doc, dict):
        if isinstance(doc.get("badput"), dict) and "goodput_e2e" in doc:
            return _from_run_ledger(doc)  # run_ledger.json
        if isinstance(doc.get("matrix"), list):  # matrix summary doc
            return {**_from_matrix_rows(doc["matrix"]),
                    **_from_ledger_section(doc)}
        if "metric" in doc and "value" in doc:
            return {**_from_bench_line(doc), **_from_tuner_doc(doc),
                    **_from_ledger_section(doc)}
        if "tokens_per_sec" in doc:
            return _from_benchmark_json(doc)
        if "metrics" in doc:  # a baseline file doubles as a synthetic run
            return {k: float(v) for k, v in doc["metrics"].items()}
        return summarize_rows([doc])
    rows = [json.loads(ln) for ln in text.splitlines() if ln.strip()]
    tuner: dict[str, float] = {}
    for r in rows:
        tuner.update(_from_tuner_doc(r))
    matrix_rows = [r for r in rows if r.get("matrix_row")]
    if matrix_rows:  # matrix stdout capture: per-row lines + summary doc
        out = _from_matrix_rows(matrix_rows)
        out.update(summarize_rows(r for r in rows if not r.get("matrix_row")))
        out.update(tuner)
        return out
    return {**summarize_rows(rows), **tuner}


def incomplete_cells(path: str) -> list[dict[str, Any]]:
    """Per-cell status entries for cells that did NOT run, from a
    matrix artifact carrying a ``cells`` status list (summary doc or stdout
    capture). Empty for artifacts that predate
    per-cell status — those gate exactly as before. This is how the gate
    refuses to bless a partial matrix silently: the cells that ran still
    gate, but a missing cell is named and the exit code says artifact-error
    (2), not pass."""
    try:
        with open(path) as f:
            text = f.read()
    except OSError:
        return []
    docs: list[dict[str, Any]] = []
    try:
        doc = json.loads(text)
        if isinstance(doc, dict):
            docs = [doc]
    except json.JSONDecodeError:
        for ln in text.splitlines():
            try:
                d = json.loads(ln)
            except json.JSONDecodeError:
                continue
            if isinstance(d, dict):
                docs.append(d)
    cells_doc = next((d for d in reversed(docs)
                      if isinstance(d.get("cells"), list)), None)
    if cells_doc is None:
        return []
    return [c for c in cells_doc["cells"]
            if isinstance(c, dict) and c.get("status") != "ran"]


def load_baseline(path: str) -> dict[str, float]:
    with open(path) as f:
        doc = json.load(f)
    metrics = doc.get("metrics", doc)
    return {k: float(v) for k, v in metrics.items() if isinstance(v, (int, float))}


def write_baseline(path: str, metrics: dict[str, float],
                   meta: dict[str, Any] | None = None,
                   merge: bool = False) -> None:
    """Write (or, with ``merge``, update) a baseline file.

    ``merge=True`` lands one cell's metrics in an existing baseline without
    erasing it: the existing document's non-metric fields and every other metric
    survive; only the given metrics are added/replaced, and ``meta`` lands
    under ``metrics_meta.tuner`` instead of clobbering the document meta.
    """
    doc: dict[str, Any] = {}
    if merge and os.path.exists(path):
        with open(path) as f:
            doc = json.load(f)
    existing = doc.get("metrics") if isinstance(doc.get("metrics"), dict) else {}
    rounded = {k: round(float(v), 6) for k, v in metrics.items()}
    doc["metrics"] = {**existing, **rounded}
    if meta:
        if merge:
            doc.setdefault("metrics_meta", {})["tuner"] = meta
        else:
            doc["meta"] = meta
    tmp = f"{path}.tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)


@dataclasses.dataclass
class Comparison:
    metric: str
    run: float | None
    base: float | None
    change: float | None  # relative move in the regression direction
    tolerance: float
    ok: bool

    def line(self) -> str:
        status = "OK" if self.ok else "FAIL"
        if self.run is None or self.base is None:
            return f"[gate] {self.metric:<12} missing from run artifact: {status}"
        if self.change is None:  # base == 0: no relative move to compute
            return (f"[gate] {self.metric:<12} run={self.run:.6g} "
                    f"base={self.base:.6g} not comparable: {status}")
        return (f"[gate] {self.metric:<12} run={self.run:.6g} base={self.base:.6g} "
                f"change={self.change * 100:+.1f}% tol={self.tolerance * 100:.1f}%: {status}")


def compare(run: dict[str, float], baseline: dict[str, float],
            tolerances: dict[str, float] | None = None,
            require: Iterable[str] = ()) -> list[Comparison]:
    """Per-metric direction-aware comparison over the baseline's metrics.

    Only metrics present in the baseline gate; a metric the run artifact lacks
    passes unless listed in ``require`` (a CPU run has no meaningful mfu, but
    a gate explicitly about tps must not pass on an empty artifact).
    """
    user_tols = dict(tolerances or {})
    user_default = user_tols.pop("default", None)
    required = set(require)
    out: list[Comparison] = []
    for metric, base in sorted(baseline.items()):
        basename = _metric_basename(metric)
        # Tolerance precedence: caller's exact key > built-in exact key >
        # caller's basename > caller's "default" > built-in basename > 5%.
        # A widened CLI default (CPU timing jitter) must still reach
        # namespaced cells the built-ins only know by basename — but never
        # override a metric the caller named explicitly.
        if metric in user_tols:
            tol = user_tols[metric]
        elif metric in DEFAULT_TOLERANCES:
            tol = DEFAULT_TOLERANCES[metric]
        elif basename in user_tols:
            tol = user_tols[basename]
        elif user_default is not None:
            tol = user_default
        else:
            tol = DEFAULT_TOLERANCES.get(basename, 0.05)
        got = run.get(metric)
        if got is None or base == 0:
            # `require` guards against the metric being MISSING from the run;
            # a present value against a zero baseline has no relative move to
            # gate (overlap_frac is legitimately 0 on single-axis runs) and
            # must not fail just because it was required
            out.append(Comparison(metric, got, base, None, tol,
                                  ok=got is not None or metric not in required))
            continue
        if HIGHER_IS_BETTER.get(metric, HIGHER_IS_BETTER.get(basename, True)):
            change = (base - got) / abs(base)  # positive = slower/worse
        else:
            change = (got - base) / abs(base)
        out.append(Comparison(metric, got, base, change, tol, ok=change <= tol))
    return out


def _parse_tolerances(pairs: Iterable[str]) -> dict[str, float]:
    out = {}
    for pair in pairs:
        if "=" not in pair:
            raise ValueError(f"--tolerance wants metric=fraction, got {pair!r}")
        k, v = pair.split("=", 1)
        out[k.strip()] = float(v)
    return out


def main(argv: list[str] | None = None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        prog="bench_gate", description=__doc__.splitlines()[0],
    )
    parser.add_argument("--run", required=True,
                        help="run artifact: training.jsonl, benchmark.json, or a bench JSON line")
    parser.add_argument("--baseline", required=True,
                        help="committed baseline JSON ({'metrics': {...}})")
    parser.add_argument("--tolerance", action="append", default=[], metavar="METRIC=FRAC",
                        help="override a tolerance, e.g. tps=0.08; "
                             "default=0.2 sets the fallback for unlisted metrics")
    parser.add_argument("--require", action="append", default=[], metavar="METRIC",
                        help="fail when METRIC is missing from the run artifact")
    parser.add_argument("--only", action="append", default=[], metavar="METRIC",
                        help="gate only baseline metrics matching METRIC (exact "
                             "key or basename, repeatable) — how CI gates just "
                             "the deterministic keys of a CPU smoke cell")
    parser.add_argument("--write-baseline", action="store_true",
                        help="write the run's metrics to --baseline and exit 0")
    parser.add_argument("--merge-baseline", action="store_true",
                        help="like --write-baseline but update in place: other "
                             "metrics and non-metric document fields survive "
                             "(one cell's path into an existing baseline)")
    parser.add_argument("--allow-incomplete", action="store_true",
                        help="gate only the cells that ran even when the "
                             "artifact names cells that didn't (default: a "
                             "missing cell is an artifact error, exit 2)")
    args = parser.parse_args(argv)

    try:
        tolerances = _parse_tolerances(args.tolerance)
        run = load_run_metrics(args.run)
        if args.write_baseline or args.merge_baseline:
            write_baseline(args.baseline, run,
                           meta={"source": os.path.abspath(args.run)},
                           merge=args.merge_baseline)
            verb = "merged into" if args.merge_baseline else "written:"
            print(f"[gate] baseline {verb} {args.baseline} <- {sorted(run)}")
            return 0
        baseline = load_baseline(args.baseline)
    except (OSError, ValueError, json.JSONDecodeError) as exc:
        print(f"[gate] ERROR: {exc}")
        return 2
    if args.only:
        only = set(args.only)
        baseline = {k: v for k, v in baseline.items()
                    if k in only or _metric_basename(k) in only}
    if not baseline:
        print(f"[gate] ERROR: no gate metrics in baseline {args.baseline}")
        return 2
    missing = incomplete_cells(args.run)
    results = compare(run, baseline, tolerances, require=args.require)
    for comparison in results:
        print(comparison.line())
    for c in missing:
        print(f"[gate] MISSING CELL: {c.get('id')} "
              f"status={c.get('status')} taxonomy={c.get('taxonomy')}")
    failed = [c.metric for c in results if not c.ok]
    if failed:
        print(f"[gate] REGRESSION: {', '.join(failed)} outside tolerance")
        return 1
    if missing and not args.allow_incomplete:
        ids = ", ".join(str(c.get("id")) for c in missing)
        print(f"[gate] ERROR: {len(missing)} cell(s) did not run: {ids} "
              f"(gated cells pass; pass --allow-incomplete to accept a "
              f"partial matrix)")
        return 2
    print("[gate] PASS")
    return 0
