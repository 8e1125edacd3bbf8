"""The program's two rows about a start, read from the run's ``training.jsonl``:
``setup_summary`` (written when the first step has finished: where set-up's seconds went,
by span) and ``compile_summary`` (written at teardown: every compile request of the
process). A program that writes no ``setup_summary`` row (any before PR 45) gives ``None``
and the readers leave their metric out; a row that is there and lacks a key, or a span a
reader names, is an error: a rename stops the run instead of thinning the line.

A rehearsal's line leaves them out too, although they need no device trace: three accepted
tests (``test_bench_rehearse.py``, ``test_bench_nemotron_h.py``, ``test_bench_qwen3_next.py``)
pin the metrics a rehearsal prints, and ``tests/benchmarks/test_bench_setup.py`` reads them
from a rehearsal's rows instead."""

from __future__ import annotations

import json
import os

# the spans that tile ``setup()`` and the loop's start up to the first fetch
BUILD_SPANS = ("setup_mesh", "setup_model", "setup_data", "setup_optimizer",
               "setup_checkpoint", "setup_loggers", "setup_step_fn", "setup_pipeline")


def row(run: dict, event: str = "setup_summary") -> dict | None:
    """The run's one row of ``event``, or None where the program has no set-up spans."""
    if run.get("rehearse"):
        return None
    path = os.path.join(run["run"].recipe.output_dir, "training.jsonl")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    if not any(r.get("event") == "setup_summary" for r in rows):
        return None
    found = [r for r in rows if r.get("event") == event]
    if len(found) != 1:
        raise RuntimeError(f"{len(found)} {event!r} rows in {path}: one is written a run")
    return found[0]


def span_s(run: dict, *names: str) -> float | None:
    """Seconds of the named set-up spans, summed."""
    summary = row(run)
    if summary is None:
        return None
    return float(sum(summary["spans"][name] for name in names))


def value(run: dict, event: str, key: str):
    """One number of one of the two rows, as the program wrote it."""
    found = row(run, event)
    return None if found is None else found[key]
