"""Unified trace timeline: one Chrome-trace JSON for a run's whole life.

Per-step metrics answer "how fast"; the timeline answers "what happened when".
Every durable phase (compile, step, eval, checkpoint, rollback) becomes a
complete event and every async incident (stall, preemption, resilience events)
an instant event, all in ``out_dir/timeline.json`` using the Chrome
trace-event format — drop the file into Perfetto (ui.perfetto.dev) or
``chrome://tracing`` and the run is one picture.

Timestamps are microseconds of ``time.perf_counter`` relative to timeline
construction, whose wall-clock time is the document's ``t0_unix_s`` (to lay the
file beside a profiler trace, which stamps its own start); ``pid`` is the JAX
process index so multi-host traces merge into one view. The writer is bounded (``max_events``, drops counted, never raises)
and atomic (tmp + rename), so a mid-run copy of the file always parses.
"""

from __future__ import annotations

import contextlib
import json
import logging
import os
import time
from typing import Any

logger = logging.getLogger(__name__)

__all__ = ["TraceTimeline"]


def _jsonable_args(args: dict[str, Any]) -> dict[str, Any]:
    out = {}
    for k, v in args.items():
        if isinstance(v, (int, str, bool)) or v is None:
            out[k] = v
        elif isinstance(v, float):
            out[k] = v if v == v and abs(v) != float("inf") else None
        else:
            out[k] = str(v)
    return out


class TraceTimeline:
    """Bounded, atomically-written Chrome trace-event collector.

    ``path=None`` (non-main processes) degrades every method to a no-op, the
    same contract MetricLogger uses.
    """

    def __init__(self, path: str | None, pid: int = 0,
                 max_events: int = 20000, flush_every: int = 2048):
        self.path = path
        self.pid = int(pid)
        self.max_events = int(max_events)
        # a loop iteration writes about eleven events (its spans, the step, a counter):
        # 2048 rewrites the file about every 190 steps
        self.flush_every = int(flush_every)
        self.dropped = 0
        self._events: list[dict[str, Any]] = []
        self._t0 = time.perf_counter()
        self._t0_unix_s = time.time()
        self._since_flush = 0
        if path is not None:
            os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)

    @property
    def enabled(self) -> bool:
        return self.path is not None

    @property
    def t0_unix_s(self) -> float:
        """Wall-clock time of the timeline's zero (the document's ``t0_unix_s``)."""
        return self._t0_unix_s

    def now(self) -> float:
        """Seconds since timeline start — pair with ``complete(start_s=...)``."""
        return time.perf_counter() - self._t0

    # ------------------------------------------------------------------ emit
    def complete(self, name: str, cat: str, start_s: float, dur_s: float,
                 tid: int = 0, **args: Any) -> None:
        """A span with explicit start/duration (Chrome phase "X")."""
        self._push({
            "name": name, "cat": cat, "ph": "X",
            "ts": round(start_s * 1e6, 1), "dur": round(max(dur_s, 0.0) * 1e6, 1),
            "pid": self.pid, "tid": tid,
            "args": _jsonable_args(args),
        })

    def instant(self, name: str, cat: str = "event", tid: int = 0, **args: Any) -> None:
        """A zero-duration incident marker (Chrome phase "i", process scope)."""
        self._push({
            "name": name, "cat": cat, "ph": "i", "s": "p",
            "ts": round(self.now() * 1e6, 1),
            "pid": self.pid, "tid": tid,
            "args": _jsonable_args(args),
        })

    def counter(self, name: str, tid: int = 0, **values: Any) -> None:
        """A counter sample (Chrome phase "C"): Perfetto renders each series in
        ``values`` as a stacked track over time — how hbm_gib_in_use/peak
        become a picture instead of a column of numbers."""
        self._push({
            "name": name, "cat": "counter", "ph": "C",
            "ts": round(self.now() * 1e6, 1),
            "pid": self.pid, "tid": tid,
            "args": _jsonable_args(values),
        })

    def counters_from_flat(self, flat: dict[str, Any], prefix: str = "dynamics",
                           tid: int = 0) -> None:
        """Fan a flat ``<prefix>/<group>/<metric>`` row into per-metric counter
        tracks: one Chrome counter per metric, one series per group — so
        ``dynamics/layers.mlp/grad_norm`` and its siblings render as a stacked
        ``dynamics/grad_norm`` track with a line per layer bucket."""
        by_metric: dict[str, dict[str, Any]] = {}
        for key, val in flat.items():
            parts = key.split("/")
            if len(parts) != 3 or parts[0] != prefix:
                continue
            by_metric.setdefault(parts[2], {})[parts[1]] = val
        for metric, series in by_metric.items():
            self.counter(f"{prefix}/{metric}", tid=tid, **series)

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "phase", tid: int = 0, **args: Any):
        """Context manager emitting a complete event for the wrapped block."""
        t0 = self.now()
        try:
            yield
        finally:
            self.complete(name, cat, t0, self.now() - t0, tid=tid, **args)

    def _push(self, event: dict[str, Any]) -> None:
        if self.path is None:
            return
        if len(self._events) >= self.max_events:
            self.dropped += 1
            return
        self._events.append(event)
        self._since_flush += 1
        if self._since_flush >= self.flush_every:
            self.write()

    # ----------------------------------------------------------------- output
    def write(self) -> None:
        """Atomic snapshot of everything collected so far; safe to call anytime."""
        if self.path is None:
            return
        self._since_flush = 0
        doc = {"traceEvents": list(self._events), "displayTimeUnit": "ms",
               "t0_unix_s": self._t0_unix_s}
        if self.dropped:
            doc["droppedEventCount"] = self.dropped
        tmp = f"{self.path}.tmp"
        try:
            with open(tmp, "w") as f:
                json.dump(doc, f)
            os.replace(tmp, self.path)
        except Exception:
            logger.exception("timeline write failed (run continues)")

    def close(self) -> None:
        self.write()
