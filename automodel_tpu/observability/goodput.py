"""Goodput accounting: classify every second of wall time into buckets.

The reference ships per-step throughput summaries but never answers "where did
the wall clock go" — a 20% regression can hide in compile, host data stalls, or
checkpoint pauses and look identical in tokens/sec. ``GoodputTracker`` bills
host wall time to named buckets (compile / data_wait / device_step / eval /
checkpoint); whatever is unaccounted is idle. Goodput is the device_step share
of total wall time — the fraction of the run actually spent training.

Attribution is host-side: the jitted step is asynchronous, so ``device_step``
measures dispatch-to-sync host time, not device occupancy. Over a log window
the two converge (the host blocks on the metrics pull), and host-side is the
only attribution that also sees data stalls and checkpoint pauses.
"""

from __future__ import annotations

import contextlib
import time
from typing import Callable

__all__ = ["BUCKETS", "GoodputTracker"]

# buckets the train loop bills explicitly; the remainder is idle. ``restore``
# is checkpoint load on resume (incl. the elastic re-partition path): the
# `restore` span inside set-up, ahead of the wall that open_wall() opens.
BUCKETS = ("compile", "data_wait", "device_step", "eval", "checkpoint",
           "rollback", "restore")


class GoodputTracker:
    """Cumulative wall-time bucket accounting for one training run.

    ``clock`` is injectable for tests (defaults to ``time.perf_counter``).
    """

    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        self._clock = clock
        self._start = clock()
        self._totals: dict[str, float] = {b: 0.0 for b in BUCKETS}

    @contextlib.contextmanager
    def track(self, bucket: str):
        t0 = self._clock()
        try:
            yield
        finally:
            self.add(bucket, self._clock() - t0)

    def add(self, bucket: str, seconds: float) -> None:
        self._totals.setdefault(bucket, 0.0)
        self._totals[bucket] += max(float(seconds), 0.0)

    def open_wall(self) -> None:
        """The wall starts now, less what the buckets already hold: a tracker
        built ahead of the loop (the recipe builds it first thing in set-up)
        bills the resume's ``restore`` span like any other and still counts as
        wall only what a bucket could have been billed for. Fractions keep
        summing to 1."""
        self._start = self._clock() - sum(self._totals.values())

    @property
    def wall_s(self) -> float:
        return max(self._clock() - self._start, 1e-9)

    def totals(self) -> dict[str, float]:
        """Per-bucket seconds including the idle remainder; sums to wall_s."""
        accounted = sum(self._totals.values())
        return {**self._totals, "idle": max(self.wall_s - accounted, 0.0)}

    def snapshot(self) -> dict[str, float]:
        """Cumulative bucket fractions + the goodput scalar, ready for a log row.

        Fractions are of total wall time and sum to 1 (idle absorbs the
        remainder); ``goodput`` is the device_step fraction.
        """
        wall = self.wall_s
        totals = self.totals()
        out = {f"goodput/{b}": round(v / wall, 4) for b, v in totals.items()}
        out["goodput"] = round(totals["device_step"] / wall, 4)
        # bare key on purpose: `goodput/` values are fractions summing to 1,
        # and the run ledger needs the absolute wall to de-normalize them
        out["goodput_wall_s"] = round(wall, 3)
        return out
