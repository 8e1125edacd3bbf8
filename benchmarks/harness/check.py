"""The comparison that decides ``correct``: the program's first steps against the
reference's, number by number, each beside its limit."""

from __future__ import annotations

import math
import statistics

import numpy as np


def norm_gap(program_sq: dict, reference_sq: dict, only=None) -> tuple[float, str]:
    """Worst leaf: |program's norm - reference's norm| over the reference's norm of
    that leaf or of the median leaf, whichever is larger (some gradients are all but
    zero; a buffer whose gradient is zero on both sides reads 0). Leaves are (name, place
    in its layer group) pairs; ``only(name)`` picks the leaves judged (the median is still
    over all). Returns the gap and the three worst leaves."""
    ref = {(k, i): math.sqrt(max(v, 0.0)) for k, arr in reference_sq.items()
           for i, v in enumerate(np.atleast_1d(arr))}
    prog = {(k, i): math.sqrt(max(v, 0.0)) for k, arr in program_sq.items()
            for i, v in enumerate(np.atleast_1d(arr))}
    if set(ref) != set(prog):
        raise ValueError(f"leaves differ: {sorted(set(ref) ^ set(prog))}")
    median = float(np.median(list(ref.values())))
    gaps = []
    for key, r in ref.items():
        if only is not None and not only(key[0]):
            continue
        gap = abs(prog[key] - r) / max(r, median, 1e-30)
        gaps.append((gap if gap == gap else math.inf, f"{key[0]}[{key[1]}]"))  # NaN is worst
    gaps.sort(reverse=True)
    return gaps[0][0], " ".join(f"{where} {gap:.2e}" for gap, where in gaps[:3])


def settled_loss(window_losses: list[float]) -> float:
    """The loss the window settled at: its median. Less than half a window of spikes,
    wherever they fall, does not move it; a loss that never fell leaves it at the first."""
    return statistics.median(window_losses)


class Verdict:
    """Collects (name, value, limit, ok) and prints each as it is added."""

    def __init__(self):
        self.rows: list[tuple[str, float, float, bool]] = []

    def at_most(self, name: str, value: float, limit: float, note: str = "") -> None:
        ok = bool(value <= limit)  # NaN fails
        self.rows.append((name, float(value), float(limit), ok))
        print(f"check {name}: {value:.6g} (limit <= {limit:.6g}) {'ok' if ok else 'FAILED'} {note}",
              flush=True)

    def at_least(self, name: str, value: float, limit: float, note: str = "") -> None:
        ok = bool(value >= limit)
        self.rows.append((name, float(value), float(limit), ok))
        print(f"check {name}: {value:.6g} (limit >= {limit:.6g}) {'ok' if ok else 'FAILED'} {note}",
              flush=True)

    @property
    def correct(self) -> bool:
        return all(ok for *_, ok in self.rows)

    def as_dict(self) -> dict:
        """For the result line: ``{name: {"value", "limit", "ok"}}`` in the order compared."""
        finite = lambda x: x if math.isfinite(x) else repr(x)  # noqa: E731  (JSON has no NaN)
        return {name: {"value": finite(value), "limit": limit, "ok": ok}
                for name, value, limit, ok in self.rows}

    def lines(self) -> str:
        """For standard error: one short line a number, the failed ones marked."""
        return "\n".join(f"{name} {value:.6g} limit {limit:.6g} {'ok' if ok else 'FAILED'}"
                         for name, value, limit, ok in self.rows)
