#!/usr/bin/env python
"""Perf-regression gate CLI (docs/observability.md "Perf-regression gate").

Compares a run artifact — a recipe ``training.jsonl``, a ``benchmark.json``
or a ``run_ledger.json`` — against a baseline file with per-metric tolerances, and exits non-zero on regression::

    python tools/bench_gate.py --run out/training.jsonl --baseline baselines/v5e.json
    python tools/bench_gate.py --run out/training.jsonl --baseline b.json --write-baseline

Thin wrapper over :mod:`automodel_tpu.observability.regression` so the gate is
importable in tests and callable from CI without a package install.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from automodel_tpu.observability.regression import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
