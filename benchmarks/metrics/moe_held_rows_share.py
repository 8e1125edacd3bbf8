"""MoE: of the (token, expert) pairs the routers chose in the window, the share whose
expert is held here, in percent: the program's counter ``moe_load/held_rows_share``
(``automodel_tpu/moe/metrics.py``), logged at every step, averaged over the window's
steps. 100 x held / all if routing is even; it sizes the expert GEMMs' work."""

import json
import os
import statistics

_KEY = "moe_load/held_rows_share"


def read(run: dict):
    path = os.path.join(run["run"].recipe.output_dir, "training.jsonl")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    values = [r[_KEY] for r in rows if _KEY in r and "loss" in r]
    if not values:
        return None  # a program that does not count the held rows
    return 100.0 * statistics.fmean(values[-run["steps"]:])
