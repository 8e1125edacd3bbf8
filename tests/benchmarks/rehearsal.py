"""Run ``benchmarks/run.py --rehearse`` in this process and read what it printed."""

import importlib
import json
import os
import sys

from benchmarks.harness import spec

sys.path.insert(0, os.path.join(spec.ROOT, "benchmarks"))
run_py = importlib.import_module("run")


def rehearse(capsys, *argv):
    """``(result object, printed lines, names of the checks that FAILED)``; what the run
    wrote to standard error is kept in ``rehearse.stderr``."""
    assert run_py.main(["--rehearse", "--seconds", "0.3", *argv]) == 0
    captured = capsys.readouterr()
    rehearse.stderr = captured.err
    lines = captured.out.strip().splitlines()
    failed = {line.split()[1].rstrip(":") for line in lines
              if line.startswith("check ") and "FAILED" in line}
    return json.loads(lines[-1]), lines, failed
