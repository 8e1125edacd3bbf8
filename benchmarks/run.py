#!/usr/bin/env python3
"""One cell of the benchmark, once.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Needs a TPU with the cell's chips (no CPU stand-in: ``--rehearse`` is the tests' toy-size
walk through the same code and prints no device metric). The last line of standard
output is the result object of BENCHMARK.json's contract. See benchmarks/README.md.
"""

import time

_T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the measured window (default: BENCHMARK.json run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tests only: CPU, the configuration's tiny block, no device metric")
    ap.add_argument("--control", action="store_true",
                    help="the cell's control: the program's lower-precision path switched on "
                         "(must come out as not correct)")
    ap.add_argument("--out", default="bench_out",
                    help="directory (inside the checkout) for the run's files")
    args = ap.parse_args(argv)
    from benchmarks.harness.run_cell import run_cell

    return run_cell(args, _T_START)


if __name__ == "__main__":
    sys.exit(main())
