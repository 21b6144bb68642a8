"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name from ``BENCHMARK.json``: the
configuration file (its ``family`` names the module in ``bench/families``
that builds the system under test and its plain reference), the traffic
file ``bench/traffic/<traffic>.json`` (its ``driver`` names the general
generator in ``bench/drivers``), and one reader per per-layer metric in
``bench/metrics/<metric>.py``.  Needs a TPU: on any other platform, or
with fewer chips than the cell asks for, it exits nonzero before set-up
and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()   # set-up is timed from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    return harness.run_cell(args.workload, args.seed, args.seconds,
                            bool(args.trace), t_process=T_PROCESS)


if __name__ == "__main__":
    sys.exit(main())
