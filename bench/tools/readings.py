"""Readings of a cell's compared numbers over many seeds in one process:
the program's (sound, or with a planted fault) and, with ``--control``,
the control's.  Each run is a whole run of the cell (``harness.run_cell``)
and prints its result line.

    python3 bench/tools/readings.py --workload <name> --seconds 2 \
        --seeds 11 12 13 [--control] [--fault half_batch]

The benchmark's own runs never run this: its readings set the limits
(PERF.md).  Needs the cell's chips, like ``run.py``.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import faults  # noqa: E402
import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", default="none", choices=sorted(faults.FAULTS))
    ap.add_argument("--platform", default="tpu")
    args = ap.parse_args(argv)
    for seed in args.seeds:
        print(f"== {args.workload} seed {seed} fault {args.fault} control "
              f"{args.control}", file=sys.stderr, flush=True)
        with faults.FAULTS[args.fault]():
            rc = harness.run_cell(args.workload, seed, args.seconds, False,
                                  time.perf_counter(), platform=args.platform,
                                  control=args.control)
        if rc:
            return rc
    return 0


if __name__ == "__main__":
    sys.exit(main())
