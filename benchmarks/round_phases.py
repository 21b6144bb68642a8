"""Split a round cell's device time by DS-FL step over a short trace.

    python benchmarks/round_phases.py --seed N [--trace-seconds 4]
        [--workload cnn-k100.era-round] [--seconds S] [--out FILE]

Runs `bench/tools/phase_split.py` (annotation on) with the traffic file's
``trace_seconds`` replaced by ``--trace-seconds``.  The profiler keeps a
bounded number of device events: with the CNN client loops run one client
at a time (`client.over_clients`), a K=100 round issues ~7,000 sequential
client-steps, and on a TPU v5e the trace holds only the first ~5.7 s of
busy device time.  A longer trace then reads one long idle gap after the
lost ops and divides the ops it kept by every traced round, so all its
per-round readings come out low.  4 s (five rounds of
`cnn-k100.era-round`) stays inside the buffer: check that ``busy_s`` over
``window_s`` is near 1 in the printed line.  Needs a TPU, as
``bench/run.py`` does; run it from the root of the checkout.
"""
from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "bench", "tools"))

import phase_split  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--trace-seconds", type=float, default=4.0)
    args, rest = ap.parse_known_args(argv)
    harness = phase_split.harness
    load_json = harness.load_json

    def short_trace(rel: str, root: str = harness.ROOT) -> dict:
        d = load_json(rel, root)
        if os.path.dirname(rel) == os.path.join("bench", "traffic"):
            d = dict(d, trace_seconds=args.trace_seconds)
        return d

    harness.load_json = short_trace
    return phase_split.main(rest + ["--annotate", "1"])


if __name__ == "__main__":
    sys.exit(main())
