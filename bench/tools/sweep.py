"""Find the knee of a served cell: serve its traffic at several fixed
rates, one window each, in one process (one set-up), and print one JSON
line per rate.  The knee is the highest rate whose window ends with no
backlog growing (every request due in it finished within the drain) and
whose tails stay flat; the cell's traffic file then fixes about four
fifths of it.

    python3 bench/tools/sweep.py --workload <name> --seconds 30 \
        --rates 1.0 1.5 2.0 2.5
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--platform", default="tpu")
    args = ap.parse_args(argv)
    m = harness.load_manifest()
    cell = harness.find(m["workloads"], args.workload, "workload")
    conf = harness.find(m["configs"], cell["config"], "config")
    cfg = harness.load_json(conf["file"])
    tr = harness.load_json(f"bench/traffic/{cell['traffic']}.json")
    harness.enable_compile_cache()
    try:
        devices = harness.devices_for(int(cell["chips"]), args.platform)
    except harness.NoDevice as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 3
    fam = harness.part("families", cfg["family"])
    drv = harness.part("drivers", tr["driver"])
    seed = harness.seed31(args.seed)
    t0 = time.perf_counter()
    obj = fam.setup(cfg, tr, seed, devices)
    print(json.dumps({"setup_s": time.perf_counter() - t0}), flush=True)
    for rate in args.rates:
        obj.engine.reset()
        reqs = drv.schedule(dict(tr, rate_per_s=rate), args.seconds, seed,
                            cfg["vocab_size"])
        w = drv.serve_window(obj.engine, reqs, args.seconds,
                             cfg["decode_chunk"], tr["prefill_group"],
                             float(tr["drain_s"]), time.perf_counter())
        res = drv.summary(w, args.seconds)
        print(json.dumps({"rate_per_s": rate, **res["e2e"],
                          "attempted": res["attempted"],
                          "failed": res["failed"], **res["counters"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
