"""Split a traced run of a round cell into the DS-FL round's steps.

    python bench/tools/phase_split.py --workload cnn-k100.era-round \
        --seed N [--seconds S] [--annotate 0|1] [--out FILE]

Runs the cell as ``bench/run.py --trace 1`` does (same harness, family,
driver and traced part), and with ``--annotate 1`` switches
`repro.obs.annotate` on for the traced part only, so the program's spans
land on the trace.  Before the trace is deleted it is read again by
`phases`: device time by named scope and device-idle time by program span.
Prints one JSON line: the traced part's wall time per round (the
window's ``round_s`` would include reading the trace), the usual
reduction's busy time, idle share, per-op total and longest idle gaps,
and per traced round in milliseconds

- ``phase_ms``: ``scope_s`` of each of `phases.PHASES` and ``unscoped``;
- ``idle_by_span_ms``: idle time by innermost program span;
- ``engine_host_idle_ms``: idle time inside any ``engine.*`` span.

``--out`` also writes the line to FILE.  Needs a TPU, as ``run.py`` does.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness  # noqa: E402


class PhaseProfiler(harness.Profiler):
    """`harness.Profiler`, with annotation on while it traces and the
    trace also reduced by `phases` before it is deleted."""

    def __init__(self, on: bool, seconds: float, annotate: bool):
        super().__init__(on, seconds)
        self.annotate, self.phases = annotate, None

    def start(self) -> None:
        super().start()
        if self.on and self.annotate:
            from repro import obs
            obs.annotate(True)

    def stop(self) -> None:
        if not self.running:
            return
        import jax

        import phases
        import tracing
        from repro import obs
        obs.annotate(False)
        self._span.__exit__(None, None, None)
        self._span = None
        self.stopped_at = time.perf_counter()
        try:
            jax.profiler.stop_trace()
            path = None
            for dirpath, _dirs, files in os.walk(self._tmp):
                for f in files:
                    if f.endswith(".xplane.pb"):
                        path = os.path.join(dirpath, f)
            st = phases.load(path)
            window = tracing.window_of(st.trace, "bench.traced")
            self.result = tracing.reduce(st.trace, *window)
            self.phases = phases.reduce(st, *window)
        finally:
            shutil.rmtree(self._tmp, ignore_errors=True)


@dataclass
class PhaseCell(harness.Cell):
    annotate: bool = False

    def profiler(self) -> PhaseProfiler:
        self._prof = PhaseProfiler(self.trace,
                                   float(self.traffic["trace_seconds"]),
                                   self.annotate)
        return self._prof


def run(workload: str, seed: int, seconds: float, annotate: bool) -> dict:
    import phases
    manifest = harness.load_manifest()
    cell = harness.find(manifest["workloads"], workload, "workload")
    conf = harness.find(manifest["configs"], cell["config"], "config")
    cfg = harness.load_json(conf["file"])
    traffic = harness.load_json(os.path.join("bench", "traffic",
                                             cell["traffic"] + ".json"))
    harness.enable_compile_cache()
    devices = harness.devices_for(int(cell["chips"]))
    src = os.path.join(harness.ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    ctx = PhaseCell(workload, seed, seconds, True, int(cell["chips"]), cfg,
                    traffic, harness.part("families", cfg["family"]),
                    devices, T_PROCESS, harness.CompileCounter(),
                    annotate=annotate)
    out = harness.part("drivers", traffic["driver"]).run(ctx)
    c, red, ph = out["counters"], out["trace"], ctx._prof.phases
    n = c["traced_rounds"]
    ms = 1e3 / n
    idle = ph["idle_by_span"]
    phase_ms = {k: ph["scope_s"].get(k, 0.0) * ms
                for k in phases.PHASES + (phases.UNSCOPED,)}
    return {
        "workload": workload, "seed": seed, "annotate": annotate,
        "device": devices[0].device_kind,
        "setup_s": out["e2e"]["setup_s"],
        "traced_rounds": n,
        "traced_round_s": c["traced_wall_s"] / n,
        "busy_s": red["busy_s"], "window_s": red["window_s"],
        "idle_share": red["idle_share"],
        "per_op_ms": sum(red["per_op_s"].values()) * ms,
        "kernel_ms": sum(red["kernel_s"].values()) * ms,
        "idle_gaps": red["idle_gaps"],
        "phase_ms": phase_ms,
        "phase_sum_ms": sum(phase_ms.values()),
        "idle_by_span_ms": {k: v * ms for k, v in idle.items()},
        "engine_host_idle_ms": sum(v for k, v in idle.items()
                                   if k.startswith("engine.")) * ms,
        "checks": {name: v for name, v, _lim in out["checks"]},
        "compiles_in_window": c["compiles_in_window"],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="cnn-k100.era-round")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--annotate", type=int, choices=(0, 1), default=1)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    try:
        res = run(args.workload, args.seed, args.seconds,
                  bool(args.annotate))
    except harness.NoDevice as e:
        print(f"phase_split: {e}", file=sys.stderr)
        return 3
    line = json.dumps(res)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "a") as f:
            f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
