"""The one harness every cell runs through (see ``run.py``).

It reads ``BENCHMARK.json``, finds the cell's parts by name, checks the
device before any set-up, hands the cell to its traffic driver, and prints
the result: the checks that decide ``correct`` as the last lines of
standard error, and one JSON object as the last line of standard output.
"""
from __future__ import annotations

import hashlib
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ------------------------------------------------------------- finding ----
def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json; have "
                   f"{[e['name'] for e in entries]}")


def load_json(rel: str, root: str = ROOT) -> dict:
    with open(os.path.join(root, rel)) as f:
        return json.load(f)


def load_module(path: str):
    """Import a file by path (part names may hold dots and dashes)."""
    name = "bench_part_" + hashlib.sha256(
        os.path.abspath(path).encode()).hexdigest()[:16]
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def part(kind: str, name: str, bench: str = BENCH):
    """``bench/<kind>/<name>.py``: a driver, a family or a metric reader."""
    path = os.path.join(bench, kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind[:-1]} {name!r}: {path} is missing")
    return load_module(path)


def cell_metrics(manifest: dict, cell: str, trace: bool) -> list:
    """The metric entries a run of ``cell`` reports: its end-to-end
    metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``."""
    e2e = [m for m in manifest["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}

    def reports(m) -> bool:
        if "workloads" in m:
            return cell in m["workloads"]
        return m["moves"] in names

    return [m for m in manifest["per_layer"] if reports(m)]


def seed31(seed: int) -> int:
    """A non-negative 31-bit seed derived from any integer ``--seed``."""
    h = hashlib.sha256(str(int(seed)).encode()).digest()
    return int.from_bytes(h[:4], "little") & 0x7FFFFFFF


# -------------------------------------------------------------- device ----
def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, every program cached, so only a cell's first run compiles."""
    import jax
    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # no eviction: a size limit set around the process (an environment
    # variable) would evict this cell's own programs between its runs
    jax.config.update("jax_compilation_cache_max_size", -1)
    return CACHE_DIR


def devices_for(chips: int, platform: str = "tpu"):
    import jax
    devs = jax.devices()
    if devs[0].platform != platform:
        raise NoDevice(f"needs a {platform}; JAX found platform "
                       f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips; JAX found "
                       f"{len(devs)} {devs[0].device_kind}")
    return devs[:chips]


def memory_peak(devices) -> int | None:
    peaks = []
    for d in devices:
        try:
            st = d.memory_stats() or {}
        except Exception:   # noqa: BLE001  (a backend without stats)
            st = {}
        if "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts XLA backend compiles (a persistent-cache hit is not one) and
    jaxpr traces; the window should see neither."""

    def __init__(self):
        import jax
        self.compiles = self.traces = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += seconds
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1

    def snapshot(self) -> tuple:
        return (self.compiles, self.traces)


# ---------------------------------------------------------------- runs ----
@dataclass
class Cell:
    """What a driver gets: the cell's data, its family, the device and the
    harness's clocks and counters."""
    name: str
    seed: int
    seconds: float
    trace: bool
    chips: int
    cfg: dict
    traffic: dict
    family: object
    devices: list
    t_process: float
    counter: CompileCounter
    control: bool = False       # also read the control (tools/readings.py)

    @property
    def seed31(self) -> int:
        return seed31(self.seed)

    def since_start(self) -> float:
        return time.perf_counter() - self.t_process

    def profiler(self) -> "Profiler":
        return Profiler(self.trace, float(self.traffic["trace_seconds"]))


class Profiler:
    """The traced part of a window: the first ``seconds`` of it, in a
    ``bench.traced`` host span.  A driver calls `start` when its window
    opens, `poll` between calls (it stops the trace once ``seconds`` have
    passed) and `stop` when the window closes; ``result`` then holds the
    trace's reduction (``tracing.reduce``) and ``stopped_at`` the host
    time the traced part ended.  Does nothing in an untraced run."""

    def __init__(self, on: bool, seconds: float):
        self.on, self.seconds = on, seconds
        self.result, self.stopped_at = None, None
        self._tmp = self._span = None

    @property
    def running(self) -> bool:
        return self._span is not None

    def start(self) -> None:
        if not self.on:
            return
        import jax
        self._tmp = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(self._tmp)
        self._span = jax.profiler.TraceAnnotation("bench.traced")
        self._span.__enter__()
        self._t0 = time.perf_counter()

    def poll(self) -> bool:
        """Stop once the traced part is long enough; True if it stopped
        now."""
        if self.running and time.perf_counter() - self._t0 >= self.seconds:
            self.stop()
            return True
        return False

    def stop(self) -> None:
        if not self.running:
            return
        import jax
        import tracing
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
            tr = tracing.load(path)
            self.result = tracing.reduce(tr, *tracing.window_of(
                tr, "bench.traced"))
        finally:
            shutil.rmtree(self._tmp, ignore_errors=True)


@dataclass
class Record:
    """What a per-layer metric reader gets."""
    cell: Cell
    trace: dict           # tracing.reduce() of the window
    counters: dict        # the driver's counts
    peaks: dict


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def _finite(x) -> bool:
    return isinstance(x, (int, float)) and math.isfinite(x)


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_process: float, root: str = ROOT, platform: str = "tpu",
             control: bool = False) -> int:
    manifest = load_manifest(root)
    cell = find(manifest["workloads"], workload, "workload")
    conf = find(manifest["configs"], cell["config"], "config")
    cfg = load_json(conf["file"], root)
    traffic = load_json(os.path.join("bench", "traffic",
                                     cell["traffic"] + ".json"), root)
    enable_compile_cache()
    try:
        devices = devices_for(int(cell["chips"]), platform)
    except NoDevice as e:
        say(f"bench: {e}")
        return 3
    import peaks as peak_table
    kind = devices[0].device_kind
    peaks = peak_table.peaks_for(kind)
    src = os.path.join(root, "src")       # the system under test
    if src not in sys.path:
        sys.path.insert(0, src)
    bench = os.path.join(root, "bench")
    ctx = Cell(workload, seed, seconds, trace, int(cell["chips"]), cfg,
               traffic, part("families", cfg["family"], bench), devices,
               t_process, CompileCounter(), control)
    out = part("drivers", traffic["driver"], bench).run(ctx)
    checks = out["checks"]
    # a request or round that never completes is as wrong as a wrong one
    correct = (bool(checks) and out["failed"] == 0
               and all(_finite(v) and v <= lim for _, v, lim in checks))
    metrics = {}
    rec = Record(ctx, out.get("trace") or {}, out.get("counters", {}), peaks)
    for m in cell_metrics(manifest, workload, trace):
        if trace:
            v = part("metrics", m["name"], bench).read(rec)
        else:
            v = out["e2e"].get(m["name"])
        if v is not None and _finite(v):
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": out.get("memory_peak_bytes")}
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if trace and rec.trace:
        device["busy_s"] = rec.trace["busy_s"]
        device["window_s"] = rec.trace["window_s"]
        result["breakdown"] = {"device_ops": rec.trace["device_ops"],
                               "idle_gaps": rec.trace["idle_gaps"]}
    result["checks"] = {name: {"value": v if _finite(v) else None,
                               "limit": lim}
                        for name, v, lim in checks}
    for note, v in out.get("counters", {}).items():
        say(f"bench: {note} = {v}")
    for name, v, lim in checks:
        say(f"check {name}: {v!r} (limit {lim!r}) "
            f"{'ok' if _finite(v) and v <= lim else 'FAILED'}")
    print(json.dumps(result), flush=True)
    return 0
