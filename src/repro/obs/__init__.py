"""`repro.obs` — one telemetry plane for train, sim, and serve.

Four pieces, all zero-overhead when disabled and all host-side only (no
instrumentation ever runs inside a jitted program, so bitwise parity and
the one-host-sync-per-chunk discipline are untouched — pinned by
``tests/test_obs.py``):

* `Tracer` (`trace.py`) — structured JSONL span/event records with
  monotonic host timestamps, pid/tid, and nesting via context managers;
  ``annotate(True)`` also opens each span as a `jax.profiler`
  annotation, so the spans land on the device trace's clock.
  `perfetto.py` exports a trace to Chrome/Perfetto ``trace_event`` JSON so
  a whole run — engine chunks, cohort slab gather/scatter, wire
  measurement, serve prefill/decode, weight hot-swaps, XLA compiles —
  renders on one timeline (``ui.perfetto.dev``).
* `MetricsRegistry` (`metrics.py`) — counters, gauges, and fixed-bucket
  histograms with percentile estimates, snapshottable to JSON.  The
  engine, sim runners, schedulers, client store, serve engine, and
  admission queue all publish into the installed registry.
* `JitCacheWatch` (`jit_watch.py`) — compile/retrace accounting: every
  XLA backend compile is recorded (and traced), tracked jitted callables
  report per-function cache sizes, and ``assert_no_new_compiles`` turns
  "no recompiles after warmup" into a checkable invariant.
* `RunProvenance` (`provenance.py`) — git sha, jax/jaxlib versions,
  platform, x64, kernel interpret mode — stamped into every trace header,
  metrics snapshot, and ``BENCH_*.json`` so numbers are interpretable
  across machines.

The module-level `tracing`/`metrics` globals are the thread-through
points: library code calls ``obs.span(...)`` / ``obs.current_registry()``
unconditionally; with nothing installed these cost one global read and
allocate nothing.
"""
from .jit_watch import JitCacheWatch, engine_compile_counts  # noqa: F401
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,  # noqa
                      percentile, percentiles)
from .provenance import RunProvenance  # noqa: F401
from .trace import (Tracer, annotate, current_registry, enabled,  # noqa
                    event, install, install_registry, instant, span, start,
                    stop, trace_to)

__all__ = [
    "Counter", "Gauge", "Histogram", "JitCacheWatch", "MetricsRegistry",
    "RunProvenance", "Tracer", "annotate", "current_registry", "enabled",
    "engine_compile_counts", "event", "install", "install_registry",
    "instant", "percentile", "percentiles", "span", "start", "stop",
    "trace_to",
]
