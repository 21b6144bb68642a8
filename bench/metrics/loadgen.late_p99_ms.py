"""loadgen.late_p99_ms: 99th percentile of how late the open-loop
generator submitted each request after its due time (host clock); a
starved generator shows here rather than as a fast server.  Moves
``ttft_p90_ms``.
In a traced run, over the requests submitted before the profiler stopped."""


def read(rec):
    return rec.counters.get("late_p99_ms")
