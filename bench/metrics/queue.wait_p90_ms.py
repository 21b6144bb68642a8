"""queue.wait_p90_ms: 90th percentile over the window's admitted requests
of the time from when each was due to when its prefill shot started (time
in the admission queue, on the host clock).  Moves ``ttft_p90_ms``.
In a traced run, over the requests admitted before the profiler stopped."""


def read(rec):
    return rec.counters.get("queue_wait_p90_ms")
