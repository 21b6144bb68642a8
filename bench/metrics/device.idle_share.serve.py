"""device.idle_share.serve: share of the traced window (arrivals and the
drain of the requests due in it) in which no operation ran on the chip,
from the profiler's device trace.  Moves ``tpot_p90_ms``."""


def read(rec):
    share = rec.trace.get("idle_share")
    return None if share is None else 100.0 * share
