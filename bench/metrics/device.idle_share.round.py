"""device.idle_share.round: share of the traced window in which no
operation ran on the chip (averaged over the cell's chips), from the
profiler's device trace.  Moves ``round_s``."""


def read(rec):
    share = rec.trace.get("idle_share")
    return None if share is None else 100.0 * share
