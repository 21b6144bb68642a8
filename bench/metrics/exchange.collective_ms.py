"""exchange.collective_ms: device time of the cross-chip collectives per
traced round, per chip: the union of the collective ops' intervals in the
profiler's device trace (``tracing.reduce``'s ``collective_s``, averaged
over the cell's chips) over the rounds the traced part completed.  On the
LLM DS-FL round that is the exchange: the all-reduce of the clients'
per-token predictions into the ERA mean.  Moves ``round_s``."""


def read(rec):
    secs = rec.trace.get("collective_s")
    rounds = rec.counters.get("traced_rounds")
    if not secs or not rounds:
        return None
    return 1e3 * secs / rounds
