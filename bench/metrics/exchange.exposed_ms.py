"""exchange.exposed_ms: the part of ``exchange.collective_ms`` during which
no other op ran on the chip (``tracing.reduce``'s
``exposed_collective_s``, averaged over the cell's chips), per traced
round: the exchange time that nothing hides.  Moves ``round_s``."""


def read(rec):
    secs = rec.trace.get("collective_s")
    rounds = rec.counters.get("traced_rounds")
    if not secs or not rounds:
        return None
    return 1e3 * rec.trace["exposed_collective_s"] / rounds
