"""mfu.decode: a decode sub-step's required FLOPs over its wall time and
the chip's bf16 peak.  The FLOPs are counted from shapes for the lanes
that were active and the positions they attended over, averaged over the
traced sub-steps; the time is the host's wall time of the decode calls
over their sub-steps.  Moves ``tpot_p90_ms``."""


def read(rec):
    c = rec.counters
    steps = c.get("traced_decode_sub_steps")
    if not steps or not c.get("traced_decode_host_s"):
        return None
    fam, cfg = rec.cell.family, rec.cell.cfg
    lanes = c["traced_decode_lane_steps"] / steps
    ctx = c["traced_decode_ctx_steps"] / steps
    flops = fam.decode_step_flops(cfg, lanes, ctx)
    return 100.0 * flops / ((c["traced_decode_host_s"] / steps)
                            * rec.cell.chips * rec.peaks["bf16_flops"])
