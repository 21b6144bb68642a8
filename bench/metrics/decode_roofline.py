"""decode_roofline: the fused decode's share of its roofline.  The least
time of one sub-step is the larger of its required HBM bytes (every weight
once, and the K/V of the positions the active lanes attend over) over the
peak bandwidth, and its required FLOPs over the bf16 peak; the measured
time is the device time of the decode-chunk programs (``XLA Modules``
events named ``*chunk*``) over their sub-steps.  Moves ``tpot_p90_ms``."""


def read(rec):
    c = rec.counters
    mods = rec.trace.get("modules", {})
    secs = sum(d for name, (_n, d) in mods.items() if "chunk" in name)
    steps = c.get("traced_decode_sub_steps")
    if not secs or not steps:
        return None
    fam, cfg, pk = rec.cell.family, rec.cell.cfg, rec.peaks
    lanes = c["traced_decode_lane_steps"] / steps
    ctx = c["traced_decode_ctx_steps"] / steps
    least = max(fam.decode_step_bytes(cfg, ctx) / pk["hbm_bytes_s"],
                fam.decode_step_flops(cfg, lanes, ctx) / pk["bf16_flops"])
    return 100.0 * least / (secs / steps)
