"""mfu.round: the whole round's share of the chips' bf16 peak.  The FLOPs a
round requires are counted from shapes by the configuration's family (no
recompute), and the round time is the traced part's wall time over the
rounds it completed.  Moves ``round_s``."""


def read(rec):
    rounds = rec.counters.get("traced_rounds")
    wall = rec.counters.get("traced_wall_s")
    if not rounds or not wall:
        return None
    flops = rec.cell.family.round_flops(rec.cell.cfg)
    return 100.0 * flops * rounds / (wall * rec.cell.chips
                                      * rec.peaks["bf16_flops"])
