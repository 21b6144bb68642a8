"""era_sharpen_roofline: the Pallas ERA kernel's share of its roofline.
The least time of one call is the larger of its HBM bytes over the peak
bandwidth and its operations over the bf16 peak (bytes bound it: it reads
the (K, |o_r|, C) f32 uploads once); the measured time is the kernel's
device time per call in the trace (its ``tpu_custom_call`` events; the
round holds no other Pallas kernel).  Moves ``round_s``."""


def read(rec):
    secs = sum(rec.trace.get("kernel_s", {}).values())
    calls = sum(rec.trace.get("kernel_calls", {}).values())
    if not secs or not calls:
        return None
    fam, cfg, pk = rec.cell.family, rec.cell.cfg, rec.peaks
    least = max(fam.era_kernel_bytes(cfg) / pk["hbm_bytes_s"],
                fam.era_kernel_flops(cfg) / pk["bf16_flops"])
    return 100.0 * least / (secs / calls)
