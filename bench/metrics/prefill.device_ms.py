"""prefill.device_ms: device time per prefill shot, from the trace's
``XLA Modules`` events of the prefill-insert programs (``*prefill*``).
Moves ``ttft_p90_ms``."""


def read(rec):
    mods = rec.trace.get("modules", {})
    n = sum(k for name, (k, _d) in mods.items() if "prefill" in name)
    secs = sum(d for name, (_k, d) in mods.items() if "prefill" in name)
    return 1e3 * secs / n if n else None
