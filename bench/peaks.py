"""Published peaks of each accelerator the benchmark may run on, keyed by
the ``device_kind`` string JAX reports.  A device missing here is an error,
never a default: a roofline or MFU against a guessed peak means nothing.

Source for the v5e row: Google Cloud documentation, "TPU v5e" (system
architecture page): 197 TFLOP/s bf16, 16 GB of HBM2 at 819 GB/s.
"""
from __future__ import annotations

_V5E = {
    "bf16_flops": 197e12,          # FLOP/s, dense bf16 matmul
    "hbm_bytes": 16e9,             # bytes of HBM per chip
    "hbm_bytes_s": 819e9,          # bytes/s HBM bandwidth
    "source": "Google Cloud documentation, TPU v5e",
}

PEAKS = {
    "TPU v5 lite": _V5E,           # what jax 0.9 reports on a v5e
    "TPU v5e": _V5E,
}


def peaks_for(device_kind: str) -> dict:
    """The peak row of ``device_kind``; raises KeyError for a device the
    table does not list."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
