"""Published peaks, keyed by ``jax.devices()[0].device_kind``.

One table, no environment override, no default: a device that is not
here is an error. ``bf16_flops`` is the figure every ``*_peak_share``
metric divides by, whatever precision tier the program runs: a 6-pass
f32 tier cannot pass 1/6 of it, a 3-pass tier 1/3, and neither can
read above 100 %.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,       # FLOP/s, one chip
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": 'Google Cloud documentation, "TPU v5e": 197 TFLOP/s '
                  "bf16, 16 GB HBM at 819 GB/s per chip",
    },
}


def peaks_for(device_kind: str) -> dict:
    """The row of ``device_kind``; raises for a kind not in the table."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
