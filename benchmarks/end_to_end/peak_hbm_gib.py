"""Largest ``peak_bytes_in_use`` over the cell"s devices, read when the
window has closed and before the check gathers anything: what decides
the largest n that fits."""

from __future__ import annotations

HEADER = {"name": "peak_hbm_gib", "unit": "GiB", "better": "lower",
          "source": "device_trace"}


def compute(run: dict):
    return max(run["peak_bytes"]) / 2 ** 30 if max(run["peak_bytes"]) else None
