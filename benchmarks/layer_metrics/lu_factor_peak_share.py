"""The LU program's share of its roofline: closed-form ``getrf(n)``
flops (``harness/flops.py``) over the PUBLISHED bf16 peak of one chip,
over ``lu_factor_s``. Compute bounds it (2n^3/3 flops against 2 passes
over 4n^2 bytes: 3.4 ms against 1 ms at n=10000). At the f32 tier
``bf16_6x`` a product takes six MXU passes, so the share cannot pass
16.7 %; it is never divided by peak/6."""

from __future__ import annotations

from benchmarks.harness import flops
from benchmarks.harness.peaks import peaks_for
from benchmarks.layer_metrics import lu_factor_s

HEADER = {"name": "lu_factor_peak_share", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "kernels",
          "moves": "solve_s"}


def compute(run: dict):
    if run["device"]["platform"] != "tpu":
        return None         # a rehearsal's backend has no published peak
    seconds = lu_factor_s.compute(run)
    if not seconds:
        return None
    peak = peaks_for(run["device"]["kind"])["bf16_flops"]
    return 100.0 * flops.getrf(run["spec"]["config"]["n"]) / peak / seconds
