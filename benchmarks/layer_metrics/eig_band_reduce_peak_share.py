"""The band reduction's share of the chip's peak: closed-form
``he2hb(n)`` = 4n^3/3 (``harness/flops_eig.py``) over the PUBLISHED
bf16 peak of one chip, over ``eig_band_reduce_s``. At the six-pass tier
``bf16_6x`` it cannot pass 16.7 %; it is never divided by peak/6."""

from __future__ import annotations

from benchmarks.harness import flops_eig
from benchmarks.harness.peaks import peaks_for
from benchmarks.layer_metrics import eig_band_reduce_s

HEADER = {"name": "eig_band_reduce_peak_share", "unit": "%",
          "better": "higher", "source": "device_trace",
          "layer": "eigen", "moves": "solve_s"}


def share(run: dict, flops: float, seconds):
    """100 * flops / (chips x the published bf16 peak) / seconds; None
    without seconds or off the TPU (a rehearsal has no published peak)."""
    if not seconds or run["device"]["platform"] != "tpu":
        return None
    peak = peaks_for(run["device"]["kind"])["bf16_flops"]
    return 100.0 * flops / (len(run["trace"].devices) * peak) / seconds


def compute(run: dict):
    return share(run, flops_eig.he2hb(run["spec"]["config"]["n"]),
                 eig_band_reduce_s.compute(run))
