"""The band reduction's share of the chip's peak: closed-form
``ge2tb(m, n)`` = 4mn^2 - 4n^3/3 (``harness/flops_svd.py``) over the
PUBLISHED bf16 peak of one chip, over ``svd_band_reduce_s``. At the
six-pass tier ``bf16_6x`` it cannot pass 16.7 %; it is never divided by
peak/6."""

from __future__ import annotations

from benchmarks.harness import flops_svd
from benchmarks.layer_metrics import svd_band_reduce_s
from benchmarks.layer_metrics.eig_band_reduce_peak_share import share

HEADER = {"name": "svd_band_reduce_peak_share", "unit": "%",
          "better": "higher", "source": "device_trace", "layer": "svd",
          "moves": "solve_s"}


def shape_of(run: dict) -> tuple:
    """(m, n) of the cell's operand: n and m / n are the configuration's
    (a rehearsal shrinks n and keeps the aspect)."""
    config = run["spec"]["config"]
    return int(round(config["m_over_n"] * config["n"])), config["n"]


def compute(run: dict):
    return share(run, flops_svd.ge2tb(*shape_of(run)),
                 svd_band_reduce_s.compute(run))
