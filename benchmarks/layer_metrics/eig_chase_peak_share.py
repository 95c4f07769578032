"""The bulge chase's share of the chip's peak: closed-form
``hb2st(n, band)`` = 6 n^2 band (``harness/flops_eig.py``) over the
PUBLISHED bf16 peak, over ``eig_chase_s``. Small by nature: the chase
is O(n^2 b) work on a serial dependency chain of n sweeps, none of it
on the MXU; the count is what stays fixed when the chaser changes.
``band`` is the one the program chased at (the root span's label)."""

from __future__ import annotations

from benchmarks.harness import flops_eig, program_spans
from benchmarks.layer_metrics import eig_chase_s
from benchmarks.layer_metrics.eig_band_reduce_peak_share import share

HEADER = {"name": "eig_chase_peak_share", "unit": "%",
          "better": "higher", "source": "device_trace",
          "layer": "eigen", "moves": "solve_s"}


def band_of(run: dict):
    """The band the traced calls chased at, from the label the program
    puts on its root span; None without spans or without the label."""
    solves = program_spans.solves_of(run)
    if not solves:
        return None
    return solves[0].root["labels"].get("band")


def compute(run: dict):
    band = band_of(run)
    if band is None:
        return None
    return share(run, flops_eig.hb2st(run["spec"]["config"]["n"], band),
                 eig_chase_s.compute(run))
