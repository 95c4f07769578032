"""Device-0 busy seconds of one traced ``slate.gesvd`` inside the band
reduction's XLA module (``jit__ge2tb_jit``: one ``shard_map`` loop that
alternates a QR panel with its left update and an LQ panel with its
right update): stage 1 of the two-stage SVD, the only stage whose
products take the configuration's tier."""

from __future__ import annotations

from benchmarks.harness import busy_inside

HEADER = {"name": "svd_band_reduce_s", "unit": "s", "better": "lower",
          "source": "device_trace", "layer": "svd", "moves": "solve_s"}
MODULES = ("jit__ge2tb_jit",)


def compute(run: dict):
    trace = run["trace"]
    if trace is None:
        return None
    return busy_inside.per_solve(trace, MODULES)
