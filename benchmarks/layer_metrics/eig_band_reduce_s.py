"""Device-0 busy seconds of one traced ``slate.heev`` inside the band
reduction's XLA module (``jit__he2hb_jit``: one ``shard_map`` loop over
the block columns, panel QR + the two-sided trailing update): stage 1
of the two-stage eigensolver, the only stage whose products take the
configuration's tier."""

from __future__ import annotations

from benchmarks.harness import busy_inside

HEADER = {"name": "eig_band_reduce_s", "unit": "s", "better": "lower",
          "source": "device_trace", "layer": "eigen",
          "moves": "solve_s"}
MODULES = ("jit__he2hb_jit",)


def compute(run: dict):
    trace = run["trace"]
    if trace is None:
        return None
    return busy_inside.per_solve(trace, MODULES)
