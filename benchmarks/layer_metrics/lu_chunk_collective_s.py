"""The part of ``lu_chunk_s`` spent in collectives: the union of the
collective ops' intervals on device 0 INSIDE the LU's chunk programs,
per traced solve. A step's are the panel's (column k's local slots
over q as a masked ``psum``, then gathered over p to the whole
[M, nb] panel on every device), the row swaps' candidate rows and the
U block-row's broadcast over p. ``getrs``'s collectives are not in it
(``getrs_grid_s`` holds those programs whole)."""

from __future__ import annotations

from benchmarks.harness.program_spans import intersect
from benchmarks.harness.trace_reduce import is_collective, merge
from benchmarks.layer_metrics.lu_chunk_s import MODULES

HEADER = {"name": "lu_chunk_collective_s", "unit": "s", "better": "lower",
          "source": "device_trace", "layer": "interconnect",
          "moves": "solve_s"}


def compute(run: dict):
    trace = run["trace"]
    if trace is None:
        return None
    dev0 = trace.first
    inside = merge((s, e) for name, s, e in dev0.modules
                   if name.startswith(MODULES))
    if not inside:
        return None
    return trace.per_solve(intersect(dev0.where(is_collective), inside))
