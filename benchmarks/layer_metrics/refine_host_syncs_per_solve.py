"""Blocking device-to-host reads the refinement loop makes inside one
call: the program's spans labelled ``sync=1`` under the root
``slate.<call>`` (``mixed.anorm``, ``mixed.rnorm``, ``mixed.xnorm``,
``mixed.beta``, one ``mixed.h`` an inner product, ``mixed.hn``), median
over the traced calls. Each is a round trip during which the device
has nothing queued."""

from __future__ import annotations

from benchmarks.harness import refine_spans
from benchmarks.layer_metrics import host_syncs_per_solve

HEADER = {"name": "refine_host_syncs_per_solve", "unit": "count",
          "better": "lower", "source": "program_counter",
          "layer": "drivers", "moves": "solve_s"}


def compute(run: dict):
    # the accepted reader, under this cell's root
    run = refine_spans.as_call(run)
    return None if run is None else host_syncs_per_solve.compute(run)
