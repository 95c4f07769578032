"""Blocking device-to-host reads the program makes inside one
``slate.gesvd``: its spans labelled ``sync=1`` under the root
(``band.gather``, ``tb2bd.bidiagonal``, two a level of the bidiagonal
solve's tree: ``stedc.zrow`` and ``stedc.roots``, and ``gesvd.values``),
median over the traced calls. Each is a round trip during which the
device has nothing queued."""

from __future__ import annotations

from benchmarks.layer_metrics import host_syncs_per_solve

HEADER = {"name": "svd_host_syncs_per_solve", "unit": "count",
          "better": "lower", "source": "program_counter", "layer": "svd",
          "moves": "solve_s"}
# the accepted reader: this cell's roots are slate.<routine> as it is
compute = host_syncs_per_solve.compute
