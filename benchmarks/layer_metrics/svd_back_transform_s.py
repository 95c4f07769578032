"""Device-0 busy seconds of one traced ``slate.gesvd`` inside the four
back-transforms' XLA modules: ``jit__apply_bulge_jit`` twice
(``unmbr_tb2bd``: the chase's U-side and V-side reflectors on the rows
of U_B and V_B), ``jit__unmqr_jit`` (``unmbr_ge2tb`` on the U side: the
QR panels' block reflectors on [U_2 U_B; 0]) and ``jit__unmbr_v_jit``
(the LQ panels' on V_2 V_B)."""

from __future__ import annotations

from benchmarks.harness import busy_inside

HEADER = {"name": "svd_back_transform_s", "unit": "s", "better": "lower",
          "source": "device_trace", "layer": "svd", "moves": "solve_s"}
SWEEPS = ("jit__apply_bulge_jit",)
MODULES = SWEEPS + ("jit__unmqr", "jit__unmbr_v_jit")


def compute(run: dict):
    trace = run["trace"]
    if trace is None:
        return None
    return busy_inside.per_solve(trace, MODULES)
