"""Device-0 busy seconds of one traced ``slate.gesvd`` inside the
``tb2bd`` rung's XLA modules: the triangular band to bidiagonal bulge
chase, stage 2. ``jit__tb2bd_vmem_jit`` is the VMEM-resident Pallas
chaser (the rung the ladder prefers on a TPU in f32),
``jit__tb2bd_wave_jit`` the XLA wavefront it demotes to; the host rungs
run nothing on the device, and the cell's check refuses a demoted answer
anyway."""

from __future__ import annotations

from benchmarks.harness import busy_inside

HEADER = {"name": "svd_chase_s", "unit": "s", "better": "lower",
          "source": "device_trace", "layer": "svd", "moves": "solve_s"}
MODULES = ("jit__tb2bd_vmem_jit", "jit__tb2bd_wave_jit")


def compute(run: dict):
    trace = run["trace"]
    if trace is None:
        return None
    return busy_inside.per_solve(trace, MODULES)
