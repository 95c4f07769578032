"""Device-0 seconds of one traced ``slate.gels`` inside the Householder
panels: the Pallas/Mosaic kernels (``custom-call`` to
``tpu_custom_call``: ``internal/panel_qr.py``, one call a 128-column
subpanel) that run inside the QR's XLA module. The serial part of the
factorization: one reflector after another, none of it on the MXU but
the strip-end updates. A factorization whose panels are XLA's ``geqrf``
holds no kernel, and this reads nothing (the trace as the harness keeps
it carries no named scope to cut ``qr_panel`` by: PERF.md section 7)."""

from __future__ import annotations

from benchmarks.harness import busy_inside
from benchmarks.harness.trace_reduce import is_kernel, merge
from benchmarks.layer_metrics.ls_factor_s import MODULES

HEADER = {"name": "ls_panel_s", "unit": "s", "better": "lower",
          "source": "device_trace", "layer": "least squares",
          "moves": "solve_s"}


def compute(run: dict):
    trace = run["trace"]
    if trace is None:
        return None
    inside = merge((s, e) for name, s, e in trace.first.modules
                   if name.startswith(MODULES))
    kernels = trace.first.where(is_kernel)
    seconds = busy_inside.overlap(kernels, inside) / len(trace.solves)
    return seconds or None
