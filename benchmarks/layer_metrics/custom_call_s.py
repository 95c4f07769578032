"""Device-0 seconds of Pallas/Mosaic kernels (``custom-call`` to
``tpu_custom_call``) per traced solve. Reads 0 when the LU is off its
Pallas panel; a cell whose programs hold no kernel does not list this
metric."""

from __future__ import annotations

from benchmarks.harness.trace_reduce import is_kernel

HEADER = {"name": "custom_call_s", "unit": "s", "better": "lower",
          "source": "device_trace", "layer": "kernels",
          "moves": "solve_s"}


def compute(run: dict):
    trace = run["trace"]
    if trace is None:
        return None
    return trace.per_solve(trace.first.where(is_kernel))
