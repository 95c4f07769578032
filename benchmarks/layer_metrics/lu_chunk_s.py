"""Device-0 busy seconds of one traced solve inside the chunk programs
of the LU on a grid, by the names the trace prints:
``jit__getrf_chunk_core`` (one per chunk of block columns, eight a
solve at kt=16 on the 2x2) or ``jit__getrf_pipe_chunk_core`` (the
software-pipelined body, ``Option.PipelineDepth``). Each holds, a step:
the panel's gather to every device, its factorization there, the row
swaps, the U block-row's solve and broadcast, the trailing product.
What is left of the device's busy time is ``getrs`` (``getrs_grid_s``)
and the trivial programs around them."""

from __future__ import annotations

from benchmarks.harness import module_seconds

HEADER = {"name": "lu_chunk_s", "unit": "s", "better": "lower",
          "source": "device_trace", "layer": "kernels",
          "moves": "solve_s"}

MODULES = ("jit__getrf_chunk", "jit__getrf_pipe_chunk")


def compute(run: dict):
    trace = run["trace"]
    if trace is None:
        return None
    return module_seconds.per_solve(trace, MODULES)
