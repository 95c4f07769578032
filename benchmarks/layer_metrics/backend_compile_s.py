"""Sum of JAX"s ``backend_compile_duration`` events during set-up (the
benchmark"s own ``jax.monitoring`` listener). The event wraps the
persistent-cache lookup too, so on a warm run this is load time; the
hits and misses are printed beside it on the ``window`` line."""

from __future__ import annotations

HEADER = {"name": "backend_compile_s", "unit": "s", "better": "lower",
          "source": "program_counter", "layer": "executable caches",
          "moves": "setup_s"}


def compute(run: dict):
    return run["compiles"]["setup"]["backend_compile_s"]
