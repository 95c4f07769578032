"""Host clock around the first warm-up call: the cell"s programs compiled, or
loaded from JAX"s persistent cache, and run once."""

from __future__ import annotations

HEADER = {"name": "first_call_s", "unit": "s", "better": "lower",
          "source": "host_clock", "layer": "entry",
          "moves": "setup_s"}


def compute(run: dict):
    return run["first_call_s"]
