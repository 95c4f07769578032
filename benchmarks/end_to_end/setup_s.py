"""Process start to the start of the window: imports, compile cache,
operands from the seed, the warm-up call (compile or cache load)."""

from __future__ import annotations

HEADER = {"name": "setup_s", "unit": "s", "better": "lower",
          "source": "host_clock"}


def compute(run: dict):
    return run["setup_s"]
