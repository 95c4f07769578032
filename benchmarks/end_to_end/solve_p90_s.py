"""90th percentile (linear interpolation) of the same walls: host
stalls, chunk-loop jitter, a recompile. p90 because a window holds
some tens to some hundreds of calls."""

from __future__ import annotations

import statistics

HEADER = {"name": "solve_p90_s", "unit": "s", "better": "lower",
          "source": "host_clock"}


def compute(run: dict):
    walls = run["walls"]
    if len(walls) < 2:
        return None
    return statistics.quantiles(walls, n=10, method="inclusive")[8]
