"""Backend compile events (cache loads included) between the window"s
start and its end. Expected 0: every shape is warmed in set-up."""

from __future__ import annotations

HEADER = {"name": "compiles_in_window", "unit": "count", "better": "lower",
          "source": "program_counter", "layer": "executable caches",
          "moves": "solve_p90_s"}


def compute(run: dict):
    return run["compiles"]["window"]["backend_compiles"]
