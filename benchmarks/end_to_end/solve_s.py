"""Median host wall of one public solve that ends in
``block_until_ready`` and a read ``info``: time to a solution of the
stated residual. All calls of the window that did not fail."""

from __future__ import annotations

import statistics

HEADER = {"name": "solve_s", "unit": "s", "better": "lower",
          "source": "host_clock"}


def compute(run: dict):
    return statistics.median(run["walls"]) if run["walls"] else None
