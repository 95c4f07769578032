"""Seconds this process spent tracing Python to jaxprs and lowering
them to MLIR (Mosaic kernels included), as the program's always-on
``obs.compile_seconds()`` has them when the line is written: the part
of a first call that no compile cache skips. The window and the check
add next to nothing (``compiles_in_window`` is 0). Prints the whole
counter as one line ``{"step": "compile_seconds", ...}``: seconds and
counts by kind, and the programs with most ``trace + lower``."""

from __future__ import annotations

import json

HEADER = {"name": "trace_lower_s", "unit": "s", "better": "lower",
          "source": "program_counter", "layer": "entry",
          "moves": "setup_s"}


def compute(run: dict):
    if run.get("trace") is None:
        return None
    from slate_tpu import obs
    read = getattr(obs, "compile_seconds", None)
    if read is None:
        return None
    seen = read()
    print(json.dumps({"step": "compile_seconds", **seen, **run["device"]}),
          flush=True)
    seconds = seen["seconds"]
    return seconds.get("trace", 0.0) + seconds.get("lower", 0.0)
