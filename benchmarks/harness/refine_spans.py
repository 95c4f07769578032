"""A cell whose traffic names its public function in ``call`` beside
``routine`` (``traffic/closed_loop_refine.py``), made readable by
``harness/program_spans`` and by the accepted readers built on it:
they pair the root spans ``slate.<traffic["routine"]>`` with the
trace's ``bench.solve`` intervals, and this cell's roots are
``slate.<call>``."""

from __future__ import annotations

from benchmarks.harness import program_spans


def as_call(run: dict):
    """``run`` with the traffic's ``call`` in the place of its
    ``routine``. None without a trace, without captured spans, or from
    a program that opens no ``slate.<call>`` root (a commit from before
    it): ``program_spans.pair`` would raise there."""
    call = run["spec"]["traffic"].get("call")
    if run.get("trace") is None or call is None:
        return None
    spans = program_spans.captured(run)
    if spans is None or not any(
            s["parent"] == 0 and s["name"] == f"slate.{call}"
            for s in spans):
        return None
    spec = run["spec"]
    return {**run, "program_spans": spans, "spec": {
        **spec, "traffic": {**spec["traffic"], "routine": call}}}


def per_solve_median(run: dict, value):
    """``program_spans.per_solve_median`` over the traced calls."""
    run = as_call(run)
    if run is None:
        return None
    return program_spans.per_solve_median(run, value)
