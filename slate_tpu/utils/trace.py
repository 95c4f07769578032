"""Tracing / profiling — compatibility facade over ``slate_tpu.obs``.

The span API (reference src/auxiliary/Trace.cc ``trace::Block``)
moved into :mod:`slate_tpu.obs.tracing`, which unified it with the
metrics registry and flop accounting (docs/observability.md).  This
module keeps the historical entry points alive so existing callers —
and the reference-parity usage ``trace.on(); …; trace.finish(path)``
— keep working unchanged:

* :func:`block` now also accepts labels (``routine=``, dims) and
  feeds the per-phase metrics table when metrics are on;
* :func:`finish` resets the session clock, so a second trace session
  starts at t=0 (the old in-module buffer kept the first session's
  offset, skewing every later session's timestamps).

New code should import ``slate_tpu.obs`` directly.
"""

from __future__ import annotations

from ..obs.tracing import (  # noqa: F401 — re-exported façade
    block, comment, finish, is_on, off, on,
)
