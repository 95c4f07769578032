"""Host-side timing discipline of the tuning sweep.

THE single source of truth for the subtract-a-round-trip logic: every
timed program reduces its output to a scalar materialized to the host
(``float(...)``), and a measured dispatch round trip (the host wall of
a trivial jitted program) is subtracted from each sample.  PR 24 judged
the method wrong for a chip (PERF.md §6); ``tune/sweep.py`` is its one
caller left, and it goes with that (ROADMAP D6).
slatelint rule SL008 bans raw
``time.perf_counter`` timing outside ``slate_tpu/obs`` and
``robust/watchdog.py`` so this discipline cannot fork again.

All helpers optionally record an obs span (``name=``/``labels=``) so
a timed region lands in the trace + metrics table automatically.

Clamp contract: the round-trip subtraction can never produce a negative
elapsed — a sample smaller than the measured round trip is floored at
0 and counted under ``timing.clamped``, and a median that clamps all
the way to zero suppresses its span (no nonsense GF/s row) while the
returned value keeps a 1e-9 floor so callers can divide by it.
"""

from __future__ import annotations

import time

import numpy as np

from . import metrics as _metrics
from . import tracing as _tracing


def _sub_latency(sample: float, t_rt: float) -> float:
    """Subtract the dispatch round trip from one timed sample, clamped
    at zero.  A negative difference means the measured latency
    exceeded this sample's whole wall — jitter, not signal — so the
    sample is floored and ``timing.clamped`` counts the event instead
    of a negative elapsed poisoning the median (and the GF/s computed
    from it)."""
    t = sample - t_rt
    if t < 0.0:
        _metrics.inc("timing.clamped")
        return 0.0
    return t


def _finish(t: float, name, labels) -> float:
    """Common tail: record the obs span (skipped when the elapsed
    clamped all the way to zero — a zero-length span would enrich to
    nonsense GF/s) and floor the returned value so callers dividing
    flops by it never hit a ZeroDivisionError."""
    if t <= 0.0:
        _metrics.inc("timing.clamped", stage="median")
        _tracing.instant("timing.clamped", span=str(name))
        return 1e-9
    if name is not None:
        _tracing.record_span(name, t, **(labels or {}))
    return t


def roundtrip_latency(iters: int = 5) -> float:
    """Median host→device→host round trip of a trivial jitted program
    (what every timed sample subtracts)."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x + 1.0)
    x = jnp.zeros(())
    float(f(x))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        float(f(x))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def timed_scalar_median(fn, *args, warmup: int = 2, iters: int = 3,
                        t_rt: float = 0.0, name: str | None = None,
                        labels: dict | None = None) -> float:
    """Time ``fn(*args) -> scalar jax value``, materialized per call;
    median of ``iters`` after ``warmup``, minus the dispatch round trip.
    When ``name`` is given the result is recorded as an obs span."""
    for _ in range(warmup):
        s = float(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        s = float(fn(*args))
        ts.append(_sub_latency(time.perf_counter() - t0, t_rt))
    del s
    return _finish(float(np.median(ts)), name, labels)


def timed_regen_median(gen, fence, op, iters: int, t_rt: float = 0.0,
                       name: str | None = None,
                       labels: dict | None = None) -> float:
    """Large-operand timing discipline (bench potrf_32k-class): stage
    ``x = gen()`` and fence it OUTSIDE the timer (async dispatch would
    otherwise leak generation into the timed window), then time only
    ``op(x) -> scalar`` materialized per call; median of ``iters``
    after one warmup.  ``x`` is regenerated fresh every iteration
    because ``op`` donates it."""
    ts = []
    for it in range(iters + 1):
        x = gen()
        float(fence(x))
        t0 = time.perf_counter()
        float(op(x))
        if it > 0:
            ts.append(_sub_latency(time.perf_counter() - t0, t_rt))
        del x
    return _finish(float(np.median(ts)), name, labels)
