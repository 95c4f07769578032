"""Traffic kind ``closed_loop_refine``: ``closed_loop_solve``'s one
caller around a public solver that *iterates* — a low-precision
factorization refined back to the working precision
(``slate.gesv_mixed_gmres`` and its siblings, which return ``(X, iters,
info)``).

A mix of this kind is a JSON file beside this one::

    {"kind": "closed_loop_refine", "routine": "gesv",
     "call": "gesv_mixed_gmres", "callers": 1, "warm_up_calls": 2,
     "seed_offset": 0}

``call`` is the public function the loop calls. ``routine`` beside it
stays the plain solver whose work is counted (``harness/flops.py``,
``mxu_peak_share``): HPL-MxP's rule is that the operations are HPL's,
whatever the refinement adds. ``config["tier"]`` goes in as
``Option.TrailingPrecision``, which a refining solver reads as the tier
of its low leg; A and B are ``closed_loop_solve``'s, from the seed.

What this kind adds to ``closed_loop_solve``:

* ``check()`` also holds every call, warm-up and window, to the
  deployment's guarantee: ``refine.converged`` (calls whose returned
  ``iters`` is >= 0, against the number of calls) and
  ``refine.fallbacks`` = 0 (calls answered by the full-precision
  fallback: ``iters`` < 0 as returned, or the program's own
  ``mixed.fallback`` counter where its metrics are on). An answer from
  the fallback is a ``gesv``, not this deployment, however right it is.
* the control ``lower_precision`` is the configuration with the
  refinement left out: ``slate.getrf`` + ``slate.getrs`` at the low
  tier (``unrefined``: the configuration's own), whose X has to fail
  the cell's limits.
* ``open_session`` refuses, before any operand is made, a program whose
  ``linalg.mixed`` cannot report a refinement's outcome (it names no
  ``mixed.fallback`` counter): the guarantee could not be checked, and
  such a program applies LAPACK pivots by a serial replay in every
  refinement solve, which a traced run does not survive (PERF.md
  section 6, PR 35).
"""

from __future__ import annotations

import math
import time

import jax

import slate_tpu as slate
from slate_tpu import obs

from benchmarks.traffic import closed_loop_solve

UNREFINED = "unrefined"


class Session(closed_loop_solve.Session):

    def __init__(self, spec: dict, devices, seed: int):
        super().__init__(spec, devices, seed)
        self.call = spec["traffic"]["call"]
        self.low_tier = spec["config"]["tier"]
        self.iters: list = []       # as returned, every call

    def _call(self):
        solve = getattr(slate, self.call)
        t0 = time.perf_counter()
        out = jax.block_until_ready(solve(self.A, self.B, self.opts))
        info = int(out[-1])
        wall = time.perf_counter() - t0
        self.iters.append(int(out[1]))
        return out, wall, (info == 0 and math.isfinite(wall))

    def lower_precision(self, tier: str):
        """The control: the low-tier factorization's own answer, no
        refinement (``benchmarks/control.py --tiers unrefined``; any
        tier's name gives that tier's)."""
        tier = self.low_tier if tier == UNREFINED else tier
        opts = {slate.Option.TrailingPrecision: tier}
        LU, piv, info = slate.getrf(self.A, opts)
        X = slate.getrs(LU, piv, self.B, opts=opts)
        if int(info) != 0:
            raise SystemExit(f"control getrf/{tier}: info != 0")
        return jax.block_until_ready(X)

    def check(self) -> list:
        rows = super().check()
        calls = len(self.iters)
        unconverged = sum(1 for i in self.iters if i < 0)
        counted = int(obs.count_total("mixed.fallback"))
        fallbacks = max(unconverged, counted)
        converged = calls - unconverged
        rows.append({"check": "refine.fallbacks", "value": fallbacks,
                     "limit": 0, "ok": fallbacks == 0,
                     "counter": counted})
        rows.append({"check": "refine.converged", "value": converged,
                     "limit": calls,
                     "ok": calls > 0 and converged == calls,
                     "iters_min": min(self.iters, default=None),
                     "iters_max": max(self.iters, default=None)})
        return rows


def open_session(spec: dict, devices, seed: int) -> Session:
    from slate_tpu.linalg import mixed
    if "mixed.fallback" not in getattr(mixed, "COUNTERS", ()):
        raise SystemExit(
            f"benchmarks/traffic/closed_loop_refine: this program's "
            f"slate_tpu.linalg.mixed names no mixed.fallback counter, so "
            f"cell {spec['name']}'s guarantee (no fallback, every call "
            f"converged) cannot be checked")
    return Session(spec, devices, seed)
