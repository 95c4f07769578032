"""Explicit backend-capability ladder with runtime demotion.

The repo has always had an implicit degradation ladder — the VMEM
Pallas bulge chaser gates on ``vmem_applies`` and falls back to the
XLA wavefront; the native C++ kernels fall back to their numpy twins
when no toolchain is present — but the ladder lived as scattered
convention across ``internal/band_wave_vmem*.py`` and
``band_bulge_native.py``.  This module makes it a first-class
registry (the design BLASX, arXiv:1510.05041, argues for in
heterogeneous BLAS runtimes):

* a :class:`Rung` carries a *capability probe* (can this backend take
  the problem at all?), an *auto-selection policy* (should it, when
  nothing was forced?), and the backend itself;
* :class:`BackendLadder.run` walks the rungs top-down.  A rung whose
  probe fails is skipped; a rung that raises or returns invalid
  (non-finite) output is retried once and then DEMOTED — the next
  rung takes the step, and the demotion is logged
  (:func:`demotion_log`) so callers and chaos tests can assert what
  actually ran.

The two concrete band-chase ladders (vmem → wave → native → numpy) are
built by :func:`hb2st_ladder` and :func:`tb2bd_ladder`;
``linalg/he2hb.hb2st`` and ``linalg/ge2tb.tb2bd`` route their backend
dispatch through them.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

from ..errors import SlateError
from .. import obs
from ..runtime import sync


@dataclasses.dataclass(frozen=True)
class Rung:
    """One backend rung.

    ``probe(*args)`` — capability: can this backend run the problem
    (shape/dtype/hardware/toolchain gates)?  ``prefer(*args)`` — auto
    policy: should the ladder START here when the caller forced
    nothing (defaults to the probe)?  ``run(*args)`` — the backend.
    """

    name: str
    run: Callable
    probe: Callable[..., bool] = lambda *a: True
    prefer: Callable[..., bool] | None = None

    def preferred(self, *args) -> bool:
        fn = self.prefer if self.prefer is not None else self.probe
        try:
            return bool(fn(*args))
        except Exception:
            return False


@dataclasses.dataclass(frozen=True)
class Demotion:
    """One logged demotion: the ladder stepped past ``from_rung``."""

    ladder: str
    from_rung: str
    to_rung: str
    reason: str

    def __str__(self):
        return (f"{self.ladder}: {self.from_rung} -> {self.to_rung} "
                f"({self.reason})")


_demotions: list[Demotion] = []
# the log is written from worker threads too (the ckpt saver persists
# it, ladders demote inside watched sections) — one lock, registered
# with slaterace
_demotions_lock = sync.Lock(name="robust.ladder.demotions")
_demotions_cell = sync.shared_cell("robust.ladder._demotions")


def record_demotion(d: Demotion) -> None:
    with _demotions_lock:
        _demotions_cell.write()
        _demotions.append(d)
    # chaos runs are diagnosable from the trace/metrics alone: every
    # demotion is an instant event + a labeled counter, not a bare log
    obs.instant("ladder.demotion", ladder=d.ladder,
                from_rung=d.from_rung, to_rung=d.to_rung,
                reason=d.reason)
    obs.count("ladder.demotions", ladder=d.ladder,
              from_rung=d.from_rung, to_rung=d.to_rung,
              reason=d.reason)


def demotion_log() -> tuple[Demotion, ...]:
    with _demotions_lock:
        _demotions_cell.read()
        return tuple(_demotions)


def clear_demotion_log() -> None:
    with _demotions_lock:
        _demotions_cell.write()
        _demotions.clear()


def demotions_as_dicts() -> list[dict]:
    """The log as plain dicts — what robust.ckpt persists alongside
    each checkpoint payload."""
    with _demotions_lock:
        _demotions_cell.read()
        return [dataclasses.asdict(d) for d in _demotions]


def restore_demotions(entries) -> int:
    """Merge checkpoint-persisted demotion records back into the live
    log (the robust.ckpt resume path): demotions recorded before a
    preempt stay visible in :func:`demotion_log` after the resumed
    process picks the job back up.  Entries already present are not
    duplicated, and restored entries are NOT re-counted in obs — they
    were counted when first recorded.  Returns the number merged."""
    with _demotions_lock:
        _demotions_cell.write()
        seen = {(d.ladder, d.from_rung, d.to_rung, d.reason)
                for d in _demotions}
        merged = 0
        for e in entries or ():
            try:
                d = Demotion(ladder=str(e["ladder"]),
                             from_rung=str(e["from_rung"]),
                             to_rung=str(e["to_rung"]),
                             reason=str(e["reason"]))
            except (KeyError, TypeError):
                continue
            key = (d.ladder, d.from_rung, d.to_rung, d.reason)
            if key in seen:
                continue
            seen.add(key)
            _demotions.append(d)
            merged += 1
        return merged


class BackendLadder:
    """Ordered backend rungs with probe-gated selection and
    runtime demotion."""

    def __init__(self, name: str, rungs: list[Rung], validate=None):
        self.name = name
        self.rungs = list(rungs)
        self.validate = validate          # result -> bool (healthy?)
        self._names = [r.name for r in self.rungs]
        self.last_rung: str | None = None  # whose answer run() returned

    def select(self, *args) -> str:
        """Auto-selection: the first rung whose policy prefers the
        problem (the last rung is the unconditional floor)."""
        for r in self.rungs[:-1]:
            if r.preferred(*args):
                return r.name
        return self.rungs[-1].name

    def _demote(self, i: int, reason: str) -> None:
        nxt = (self._names[i + 1] if i + 1 < len(self._names)
               else "<none>")
        record_demotion(Demotion(self.name, self._names[i], nxt, reason))

    def run(self, *args, start: str | None = None):
        """Run the problem, demoting through the rungs as needed.

        ``start`` pins the first rung to try (the env-override path);
        None auto-selects via :meth:`select`.  Per rung: a failing
        capability probe demotes immediately; an exception or invalid
        (validator-rejected) result is retried once on the same rung,
        then demotes.  Exhausting the ladder raises
        :class:`SlateError`.
        """
        first = self._names.index(start if start is not None
                                  else self.select(*args))
        last_err: Exception | None = None
        for i in range(first, len(self.rungs)):
            rung = self.rungs[i]
            try:
                probed = bool(rung.probe(*args))
                obs.count("ladder.probes", ladder=self.name,
                          rung=rung.name, ok=probed)
                if not probed:
                    self._demote(i, "probe failed")
                    continue
            except Exception as e:      # a probe that raises is a no
                obs.count("ladder.probes", ladder=self.name,
                          rung=rung.name, ok=False)
                self._demote(i, f"probe raised {type(e).__name__}")
                continue
            for attempt in (0, 1):
                obs.count("ladder.attempts", ladder=self.name,
                          rung=rung.name)
                try:
                    with obs.span(f"ladder.{self.name}",
                                  rung=rung.name, attempt=attempt):
                        out = rung.run(*args)
                except Exception as e:  # noqa: BLE001 — demotion contract
                    last_err = e
                    if attempt == 0:
                        continue        # retry the step once
                    self._demote(i, f"raised {type(e).__name__}")
                    break
                if self.validate is not None and not self.validate(out):
                    if attempt == 0:
                        continue
                    self._demote(i, "non-finite output")
                    break
                self.last_rung = rung.name
                return out
        raise SlateError(
            f"backend ladder {self.name!r} exhausted "
            f"(last error: {last_err!r})")


# ---------------------------------------------------------------------------
# the concrete band-chase ladders: vmem -> wave -> native -> numpy
# ---------------------------------------------------------------------------

_hb2st: BackendLadder | None = None
_tb2bd: BackendLadder | None = None


def _band_geom(band):
    return band.shape[0] - 1, band.shape[1]


def _chaseable(band) -> bool:
    b, n = _band_geom(band)
    return b >= 2 and n >= 2


def _hb2st_valid(result) -> bool:
    """Health check on a chaser result (d, e, ...): the tridiagonal
    (hb2st) or bidiagonal (tb2bd) must be finite (host-side numpy —
    the result is already on host)."""
    import numpy as np
    d, e = result[0], result[1]
    return bool(np.isfinite(np.asarray(d)).all()
                and np.isfinite(np.asarray(e)).all())


def _chase_ladder(name: str, vmem_gate, backends) -> BackendLadder:
    """The four rungs of a band bulge chase (``hb2st``: Hermitian band
    to tridiagonal; ``tb2bd``: triangular band to bidiagonal), kernel
    modules imported only when their rung is probed or run:

    * ``vmem``  — VMEM-resident Pallas chaser; probe = TPU backend and
      the kernel's own footprint gate ``vmem_gate()(n, b, dtype)``;
    * ``wave``  — XLA wavefront chaser; capable whenever a chase
      exists (b >= 2), auto-preferred on accelerators at n >= 1024
      where it amortizes dispatch;
    * ``native`` — single-thread C++ kernel; probe = the toolchain
      actually produced a library (``native_missing`` fault or a
      compilerless host demote past it);
    * ``numpy`` — the pure-numpy reference twin, unconditional floor.

    ``backends()`` returns the four callables in that order."""

    def vmem_probe(band):
        if not _chaseable(band):
            return False
        try:
            import jax
            if jax.default_backend() != "tpu":
                return False
        except Exception:
            return False
        b, n = _band_geom(band)
        return vmem_gate()(n, b, band.dtype)

    def wave_prefer(band):
        if not _chaseable(band):
            return False
        try:
            import jax
            accel = jax.default_backend() not in ("cpu",)
        except Exception:
            accel = False
        b, n = _band_geom(band)
        return accel and n >= 1024

    def native_probe(band):
        from ..internal import band_bulge_native
        return band_bulge_native.get_lib() is not None

    def rung(i):
        return lambda band: backends()[i](band)

    return BackendLadder(name, [
        Rung("vmem", rung(0), probe=vmem_probe),
        Rung("wave", rung(1), probe=_chaseable, prefer=wave_prefer),
        Rung("native", rung(2), probe=native_probe,
             prefer=lambda band: True),
        Rung("numpy", rung(3)),
    ], validate=_hb2st_valid)


def hb2st_ladder() -> BackendLadder:
    """The Hermitian-band bulge-chasing ladder (built lazily; see
    :func:`_chase_ladder` for its rungs)."""
    global _hb2st
    if _hb2st is not None:
        return _hb2st

    def gate():
        from ..internal.band_wave_vmem import vmem_applies
        return vmem_applies

    def backends():
        from ..internal import band_bulge, band_bulge_native
        from ..internal.band_bulge_wave import hb2st_wave
        from ..internal.band_wave_vmem import hb2st_wave_vmem
        return (hb2st_wave_vmem, hb2st_wave, band_bulge_native.hb2st,
                band_bulge.hb2st)

    _hb2st = _chase_ladder("hb2st", gate, backends)
    return _hb2st


def tb2bd_ladder() -> BackendLadder:
    """The triangular-band bulge-chasing ladder of the two-stage SVD
    (``linalg/ge2tb.tb2bd`` routes its backend dispatch through it).
    The ``vmem`` rung's gate is the bidiagonal twin's own
    (``vmem_applies_bd``: its four per-step output windows are not in
    the eig twin's footprint model)."""
    global _tb2bd
    if _tb2bd is not None:
        return _tb2bd

    def gate():
        from ..internal.band_wave_vmem_bd import vmem_applies_bd
        return vmem_applies_bd

    def backends():
        from ..internal import band_bulge, band_bulge_native
        from ..internal.band_bulge_wave_bd import tb2bd_wave
        from ..internal.band_wave_vmem_bd import tb2bd_wave_vmem
        return (tb2bd_wave_vmem, tb2bd_wave, band_bulge_native.tb2bd,
                band_bulge.tb2bd)

    _tb2bd = _chase_ladder("tb2bd", gate, backends)
    return _tb2bd
