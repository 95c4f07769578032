"""Host-side section deadlines, retry/backoff, and guarded subprocess
compiles.

BENCH_r05 showed the cost of running without guard rails: one hung
section (``getrf_45056_error: "SectionTimeout"``) burned 495 s of the
round with no retry and no partial result.  This module gives every
host-side section the same structured contract:

* :func:`deadline` — a SIGALRM wall-clock cap (no-op off the main
  thread, where SIGALRM cannot be delivered) raising a structured
  :class:`SectionTimeout` that carries the section name, cap, elapsed
  time, and any partial results the caller registered;
* :func:`with_retry` — bounded retry with exponential backoff and
  deterministic seedable jitter, every attempt visible as a
  ``retry.attempt{outcome}`` obs counter;
* :func:`run_resumable` — the checkpoint escalation policy: on
  :class:`SectionPreempted`/:class:`SectionTimeout` the retry resumes
  from the latest valid checkpoint (``robust.ckpt``) instead of
  rerunning, demoting to from-scratch (a logged ladder demotion) only
  when no valid checkpoint exists;
* :func:`run_watched` — deadline + retry + cleanup in one call,
  returning a :class:`SectionRecord` instead of leaking exceptions;
* :func:`checked_run` — the subprocess.run wrapper used by every
  native-compile call site (``runtime/__init__.py``,
  ``c_api/__init__.py``, ``internal/band_bulge_native.py``): honours
  the ``compile_timeout`` fault injection and retries a timed-out
  compile once before giving up, so a transiently wedged compiler
  does not permanently demote the process to the numpy rungs.

Simulated preemption (the ``preempt`` fault class) surfaces here as
:class:`SectionPreempted`, raised at section entry by
``faults.check_preempt``.
"""

from __future__ import annotations

import dataclasses
import random
import signal
import subprocess
import time

from ..errors import SlateError
from .. import obs
from ..runtime import sync


class SectionTimeout(Exception):
    """A watched section exceeded its wall-clock cap.

    Structured record: ``name``, ``cap_s``, ``elapsed_s``, and
    ``partial`` (whatever the caller's ``partial()`` callable returned
    at timeout — the results accumulated so far, preserved instead of
    eaten by the timeout)."""

    def __init__(self, name: str = "", cap_s: float = 0.0,
                 elapsed_s: float = 0.0, partial=None):
        self.name = name
        self.cap_s = cap_s
        self.elapsed_s = elapsed_s
        self.partial = partial
        super().__init__(
            f"section {name!r} exceeded its {cap_s:.0f}s cap "
            f"after {elapsed_s:.1f}s")

    def as_dict(self) -> dict:
        return {"name": self.name, "cap_s": self.cap_s,
                "elapsed_s": round(self.elapsed_s, 1),
                "partial": self.partial}


class SectionPreempted(SlateError):
    """A watched section was preempted at entry (simulated TPU/host
    preemption — the ``preempt`` fault class)."""

    def __init__(self, name: str = ""):
        self.name = name
        super().__init__(f"section {name!r} preempted")


@dataclasses.dataclass
class SectionRecord:
    """Outcome of one watched section."""

    name: str
    ok: bool
    wall_s: float
    value: object = None
    error: str = ""
    partial: object = None
    retries: int = 0

    def as_dict(self) -> dict:
        return {"name": self.name, "ok": self.ok,
                "wall_s": round(self.wall_s, 1), "error": self.error,
                "partial": self.partial, "retries": self.retries}


class deadline:
    """Context manager capping the wall time of its body (main thread
    only — SIGALRM is undeliverable elsewhere, so off the main thread
    the body runs uncapped rather than silently unmonitored: the
    caller still gets preemption checks and timing).

    ``partial`` is an optional zero-arg callable evaluated at timeout;
    its return value rides on the :class:`SectionTimeout`.
    """

    def __init__(self, name: str, cap_s: float | None,
                 partial=None):
        self.name = name
        self.cap_s = cap_s
        self.partial = partial
        self._t0 = 0.0
        self._prev = None
        self._armed = False

    def _on_alarm(self, signum, frame):
        part = None
        if self.partial is not None:
            try:
                part = self.partial()
            except Exception:
                part = None
        obs.instant("section.timeout", section=self.name,
                    cap_s=float(self.cap_s))
        # slateflight: a watchdog firing is exactly the moment the
        # post-hoc trace would have been most wanted — freeze the ring
        try:
            from ..obs import flight
            flight.auto_dump("watchdog_timeout", section=self.name,
                             cap_s=float(self.cap_s),
                             elapsed_s=time.time() - self._t0)
        except Exception:  # noqa: BLE001 — never mask the timeout
            pass
        raise SectionTimeout(self.name, float(self.cap_s),
                             time.time() - self._t0, part)

    def __enter__(self):
        from . import faults
        faults.check_preempt(self.name)
        self._t0 = time.time()
        if self.cap_s is not None and sync.in_main_thread():
            self._prev = signal.signal(signal.SIGALRM, self._on_alarm)
            signal.alarm(max(int(self.cap_s), 1))
            self._armed = True
        return self

    def __exit__(self, *exc):
        if self._armed:
            signal.alarm(0)
            signal.signal(signal.SIGALRM, self._prev)
        outcome = "ok"
        if exc and exc[0] is not None:
            outcome = ("timeout" if issubclass(exc[0], SectionTimeout)
                       else "error")
        obs.record_span("section." + self.name,
                        time.time() - self._t0, outcome=outcome)
        return False


class post_deadline:
    """Post-hoc wall-clock cap — the worker-thread sibling of
    :class:`deadline` for sections whose body must never be
    interrupted (a dispatched device program runs to completion) or
    that run where SIGALRM cannot be delivered (the slateflow dispatch
    thread).  The body always finishes; the elapsed wall is judged at
    exit and a :class:`SectionTimeout` raised *after the fact* when it
    exceeded the cap — the caller keeps whatever the body computed via
    ``partial`` while still getting the structured timeout record.

    Emits the same instrumentation as :class:`deadline`: a
    ``section.timeout`` instant, a ``watchdog_timeout`` flight dump,
    and a ``section.<name>`` span labeled with the outcome."""

    def __init__(self, name: str, cap_s: float | None, partial=None):
        self.name = name
        self.cap_s = cap_s
        self.partial = partial
        self._t0 = 0.0

    def __enter__(self):
        from . import faults
        faults.check_preempt(self.name)
        self._t0 = time.time()
        return self

    def __exit__(self, *exc):
        elapsed = time.time() - self._t0
        overran = (self.cap_s is not None and elapsed >= self.cap_s
                   and (not exc or exc[0] is None))
        outcome = "ok"
        if exc and exc[0] is not None:
            outcome = ("timeout" if issubclass(exc[0], SectionTimeout)
                       else "error")
        elif overran:
            outcome = "timeout"
        obs.record_span("section." + self.name, elapsed,
                        outcome=outcome)
        if not overran:
            return False
        part = None
        if self.partial is not None:
            try:
                part = self.partial()
            except Exception:
                part = None
        obs.instant("section.timeout", section=self.name,
                    cap_s=float(self.cap_s))
        try:
            from ..obs import flight
            flight.auto_dump("watchdog_timeout", section=self.name,
                             cap_s=float(self.cap_s),
                             elapsed_s=elapsed)
        except Exception:  # noqa: BLE001 — never mask the timeout
            pass
        raise SectionTimeout(self.name, float(self.cap_s), elapsed,
                             part)


class SoftDeadline:
    """Cooperative wall-clock budget — the non-signal sibling of
    :class:`deadline` for callers that cannot take a SIGALRM (worker
    threads, nested sections) or must not be interrupted mid-kernel
    (a dispatched device program should run to completion; the serving
    scheduler checks the budget *between* bucket dispatches instead).

    Poll :attr:`expired` / :attr:`remaining_s` between units of work;
    ``cap_s=None`` never expires (remaining is None)."""

    def __init__(self, cap_s: float | None):
        self.cap_s = cap_s
        self._t0 = time.time()

    @property
    def elapsed_s(self) -> float:
        return time.time() - self._t0

    @property
    def remaining_s(self) -> float | None:
        if self.cap_s is None:
            return None
        return max(0.0, self.cap_s - self.elapsed_s)

    @property
    def expired(self) -> bool:
        return self.cap_s is not None and self.elapsed_s >= self.cap_s


def with_retry(fn, retries: int = 1, backoff_s: float = 0.0,
               retry_on=(Exception,), jitter_s: float = 0.0,
               seed: int = 0, max_elapsed_s: float | None = None):
    """Call ``fn()``; on a ``retry_on`` exception retry up to
    ``retries`` more times with exponential backoff
    (``backoff_s * 2**(attempt-1)``) plus deterministic seedable
    jitter (uniform in ``[0, jitter_s]`` from ``random.Random(seed)``
    — chaos runs reproduce their sleep schedule exactly).  Returns
    ``(value, attempts_used)``; the final failure propagates.  Every
    attempt lands in the obs stream as a ``retry.attempt`` counter
    labeled with its outcome (ok / retry / exhausted).

    ``max_elapsed_s`` caps the TOTAL wall the retry loop may consume:
    once the elapsed time at a failure reaches it no further attempt
    is made (the failure propagates as exhausted), and a scheduled
    backoff sleep is clamped so the loop never sleeps past the cap —
    exponential backoff cannot exceed a section's remaining budget."""
    rng = random.Random(seed) if jitter_s else None
    attempt = 0
    t0 = time.time()
    while True:
        try:
            value = fn()
            obs.count("retry.attempt", outcome="ok")
            return value, attempt
        except retry_on:
            elapsed = time.time() - t0
            if attempt >= retries or (max_elapsed_s is not None
                                      and elapsed >= max_elapsed_s):
                obs.count("retry.attempt", outcome="exhausted")
                raise
            obs.count("retry.attempt", outcome="retry")
            attempt += 1
            delay = backoff_s * (2 ** (attempt - 1)) if backoff_s else 0.0
            if rng is not None:
                delay += rng.uniform(0.0, jitter_s)
            if max_elapsed_s is not None:
                delay = min(delay, max(0.0, max_elapsed_s - elapsed))
            if delay > 0:
                time.sleep(delay)


def _escalation_reason(e) -> str:
    """Low-cardinality escalation label for a retried exception:
    ``preempt`` / ``timeout`` / ``sdc`` (an abft
    :class:`~.abft.SdcDetected` checksum violation) / the class name."""
    if isinstance(e, SectionPreempted):
        return "preempt"
    if isinstance(e, SectionTimeout):
        return "timeout"
    try:
        from .abft import SdcDetected
        if isinstance(e, SdcDetected):
            return "sdc"
    except Exception:  # noqa: BLE001 — labeling only
        pass
    return type(e).__name__


def run_resumable(name: str, fresh, resume=None, has_checkpoint=None,
                  retries: int = 1, backoff_s: float = 0.0,
                  jitter_s: float = 0.0, seed: int = 0,
                  retry_on=None, max_elapsed_s: float | None = None):
    """The preempt/timeout/sdc escalation policy (docs/robustness.md
    "Checkpoint & resume"): run ``fresh()``; on a ``retry_on``
    exception (default :class:`SectionPreempted` /
    :class:`SectionTimeout` / ``abft.SdcDetected``) retry with
    exponential backoff + deterministic jitter, calling ``resume()``
    when ``has_checkpoint()`` reports a valid checkpoint and demoting
    to ``fresh()`` — recorded in ``ladder.demotion_log()`` — when none
    exists.  Each retried failure lands as a ``retry.escalation``
    counter labeled with its reason (``preempt``/``timeout``/``sdc``).
    ``max_elapsed_s`` bounds the loop's total wall (see
    :func:`with_retry`).  Returns ``(value, attempts_used)``."""
    if retry_on is None:
        retry_on = (SectionPreempted, SectionTimeout)
        try:
            from .abft import SdcDetected
            retry_on += (SdcDetected,)
        except Exception:  # noqa: BLE001 — abft is optional here
            pass
    state = {"first": True}

    def attempt_once():
        try:
            if state["first"]:
                state["first"] = False
                return fresh()
            if resume is not None and (has_checkpoint is None
                                       or has_checkpoint()):
                obs.count("retry.resume", section=name)
                return resume()
            if resume is not None:
                from . import ladder
                ladder.record_demotion(ladder.Demotion(
                    "ckpt." + name, "resume", "scratch",
                    "no valid checkpoint"))
            return fresh()
        except retry_on as e:
            obs.count("retry.escalation", section=name,
                      reason=_escalation_reason(e))
            raise

    return with_retry(attempt_once, retries=retries, backoff_s=backoff_s,
                      retry_on=retry_on, jitter_s=jitter_s, seed=seed,
                      max_elapsed_s=max_elapsed_s)


def run_watched(name: str, fn, cap_s: float | None = None,
                retries: int = 0, backoff_s: float = 0.0,
                partial=None, cleanup=None, resume=None,
                has_checkpoint=None, jitter_s: float = 0.0,
                seed: int = 0, retry_on=(Exception,),
                cap_mode: str = "signal") -> SectionRecord:
    """Run ``fn()`` under a deadline with bounded retry; never raises.

    Timeouts, preemptions, and ordinary exceptions all land in the
    returned :class:`SectionRecord` (``error`` holds the exception
    class name; ``partial`` the timeout's partial results).  ``cleanup``
    always runs, success or failure.  ``resume``/``has_checkpoint``
    route retries through the :func:`run_resumable` escalation policy
    (each attempt — fresh or resumed — runs under its own deadline);
    ``retry_on`` narrows which exceptions are retried at all (the
    serving scheduler retries only :class:`SectionPreempted`).

    ``cap_mode`` selects the guard: ``"signal"`` (default) is the
    SIGALRM :class:`deadline`; ``"post"`` is :class:`post_deadline` —
    the body runs to completion and the cap is judged at exit, the
    mode worker threads (e.g. the slateflow dispatch thread) use."""
    if cap_mode not in ("signal", "post"):
        raise ValueError(f"run_watched: unknown cap_mode {cap_mode!r}")
    guard = deadline if cap_mode == "signal" else post_deadline
    t0 = time.time()
    attempts = 0
    try:
        def once_fresh():
            with guard(name, cap_s, partial=partial):
                return fn()

        def once_resume():
            with guard(name, cap_s, partial=partial):
                return resume()
        value, attempts = run_resumable(
            name, once_fresh,
            resume=once_resume if resume is not None else None,
            has_checkpoint=has_checkpoint, retries=retries,
            backoff_s=backoff_s, jitter_s=jitter_s, seed=seed,
            retry_on=retry_on)
        return SectionRecord(name=name, ok=True,
                             wall_s=time.time() - t0, value=value,
                             retries=attempts)
    except SectionTimeout as e:
        return SectionRecord(name=name, ok=False,
                             wall_s=time.time() - t0,
                             error="SectionTimeout", partial=e.partial,
                             retries=attempts)
    except Exception as e:  # noqa: BLE001 — structured record contract
        return SectionRecord(name=name, ok=False,
                             wall_s=time.time() - t0,
                             error=type(e).__name__, retries=attempts)
    finally:
        if cleanup is not None:
            try:
                cleanup()
            except Exception:
                pass


def checked_run(cmd, timeout: float, what: str = "",
                retries: int = 1, backoff_s: float = 0.0):
    """``subprocess.run(check=True, capture_output=True)`` with the
    repo's compile guard rails: the ``compile_timeout`` fault class
    injects a deterministic ``TimeoutExpired``, and a (real or
    injected) timeout is retried ``retries`` times before the final
    ``TimeoutExpired`` propagates — callers keep their existing
    ``except (OSError, subprocess.SubprocessError)`` fallbacks."""
    from . import faults
    last = None
    for attempt in range(retries + 1):
        spec = faults.enabled("compile_timeout", what)
        if spec is not None:
            faults.record("compile_timeout", what or str(cmd[0]),
                          f"attempt {attempt}")
            last = subprocess.TimeoutExpired(cmd, timeout)
            continue
        try:
            return subprocess.run(cmd, check=True, capture_output=True,
                                  timeout=timeout)
        except subprocess.TimeoutExpired as e:
            last = e
            if backoff_s:
                time.sleep(backoff_s * (attempt + 1))
    raise last
