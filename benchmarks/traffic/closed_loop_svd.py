"""Traffic kind ``closed_loop_svd``: ``closed_loop_solve``'s one caller
around a public singular value decomposition, ``s, U, VT =
slate.<routine>(A, opts, want_u=True, want_vt=True)`` of a tall A
(m = ``m_over_n`` x n rows): the economy SVD, LAPACK's JOBU = JOBVT =
'S'.

A mix of this kind is a JSON file beside this one::

    {"kind": "closed_loop_svd", "routine": "gesvd", "jobu": "S",
     "jobvt": "S", "callers": 1, "warm_up_calls": 2, "seed_offset": 0}

The operand is made on the cell's device from the seed
(``slate.random_matrix``, iid normal); every call decomposes the same
A, and ends when U and VT are ready (``block_until_ready``) and s is
read. The options are the configuration's: ``method_svd`` as
``Option.MethodSVD`` and ``tier`` as ``Option.TrailingPrecision``.
Nothing else is passed and no environment variable is set: the band the
chase runs at, the ``tb2bd`` backend and the route of the bidiagonal
solve are the library's choice, and are read back.

The comparison that decides ``correct`` is ``harness/plain_svd.py``:
the defining equations of (s, U, VT) and the singular values against a
float64 LAPACK solve, on the warm-up answer and on the window's last,
each number beside its limit in units of 2^-24 (``tol_fro_eps``:
``svd_residual_fro``, ``tol_eps``: ``svd_residual_max``,
``tol_orth_u_eps``, ``tol_orth_v_eps``, ``tol_values_eps``);
``svd.descending`` (finite, non-negative, descending); ``svd.shape``;
``svd.demotions`` = 0: an answer from a rung below the one the ladder
preferred is right and is not this deployment; and ``svd.program``: the
program's counters over the warm-up calls name the two-stage path, the
``vmem`` rung and one route of the bidiagonal solve, once a call each.

``benchmarks/control.py`` sweeps this kind as it stands: ``errors_of``
answers its two keys with ``svd_residual_max`` (``inf``) and
``svd_residual_fro`` (``fro``) of an (s, U, VT), and prints all five
numbers of every answer on a line of their own (``"step":
"svd_errors"``), which is where the other three limits are set from.

Under ``--rehearse-on-cpu --n`` the shape keeps m / n (``cells.load_cell``
shrinks ``n`` and ``nb`` only), and ``svd.program`` asks for no rung by
name (off the chip the ladder prefers another).

``open_session`` refuses, before any operand is made, a program whose
``linalg.svd`` names no ``slate.gesvd`` root span or no ``gesvd.path``
counter: its spans could not be paired with the trace, what answered
could not be read back, and such a program solves the bidiagonal
problem on the host (a symmetric eigenproblem of order 2n in scipy, with
the chip idle).
"""

from __future__ import annotations

import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

import slate_tpu as slate
from slate_tpu import obs
from slate_tpu.robust import ladder

from benchmarks.harness import check, plain_svd
from benchmarks.traffic import closed_loop_solve

LIMITS = {"residual_fro": "tol_fro_eps", "residual_max": "tol_eps",
          "orth_u": "tol_orth_u_eps", "orth_v": "tol_orth_v_eps",
          "values_max": "tol_values_eps"}
CHOICES = {"gesvd.path": "path", "tb2bd.backend": "rung",
           "gesvd.bidiag": "route"}


class Session:
    """One cell's system under test, its operand and its records."""

    def __init__(self, spec: dict, devices, seed: int):
        config, traffic = spec["config"], spec["traffic"]
        if traffic["callers"] != 1:
            raise ValueError("closed_loop_svd drives one caller")
        for job in ("jobu", "jobvt"):
            if traffic[job] != "S" or config[job] != "S":
                raise ValueError("closed_loop_svd asks for the economy "
                                 "SVD with both sets of vectors")
        self.routine = traffic["routine"]
        self.warm_up_calls = max(1, traffic.get("warm_up_calls", 1))
        self.n, self.nb = config["n"], config["nb"]
        self.m = int(round(config["m_over_n"] * self.n))
        p, q = config["grid"]
        self.chips = p * q
        self.method = slate.MethodSVD[config["method_svd"]]
        self.tier = config["tier"]
        self.opts = self._opts(self.tier)
        self.limits = {name: spec["cell"][key] * check.EPS
                       for name, key in LIMITS.items()}
        self.on_chip = devices[0].platform == "tpu"
        grid = slate.Grid(p, q, devices=devices[:self.chips])
        (sa,) = closed_loop_solve.sub_seeds(
            seed, traffic.get("seed_offset", 0), 1)
        self.A = slate.random_matrix(self.m, self.n, self.nb, grid,
                                     jnp.dtype(config["dtype"]), seed=sa)
        jax.block_until_ready(self.A)
        self.demotions_before = len(ladder.demotion_log())
        self.walls: list = []
        self.attempted = 0
        self.failed = 0
        self.first_x = None         # (s, U, VT) of the first warm-up
        self.last = None            # ((s, U, VT),) of the newest call
        self.program: dict = {}     # what the program said of itself
        self._reference = None

    def _opts(self, tier: str) -> dict:
        return {slate.Option.MethodSVD: self.method,
                slate.Option.TrailingPrecision: tier}

    # -------------------------------------------------------------- calls

    def _shaped(self, answer) -> bool:
        s, U, VT = answer
        k = min(self.m, self.n)
        return (np.shape(s) == (k,) and (U.m, U.n) == (self.m, k)
                and (VT.m, VT.n) == (k, self.n))

    def _call(self, opts=None):
        """One public decomposition, U and VT drained and s read:
        (((s, U, VT),), wall seconds, ok)."""
        solve = getattr(slate, self.routine)
        t0 = time.perf_counter()
        s, U, VT = solve(self.A, self.opts if opts is None else opts,
                         want_u=True, want_vt=True)
        jax.block_until_ready((U.data, VT.data))
        s = np.asarray(s)
        wall = time.perf_counter() - t0
        ok = self._shaped((s, U, VT)) and math.isfinite(wall)
        return ((s, U, VT),), wall, ok

    def warm_up(self) -> float:
        """``warm_up_calls`` calls of the one program set the window
        uses. Returns the first call's wall. The program's counters are
        on for these calls alone (they are off in the window, as in a
        deployment), which is where ``program`` comes from: the path,
        the rung that chased, the route of the bidiagonal solve and the
        share of poles its merges deflated."""
        first_s = None
        was_on = obs.metrics_enabled()
        obs.metrics_on()
        before = self._counters()
        try:
            for _ in range(self.warm_up_calls):
                self.last = out = None
                out, wall, ok = self._call()
                if not ok:
                    raise SystemExit(f"warm-up {self.routine}: no answer")
                if first_s is None:
                    first_s, self.first_x = wall, out[0]
                self.last = out
            after = self._counters()
        finally:
            if not was_on:
                obs.metrics_off()
        self.program = {
            label: ({k: v - before[label].get(k, 0)
                     for k, v in counted.items()
                     if v - before[label].get(k, 0)}
                    if isinstance(counted, dict)
                    else counted - before[label])
            for label, counted in after.items()}
        return first_s

    @staticmethod
    def _counters() -> dict:
        """``{"path": {"two_stage": 2}, "rung": {...}, "route": {...},
        "merges", "poles", "deflated", "demotions_counted"}`` as the
        program's counters stand."""
        from slate_tpu.obs import metrics
        said = {label: {dict(labels).get(label): int(v) for labels, v
                        in metrics.counters_named(counter).items()}
                for counter, label in CHOICES.items()}
        for label, counter in (("merges", "stedc.merges"),
                               ("poles", "stedc.poles"),
                               ("deflated", "stedc.deflated"),
                               ("demotions_counted", "tb2bd.demotion")):
            said[label] = int(obs.count_total(counter))
        return said

    # the same loop: it needs ``_call``, ``walls``, ``attempted``,
    # ``failed`` and ``last``, which this session has under those names
    drive = closed_loop_solve.Session.drive

    def lower_precision(self, tier: str):
        """The control: (s, U, VT) of the same public call at a lower
        tier (``benchmarks/control.py``; no run calls this)."""
        out, _, ok = self._call(self._opts(tier))
        if not ok:
            raise SystemExit(f"control {self.routine}/{tier}: no answer")
        return out[0]

    # -------------------------------------------------------------- check

    def numbers_of(self, answers: dict) -> dict:
        """``{label: {"residual_fro", "residual_max", "orth_u", "orth_v",
        "values_max"}}`` for each (s, U, VT) in ``answers`` (None: no
        answer, reads nan), each printed on a line of its own in units
        of 2^-24."""
        Ad = self.A.to_dense()
        if self._reference is None:
            t0 = time.perf_counter()
            self._reference = plain_svd.reference_values(Ad)
            print(json.dumps({"step": "svd_reference",
                              "gram_eigvalsh_f64_s":
                              time.perf_counter() - t0}), flush=True)
        out = {}
        for label, answer in answers.items():
            if answer is None:
                out[label] = dict.fromkeys(LIMITS, float("nan"))
                continue
            s, U, VT = answer
            numbers = plain_svd.equations(Ad, s, U.to_dense(),
                                          VT.to_dense())
            numbers["values_max"] = plain_svd.values_error(
                s, self._reference)
            out[label] = numbers
            print(json.dumps({"step": "svd_errors", "answer": label,
                              "in_eps": {k: v / check.EPS for k, v
                                         in numbers.items()}}), flush=True)
        return out

    def errors_of(self, answers: dict) -> dict:
        """``control.py``'s two keys: ``inf`` is ``svd_residual_max``,
        ``fro`` is ``svd_residual_fro``."""
        return {label: {"inf": n["residual_max"], "fro": n["residual_fro"]}
                for label, n in self.numbers_of(answers).items()}

    def check(self) -> list:
        """Each number compared, beside its limit: the five of the
        warm-up answer and of the window's last, that every s checked
        descends, the shapes, that no call was answered by a demoted
        rung, and what the program's counters said answered the warm-up
        calls."""
        answers = {"warm_up": self.first_x,
                   "last": self.last[0] if self.last else None}
        rows = []
        for label, numbers in self.numbers_of(answers).items():
            for name, value in numbers.items():
                limit = self.limits[name]
                rows.append({"check": f"svd_{name}.{label}",
                             "value": value, "limit": limit,
                             "ok": check.within(value, limit)})
        given = [a for a in answers.values() if a is not None]
        for name, holds in (
                ("svd.descending", lambda a: plain_svd.descending(a[0])),
                ("svd.shape", self._shaped)):
            good = sum(1 for a in given if holds(a))
            rows.append({"check": name, "value": good,
                         "limit": len(answers),
                         "ok": good == len(answers)})
        said = self.program
        logged = [str(d) for d in
                  ladder.demotion_log()[self.demotions_before:]
                  if d.ladder == "tb2bd"]
        demotions = max(len(logged), said.get("demotions_counted", 0))
        rows.append({"check": "svd.demotions", "value": demotions,
                     "limit": 0, "ok": demotions == 0, "log": logged})
        # one path (two-stage), one rung (on the chip: vmem), one route
        # of the bidiagonal solve (the device one), each counted once a
        # warm-up call
        one_each = all(
            len(said.get(label, {})) == 1
            and sum(said[label].values()) == self.warm_up_calls
            for label in CHOICES.values())
        ok = (one_each and list(said["path"]) == ["two_stage"]
              and list(said["route"]) == ["gk_stedc"]
              and (not self.on_chip or list(said["rung"]) == ["vmem"]))
        poles = said.get("poles", 0)
        rows.append({"check": "svd.program", "value": int(ok), "limit": 1,
                     "ok": ok, **said, "deflated_share":
                     said.get("deflated", 0) / poles if poles else None})
        return rows


def open_session(spec: dict, devices, seed: int) -> Session:
    from slate_tpu.linalg import svd
    if ("slate.gesvd" not in getattr(svd, "SPANS", ())
            or "gesvd.path" not in getattr(svd, "COUNTERS", ())):
        raise SystemExit(
            f"benchmarks/traffic/closed_loop_svd: this program's "
            f"slate_tpu.linalg.svd names no slate.gesvd root span / no "
            f"gesvd.path counter, so cell {spec['name']}'s spans cannot "
            f"be read, what answered the call cannot be checked, and its "
            f"bidiagonal solve is a host eigenproblem of order 2n")
    return Session(spec, devices, seed)
