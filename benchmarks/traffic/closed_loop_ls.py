"""Traffic kind ``closed_loop_ls``: ``closed_loop_solve``'s one caller
around a public least-squares solver, ``X = slate.<routine>(A, B, opts)``
with a tall A (m = ``m_over_n`` x n rows).

A mix of this kind is a JSON file beside this one::

    {"kind": "closed_loop_ls", "routine": "gels", "callers": 1,
     "warm_up_calls": 2, "seed_offset": 0}

The operands are made on the cell's device from the seed
(``slate.random_matrix``, iid normal: A m x n, B m x nrhs, two
sub-seeds); every call solves the same problem. The options are the
configuration's: ``method_gels`` as ``Option.MethodGels`` and ``tier``
as ``Option.TrailingPrecision``. Nothing else is passed and no
environment variable is set: which ``geqrf`` program and which panel
form answer is the library's choice, and is read back.

The comparison that decides ``correct`` is ``harness/plain_ls.py``:
the float64 LAPACK solution of the gathered A and B, and three numbers
of the warm-up X and of the window's last X against it, each beside its
limit in units of 2^-24 (``tol_opt_eps``: ``ls_optimality``,
``tol_forward_eps``: ``ls_forward``, ``tol_excess_eps``:
``ls_residual_excess``); the shape of X; and ``ls.program``: the
program's counters over the warm-up calls name the configuration's
method, one ``geqrf`` program and one panel form, once a call each.

``benchmarks/control.py`` sweeps this kind as it stands: ``errors_of``
answers its two keys with ``ls_optimality`` (``inf``) and ``ls_forward``
(``fro``), and prints all three numbers of every X on a line of their
own (``"step": "ls_errors"``), which is where the limits are set from.

Under ``--rehearse-on-cpu --n`` the shape keeps m / n (``cells.load_cell``
shrinks ``n`` and ``nb`` only).

``open_session`` refuses, before any operand is made, a program whose
``linalg.geqrf`` names no ``slate.gels`` root span or no ``geqrf.path``
counter: its spans could not be paired with the trace and what answered
could not be read back.
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

from benchmarks.harness import check, plain_ls
from benchmarks.traffic import closed_loop_solve

LIMITS = {"optimality": "tol_opt_eps", "forward": "tol_forward_eps",
          "residual_excess": "tol_excess_eps"}
CHOICES = {"gels.method": "method", "geqrf.path": "program",
           "geqrf.panel": "panel"}


class Session:
    """One cell's system under test, its operands and its records."""

    def __init__(self, spec: dict, devices, seed: int):
        config, traffic = spec["config"], spec["traffic"]
        if traffic["callers"] != 1:
            raise ValueError("closed_loop_ls drives one caller")
        self.routine = traffic["routine"]
        self.warm_up_calls = max(1, traffic.get("warm_up_calls", 1))
        self.n, self.nb = config["n"], config["nb"]
        self.m = config["m_over_n"] * self.n
        self.nrhs = config["nrhs"]
        p, q = config["grid"]
        self.chips = p * q
        self.method = slate.MethodGels[config["method_gels"]]
        self.tier = config["tier"]
        self.opts = self._opts(self.tier)
        self.limits = {name: spec["cell"][key] * check.EPS
                       for name, key in LIMITS.items()}
        dtype = jnp.dtype(config["dtype"])
        grid = slate.Grid(p, q, devices=devices[:self.chips])
        sa, sb = closed_loop_solve.sub_seeds(
            seed, traffic.get("seed_offset", 0), 2)
        self.A = slate.random_matrix(self.m, self.n, self.nb, grid, dtype,
                                     seed=sa)
        self.B = slate.random_matrix(self.m, self.nrhs, self.nb, grid,
                                     dtype, seed=sb)
        jax.block_until_ready((self.A, self.B))
        self.walls: list = []
        self.attempted = 0
        self.failed = 0
        self.first_x = None         # X of the first warm-up
        self.last = None            # (X,) of the newest call
        self.program: dict = {}     # what the program said of itself
        self._reference = None

    def _opts(self, tier: str) -> dict:
        return {slate.Option.MethodGels: self.method,
                slate.Option.TrailingPrecision: tier}

    # -------------------------------------------------------------- calls

    def _call(self, opts=None):
        """One public solve, X drained: ((X,), wall seconds, ok)."""
        solve = getattr(slate, self.routine)
        t0 = time.perf_counter()
        X = solve(self.A, self.B, self.opts if opts is None else opts)
        jax.block_until_ready(X.data)
        wall = time.perf_counter() - t0
        ok = (X.m, X.n) == (self.n, self.nrhs) and math.isfinite(wall)
        return (X,), wall, ok

    def warm_up(self) -> float:
        """``warm_up_calls`` calls of the one program set the window
        uses. Returns the first call's wall. The program's counters are
        on for these calls alone (they are off in the window, as in a
        deployment), which is where ``program`` comes from: the method,
        the ``geqrf`` program and the panel form that answered."""
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
            label: {k: v - before[label].get(k, 0)
                    for k, v in counted.items()
                    if v - before[label].get(k, 0)}
            for label, counted in after.items()}
        return first_s

    @staticmethod
    def _counters() -> dict:
        """``{"method": {"Geqrf": 2}, "program": {...}, "panel": {...}}``
        as the program's counters stand."""
        from slate_tpu.obs import metrics
        return {label: {dict(labels).get(label): int(v) for labels, v
                        in metrics.counters_named(counter).items()}
                for counter, label in CHOICES.items()}

    # the same loop: it needs ``_call``, ``walls``, ``attempted``,
    # ``failed`` and ``last``, which this session has under those names
    drive = closed_loop_solve.Session.drive

    def lower_precision(self, tier: str):
        """The control: X of the same public call at a lower tier
        (``benchmarks/control.py``; no run calls this)."""
        out, _, ok = self._call(self._opts(tier))
        if not ok:
            raise SystemExit(f"control {self.routine}/{tier}: no answer")
        return out[0]

    # -------------------------------------------------------------- check

    def numbers_of(self, answers: dict) -> dict:
        """``{label: {"optimality", "forward", "residual_excess"}}`` for
        each X in ``answers`` (None: no answer, reads nan), each printed
        on a line of its own in units of 2^-24."""
        Ad = np.asarray(self.A.to_dense())
        Bd = np.asarray(self.B.to_dense())
        if self._reference is None:
            t0 = time.perf_counter()
            self._reference = plain_ls.reference(Ad, Bd)
            print(json.dumps({"step": "ls_reference", "qr_f64_s":
                              time.perf_counter() - t0}), flush=True)
        out = {}
        for label, X in answers.items():
            if X is None:
                out[label] = dict.fromkeys(LIMITS, float("nan"))
                continue
            out[label] = plain_ls.numbers(Ad, Bd, np.asarray(X.to_dense()),
                                          self._reference)
            print(json.dumps({"step": "ls_errors", "answer": label,
                              "in_eps": {k: v / check.EPS for k, v
                                         in out[label].items()}}),
                  flush=True)
        return out

    def errors_of(self, answers: dict) -> dict:
        """``control.py``'s two keys: ``inf`` is ``ls_optimality``,
        ``fro`` is ``ls_forward``."""
        return {label: {"inf": n["optimality"], "fro": n["forward"]}
                for label, n in self.numbers_of(answers).items()}

    def check(self) -> list:
        """Each number compared, beside its limit: the three of the
        warm-up X and of the window's last, the shape of each X, and
        what the program's counters said answered the warm-up calls."""
        answers = {"warm_up": self.first_x,
                   "last": self.last[0] if self.last else None}
        rows = []
        for label, numbers in self.numbers_of(answers).items():
            for name, value in numbers.items():
                limit = self.limits[name]
                rows.append({"check": f"ls_{name}.{label}", "value": value,
                             "limit": limit,
                             "ok": check.within(value, limit)})
        shaped = sum(1 for X in answers.values() if X is not None
                     and (X.m, X.n) == (self.n, self.nrhs))
        rows.append({"check": "ls.shape", "value": shaped,
                     "limit": len(answers), "ok": shaped == len(answers)})
        # one method (the configuration's), one program, one panel form,
        # each counted once a warm-up call
        said = self.program
        one_each = all(
            len(said.get(label, {})) == 1
            and sum(said[label].values()) == self.warm_up_calls
            for label in CHOICES.values())
        ok = one_each and list(said["method"]) == [self.method.name]
        rows.append({"check": "ls.program", "value": int(ok), "limit": 1,
                     "ok": ok, **said})
        return rows


def open_session(spec: dict, devices, seed: int) -> Session:
    from slate_tpu.linalg import geqrf
    if ("slate.gels" not in getattr(geqrf, "SPANS", ())
            or "geqrf.path" not in getattr(geqrf, "COUNTERS", ())):
        raise SystemExit(
            f"benchmarks/traffic/closed_loop_ls: this program's "
            f"slate_tpu.linalg.geqrf names no slate.gels root span / no "
            f"geqrf.path counter, so cell {spec['name']}'s spans cannot "
            f"be read and what answered the call cannot be checked")
    return Session(spec, devices, seed)
