"""Traffic kind ``closed_loop_eig``: ``closed_loop_solve``'s one caller
around a public eigensolver, ``lam, Z = slate.<routine>(A, opts)``.

A mix of this kind is a JSON file beside this one::

    {"kind": "closed_loop_eig", "routine": "heev", "jobz": "V",
     "callers": 1, "warm_up_calls": 2, "seed_offset": 0}

The operand is made on the cell's device from the seed
(``slate.random_spd``); every call decomposes the same A. The options
are the configuration's: ``method_eig`` as ``Option.MethodEig`` and
``tier`` as ``Option.TrailingPrecision``. Nothing else is passed and no
environment variable is set: the band the chase runs at and the
``hb2st`` backend are the library's choice, and are read back.

The comparison that decides ``correct`` is ``harness/plain_eig.py``:
the defining equations of (lam, Z) and the eigenvalues against a
float64 LAPACK solve, on the warm-up answer and on the window's last,
each number beside its limit (``tol_eps``: ``eig_residual_max``,
``tol_fro_eps``: ``eig_residual_fro``, ``tol_orth_eps``,
``tol_values_eps``, all in units of 2^-24); ``eig.ascending``; and
``eig.demotions`` = 0: an answer from a rung below the one the ladder
preferred is right and is not this deployment.

``benchmarks/control.py`` sweeps this kind as it stands: ``errors_of``
answers its two keys with ``eig_residual_max`` (``inf``) and
``eig_residual_fro`` (``fro``) of a (lam, Z) pair, and prints all four
numbers of every pair on a line of their own (``"step":
"eig_errors"``), which is where the other two limits are set from.

``open_session`` refuses, before any operand is made, a program whose
``linalg.eig`` names no ``slate.heev`` root span or no
``hb2st.demotion`` counter: its spans could not be paired with the
trace, the guarantee could not be checked, and such a program solves
every merge's secular equation on the host in numpy, minutes a run.
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

from benchmarks.harness import check, plain_eig
from benchmarks.traffic import closed_loop_solve

LIMITS = {"residual_max": "tol_eps", "residual_fro": "tol_fro_eps",
          "orth_fro": "tol_orth_eps", "values_max": "tol_values_eps"}


class Session:
    """One cell's system under test, its operand and its records."""

    def __init__(self, spec: dict, devices, seed: int):
        config, traffic = spec["config"], spec["traffic"]
        if traffic["callers"] != 1:
            raise ValueError("closed_loop_eig drives one caller")
        if traffic["jobz"] != "V" or config["jobz"] != "V":
            raise ValueError("closed_loop_eig asks for vectors")
        self.routine = traffic["routine"]
        self.warm_up_calls = max(1, traffic.get("warm_up_calls", 1))
        self.n, self.nb = config["n"], config["nb"]
        p, q = config["grid"]
        self.chips = p * q
        self.method = slate.MethodEig[config["method_eig"]]
        self.tier = config["tier"]
        self.opts = self._opts(self.tier)
        self.limits = {name: spec["cell"][key] * check.EPS
                       for name, key in LIMITS.items()}
        grid = slate.Grid(p, q, devices=devices[:self.chips])
        (sa,) = closed_loop_solve.sub_seeds(
            seed, traffic.get("seed_offset", 0), 1)
        self.A = slate.random_spd(self.n, nb=self.nb, grid=grid,
                                  dtype=jnp.dtype(config["dtype"]),
                                  seed=sa)
        jax.block_until_ready(self.A)
        self.demotions_before = len(ladder.demotion_log())
        self.walls: list = []
        self.attempted = 0
        self.failed = 0
        self.first_x = None         # (lam, Z) of the first warm-up
        self.last = None            # ((lam, Z),) of the newest call
        self.program: dict = {}     # what the program said of itself
        self._reference = None

    def _opts(self, tier: str) -> dict:
        return {slate.Option.MethodEig: self.method,
                slate.Option.TrailingPrecision: tier}

    # -------------------------------------------------------------- calls

    def _call(self, opts=None):
        """One public eigensolve, Z drained and lam read: (((lam, Z),),
        wall seconds, ok)."""
        solve = getattr(slate, self.routine)
        t0 = time.perf_counter()
        lam, Z = solve(self.A, self.opts if opts is None else opts)
        jax.block_until_ready(Z.data)
        lam = np.asarray(lam)
        wall = time.perf_counter() - t0
        ok = (lam.shape == (self.n,) and (Z.m, Z.n) == (self.n, self.n)
              and math.isfinite(wall))
        return ((lam, Z),), wall, ok

    def warm_up(self) -> float:
        """``warm_up_calls`` calls of the one program set the window
        uses. Returns the first call's wall. The program's counters are
        on for these calls alone (they are off in the window, as in a
        deployment), which is where ``program`` comes from: the rung
        that chased, and the share of poles the merges deflated."""
        first_s = None
        was_on = obs.metrics_enabled()
        obs.metrics_on()
        try:
            for _ in range(self.warm_up_calls):
                self.last = out = None
                out, wall, ok = self._call()
                if not ok:
                    raise SystemExit(f"warm-up {self.routine}: no answer")
                if first_s is None:
                    first_s, self.first_x = wall, out[0]
                self.last = out
            self.program = self._counters()
        finally:
            if not was_on:
                obs.metrics_off()
        return first_s

    def _counters(self) -> dict:
        from slate_tpu.obs import metrics
        rungs = {dict(labels).get("rung"): int(v) for labels, v in
                 metrics.counters_named("hb2st.backend").items()}
        poles = obs.count_total("stedc.poles")
        return {"chase_backend": rungs,
                "merges": int(obs.count_total("stedc.merges")),
                "deflated_share": (obs.count_total("stedc.deflated")
                                   / poles if poles else None),
                "demotions_counted": int(
                    obs.count_total("hb2st.demotion"))}

    # the same loop: it needs ``_call``, ``walls``, ``attempted``,
    # ``failed`` and ``last``, which this session has under those names
    drive = closed_loop_solve.Session.drive

    def lower_precision(self, tier: str):
        """The control: (lam, Z) of the same public call at a lower
        tier (``benchmarks/control.py``; no run calls this)."""
        out, _, ok = self._call(self._opts(tier))
        if not ok:
            raise SystemExit(f"control {self.routine}/{tier}: no answer")
        return out[0]

    # -------------------------------------------------------------- check

    def numbers_of(self, answers: dict) -> dict:
        """``{label: {"residual_max", "residual_fro", "orth_fro",
        "values_max"}}`` for each (lam, Z) in ``answers`` (None: no
        answer, reads nan), each printed on a line of its own in units
        of 2^-24."""
        Ad = plain_eig.symmetric_of(self.A.to_dense())
        if self._reference is None:
            t0 = time.perf_counter()
            self._reference = plain_eig.reference_values(Ad)
            print(json.dumps({"step": "eig_reference", "eigvalsh_f64_s":
                              time.perf_counter() - t0}), flush=True)
        out = {}
        for label, pair in answers.items():
            if pair is None:
                out[label] = dict.fromkeys(LIMITS, float("nan"))
                continue
            lam, Z = pair
            numbers = plain_eig.equations(Ad, lam, Z.to_dense())
            numbers["values_max"] = plain_eig.values_error(
                lam, self._reference)
            out[label] = numbers
            print(json.dumps({"step": "eig_errors", "answer": label,
                              "in_eps": {k: v / check.EPS for k, v
                                         in numbers.items()}}), flush=True)
        return out

    def errors_of(self, answers: dict) -> dict:
        """``control.py``'s two keys: ``inf`` is ``eig_residual_max``,
        ``fro`` is ``eig_residual_fro``."""
        return {label: {"inf": n["residual_max"], "fro": n["residual_fro"]}
                for label, n in self.numbers_of(answers).items()}

    def check(self) -> list:
        """Each number compared, beside its limit: the four of the
        warm-up answer and of the window's last, that every lam checked
        ascends, and that no call was answered by a demoted rung."""
        answers = {"warm_up": self.first_x,
                   "last": self.last[0] if self.last else None}
        rows = []
        for label, numbers in self.numbers_of(answers).items():
            for name, value in numbers.items():
                limit = self.limits[name]
                rows.append({"check": f"eig_{name}.{label}",
                             "value": value, "limit": limit,
                             "ok": check.within(value, limit)})
        sorted_ = sum(1 for pair in answers.values() if pair is not None
                      and plain_eig.ascending(pair[0]))
        rows.append({"check": "eig.ascending", "value": sorted_,
                     "limit": len(answers), "ok": sorted_ == len(answers)})
        logged = [str(d) for d in
                  ladder.demotion_log()[self.demotions_before:]
                  if d.ladder == "hb2st"]
        demotions = max(len(logged),
                        self.program.get("demotions_counted", 0))
        rows.append({"check": "eig.demotions", "value": demotions,
                     "limit": 0, "ok": demotions == 0, "log": logged,
                     **self.program})
        return rows


def open_session(spec: dict, devices, seed: int) -> Session:
    from slate_tpu.linalg import eig
    if ("slate.heev" not in getattr(eig, "SPANS", ())
            or "hb2st.demotion" not in getattr(eig, "COUNTERS", ())):
        raise SystemExit(
            f"benchmarks/traffic/closed_loop_eig: this program's "
            f"slate_tpu.linalg.eig names no slate.heev root span / no "
            f"hb2st.demotion counter, so cell {spec['name']}'s spans "
            f"cannot be read and its guarantee (no demoted rung) cannot "
            f"be checked")
    return Session(spec, devices, seed)
