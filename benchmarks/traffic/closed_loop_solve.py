"""Traffic kind ``closed_loop_solve``: callers that each wait for their
answer before asking again — one caller here, so the device sees one
public ``slate.<routine>(A, B)`` at a time.

A mix of this kind is a JSON file beside this one::

    {"kind": "closed_loop_solve", "routine": "posv", "callers": 1,
     "warm_up_calls": 2, "seed_offset": 0}

The operands are made on the cell's device(s) from the seed
(``slate.random_spd`` / ``slate.random_matrix``); every call solves the
same system, as a loop around a solver does while it waits for nothing
else. Only the public API is touched.
"""

from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np

import slate_tpu as slate

from benchmarks.harness import check


def sub_seeds(seed: int, offset: int, count: int) -> list:
    """``count`` 31-bit seeds from any whole ``--seed`` (the program's
    generators take an int32)."""
    state = np.random.SeedSequence([int(seed), int(offset)])
    return [int(s) % (2 ** 31 - 1) for s in state.generate_state(count)]


class Session:
    """One cell's system under test, its operands and its records."""

    def __init__(self, spec: dict, devices, seed: int):
        config, traffic = spec["config"], spec["traffic"]
        if traffic["callers"] != 1:
            raise ValueError("closed_loop_solve drives one caller")
        self.routine = traffic["routine"]
        self.warm_up_calls = max(1, traffic.get("warm_up_calls", 1))
        self.hermitian = self.routine == "posv"
        self.n, self.nb = config["n"], config["nb"]
        self.nrhs = config["nrhs"]
        p, q = config["grid"]
        self.chips = p * q
        # the configuration's stated precision, through the one public
        # switch the program has for it
        self.opts = {slate.Option.TrailingPrecision: config["tier"]}
        self.limits = {"inf": spec["cell"]["tol_eps"] * check.EPS,
                       "fro": spec["cell"]["tol_fro_eps"] * check.EPS}
        dtype = jnp.dtype(config["dtype"])
        grid = slate.Grid(p, q, devices=devices[:self.chips])
        sa, sb = sub_seeds(seed, traffic.get("seed_offset", 0), 2)
        if self.hermitian:
            self.A = slate.random_spd(self.n, nb=self.nb, grid=grid,
                                      dtype=dtype, seed=sa)
        else:
            self.A = slate.random_matrix(self.n, self.n, self.nb, grid,
                                         dtype, seed=sa)
        self.B = slate.random_matrix(self.n, self.nrhs, self.nb, grid,
                                     dtype, seed=sb)
        jax.block_until_ready((self.A, self.B))
        self.walls: list = []
        self.attempted = 0
        self.failed = 0
        self.first_x = None
        self.last = None

    # -------------------------------------------------------------- calls

    def _call(self):
        """One public solve, drained, its ``info`` read: (outputs, wall
        seconds, ok)."""
        solve = getattr(slate, self.routine)
        t0 = time.perf_counter()
        out = jax.block_until_ready(solve(self.A, self.B, self.opts))
        info = int(out[-1])
        wall = time.perf_counter() - t0
        return out, wall, (info == 0 and math.isfinite(wall))

    def warm_up(self) -> float:
        """``warm_up_calls`` calls of the one program set this cell's
        window uses (a second one because on the 2x2 only the second
        call reaches the memory and the pace of the rest, PERF.md
        section 5). Returns the first call's wall; a warm-up that does
        not answer ``info == 0`` ends the run."""
        first_s = None
        for _ in range(self.warm_up_calls):
            self.last = out = None
            out, wall, ok = self._call()
            if not ok:
                raise SystemExit(f"warm-up {self.routine}: info != 0")
            if first_s is None:
                first_s, self.first_x = wall, out[0]
            self.last = out
        return first_s

    def drive(self, until: float | None = None, calls: int | None = None,
              annotate=None) -> None:
        """Call until the clock passes ``until`` (the call in flight
        finishes and counts) or ``calls`` times; each call inside
        ``annotate()`` where given. Only the newest outputs are kept,
        and the ones before are freed ahead of the next call."""
        done = 0
        while (calls is None or done < calls) and \
                (until is None or time.perf_counter() < until):
            self.last = out = None      # nothing of the call before lives on
            self.attempted += 1
            try:
                if annotate is None:
                    out, wall, ok = self._call()
                else:
                    with annotate():
                        out, wall, ok = self._call()
            except (RuntimeError, ValueError, ArithmeticError) as e:
                print(f"# solve raised {type(e).__name__}: {e}",
                      flush=True)
                wall, ok = float("nan"), False
            if ok:
                self.walls.append(wall)
                self.last = out
            else:
                self.failed += 1
            done += 1

    def lower_precision(self, tier: str):
        """The control: X of the program with its own lower-precision
        path switched on (``benchmarks/control.py``; no run calls this).
        ``slate.gesv`` drops ``opts`` on its one-chip fast path (PERF.md
        Open questions), so there the same factorization is reached
        through ``slate.getrf`` + ``slate.getrs``, which pass the tier."""
        opts = {slate.Option.TrailingPrecision: tier}
        if self.routine == "gesv" and self.chips == 1:
            LU, piv, info = slate.getrf(self.A, opts)
            X = slate.getrs(LU, piv, self.B, opts=opts)
        else:
            out = getattr(slate, self.routine)(self.A, self.B, opts)
            X, info = out[0], out[-1]
        if int(info) != 0:
            raise SystemExit(f"control {self.routine}/{tier}: info != 0")
        return jax.block_until_ready(X)

    # -------------------------------------------------------------- check

    def errors_of(self, answers: dict) -> dict:
        """``{label: {"inf": e, "fro": e}}`` for each X in ``answers``
        (None: no answer, reads nan)."""
        Ad = check.dense_of(self.A, self.hermitian)
        Bd = self.B.to_dense()
        nan = {"inf": float("nan"), "fro": float("nan")}
        return {label: (check.backward_errors(Ad, X.to_dense(), Bd)
                        if X is not None else nan)
                for label, X in answers.items()}

    def check(self) -> list:
        """Each number compared, beside its limit: the backward errors
        of the warm-up X and of the window's last X and, over several
        chips, where A, the factor and X sit."""
        rows = []
        errors = self.errors_of({
            "warm_up": self.first_x,
            "last": self.last[0] if self.last else None})
        for label, by_norm in errors.items():
            for norm, value in by_norm.items():
                limit = self.limits[norm]
                rows.append({"check": f"backward_error_{norm}.{label}",
                             "value": value, "limit": limit,
                             "ok": check.within(value, limit)})
        if self.chips > 1 and self.last is not None:
            for label, M in (("A", self.A), ("factor", self.last[1]),
                             ("X", self.last[0])):
                where = check.equal_shards(M.data, self.chips)
                rows.append({"check": f"equal_shards.{label}",
                             "value": where["devices"],
                             "limit": self.chips, "ok": where["ok"],
                             "shard_bytes": where["shard_bytes"]})
        return rows


def open_session(spec: dict, devices, seed: int) -> Session:
    return Session(spec, devices, seed)
