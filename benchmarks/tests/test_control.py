"""The control of the correctness check, at a size a test run holds.

On the chip the control is the program itself with its public
``Option.TrailingPrecision`` set one tier down (``benchmarks/control.py``;
PERF.md section 2 holds those readings). Here, where every tier is f32,
the control is the plain solver of ``harness/plain_solver.py`` put in the
program's place with its trailing products computed as the MXU computes
them at that tier. Against each one-chip cell's own limits the plain
f32 answer passes both numbers and the lower precision fails one.

The residual is evaluated in float64 here: XLA:CPU's f32 accumulation
makes the check's own rounding as large as the sound error at this
size, which the chip's MXU accumulation does not (PERF.md section 2:
on the chip the sound Frobenius reading is 0.058 eps, the control's
0.217 eps, through ``check.backward_errors`` itself).
"""

import numpy as np
import pytest

from benchmarks.harness import cells, check, plain_solver

N, NB, NRHS = 2048, 256, 8
SEEDS = (3, 2_147_483_659, 4_000_000_007)


def operands(routine, seed):
    rng = np.random.default_rng(seed)
    G = rng.standard_normal((N, N)).astype(np.float32)
    A = (G @ G.T / N + np.eye(N, dtype=np.float32)) \
        if routine == "posv" else G
    return A.astype(np.float32), \
        rng.standard_normal((N, NRHS)).astype(np.float32)


def errors_in_eps(A, X, B):
    """``check.backward_errors``' formula, in float64."""
    A, X, B = (np.asarray(M, np.float64) for M in (A, X, B))
    R = A @ X - B
    out = {}
    for label, ord_ in (("inf", np.inf), ("fro", "fro")):
        def norm(M):
            return np.linalg.norm(M, ord=ord_)
        out[label] = norm(R) / (norm(A) * norm(X) + norm(B)) / check.EPS
    return out


def test_the_float64_evaluation_is_the_checks_formula():
    A, B = operands("gesv", 1)
    X = plain_solver.gesv(A, B, NB, "mxu_bf16")     # a large residual
    ours, theirs = errors_in_eps(A, X, B), check.backward_errors(A, X, B)
    for norm in ours:
        assert theirs[norm] / check.EPS == pytest.approx(ours[norm],
                                                         rel=1e-3)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("cell", ["gesv_16k_1x1", "posv_16k_1x1"])
def test_plain_f32_passes_and_the_control_fails(cell, seed):
    spec = cells.load_cell(cell)
    routine = spec["traffic"]["routine"]
    limits = {"inf": spec["cell"]["tol_eps"],
              "fro": spec["cell"]["tol_fro_eps"]}
    control = spec["cell"]["control_tier"]
    solve = getattr(plain_solver, routine)
    A, B = operands(routine, seed)
    sound = errors_in_eps(A, solve(A, B, NB, "f32"), B)
    lower = errors_in_eps(A, solve(A, B, NB, control), B)
    assert all(sound[norm] <= limits[norm] for norm in limits), sound
    assert any(lower[norm] > limits[norm] for norm in limits), \
        (control, lower, limits)
    assert lower["fro"] > 2.5 * sound["fro"]


def test_the_split_products_are_what_they_claim():
    rng = np.random.default_rng(0)
    a = rng.standard_normal((64, 256)).astype(np.float32)
    b = rng.standard_normal((256, 64)).astype(np.float32)
    exact = a.astype(np.float64) @ b.astype(np.float64)
    scale = np.abs(a).astype(np.float64) @ np.abs(b).astype(np.float64)

    def err(p):
        return np.max(np.abs(plain_solver.dot_as(a, b, p) - exact) / scale)

    assert err("f32") < 2.0 ** -22
    assert 2.0 ** -22 < err("bf16_3x") < 2.0 ** -14
    assert 2.0 ** -12 < err("mxu_bf16") < 2.0 ** -7
