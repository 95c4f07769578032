"""GMRES-IR as the reference defines it (PR 35): ``slate.gesv_mixed_gmres``
and its three siblings against the plain numpy reference
``benchmarks/harness/plain_refine.py``, on 1×1 and 2×2.

On the CPU every precision tier is true f32, so a low-tier LU refines
nothing here. Real refinement is driven two ways: f64 working precision
over an f32 factor (the public call as it is), and f32 working
precision over a factor made by ``plain_refine`` with the trailing
products as the MXU computes them at ``bf16_3x`` / ``mxu_bf16`` (the
public call with only ``_getrf_native`` swapped).
"""

import jax.numpy as jnp
import numpy as np
import pytest

import slate_tpu as st
from slate_tpu import obs
from slate_tpu.linalg import getrf as getrf_mod
from slate_tpu.linalg import mixed
from slate_tpu.obs import flight, metrics, tracing
from benchmarks.harness import plain_refine

EPS32 = 2.0 ** -24
N, NB = 256, 32
ROUTINES = ("gesv_mixed", "posv_mixed", "gesv_mixed_gmres",
            "posv_mixed_gmres")


@pytest.fixture(autouse=True)
def _fresh_obs(monkeypatch):
    """Spans captured as inside a profiler session, counters on."""
    was_metrics, was_flight = obs.metrics_enabled(), flight.enabled()
    flight.enable()
    obs.reset()
    monkeypatch.setattr(tracing, "_profiling", lambda: True)
    obs.metrics_on()
    yield
    if not was_metrics:
        obs.metrics_off()
    if not was_flight:
        flight.disable()
    obs.reset()


@pytest.fixture(params=["1x1", "2x2"])
def grid(request, grid11, grid22):
    return grid11 if request.param == "1x1" else grid22


def never_satisfied(monkeypatch):
    """A stop criterion no f32 residual meets (on the CPU every tier is
    true f32 and the first residual check would pass): the refinement
    runs the steps it is allowed."""
    real = mixed._stop_factor
    monkeypatch.setattr(mixed, "_stop_factor",
                        lambda A, B: real(A, B) * 1e-9)


def operands(seed, dtype=np.float32, n=N):
    rng = np.random.default_rng(seed)
    return (rng.uniform(-1, 1, (n, n)).astype(dtype),
            rng.uniform(-1, 1, (n, 1)).astype(dtype))


def backward_error_eps(a, x, b):
    a = np.asarray(a, np.float64)
    x, b = (np.asarray(v, np.float64).reshape(-1) for v in (x, b))
    return (np.linalg.norm(a @ x - b, np.inf)
            / (np.linalg.norm(a, np.inf) * np.linalg.norm(x, np.inf)
               + np.linalg.norm(b, np.inf))) / EPS32


def on_grid(a, b, grid, nb=NB):
    return (st.Matrix.from_dense(a, nb=nb, grid=grid),
            st.Matrix.from_dense(b, nb=nb, grid=grid))


def plain_factor(monkeypatch, a, nb, grid, precision):
    """``_getrf_native`` answers with ``plain_refine``'s LU of ``a`` at
    ``precision``: an order on one device, LAPACK pivots on a grid."""
    LU, order = plain_refine.lu_factor(a, nb, precision)
    order2 = jnp.asarray(order.reshape(-1, nb), jnp.int32)
    piv = (getrf_mod.PivotOrder(order2) if grid.size == 1
           else getrf_mod.pivot_order_to_ipiv(order2))

    def fake(A, opts=None, *args, **kw):
        return (st.Matrix.from_dense(LU, nb=nb, grid=grid), piv,
                jnp.zeros((), jnp.int32))

    monkeypatch.setattr(getrf_mod, "_getrf_native", fake)
    return LU, order


def root_of(routine):
    (root,) = [s for s in obs.captured_spans()
               if s["name"] == "slate." + routine]
    return root


def spans_named(name):
    return [s for s in obs.captured_spans() if s["name"] == name]


# ------------------------------------------ against the plain reference

@pytest.mark.parametrize("seed", [11, 12])
def test_f64_over_f32_factor_matches_plain_reference(grid, seed):
    a, b = operands(seed, np.float64, n=192)
    # a few singular values pulled down: an f32 factor then leaves
    # GMRES more than one step to do
    u, s, vt = np.linalg.svd(a)
    a = (u * np.geomspace(s[0], s[0] * 1e-6, s.size)) @ vt
    A, B = on_grid(a, b, grid)
    X, iters, info = st.gesv_mixed_gmres(A, B)
    LU, order = plain_refine.lu_factor(a.astype(np.float32), NB, "f32")
    x, report = plain_refine.gmres_ir(a, b, LU, order)
    assert int(info) == 0 and report["converged"]
    assert report["inner"] >= 2
    assert abs(iters - report["inner"]) <= 1
    got = np.asarray(X.to_dense())[:, 0]
    # both stop at the same criterion, ε·√n·‖A‖∞‖x‖max on the
    # residual: the two x agree to that times the condition number
    assert np.abs(got - x).max() <= 1e-7 * np.abs(x).max()
    root = root_of("gesv_mixed_gmres")
    assert root["labels"]["converged"] == 1
    assert root["labels"]["fallback"] == 0
    assert root["labels"]["tier_lo"] == "float32"
    assert root["labels"]["inner"] == iters


@pytest.mark.parametrize("precision", ["bf16_3x", "mxu_bf16"])
def test_f32_over_plain_low_tier_factor(grid, precision, monkeypatch):
    a, b = operands(21)
    A, B = on_grid(a, b, grid)
    LU, order = plain_factor(monkeypatch, a, NB, grid, precision)
    X, iters, info = st.gesv_mixed_gmres(A, B)
    x, report = plain_refine.gmres_ir(a, b, LU, order)
    assert report["converged"] and report["outer"] == 1
    assert iters >= 1 and abs(iters - report["inner"]) <= 1
    got = np.asarray(X.to_dense())[:, 0]
    assert np.abs(got - x).max() <= 2e-4 * np.abs(x).max()
    # the limit a refined answer passes and the unrefined one fails
    limit = 20.0
    assert backward_error_eps(a, got, b) <= limit
    assert backward_error_eps(a, x, b) <= limit
    # the inner loop was left on the residual estimate
    (cycle,) = spans_named("mixed.cycle")
    assert cycle["labels"]["steps"] == iters < mixed.GMRES_RESTART
    if precision == "mxu_bf16":
        assert iters >= 3
    phases = [s["labels"]["phase"] for s in spans_named("mixed.solve_lo")]
    assert phases == ["initial"] + ["arnoldi"] * iters + ["update"]
    kind = "order_gather" if grid.size == 1 else "swap_sim"
    assert {s["labels"]["kind"] for s in
            spans_named("getrs.apply_pivots")} == {kind}
    assert metrics.counter_value(
        "mixed.solve_lo", routine="gesv_mixed_gmres",
        pivots=kind) == iters + 2
    assert metrics.counter_value("mixed.iters", kind="inner",
                                 routine="gesv_mixed_gmres") == iters
    assert metrics.counter_total("mixed.fallback") == 0


@pytest.mark.parametrize("precision", ["bf16_3x", "mxu_bf16"])
def test_control_without_refinement_fails_the_limit(grid, precision,
                                                    monkeypatch):
    a, b = operands(21)
    A, B = on_grid(a, b, grid)
    LU, order = plain_factor(monkeypatch, a, NB, grid, precision)
    X, iters, info = st.gesv_mixed_gmres(
        A, B, {st.Option.MaxIterations: 0,
               st.Option.UseFallbackSolver: False})
    assert iters == -1
    got = np.asarray(X.to_dense())[:, 0]
    x0, report = plain_refine.gmres_ir(a, b, LU, order, refine=False)
    assert not report["converged"]
    assert backward_error_eps(a, got, b) > 20.0
    assert backward_error_eps(a, x0, b) > 20.0
    assert not spans_named("mixed.cycle")
    assert not spans_named("mixed.fallback")


# --------------------------------------------------- the one-chip order

def test_fast_path_order_reaches_every_refinement_solve(grid11,
                                                        monkeypatch):
    monkeypatch.setenv("SLATE_LU_FAST", "1")
    n, nb = 384, 128
    a, b = operands(31, n=n)
    A, B = on_grid(a, b, grid11, nb)
    never_satisfied(monkeypatch)
    X, iters, info = st.gesv_mixed_gmres(
        A, B, {st.Option.MaxIterations: 3,
               st.Option.UseFallbackSolver: False})
    assert int(info) == 0 and iters == -4
    applied = spans_named("getrs.apply_pivots")
    assert len(applied) >= 5
    assert {s["labels"]["kind"] for s in applied} == {"order_gather"}
    (chunk,) = spans_named("getrf.chunk")
    assert chunk["labels"]["phase"] == "fast_path"
    syncs = {s["name"] for s in obs.captured_spans()
             if s["labels"].get("sync") == 1}
    assert syncs <= {"mixed.anorm", "mixed.rnorm", "mixed.xnorm",
                     "mixed.beta", "mixed.h", "mixed.hn"}
    assert "gesv.order_to_ipiv" not in syncs
    assert backward_error_eps(a, np.asarray(X.to_dense())[:, 0], b) < 20.0


def test_public_getrf_still_returns_lapack_pivots(grid11, monkeypatch):
    monkeypatch.setenv("SLATE_LU_FAST", "1")
    n, nb = 384, 128
    a, b = operands(32, n=n)
    A, B = on_grid(a, b, grid11, nb)
    LU, piv, info = st.getrf(A)
    assert not isinstance(piv, getrf_mod.PivotOrder)
    assert piv.shape == (n // nb, nb) and piv.dtype == jnp.int32
    LUn, order, _ = getrf_mod._getrf_native(A)
    assert isinstance(order, getrf_mod.PivotOrder)
    assert np.array_equal(
        np.asarray(getrf_mod.pivot_order_to_ipiv(order)), np.asarray(piv))
    X1 = st.getrs(LU, piv, B)
    X2 = st.getrs(LUn, order, B)
    assert np.array_equal(np.asarray(X1.to_dense()),
                          np.asarray(X2.to_dense()))


# ------------------------------------- the four routines, one behaviour

def full_solver(routine):
    """(module, name) of the full-precision solver ``routine`` falls
    back to, where ``linalg/mixed.py`` looks it up at call time."""
    if routine.startswith("gesv"):
        return getrf_mod, "gesv"
    from slate_tpu.linalg import potrf as potrf_mod
    return potrf_mod, "posv"


def hard_operands(routine, grid, seed=41, n=96, nb=32):
    a, b = operands(seed, np.float32, n)
    if routine.startswith("posv"):
        a = (a @ a.T / n + np.eye(n)).astype(np.float32)
        A = st.HermitianMatrix.from_dense(a, nb=nb, grid=grid)
    else:
        A = st.Matrix.from_dense(a, nb=nb, grid=grid)
    return a, b, A, st.Matrix.from_dense(b, nb=nb, grid=grid)


@pytest.mark.parametrize("routine", ROUTINES)
def test_root_span_and_convergence_of_each_routine(routine, grid22):
    a, b, A, B = hard_operands(routine, grid22)
    X, iters, info = getattr(st, routine)(A, B)
    assert int(info) == 0 and iters >= 0
    root = root_of(routine)
    labels = root["labels"]
    assert root["parent"] == 0
    assert {k: labels[k] for k in ("routine", "n", "nb", "nrhs", "grid",
                                   "tier_lo")} == {
        "routine": routine, "n": 96, "nb": 32, "nrhs": 1, "grid": "2x2",
        "tier_lo": "bf16_3x"}
    assert labels["converged"] == 1 and labels["fallback"] == 0
    assert labels["inner"] == iters
    below = {s["name"] for s in obs.captured_spans()
             if s["solve"] == root["solve"]}
    assert {"mixed.factor_lo", "mixed.solve_lo", "mixed.residual",
            "mixed.anorm", "mixed.rnorm", "mixed.xnorm"} <= below
    assert backward_error_eps(a, np.asarray(X.to_dense())[:, 0], b) < 20.0


def test_every_product_with_a_is_one_lane_group_wide(grid, monkeypatch):
    """The residuals and the Arnoldi products are ``gemm`` of A against
    one column in a 256-wide tile: each ``gemm`` span under
    ``mixed.residual`` / ``mixed.matvec`` says ``nrhs`` = 1 and ``w`` =
    128, ``gemm.narrow`` counts them, and they are the root's steps
    (``refine_steps_per_solve``: inner + outer + 1). A product of two
    square matrices counts none."""
    never_satisfied(monkeypatch)
    n, nb = 512, 256
    a, b = operands(31, n=n)
    A, B = on_grid(a + 4 * np.eye(n, dtype=np.float32), b, grid, nb)
    st.gesv_mixed_gmres(
        A, B, {st.Option.MaxIterations: 3, st.Option.UseFallbackSolver: False})
    labels = root_of("gesv_mixed_gmres")["labels"]
    steps = labels["inner"] + labels["outer"] + 1
    assert labels["inner"] == 3 and steps >= 5
    by_id = {s["id"]: s for s in obs.captured_spans()}
    gemms = spans_named("gemm")
    assert len(gemms) == steps
    assert {by_id[s["parent"]]["name"] for s in gemms} == {
        "mixed.residual", "mixed.matvec"}
    assert {(s["labels"]["nrhs"], s["labels"]["w"]) for s in gemms} == {
        (1, 128)}
    assert metrics.counter_total("gemm.narrow") == steps
    C = st.Matrix.zeros(n, n, nb, grid, dtype=np.float32)
    st.multiply(1.0, A, A, 0.0, C)
    (wide,) = spans_named("gemm")[steps:]
    assert (wide["labels"]["nrhs"], wide["labels"]["w"]) == (
        n, n // grid.q)
    assert metrics.counter_total("gemm.narrow") == steps


@pytest.mark.parametrize("routine", ROUTINES)
def test_fallback_runs_at_the_working_tier_and_returns_its_info(
        routine, grid22, monkeypatch):
    a, b, A, B = hard_operands(routine, grid22)
    owner, full_name = full_solver(routine)
    real, seen = getattr(owner, full_name), []

    def full(A_, B_, opts=None):
        seen.append(dict(opts or {}))
        out = real(A_, B_, opts)
        return (*out[:-1], jnp.asarray(7, jnp.int32))

    monkeypatch.setattr(owner, full_name, full)
    never_satisfied(monkeypatch)
    opts = {st.Option.TrailingPrecision: "mxu_bf16",
            st.Option.MaxIterations: 2}
    X, iters, info = getattr(st, routine)(A, B, opts)
    assert iters == -3 or iters == -(2 + 1)
    assert int(info) == 7                   # the fallback's, not the factor's
    (passed,) = seen
    assert st.Option.TrailingPrecision not in passed
    assert passed[st.Option.MaxIterations] == 2
    labels = root_of(routine)["labels"]
    assert labels["converged"] == 0 and labels["fallback"] == 1
    assert len(spans_named("mixed.fallback")) == 1
    assert metrics.counter_value("mixed.fallback", routine=routine) == 1
    assert backward_error_eps(a, np.asarray(X.to_dense())[:, 0], b) < 20.0


@pytest.mark.parametrize("routine", ROUTINES)
def test_use_fallback_solver_false_is_obeyed(routine, grid22, monkeypatch):
    a, b, A, B = hard_operands(routine, grid22)
    owner, full_name = full_solver(routine)

    def never(*args, **kw):
        raise AssertionError("the fallback solver ran")

    monkeypatch.setattr(owner, full_name, never)
    never_satisfied(monkeypatch)
    X, iters, info = getattr(st, routine)(
        A, B, {st.Option.MaxIterations: 2,
               st.Option.UseFallbackSolver: False})
    assert iters == -3 and int(info) == 0   # -(MaxIterations + 1)
    labels = root_of(routine)["labels"]
    assert labels["converged"] == 0 and labels["fallback"] == 0
    assert labels["inner"] == 2
    assert metrics.counter_total("mixed.fallback") == 0
    assert backward_error_eps(a, np.asarray(X.to_dense())[:, 0], b) < 20.0


def test_a_factor_that_broke_down_reads_minus_three(grid11):
    n, nb = 64, 32
    a = np.zeros((n, n), np.float32)
    b = np.ones((n, 1), np.float32)
    A, B = on_grid(a, b, grid11, nb)
    X, iters, info = st.gesv_mixed(A, B)
    assert iters == -3 and int(info) != 0
    labels = root_of("gesv_mixed")["labels"]
    assert labels["converged"] == 0 and labels["fallback"] == 1


def test_plain_refine_imports_nothing_of_the_program():
    import ast
    import inspect
    tree = ast.parse(inspect.getsource(plain_refine))
    names = [n.module or "" for n in ast.walk(tree)
             if isinstance(n, ast.ImportFrom)] + [
        a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
        for a in n.names]
    assert not [n for n in names if n.startswith(("slate_tpu", "jax"))]
