"""``slate.gels`` by Householder QR, the deployment of the benchmark's
cell ``gels_16384x1024_1x1`` (PR 44), at a small tall-skinny shape: the
public call with ``MethodGels.Geqrf`` through both ``geqrf`` programs
(the SPMD one-program and the exact-shape one, the latter also with the
Pallas panel in interpret mode) against the plain reference the
benchmark keeps (``benchmarks/harness/plain_ls.py``: the float64 LAPACK
solution and three numbers of an X against it); what ``Auto`` takes at
this shape; what a call reports (the root span, its three children, the
counters, the named scopes of the device programs); the operation
counts; and the principle of the benchmark's control: a blocked
Householder QR whose trailing products run at ``bf16_3x`` reads worse
than one at f32 (on the CPU every tier is true f32, so the lower tier
is ``plain_ls``'s numpy one).
"""

import jax
import numpy as np
import pytest

import slate_tpu as st
from slate_tpu import obs
from slate_tpu.linalg import geqrf as qr
from slate_tpu.obs import metrics
from slate_tpu.types import MethodGels, Option
from benchmarks.harness import flops_ls, plain_ls

M, N, NB, NRHS = 512, 128, 32, 3
# units of 2^-24, f32 on the CPU at this size (readings: 0.6-1.1 /
# 12-20 / 1e-6-7e-6); the chip's limits are the cell's own
LIMITS = {"optimality": 4.0, "forward": 80.0, "residual_excess": 1e-4}
# which program answers, how it is forced here, and a shape it takes
# (the Pallas panel wants whole 128-lane subpanels: nb = 128)
PROGRAMS = {
    "one_program": ({"SLATE_QR_FAST": "0"}, (M, N, NB), "xla"),
    "fast": ({"SLATE_QR_FAST": "1"}, (M, N, NB), "xla"),
    "fast_pallas": ({"SLATE_QR_FAST": "1", "SLATE_QR_PANEL": "1"},
                    (M, 2 * N, 128), "pallas"),
}


def opts(method=MethodGels.Geqrf, tier="bf16_6x"):
    return {Option.MethodGels: method, Option.TrailingPrecision: tier}


def problem(grid, seed, shape=(M, N, NB), nrhs=NRHS):
    m, n, nb = shape
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((m, n)).astype(np.float32)
    b = rng.standard_normal((m, nrhs)).astype(np.float32)
    return (a, b, st.Matrix.from_dense(a, nb=nb, grid=grid),
            st.Matrix.from_dense(b, nb=nb, grid=grid))


def in_eps(a, b, x):
    return {k: v / plain_ls.EPS for k, v in
            plain_ls.numbers(a, b, x, plain_ls.reference(a, b)).items()}


def forced(monkeypatch, program):
    env, shape, panel = PROGRAMS[program]
    for key in ("SLATE_QR_FAST", "SLATE_QR_PANEL"):
        monkeypatch.delenv(key, raising=False)
    for key, value in env.items():
        monkeypatch.setenv(key, value)
    return shape, panel


# ------------------------------------------------- against the reference

@pytest.mark.parametrize("seed", [11, 2_147_483_659])
@pytest.mark.parametrize("program", list(PROGRAMS))
def test_gels_geqrf_against_plain_ls(program, seed, grid11, observed,
                                     monkeypatch):
    shape, panel = forced(monkeypatch, program)
    a, b, A, B = problem(grid11, seed, shape)
    X = st.gels(A, B, opts())
    assert (X.m, X.n) == (shape[1], NRHS)
    got = in_eps(a, b, np.asarray(X.to_dense()))
    assert all(got[k] <= LIMITS[k] for k in LIMITS), got
    # and the answer came from the program that was asked for
    name = "one_program" if program == "one_program" else "fast"
    assert metrics.counter_value("gels.method", method="Geqrf") == 1
    assert metrics.counter_value("geqrf.path", program=name) == 1
    assert metrics.counter_value("geqrf.panel", panel=panel) == 1
    assert metrics.counter_total("geqrf.path") == 1


def test_auto_at_this_shape_answers_cholqr(grid11, observed):
    """m >= 2n: ``Auto`` is CholQR, and none of the QR family runs."""
    a, b, A, B = problem(grid11, 5)
    X = st.gels(A, B, opts(MethodGels.Auto))
    assert metrics.counter_value("gels.method", method="Cholqr") == 1
    assert metrics.counter_total("geqrf.path") == 0
    assert metrics.counter_total("geqrf.panel") == 0
    (root,) = [s for s in obs.captured_spans() if s["parent"] == 0]
    assert root["labels"]["method"] == "Cholqr"
    assert "program" not in root["labels"]
    # kappa(A) is 3 here, so squaring it costs nothing yet
    got = in_eps(a, b, np.asarray(X.to_dense()))
    assert all(got[k] <= LIMITS[k] for k in LIMITS), got
    # below m = 2n Auto is Householder QR
    a2, b2, A2, B2 = problem(grid11, 6, (192, N, NB))
    st.gels(A2, B2, opts(MethodGels.Auto))
    assert metrics.counter_value("gels.method", method="Geqrf") == 1


def test_cholqr_loses_what_householder_keeps(grid11):
    """kappa(A) = 2000 in f32: CholQR's error grows as kappa^2 eps,
    Householder QR's as kappa eps (the docstring's reason to pass
    ``MethodGels.Geqrf``)."""
    rng = np.random.default_rng(9)
    u, _ = np.linalg.qr(rng.standard_normal((M, N)))
    v, _ = np.linalg.qr(rng.standard_normal((N, N)))
    a = ((u * np.geomspace(1.0, 1 / 2000.0, N)) @ v.T).astype(np.float32)
    b = rng.standard_normal((M, NRHS)).astype(np.float32)
    A = st.Matrix.from_dense(a, nb=NB, grid=grid11)
    B = st.Matrix.from_dense(b, nb=NB, grid=grid11)
    house = in_eps(a, b, np.asarray(st.gels(A, B, opts()).to_dense()))
    chol = in_eps(a, b, np.asarray(
        st.gels(A, B, opts(MethodGels.Cholqr)).to_dense()))
    # readings: forward 1,047 against 278,477 units, excess 0.005
    # against 93 (at kappa = 6000 CholQR's X is off by 12 %)
    assert house["forward"] < 1.5 * 2000 and house["residual_excess"] < 0.1
    assert chol["forward"] > 50 * house["forward"], (chol, house)
    assert chol["residual_excess"] > 1000 * house["residual_excess"]


def test_a_wide_problem_takes_the_lq_branch(grid11, observed):
    """m < n: the minimum-norm solution, under the same root."""
    rng = np.random.default_rng(4)
    a = rng.standard_normal((N, 2 * N)).astype(np.float32)
    b = rng.standard_normal((N, NRHS)).astype(np.float32)
    X = st.gels(st.Matrix.from_dense(a, nb=NB, grid=grid11),
                st.Matrix.from_dense(b, nb=NB, grid=grid11), opts())
    ref = np.linalg.lstsq(a.astype(np.float64), b.astype(np.float64),
                          rcond=None)[0]
    x = np.asarray(X.to_dense(), np.float64)
    assert np.linalg.norm(x - ref) / np.linalg.norm(ref) < 100 * plain_ls.EPS
    (root,) = [s for s in obs.captured_spans() if s["parent"] == 0]
    assert (root["name"], root["labels"]["method"]) == ("slate.gels", "Geqrf")
    assert (root["labels"]["m"], root["labels"]["n"]) == (N, 2 * N)
    kids = {s["name"] for s in obs.captured_spans()
            if s["name"].startswith("gels.")}
    assert kids == set(qr.SPANS[1:])


# ---------------------------------------------------- what a call reports

@pytest.mark.parametrize("program", ["one_program", "fast", "cholqr"])
def test_span_tree_and_counters_once_a_call(program, grid11, observed,
                                            monkeypatch):
    method = MethodGels.Cholqr if program == "cholqr" else MethodGels.Geqrf
    if program != "cholqr":
        forced(monkeypatch, program)
    a, b, A, B = problem(grid11, 21)
    calls = 2
    for _ in range(calls):
        st.gels(A, B, opts(method))
    spans = obs.captured_spans()
    roots = [s for s in spans if s["parent"] == 0]
    assert [r["name"] for r in roots] == [qr.SPANS[0]] * calls
    for root in roots:
        labels = root["labels"]
        assert {k: labels[k] for k in ("routine", "m", "n", "nrhs", "nb",
                                       "grid", "method", "tier")} == {
            "routine": "gels", "m": M, "n": N, "nrhs": NRHS, "nb": NB,
            "grid": "1x1", "method": method.name, "tier": "bf16_6x"}
        if program != "cholqr":
            assert (labels["program"], labels["panel"]) == (program, "xla")
        kids = sorted((s for s in spans if s["parent"] == root["id"]),
                      key=lambda s: s["start_ns"])
        assert [s["name"] for s in kids] == list(qr.SPANS[1:])
        # the blocks that were there nest under the three children
        inner = {"cholqr": ("cholqr", "gemm", "trsm")}.get(
            program, ("geqrf", "unmqr", "trsm"))
        for kid, name in zip(kids, inner):
            below = [s["name"] for s in spans if s["parent"] == kid["id"]]
            assert name in below, (kid["name"], below)
    if program != "cholqr":
        (chunk,) = {s["labels"]["phase"] for s in spans
                    if s["name"] == "geqrf.chunk"}
        assert chunk == {"fast": "fast_path"}.get(program, program)
        assert metrics.counter_value("geqrf.path", program=program) == calls
        assert metrics.counter_value("geqrf.panel", panel="xla") == calls
    assert metrics.counter_value("gels.method", method=method.name) == calls
    assert metrics.counter_total("gels.method") == calls
    assert set(qr.COUNTERS) == {"gels.method", "geqrf.path", "geqrf.panel"}


def test_geqrf_alone_counts_its_choice_too(grid11, observed, monkeypatch):
    forced(monkeypatch, "fast")
    _, _, A, _ = problem(grid11, 2)
    st.geqrf(A)
    assert metrics.counter_value("geqrf.path", program="fast") == 1
    assert metrics.counter_total("gels.method") == 0
    (span,) = [s for s in obs.captured_spans() if s["name"] == "geqrf"]
    assert (span["labels"]["program"], span["labels"]["panel"]) == (
        "fast", "xla")


def test_the_panel_form_is_read_off_the_shape(grid11):
    """``pallas`` only when every panel fits the kernel: f32, whole
    128-lane subpanels, no taller than its VMEM window."""
    from slate_tpu.internal import panel_qr
    g = grid11

    def form(m, n, nb, dtype=np.float32, mode="tpu"):
        data = jax.ShapeDtypeStruct((1, 1, m // nb, n // nb, nb, nb), dtype)
        return qr._panel_form(st.Matrix(data=data, m=m, n=n, nb=nb, grid=g),
                              mode)

    assert form(16384, 1024, 256) == "pallas"          # the cell's shape
    assert form(16384, 1024, 256, mode=None) == "xla"
    assert form(512, 128, 32) == "xla"                 # nb off the lanes
    assert form(512, 256, 128, np.float64) == "xla"
    tall = panel_qr.H_MAX + 256
    assert form(tall, 512, 256) == "mixed"             # the first is over


def _operand_stub(m, n, nb, devices=1, platform="tpu", mtl=None, ntl=None):
    """What ``_qr_fast_applies`` reads of an A: no array behind it."""
    from types import SimpleNamespace as NS
    mt, nt = -(-m // nb), -(-n // nb)
    return NS(m=m, n=n, nb=nb, mt=mt, nt=nt,
              data=NS(shape=(1, 1, mtl or mt, ntl or nt, nb, nb)),
              grid=NS(size=devices, devices=[NS(platform=platform)]))


@pytest.mark.parametrize("A,flag,fast", [
    (_operand_stub(16384, 1024, 256), "", True),       # the cell
    (_operand_stub(16384, 512, 256), "", True),        # n = FAST_FROM_N
    (_operand_stub(16384, 256, 256), "", False),       # one panel
    (_operand_stub(2048, 2048, 256), "", True),        # square
    (_operand_stub(1024, 2048, 256), "", False),       # m < n
    (_operand_stub(16000, 1024, 256), "", False),      # a ragged tile row
    (_operand_stub(16384, 1000, 256), "", False),      # a ragged column
    (_operand_stub(16384, 1024, 256, mtl=65), "", False),  # stored padding
    (_operand_stub(16384, 1024, 256, devices=4), "", False),   # a grid
    (_operand_stub(16640, 16640, 256), "", False),     # kt = 65: unrolled
    (_operand_stub(16384, 16384, 256), "", True),      # kt = 64
    (_operand_stub(16384, 1024, 256, platform="cpu"), "", False),
    # the variable: off wins over everything, on only over the size and
    # the platform, not over the shape
    (_operand_stub(16384, 1024, 256), "0", False),
    (_operand_stub(128, 64, 32, platform="cpu"), "1", True),
    (_operand_stub(120, 64, 32, platform="cpu"), "1", False),
    (_operand_stub(128, 64, 32, devices=8, platform="cpu"), "1", False),
])
def test_the_exact_shape_program_is_read_off_the_shape(A, flag, fast,
                                                       monkeypatch):
    monkeypatch.setenv("SLATE_QR_FAST", flag)
    assert qr.FAST_FROM_N == 512
    assert qr._qr_fast_applies(A) is fast


@pytest.mark.parametrize("program", ["one_program", "fast", "unmqr"])
def test_the_device_programs_carry_their_scopes(program, grid11):
    _, _, A, B = problem(grid11, 1)
    if program == "one_program":
        lowered = qr._geqrf_jit.lower(A, "bf16_6x", 0)
        scopes = ("qr_panel", "qr_T", "qr_trailing")
    elif program == "fast":
        lowered = qr._geqrf_fast_jit.lower(A, panel_mode=None,
                                           tier="bf16_6x")
        scopes = ("qr_panel", "qr_T", "qr_trailing")
    else:
        T = jax.ShapeDtypeStruct((N // NB, NB, NB), np.float32)
        lowered = qr._unmqr_jit.lower(A, T, B, False)
        scopes = ("unmqr_apply",)
    text = lowered.as_text(debug_info=True)
    for scope in scopes:
        assert f"{scope}/" in text or f"/{scope}" in text, scope


# --------------------------------------------------- the operation counts

def test_the_closed_forms():
    m, n, nrhs, nb = 16384, 1024, 8, 256
    assert flops_ls.geqrf(m, n) == pytest.approx(
        2 * m * n * n - 2 * n ** 3 / 3)
    assert flops_ls.geqrf(m, n) == pytest.approx(33.64e9, rel=1e-3)
    # four panels of 256 columns on 16384, 16128, 15872, 15616 rows
    by_hand = sum(2 * h * 256 ** 2 - 2 * 256 ** 3 / 3
                  for h in (16384, 16128, 15872, 15616))
    assert flops_ls.geqr2_panels(m, n, nb) == pytest.approx(by_hand)
    assert flops_ls.geqr2_panels(m, n, n) == flops_ls.geqrf(m, n)
    assert flops_ls.unmqr(m, n, nrhs) == pytest.approx(
        4 * m * nrhs * n - 2 * nrhs * n * n)
    assert flops_ls.trsm(n, nrhs) == n * n * nrhs
    assert flops_ls.gels(m, n, nrhs) == pytest.approx(
        flops_ls.geqrf(m, n) + flops_ls.unmqr(m, n, nrhs)
        + flops_ls.trsm(n, nrhs))
    # V once (the trapezoid), C read and written once a panel, 8 wide
    assert flops_ls.unmqr_bytes(m, n, nrhs, nb) == pytest.approx(
        4 * (m * n - n * n / 2 + 2 * 4 * m * nrhs))
    # 1.0 ms at the six-pass ceiling; 83 us of HBM for the apply
    assert flops_ls.geqrf(m, n) / (197e12 / 6) == pytest.approx(
        1.02e-3, rel=0.01)
    assert flops_ls.unmqr_bytes(m, n, nrhs, nb) / 819e9 == pytest.approx(
        8.45e-5, rel=0.01)


# ------------------------------------------- the control's principle

@pytest.mark.parametrize("seed", [3, 2_147_483_659, 4_000_000_007])
def test_a_lower_tier_reads_worse_by_two_of_the_three(seed):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((M, N)).astype(np.float32)
    b = rng.standard_normal((M, NRHS)).astype(np.float32)
    sound = in_eps(a, b, plain_ls.gels_qr(a, b, NB, "f32"))
    lower = in_eps(a, b, plain_ls.gels_qr(a, b, NB, "bf16_3x"))
    coarse = in_eps(a, b, plain_ls.gels_qr(a, b, NB, "mxu_bf16"))
    assert all(sound[k] <= LIMITS[k] for k in LIMITS), sound
    assert lower["optimality"] > 2.5 * sound["optimality"], (lower, sound)
    assert lower["forward"] > 2.5 * sound["forward"], (lower, sound)
    assert not all(coarse[k] <= LIMITS[k] for k in LIMITS)
    assert coarse["optimality"] > 100 * lower["optimality"]
    # the excess is second order: tiny at either tier, so it is the
    # guard against an X that minimises nothing, not against a tier
    assert 0 <= lower["residual_excess"] < 1e-3
    wrong = plain_ls.gels_qr(a, b, NB, "f32")
    wrong[0] *= 1.1              # one row of X off by a tenth
    assert in_eps(a, b, wrong)["residual_excess"] > 10
