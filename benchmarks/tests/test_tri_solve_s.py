"""``tri_solve_s`` (PR 28): device-0 busy seconds a traced solve inside
the ``jit__trsm*`` modules, on ``recorded_posv_16k_1x1.json`` (one whole
traced solve of ``posv_16k_1x1`` on a TPU v5 lite at PR 28, every one of
its 4,941 device ops, cut with ``cut_trace.py --solves 1 --events
6000``), on the three older recorded traces, which end inside the
factorization, and on the hand-made trace of the nb=384 cell."""

import pytest

from benchmarks.harness import module_seconds, trace_reduce as tr
from benchmarks.layer_metrics import lu_factor_s, tri_solve_s
from benchmarks.tests.test_gesv_10000_nb384 import hand_trace, recorded


def test_a_whole_recorded_posv_solve_splits_into_potrf_and_two_trsm():
    red = recorded("recorded_posv_16k_1x1.json")
    dev0 = red.first
    assert len(red.solves) == 1 and len(dev0.ops) == 4941
    assert [m[0] for m in dev0.modules] == [
        "jit__potrf_core", "jit_convert_element_type",
        "jit__trsm_left_jit", "jit_convert_element_type",
        "jit__trsm_left_jit"]
    tri = tri_solve_s.compute({"trace": red})
    # L then conj_transpose(L) in place, 8 right-hand sides carried 128
    # wide: 7.39 + 7.69 ms (the padded tile took 50.3 ms)
    assert tri == pytest.approx(0.0150755, rel=1e-4)
    spans = [e - s for name, s, e in dev0.modules
             if name.startswith("jit__trsm")]
    assert tri <= sum(spans) and tri > 0.99 * sum(spans)
    potrf = module_seconds.per_solve(red, ("jit__potrf",))
    assert potrf == pytest.approx(0.0799800, rel=1e-4)
    # the two converts of alpha are a microsecond each
    assert potrf + tri == pytest.approx(tr.total(dev0.busy()), rel=1e-4)
    assert lu_factor_s.compute({"trace": red}) is None


@pytest.mark.parametrize("name", ["recorded_gesv_16k_1x1.json",
                                  "recorded_gesv_10000_nb384_1x1.json",
                                  "recorded_posv_16k_2x2.json"])
def test_a_trace_cut_inside_the_factorization_has_nothing_to_read(name):
    red = recorded(name)
    assert not [m for m in red.first.modules if "trsm" in m[0]]
    assert tri_solve_s.compute({"trace": red}) is None


def test_no_trace_no_reading():
    assert tri_solve_s.compute({"trace": None}) is None


@pytest.mark.parametrize("pivot_module", ["jit__apply_piv_jit",
                                          "jit__apply_order_jit"])
def test_the_two_solves_of_a_gesv_are_summed(pivot_module):
    red = hand_trace(pivot_module)
    assert tri_solve_s.compute({"trace": red}) == pytest.approx(0.011)
