"""``trsm(Side.Left)`` against a B narrower than its storage on one
device column (q = 1: every one-chip solve, and p x 1), where a step
reads column k of A tile by tile from the diagonal on
(``_reads_tiles``): three tile rows with a ragged last one (n = 600) and
five (n = 1100) dealt to one, two and four device rows. The body, the
shared systems and the cases on wider meshes are in
tests/test_blas_trsm_narrow_b.py.
"""

import pytest

from slate_tpu.types import Uplo
from tests.test_blas_trsm_narrow_b import (_narrow_b_cases, check_narrow_b,
                                           once)  # noqa: F401 (a fixture)


@pytest.mark.parametrize("uplo", [Uplo.Lower, Uplo.Upper])
@pytest.mark.parametrize("op", ["n", "t", "c"])
@pytest.mark.parametrize("shape,n,nrhs,diag", _narrow_b_cases(True))
def test_trsm_left_narrow_b(once, shape, n, op, uplo, nrhs, diag):
    check_narrow_b(once, shape, n, op, uplo, nrhs, diag)
