"""Operations and bytes of the two-stage singular value decomposition
with both sets of vectors (the economy SVD of a tall m x n matrix), stage
by stage, from its shapes alone: leading terms for real arithmetic.
Sources: LAPACK Working Note 41, "Installation Guide", table of
operation counts (xGEBRD 4mn^2 - 4n^3/3 for m >= n; xORMQR / xORMBR
applied from the left to an m x n matrix with k reflectors, 2nk(2m - k));
Haidar, Kurzak, Luszczek, "An improved parallel singular value algorithm
and its implementation for multicore hardware", SC'13, section 4 (the
first stage keeps the one-stage reduction's count, the second is
O(n^2 b) and each side has two back-transforms). Kept here so that no
later PR to the program can move the yardstick: every count is the least
the algorithm needs, not what the program multiplies (its bidiagonal
solve works on the Golub-Kahan form of order 2n, eight times the
products of a bidiagonal divide and conquer: that is the program's
cost, not the problem's)."""

from __future__ import annotations


def ge2tb(m: int, n: int) -> float:
    """General to triangular band, m >= n: xGEBRD's count."""
    return 4.0 * m * float(n) ** 2 - 4.0 * float(n) ** 3 / 3.0


def tb2bd(n: int, band: int) -> float:
    """Triangular band to bidiagonal by bulge chasing. Sweep i has
    (n - i) / band tasks, n^2 / (2 band) in all; a task applies its two
    reflectors of length ``band`` (the right one v, the left one u) to
    two band x band blocks each (v to the bulge block it was formed from
    and to the diagonal block, u to the diagonal block and to the next
    bulge block), 4 band^2 operations an application: 16 band^2 a task,
    8 n^2 band in all. (The symmetric chase of ``flops_eig.hb2st`` has
    three applications a task, one of them two-sided on a symmetric
    block: 12 band^2 a task, 6 n^2 band.)"""
    return 8.0 * float(n) ** 2 * band


def bdsdc(n: int) -> float:
    """The bidiagonal divide and conquer with both sets of vectors, no
    deflation (its worst case): two merge products a level, one for U
    and one for V, each n^3 (1 + 1/4 + 1/16 + ...) = 4n^3/3 as
    ``flops_eig.stedc``."""
    return 8.0 * float(n) ** 3 / 3.0


def unmbr_tb2bd(n: int) -> float:
    """The chase's reflectors applied to n columns, on both sides
    (U_2 U_B and V_2 V_B): 2n^3 a side as ``flops_eig.unmtr_hb2st``."""
    return 4.0 * float(n) ** 3


def unmbr_ge2tb(m: int, n: int) -> float:
    """The band reduction's block reflectors: Q_1 (n reflectors of
    length m) applied to the m x n [U_2 U_B; 0], 2n n (2m - n) = 4mn^2 -
    2n^3, and P_1 applied to the n x n V_2 V_B, 2n^3."""
    return (4.0 * m * float(n) ** 2 - 2.0 * float(n) ** 3) \
        + 2.0 * float(n) ** 3


def gesvd_vectors(m: int, n: int, band: int) -> float:
    """One ``slate.gesvd`` of a tall m x n matrix with both sets of
    vectors through the two stages."""
    return (ge2tb(m, n) + tb2bd(n, band) + bdsdc(n) + unmbr_tb2bd(n)
            + unmbr_ge2tb(m, n))


def unmbr_tb2bd_bytes(n: int, band: int, itemsize: int = 4) -> float:
    """HBM traffic of the two ``unmbr_tb2bd`` applications in their
    blocked form, the least that form moves: twice
    ``flops_eig.unmtr_hb2st_bytes`` (the reflectors of ``band``
    consecutive sweeps at one chase step are applied together to the
    2 * band rows they touch, read and written once; n / band groups of
    sweeps, on average n / (2 * band) live steps each: 2 * itemsize *
    n^3 / band a side). U_2 U_B and V_2 V_B (n^2 words each) are larger
    than the chip's fast memory from n = 5,793 up."""
    return 2.0 * (2.0 * itemsize * float(n) ** 3 / band)
