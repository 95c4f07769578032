"""Operations and bytes of the two-stage Hermitian eigensolver with
vectors, stage by stage, from its shapes alone: leading terms for real
arithmetic (LAPACK Working Note 41, "Installation Guide", table of
operation counts: xSYTRD 4n^3/3, xSTEDC with vectors 4n^3/3 at most,
xORMTR 2n^3 for n columns; Haidar, Ltaief, Dongarra, "Parallel reduction
to condensed forms for symmetric eigenvalue problems using aggregated
fine-grained and memory-aware kernels", SC'11, section 3: the first
stage keeps the 4n^3/3 of the one-stage reduction, the bulge chase is
6 b n^2, and each of the two back-transforms is 2n^3). Kept here so
that no later PR to the program can move the yardstick."""

from __future__ import annotations


def he2hb(n: int) -> float:
    """Full to band."""
    return 4.0 * n ** 3 / 3.0


def hb2st(n: int, band: int) -> float:
    """Band to tridiagonal by bulge chasing."""
    return 6.0 * float(n) ** 2 * band


def stedc(n: int) -> float:
    """Divide and conquer with vectors, no deflation (its worst case:
    the merge products, n^3 (1 + 1/4 + 1/16 + ...))."""
    return 4.0 * n ** 3 / 3.0


def unmtr_hb2st(n: int) -> float:
    """The chase's reflectors applied to n columns."""
    return 2.0 * n ** 3


def unmtr_he2hb(n: int) -> float:
    """The band reduction's block reflectors applied to n columns."""
    return 2.0 * n ** 3


def heev_vectors(n: int, band: int) -> float:
    """One ``slate.heev`` with all n vectors through the two stages."""
    return (he2hb(n) + hb2st(n, band) + stedc(n) + unmtr_hb2st(n)
            + unmtr_he2hb(n))


def unmtr_hb2st_bytes(n: int, band: int, itemsize: int = 4) -> float:
    """HBM traffic of ``unmtr_hb2st`` in its blocked form, the least
    that form moves: the reflectors of ``band`` consecutive sweeps at
    one chase step are applied together to the 2 * band rows of Z they
    touch, read and written once; n / band groups of sweeps, on average
    n / (2 * band) live steps each: 2 * itemsize * n^3 / band. Z (n^2
    words) is larger than the chip's fast memory from n = 5,793 up, so
    no form keeps it there. (One sweep at a time reads and writes a
    window of n rows a sweep, band times as much: 8 n^3 in f32.)"""
    return 2.0 * itemsize * float(n) ** 3 / band
