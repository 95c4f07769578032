"""Operations and bytes of a least-squares solve by Householder QR,
stage by stage, from its shapes alone: leading terms for real
arithmetic (LAPACK Users' Guide, 3rd ed., table 3.13 / LAPACK Working
Note 41, "Installation Guide", operation counts: xGEQRF 2mn^2 - 2n^3/3
for m >= n, xORMQR from the left with k reflectors on an m x nrhs C
4 m nrhs k - 2 nrhs k^2, xTRSM n^2 nrhs, xGEQR2 on an h x w panel
2 h w^2 - 2 w^3 / 3). Copied here so that no later PR to the program
can move the yardstick."""

from __future__ import annotations


def geqrf(m: int, n: int) -> float:
    """Householder QR of an m x n matrix, m >= n."""
    return 2.0 * m * float(n) ** 2 - 2.0 * float(n) ** 3 / 3.0


def geqr2_panels(m: int, n: int, nb: int) -> float:
    """The unblocked panel factorizations inside a blocked ``geqrf``
    with panels ``nb`` wide: panel k is (m - k nb) x w."""
    total = 0.0
    for k0 in range(0, n, nb):
        w = min(nb, n - k0)
        total += geqrf(m - k0, w)
    return total


def unmqr(m: int, n: int, nrhs: int) -> float:
    """Q^T C from the left: n reflectors of an m-row QR on nrhs
    columns."""
    return 4.0 * m * nrhs * float(n) - 2.0 * nrhs * float(n) ** 2


def trsm(n: int, nrhs: int) -> float:
    """One triangular solve with the n x n R."""
    return float(n) ** 2 * nrhs


def gels(m: int, n: int, nrhs: int) -> float:
    """One ``gels`` by Householder QR: geqrf + unmqr + trsm."""
    return geqrf(m, n) + unmqr(m, n, nrhs) + trsm(n, nrhs)


def unmqr_bytes(m: int, n: int, nrhs: int, nb: int,
                itemsize: int = 4) -> float:
    """The least HBM traffic of applying Q^T panel by panel: every
    reflector (the m x n trapezoid of V below the diagonal, n^2/2 words
    short of m n) read once, and C read and written once a panel at the
    width ``nrhs`` needs."""
    v_words = m * float(n) - float(n) ** 2 / 2.0
    panels = -(-n // nb)
    return itemsize * (v_words + 2.0 * panels * m * nrhs)
