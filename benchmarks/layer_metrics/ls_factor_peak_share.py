"""The QR factorization's share of the chip's peak: closed-form
``geqrf(m, n)`` = 2mn^2 - 2n^3/3 (``harness/flops_ls.py``) over the
PUBLISHED bf16 peak of one chip, over ``ls_factor_s``. At the six-pass
tier ``bf16_6x`` it cannot pass 16.7 %; it is never divided by peak/6.
The count is LAPACK's, whatever the program multiplies."""

from __future__ import annotations

from benchmarks.harness import flops_ls
from benchmarks.layer_metrics import ls_factor_s
from benchmarks.layer_metrics.eig_band_reduce_peak_share import share

HEADER = {"name": "ls_factor_peak_share", "unit": "%",
          "better": "higher", "source": "device_trace",
          "layer": "least squares", "moves": "solve_s"}


def shape_of(run: dict) -> tuple:
    """(m, n, nrhs, nb) of the cell as it ran (a rehearsal shrinks n
    and nb and keeps m / n)."""
    config = run["spec"]["config"]
    return (config["m_over_n"] * config["n"], config["n"],
            config["nrhs"], config["nb"])


def compute(run: dict):
    m, n, _, _ = shape_of(run)
    return share(run, flops_ls.geqrf(m, n), ls_factor_s.compute(run))
