"""The Householder panel kernel's share of the chip's peak: closed-form
``geqr2_panels(m, n, nb)`` (``harness/flops_ls.py``: the unblocked
factorization of each (m - k nb) x nb panel, 2hw^2 - 2w^3/3) over the
PUBLISHED bf16 peak, over ``ls_panel_s``. Small by nature, far under
1 %: a panel is a serial chain of nb reflectors, each a norm, a scale
and a rank-1 update on the vector unit; the count is what stays fixed
when the kernel changes."""

from __future__ import annotations

from benchmarks.harness import flops_ls
from benchmarks.layer_metrics import ls_panel_s
from benchmarks.layer_metrics.eig_band_reduce_peak_share import share
from benchmarks.layer_metrics.ls_factor_peak_share import shape_of

HEADER = {"name": "ls_panel_peak_share", "unit": "%",
          "better": "higher", "source": "device_trace",
          "layer": "least squares", "moves": "solve_s"}


def compute(run: dict):
    m, n, _, nb = shape_of(run)
    return share(run, flops_ls.geqr2_panels(m, n, nb),
                 ls_panel_s.compute(run))
