"""``unmqr``'s share of its roofline, which is memory: the least
traffic of applying Q^T panel by panel, ``unmqr_bytes(m, n, nrhs, nb)``
(``harness/flops_ls.py``: V read once, B read and written once a panel
at the width nrhs needs), over the PUBLISHED HBM bandwidth of one chip,
over ``ls_apply_q_s``. Its 4 m n nrhs flops at six passes take a tenth
of that time at the bf16 peak, so memory is the ceiling."""

from __future__ import annotations

from benchmarks.harness import flops_ls
from benchmarks.harness.peaks import peaks_for
from benchmarks.layer_metrics import ls_apply_q_s
from benchmarks.layer_metrics.ls_factor_peak_share import shape_of

HEADER = {"name": "ls_apply_q_hbm_share", "unit": "%",
          "better": "higher", "source": "device_trace",
          "layer": "least squares", "moves": "solve_s"}


def compute(run: dict):
    seconds = ls_apply_q_s.compute(run)
    if not seconds or run["device"]["platform"] != "tpu":
        return None
    itemsize = {"float32": 4}[run["spec"]["config"]["dtype"]]
    least_s = flops_ls.unmqr_bytes(*shape_of(run), itemsize) \
        / peaks_for(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
