"""The low-tier LU program's share of its roofline: closed-form
``getrf(n)`` flops (``harness/flops.py``) over the PUBLISHED bf16 peak
of one chip, over ``mixed_factor_s``. The low tier decides how far it
can go: at ``mxu_bf16`` a product is one MXU pass and the whole peak is
there to reach, at ``bf16_3x`` three passes and 33.3 %; it is never
divided by peak over the passes."""

from __future__ import annotations

from benchmarks.layer_metrics import lu_factor_peak_share

HEADER = {"name": "mixed_factor_peak_share", "unit": "%",
          "better": "higher", "source": "device_trace",
          "layer": "kernels", "moves": "solve_s"}

# the same closed form over the same programs' seconds (mixed_factor_s)
compute = lu_factor_peak_share.compute
