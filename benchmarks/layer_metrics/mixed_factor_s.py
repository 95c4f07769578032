"""Device-0 busy seconds of one traced call of a mixed-precision
solver inside the low-precision factorization's XLA modules
(``jit__getrf*``: on one chip at n=16384 ``jit__getrf_fast_core`` at
the configuration's low tier, the program ``gesv_16k_1x1`` runs at
``bf16_6x`` and reads as ``lu_factor_s``). Read against that cell it is
what the lower tier buys on the same LU: only the trailing products
take the tier, the panel and the pivot gather stay as they are."""

from __future__ import annotations

from benchmarks.layer_metrics import lu_factor_s

HEADER = {"name": "mixed_factor_s", "unit": "s", "better": "lower",
          "source": "device_trace", "layer": "kernels",
          "moves": "solve_s"}

MODULES = lu_factor_s.MODULES      # the same programs, at another tier

compute = lu_factor_s.compute
