"""Microbenchmark the folded PLU KERNEL at [16384, 128] — the carry
CHAINS each call's factored output into the next call's input, so the
in-place aliasing donates cleanly (no per-iteration operand copy)."""
import sys, time
import numpy as np
import jax, jax.numpy as jnp
from jax import lax
sys.path.insert(0, '/root/repo')
from slate_tpu.internal import panel_plu as pp

h = 16384
rng = np.random.default_rng(0)
sub = jnp.asarray(rng.standard_normal((h, pp.W)).astype(np.float32))
act1 = jnp.ones((8, h // 8), jnp.float32)
pF0 = pp.transpose_fold(sub, False)

def body(carry, _):
    out, actout, piv, info = pp._plu_call_folded(carry, act1, False)
    return out, piv[0, 0]
g = jax.jit(lambda x: lax.scan(body, x, None, length=50)[1][-1])
t0 = time.time(); int(g(pF0)); print('compile', round(time.time()-t0,1), flush=True)
ts = []
for _ in range(5):
    t0 = time.perf_counter(); int(g(pF0)); ts.append(time.perf_counter() - t0)
# subtract the ~0.088 s dispatch round trip BEFORE dividing by the
# chain length (forgetting this inflated early r5 readings 3-5x)
t = (float(np.median(ts)) - 0.088) / 50
print(f'kernel per-call {t*1e3:.3f} ms  ({t/128*1e6:.2f} us/col)', flush=True)
