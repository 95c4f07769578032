import os, sys, time
import numpy as np
sys.path.insert(0, '/root/repo')
import jax
from slate_tpu.cache import place_jax_compile_cache
place_jax_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 5.0)
import jax.numpy as jnp
import jax.random as jrnd
import slate_tpu as st

nbig = 45056
gen0 = jax.jit(lambda: jrnd.normal(jrnd.PRNGKey(7), (nbig, nbig), jnp.float32))
regen = jax.jit(lambda dead: dead * 0.0 + jrnd.normal(jrnd.PRNGKey(7), (nbig, nbig), jnp.float32), donate_argnums=0)
red = jax.jit(lambda o: jnp.sum(jnp.abs(o)))
buf = gen0()
t0 = time.time()
out, piv, info = st.getrf_dense_inplace(buf, nb=1024)
float(red(out))
print('warm(compile) wall', round(time.time()-t0, 1), 'info', int(info), flush=True)
buf = regen(out); del out, piv
t0 = time.perf_counter()
out, piv, info = st.getrf_dense_inplace(buf, nb=1024)
float(red(out))
t = time.perf_counter() - t0 - 0.088
print(f'getrf 45056: {t:.3f}s  {2*nbig**3/3/t/1e9:.1f} GF/s', flush=True)
