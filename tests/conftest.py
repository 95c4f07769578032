"""Test harness: virtual 8-device CPU mesh.

Mirrors the reference's test strategy (SURVEY §4): SLATE exercises
multi-rank behavior with ``mpirun -np 4`` on one box; here the same
role is played by 8 virtual CPU devices
(``--xla_force_host_platform_device_count=8``) forming 2×4 / 1×1
grids. f64 is enabled for reference-accuracy checks.
"""

import os

# One BLAS thread for numpy. OpenBLAS starts a thread a core and each
# spins after a call, so the reference solves of six xdist workers
# fight the XLA programs for eight cores (12 cases of
# test_trsm_left_narrow_b alone: 49 s of CPU, 25 with one thread). The
# variable is for the children the tests start; a pytest plugin has
# imported numpy before this file, so this process needs the call.
os.environ["OPENBLAS_NUM_THREADS"] = "1"
try:
    import threadpoolctl
    threadpoolctl.threadpool_limits(1, user_api="blas")
except ImportError:
    pass

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import math  # noqa: E402
import re  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def grid24():
    from slate_tpu import Grid
    return Grid(2, 4)


@pytest.fixture(scope="session")
def grid22():
    from slate_tpu import Grid
    return Grid(2, 2, devices=jax.devices()[:4])


@pytest.fixture(scope="session")
def grid11():
    from slate_tpu import Grid
    return Grid(1, 1, devices=jax.devices()[:1])


@pytest.fixture
def observed(monkeypatch):
    """Spans captured as inside a profiler session, counters on (what a
    public call reports: tests of ``slate.heev``, ``slate.gels``,
    ``slate.gesvd``)."""
    from slate_tpu import obs
    from slate_tpu.obs import flight, tracing
    was_metrics, was_flight = obs.metrics_enabled(), flight.enabled()
    flight.enable()
    obs.reset()
    monkeypatch.setattr(tracing, "_profiling", lambda: True)
    obs.metrics_on()
    yield
    if not was_metrics:
        obs.metrics_off()
    if not was_flight:
        flight.disable()
    obs.reset()


def rand(m, n, dtype=np.float64, seed=0):
    rng = np.random.default_rng(seed)
    if np.issubdtype(np.dtype(dtype), np.complexfloating):
        a = rng.standard_normal((m, n)) + 1j * rng.standard_normal((m, n))
    else:
        a = rng.standard_normal((m, n))
    return a.astype(dtype)


def spd(n, dtype=np.float64, seed=0):
    g = rand(n, n, dtype, seed)
    return (g @ np.conj(g.T) / n + np.eye(n)).astype(dtype)


def tri(a, lower, unit=False):
    t = np.tril(a) if lower else np.triu(a)
    if unit:
        np.fill_diagonal(t, 1.0)
    return t


def padded_dense(M):
    """A matrix as it is stored: every tile of every device, padding
    included, as one dense array."""
    from slate_tpu.matrix import bc_to_tiles, tiles_to_dense
    tiles = bc_to_tiles(M.data)
    return np.asarray(tiles_to_dense(tiles, tiles.shape[0] * M.nb,
                                     tiles.shape[1] * M.nb))


def all_reduce_shapes(hlo_text):
    """(bytes an element, dims) of every result of every all-reduce in
    an optimized HLO text, the operands XLA combined into one tuple
    included."""
    from slate_tpu.obs import costmodel
    return [shape for op in costmodel.collective_shapes(hlo_text).get(
        "all-reduce", []) for shape in op]


# -- compiling for a described v5e:2x2 (tests/test_aot_tpu_*.py) -------------
# The topology is described inside a module-scoped fixture and nowhere
# at import time (one process at a time may load libtpu; every xdist
# worker imports every test file), and by none but the tests that ask
# for it. JAX's persistent compilation cache is off around these
# compiles: an entry written without a chip cannot be read back and only
# produces warnings. So is x64, which this file turns on for the CPU
# references: the chip runs with jax's default, and Mosaic has no 64-bit
# integers. The driver runs the three files in three workers with
# ALLOW_MULTIPLE_LIBTPU_LOAD=1; without it the second worker to ask is
# refused the library and its file skips.

AOT_H, AOT_W, AOT_NB = 16384, 128, 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / lock held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = (jax.config.jax_enable_compilation_cache,
            jax.config.jax_enable_x64)
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev[0])
    jax.config.update("jax_enable_x64", prev[1])
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def tpu_grid22(topo):
    from slate_tpu import Grid
    return Grid(2, 2, devices=list(topo.devices))


def aot_compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def aot_kernels(compiled) -> int:
    """Mosaic kernel calls in a program compiled for the TPU."""
    return compiled.as_text().count("tpu_custom_call")


def aot_tiles(grid, n=AOT_H, nb=AOT_NB):
    """The stored tiles of an n x n f32 matrix on ``grid``, as a shape."""
    import jax.numpy as jnp
    t = n // nb
    return jax.ShapeDtypeStruct(
        (grid.p, grid.q, t // grid.p, t // grid.q, nb, nb), jnp.float32,
        sharding=grid.sharding())


def widest_product(text: str) -> int:
    """Elements of the largest f32 result of a ``dot`` or ``convolution``
    in a compiled text."""
    products = re.findall(
        r"= f32\[([\d,]+)\]\S* (?:convolution|dot)\(", text)
    assert products
    return max(math.prod(map(int, dims.split(","))) for dims in products)


@pytest.fixture
def materialized_ops(monkeypatch):
    """Class names of the operands with an op that ``materialize()``
    was called on (each one a re-laid copy: all-to-alls)."""
    from slate_tpu.matrix import BaseTiledMatrix
    from slate_tpu.types import Op
    calls = []
    orig = BaseTiledMatrix.materialize

    def counting(self):
        if self.op != Op.NoTrans:
            calls.append(type(self).__name__)
        return orig(self)

    monkeypatch.setattr(BaseTiledMatrix, "materialize", counting)
    return calls


@pytest.fixture
def nprand():
    return rand


@pytest.fixture
def npspd():
    return spd


@pytest.fixture(autouse=True, scope="module")
def _clear_jit_caches_per_module():
    """Bound the in-process XLA compiler state: a full-suite run
    accumulates 600+ compiled programs in one process and the CPU
    backend compiler sporadically segfaults late in the run (observed
    at ~78-96% across clean runs; any single module passes alone).
    Dropping the jit caches between modules keeps compiler state
    bounded; cross-module recompiles are cheap relative to the suite."""
    yield
    import jax
    try:
        jax.clear_caches()
    except Exception:
        pass
