"""Test harness: virtual 8-device CPU mesh.

Mirrors the reference's test strategy (SURVEY §4): SLATE exercises
multi-rank behavior with ``mpirun -np 4`` on one box; here the same
role is played by 8 virtual CPU devices
(``--xla_force_host_platform_device_count=8``) forming 2×4 / 1×1
grids. f64 is enabled for reference-accuracy checks.
"""

import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

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
    import re
    shapes = []
    for line in hlo_text.splitlines():
        head, found, _ = line.partition(" all-reduce(")
        if not found:
            continue
        for dt, dims in re.findall(r"\b([fcsu]\d+)\[([\d,]*)\]",
                                   head.split("=", 1)[1]):
            shapes.append((int(dt[1:]) // 8,
                           tuple(int(d) for d in filter(None,
                                                        dims.split(",")))))
    return shapes


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
