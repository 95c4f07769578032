"""ctypes bridge to the C++ band bulge-chasing kernels
(runtime/native/band_bulge.cc), with transparent fallback to the
pure-numpy twin (band_bulge.py).

``hb2st(ab)`` and ``tb2bd(ub)`` present one API regardless of backend;
set ``SLATE_TPU_NO_NATIVE=1`` to force the numpy path (tests compare
the two).  Same packed reflector format either way — see
band_bulge.py's docstring.
"""

from __future__ import annotations

import ctypes
import os
import subprocess

import numpy as np

from . import band_bulge as _np_impl

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "..", "runtime", "native", "band_bulge.cc")
_VER = 1          # keep equal to slate_bulge_version() in band_bulge.cc
_SO = os.path.join(_HERE, "..", "runtime", "native",
                   f"libslate_bulge_v{_VER}.so")

_lib = None
_tried = False

_SUFFIX = {np.float32: "s", np.float64: "d",
           np.complex64: "c", np.complex128: "z"}


def _build():
    # compile to a private temp path, then atomically rename — racing
    # builders (pytest workers, multi-process hosts) each land a
    # complete .so instead of interleaving writes into one
    from ..robust.watchdog import checked_run
    tmp = f"{_SO}.tmp.{os.getpid()}"
    cmd = ["g++", "-O3", "-funroll-loops", "-shared", "-fPIC",
           "-std=c++17", _SRC, "-o", tmp]
    try:
        checked_run(cmd, timeout=180, what="band_bulge")
        os.replace(tmp, _SO)
        return True
    except (OSError, subprocess.SubprocessError):
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False


def get_lib():
    """Load (building on demand) the native library, or None."""
    global _lib, _tried
    from ..robust import faults as _faults
    if _faults.enabled("native_missing", "band_bulge") is not None:
        # simulated toolchain-missing fault: checked before the load
        # cache so chaos tests see it regardless of prior loads
        _faults.record("native_missing", "band_bulge")
        return None
    if _tried:
        return _lib
    _tried = True
    if os.environ.get("SLATE_TPU_NO_NATIVE"):
        return None
    if not (os.path.exists(_SO)
            and os.path.getmtime(_SO) >= os.path.getmtime(_SRC)):
        if not _build():
            return None
    try:
        lib = ctypes.CDLL(_SO)
        if lib.slate_bulge_version() != _VER:
            return None
        _lib = lib
    except OSError:
        return None
    return _lib


def _suffix(dtype):
    return _SUFFIX[np.dtype(dtype).type]


def hb2st(ab):
    """Hermitian band (lower, ``ab[d, j] = A[j+d, j]``) → real
    tridiagonal.  Returns (d, e, V, tau) — see band_bulge.hb2st."""
    ab = np.ascontiguousarray(ab)
    lib = get_lib()
    band, n = ab.shape[0] - 1, ab.shape[1]
    if lib is None or band < 1 or n <= 2:
        return _np_impl.hb2st(ab)
    S, T = n - 1, _np_impl.max_chase(n, band)
    rdt = np.zeros(1, ab.dtype).real.dtype
    d = np.zeros(n, rdt)
    e = np.zeros(n - 1, rdt)
    V = np.zeros((S, T, band), ab.dtype)
    tau = np.zeros((S, T), ab.dtype)
    fn = getattr(lib, f"slate_hb2st_{_suffix(ab.dtype)}")
    fn(ctypes.c_int64(n), ctypes.c_int64(band),
       ab.ctypes.data_as(ctypes.c_void_p),
       d.ctypes.data_as(ctypes.c_void_p),
       e.ctypes.data_as(ctypes.c_void_p),
       V.ctypes.data_as(ctypes.c_void_p),
       tau.ctypes.data_as(ctypes.c_void_p))
    return d, e, V, tau


def tb2bd(ub):
    """Upper triangular band (``ub[d, j] = A[j, j+d]``) → real
    bidiagonal.  Returns (d, e, Vu, tauu, Vv, tauv, phase0) — see
    band_bulge.tb2bd."""
    ub = np.ascontiguousarray(ub)
    lib = get_lib()
    band, n = ub.shape[0] - 1, ub.shape[1]
    if lib is None or band < 1 or n <= 1:
        return _np_impl.tb2bd(ub)
    S, T = n - 1, _np_impl.max_chase(n, band)
    rdt = np.zeros(1, ub.dtype).real.dtype
    d = np.zeros(n, rdt)
    e = np.zeros(n - 1, rdt)
    Vu = np.zeros((S, T, band), ub.dtype)
    tauu = np.zeros((S, T), ub.dtype)
    Vv = np.zeros((S, T, band), ub.dtype)
    tauv = np.zeros((S, T), ub.dtype)
    phase0 = np.ones(1, ub.dtype)
    fn = getattr(lib, f"slate_tb2bd_{_suffix(ub.dtype)}")
    fn(ctypes.c_int64(n), ctypes.c_int64(band),
       ub.ctypes.data_as(ctypes.c_void_p),
       d.ctypes.data_as(ctypes.c_void_p),
       e.ctypes.data_as(ctypes.c_void_p),
       Vu.ctypes.data_as(ctypes.c_void_p),
       tauu.ctypes.data_as(ctypes.c_void_p),
       Vv.ctypes.data_as(ctypes.c_void_p),
       tauv.ctypes.data_as(ctypes.c_void_p),
       phase0.ctypes.data_as(ctypes.c_void_p))
    return d, e, Vu, tauu, Vv, tauv, phase0[0]
