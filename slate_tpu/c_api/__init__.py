"""C API builder (reference include/slate/c_api + src/c_api analog).

``build_library()`` compiles ``libslate_tpu_c.so`` — a C-ABI shared
library (header: ``slate_tpu.h``) that embeds CPython and drives the
framework, so C/Fortran programs can call ``slate_tpu_dgesv`` etc.
directly. See tests/test_c_api.py for an end-to-end C program.
"""

from __future__ import annotations

import os
import subprocess
import sysconfig

_HERE = os.path.dirname(os.path.abspath(__file__))
HEADER = os.path.join(_HERE, "slate_tpu.h")
_SRC = os.path.join(_HERE, "slate_tpu_c.cc")
_VER = 26          # bump with slate_tpu_version() in slate_tpu_c.cc
# versioned filename — a stale build from an older source revision is
# never loaded (same scheme as runtime/native slate_runtime_v*.so)
_SO = os.path.join(_HERE, f"libslate_tpu_c_v{_VER}.so")


def build_library(force: bool = False) -> str | None:
    """Compile (once) and return the path of libslate_tpu_c.so.
    Rebuilds when the source is newer than the library."""
    if os.path.exists(_SO) and not force:
        src_mtime = max(os.path.getmtime(_SRC),
                        os.path.getmtime(HEADER))
        if os.path.getmtime(_SO) >= src_mtime:
            return _SO
    inc = sysconfig.get_paths()["include"]
    libdir = sysconfig.get_config_var("LIBDIR") or ""
    ver = sysconfig.get_config_var("LDVERSION") \
        or sysconfig.get_config_var("VERSION")
    cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17",
           f"-I{inc}", _SRC, "-o", _SO,
           f"-L{libdir}", f"-lpython{ver}",
           f"-Wl,-rpath,{libdir}"]
    from ..robust.watchdog import checked_run
    try:
        checked_run(cmd, timeout=180, what="c_api")
        return _SO
    except (OSError, subprocess.SubprocessError):
        return None
