"""C-ABI shim build/load helpers.

``lib_path()`` compiles ``csrc/capi.cpp`` into ``_capi-<hash>.so`` (named by
a hash of the source and the paths it bakes in) and returns its path;
external bindings load it with ``dlopen`` / ``ctypes.CDLL``.  The library
embeds CPython when loaded from a plain C program, or joins the running
interpreter when loaded from Python.

Reference counterpart: the exported surface of ``src/c_api.cpp`` (subset —
the handle-based Dataset/Booster workflow used by the official language
bindings; see ``include/lightgbm_tpu_c_api.h`` for the exact list).
"""

from __future__ import annotations

import os
import sysconfig
import threading
from typing import Optional

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "csrc", "capi.cpp")
_HEADER = os.path.join(_DIR, "include", "lightgbm_tpu_c_api.h")
_lock = threading.Lock()


def header_path() -> str:
    return _HEADER


def lib_path() -> Optional[str]:
    """Build (unless cached) and return the shared library path, or None
    when the toolchain is unavailable."""
    from ..utils.sobuild import cached_library
    inc = sysconfig.get_paths()["include"]
    libdir = sysconfig.get_config_var("LIBDIR") or ""
    ldlib = sysconfig.get_config_var("LDLIBRARY") or ""
    # libpython3.x.so -> python3.x
    pylib = ldlib
    for pre in ("lib",):
        if pylib.startswith(pre):
            pylib = pylib[len(pre):]
    for suf in (".so", ".a", ".dylib"):
        if pylib.endswith(suf):
            pylib = pylib[: -len(suf)]
    pkg_root = os.path.dirname(os.path.dirname(_DIR))

    def cmd(out):
        return ["g++", "-O2", "-std=c++17", "-shared", "-fPIC",
                f"-I{inc}", f"-DLTPU_PKG_DIR=\"{pkg_root}\"",
                "-o", out, _SRC,
                f"-L{libdir}", f"-l{pylib}", f"-Wl,-rpath,{libdir}"]

    with _lock:
        # the binary bakes in the checkout path and the libpython it links
        return cached_library(_SRC, "_capi", cmd,
                              baked=f"{pkg_root}|{inc}|{libdir}|{pylib}")
