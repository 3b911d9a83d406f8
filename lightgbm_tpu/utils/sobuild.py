"""Build-and-cache for the package's two C++ shared libraries.

The cached file is named by a hash of everything that went into it (the
source text plus whatever the build bakes in), so a binary can only load
for the exact inputs it was built from.  File times say nothing: a copy
or a checkout makes them arbitrary.
"""

from __future__ import annotations

import glob
import hashlib
import os
import subprocess
from typing import Callable, List, Optional


def cached_library(src: str, stem: str, compile_cmd: Callable[[str], List[str]],
                   baked: str = "") -> Optional[str]:
    """Path of ``<dir of src>/../<stem>-<hash>.so``, compiled on first use
    with ``compile_cmd(output_path)``; None when the toolchain fails.
    ``baked`` names anything the command compiles in besides ``src``."""
    with open(src, "rb") as fh:
        digest = hashlib.sha256(fh.read() + b"\0" + baked.encode())
    out_dir = os.path.dirname(os.path.dirname(os.path.abspath(src)))
    path = os.path.join(out_dir, f"{stem}-{digest.hexdigest()[:16]}.so")
    if os.path.exists(path):
        return path
    tmp = os.path.join(out_dir, f".{stem}.{os.getpid()}.so.tmp")
    try:
        subprocess.run(compile_cmd(tmp), check=True, capture_output=True,
                       timeout=240)
        os.replace(tmp, path)
    except (OSError, subprocess.SubprocessError):
        return None
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    for stale in glob.glob(os.path.join(out_dir, f"{stem}*.so")):
        if stale != path:
            try:
                os.unlink(stale)       # binaries of other sources/paths
            except OSError:
                pass
    return path
