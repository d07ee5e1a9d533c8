"""Native (C++) runtime components.

The compute path is JAX/XLA; these are host-side runtime pieces where the
reference uses native-adjacent code (PalDB). Shared objects build on first
use with g++ and are cached under ``_build/``, each object named by a
hash of the source it was built from.
"""

from __future__ import annotations

import hashlib
import os
import subprocess
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_BUILD_DIR = os.path.join(_HERE, "_build")
_LOCK = threading.Lock()


def build_library(name: str, link: tuple[str, ...] = ()) -> str:
    """Compile ``<name>.cc`` into ``_build/lib<name>-<digest>.so`` (once)
    and return the path. The digest is of the source bytes and the
    linker flags, so an object copied in from another checkout or left
    by an older source is never loaded: a changed source is a new file
    name. ``link`` appends linker flags (e.g. ``("-lz",)``)."""
    src = os.path.join(_HERE, f"{name}.cc")
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read() + " ".join(link).encode())
    out = os.path.join(_BUILD_DIR,
                       f"lib{name}-{digest.hexdigest()[:16]}.so")
    with _LOCK:
        if not os.path.exists(out):
            os.makedirs(_BUILD_DIR, exist_ok=True)
            # pid-suffixed temp + atomic rename: concurrent builders (e.g.
            # pytest-xdist workers — the threading lock is per-process) each
            # write their own object and the last rename wins intact.
            tmp = f"{out}.{os.getpid()}.tmp"
            try:
                subprocess.run(
                    ["g++", "-std=c++17", "-O2", "-shared", "-fPIC",
                     "-o", tmp, src, *link],
                    check=True, capture_output=True)
                os.replace(tmp, out)
            finally:
                if os.path.exists(tmp):
                    os.unlink(tmp)
    return out
