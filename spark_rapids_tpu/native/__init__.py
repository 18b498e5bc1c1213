"""Native (C++) runtime components, loaded via ctypes.

SURVEY §2.2: the reference's serializers/runtime are native; the build
mandate is "tpu-native equivalents in C++, not Python-only wrappers".
Libraries compile on demand with the baked-in g++ toolchain into
``_build/`` next to the sources (git-ignored), one shared object per
hash of the source text — so what loads was built from the files of
THIS checkout, never reused by mtime from another one.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

_lock = threading.Lock()
_libs = {}

_SRC_DIR = os.path.dirname(os.path.abspath(__file__))


def _build_dir() -> str:
    d = os.path.join(_SRC_DIR, "_build")
    os.makedirs(d, exist_ok=True)
    return d


def load_library(name: str) -> Optional[ctypes.CDLL]:
    """Compile (once) and dlopen lib<name>.so from <name>.cpp.

    Returns None when no C++ toolchain is available (or the build
    failed) — callers must keep a Python fallback path and flag
    themselves non-accelerated (``serializer.native_enabled()``, which
    chip_smoke.py prints)."""
    with _lock:
        if name in _libs:
            return _libs[name]
        src = os.path.join(_SRC_DIR, f"{name}.cpp")
        try:
            with open(src, "rb") as f:
                digest = hashlib.sha1(f.read()).hexdigest()[:16]
            out = os.path.join(_build_dir(), f"lib{name}-{digest}.so")
            if not os.path.exists(out):
                tmp = f"{out}.{os.getpid()}.tmp"
                cmd = ["g++", "-O3", "-march=native", "-shared", "-fPIC",
                       "-std=c++17", "-pthread", src, "-o", tmp]
                subprocess.run(cmd, check=True, capture_output=True)
                os.replace(tmp, out)
            lib = ctypes.CDLL(out)
        except (OSError, subprocess.CalledProcessError):
            lib = None
        _libs[name] = lib
        return lib
