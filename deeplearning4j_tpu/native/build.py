"""Compile-on-first-use for the native kernels.

Builds each .cpp in this directory into a shared library under
``_build/`` next to the sources (inside the repo; nothing is written
elsewhere). The library's file name carries a hash of its source, so a
library is only ever loaded for the source it was built from: a
checkout resets file times and ``_build/`` is not tracked, so neither a
newer time stamp nor a leftover from another revision proves anything.
Failures are cached for the process so a missing compiler costs one
attempt, then every caller takes the Python fallback.
"""
from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
from typing import Dict, Optional

_DIR = os.path.dirname(os.path.abspath(__file__))
_BUILD = os.path.join(_DIR, "_build")
_failed: Dict[str, str] = {}
_loaded: Dict[str, ctypes.CDLL] = {}


def _compiler() -> Optional[str]:
    for cc in ("g++", "c++", "clang++"):
        try:
            subprocess.run([cc, "--version"], capture_output=True,
                           check=True)
            return cc
        except Exception:
            continue
    return None


def load(name: str) -> Optional[ctypes.CDLL]:
    """Load (building if needed) lib<name>.so from <name>.cpp; None if
    the toolchain is unavailable or the build failed."""
    if name in _loaded:
        return _loaded[name]
    if name in _failed:
        return None
    src = os.path.join(_DIR, f"{name}.cpp")
    try:
        with open(src, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:16]
        so = os.path.join(_BUILD, f"lib{name}-{digest}.so")
        if not os.path.exists(so):
            cc = _compiler()
            if cc is None:
                _failed[name] = "no C++ compiler on PATH"
                return None
            os.makedirs(_BUILD, exist_ok=True)
            tmp = f"{so}.{os.getpid()}.tmp"
            cmd = [cc, "-O3", "-shared", "-fPIC", "-std=c++17",
                   src, "-o", tmp]
            res = subprocess.run(cmd, capture_output=True, text=True)
            if res.returncode != 0:
                _failed[name] = res.stderr[-2000:]
                return None
            os.replace(tmp, so)     # atomic: no reader sees half a file
            # libraries of other revisions of this source
            for stale in glob.glob(os.path.join(_BUILD, f"lib{name}-*.so")) \
                    + glob.glob(os.path.join(_BUILD, f"lib{name}.so")):
                if stale != so:
                    os.remove(stale)
        lib = ctypes.CDLL(so)
        _loaded[name] = lib
        return lib
    except Exception as e:            # pragma: no cover - env specific
        _failed[name] = str(e)
        return None


def native_available(name: str = "fastcsv") -> bool:
    return load(name) is not None


def build_error(name: str) -> Optional[str]:
    return _failed.get(name)
