"""Native (C++) runtime components, loaded via ctypes.

The reference's scheduling algorithm is native (Go); this package holds
the framework's native pieces — currently the planes-layout batch solver
(``solver.cc``), used as the CPU-native backend and as an independent
differential oracle for the TPU kernels.

No pybind11 in this environment: the library is a plain ``extern "C"``
shared object built with g++ and bound with ctypes on flat numpy
buffers (the planes layout is already columnar, so there is no object
marshalling at the boundary). Everything degrades gracefully: if the
compiler or library is unavailable, ``load()`` returns None and callers
fall back to the JAX backends.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import platform
import subprocess
import threading

_logger = logging.getLogger(__name__)

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "solver.cc")
# -ffp-contract=off: no FMA contraction — the solver's f32 math must
# round exactly like XLA's separate mul/add for the bit-identical
# differential contract
_CFLAGS = ["-O3", "-march=native", "-ffp-contract=off", "-shared", "-fPIC"]

_lock = threading.Lock()
_lib = None
_tried = False


def _cpu_flags() -> str:
    """The host CPU's feature flags (``-march=native`` compiles for
    exactly these), or the machine name where /proc/cpuinfo is absent."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    return line.split(":", 1)[1].strip()
    except OSError:
        pass
    return platform.machine()


def lib_path() -> str:
    """Library file for THIS source, compile command and host CPU: the
    hash of all three is in the name, so a library built from another
    ``solver.cc``, with other flags, or on a CPU with other features (a
    tree copied from another machine) is never loaded."""
    h = hashlib.sha256()
    with open(_SRC, "rb") as f:
        h.update(f.read())
    h.update(" ".join(["g++"] + _CFLAGS).encode())
    h.update(_cpu_flags().encode())
    return os.path.join(_DIR, f"libktpu_solver-{h.hexdigest()[:16]}.so")


def build() -> str | None:
    """Compile solver.cc into ``lib_path()`` unless it exists. Returns
    the library path, or None when the build is unavailable."""
    lib = lib_path()
    if os.path.exists(lib):
        return lib
    tmp = os.path.join(_DIR, f".build-{os.getpid()}.so")
    cmd = ["g++"] + _CFLAGS + ["-o", tmp, _SRC]
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=120
        )
    except (OSError, subprocess.TimeoutExpired) as e:
        _logger.warning("native solver build unavailable: %s", e)
        return None
    if proc.returncode != 0:
        _logger.warning("native solver build failed:\n%s", proc.stderr)
        return None
    os.replace(tmp, lib)   # atomic: concurrent builders never see half a file
    return lib


def load():
    """Load (building on first use) the native library. Returns the
    ctypes CDLL with ``ktpu_solve`` configured, or None."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        path = build()
        if path is None:
            return None
        try:
            lib = ctypes.CDLL(path)
        except OSError as e:
            _logger.warning("native solver load failed: %s", e)
            return None
        i32p = ctypes.POINTER(ctypes.c_int32)
        f32p = ctypes.POINTER(ctypes.c_float)
        lib.ktpu_solve.restype = ctypes.c_int
        lib.ktpu_solve.argtypes = [
            i32p, f32p, i32p, i32p, i32p, i32p, f32p, i32p, f32p,
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int64,
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
        ]
        _lib = lib
        return _lib
