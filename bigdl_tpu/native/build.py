"""Build + load the native library via g++ and ctypes (no pybind11 in the
image; the C API + ctypes is the binding layer, like the reference's
ctypes-into-libllama path, SURVEY.md §2.8)."""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from typing import Optional

logger = logging.getLogger("bigdl_tpu.native")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False

_SRC = os.path.join(os.path.dirname(__file__), "quant.cpp")
# no -march=native: the library is git-ignored and a working tree is
# copied between machines as it stands, so the artefact must run on any
# x86-64 host, and its name carries the hash of what it was built from
# — a library left behind by other source or other flags is not loaded
_CXXFLAGS = ["-O3", "-shared", "-fPIC"]


def _out_path() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read() + " ".join(_CXXFLAGS).encode())
    return os.path.join(os.path.dirname(__file__),
                        f"libbigdl_tpu_quant-{digest.hexdigest()[:12]}.so")


def _build() -> Optional[str]:
    out = _out_path()
    if os.path.exists(out):
        return out
    for flags in (["-fopenmp"], []):   # openmp when available
        # build beside the target and rename: a concurrent process must
        # never load a half-written library
        tmp = f"{out}.{os.getpid()}.tmp"
        cmd = ["g++", *_CXXFLAGS, *flags, _SRC, "-o", tmp]
        try:
            r = subprocess.run(cmd, capture_output=True, timeout=120)
            if r.returncode == 0:
                os.replace(tmp, out)
                logger.info("built %s (%s)", out,
                            "openmp" if flags else "single-thread")
                return out
            logger.debug("native build failed: %s", r.stderr.decode())
        except (OSError, subprocess.TimeoutExpired) as e:
            logger.debug("native build error: %s", e)
    return None


def get_lib() -> Optional[ctypes.CDLL]:
    """The loaded library, building on first call; None if unavailable."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        path = _build()
        if path is None:
            logger.info("native quant lib unavailable; numpy fallback")
            return None
        lib = ctypes.CDLL(path)
        i64, f32p = ctypes.c_int64, ctypes.POINTER(ctypes.c_float)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        u16p = ctypes.POINTER(ctypes.c_uint16)
        i8p = ctypes.POINTER(ctypes.c_int8)
        lib.quantize_q4_0.argtypes = [f32p, i64, i64, u8p, u16p]
        lib.dequantize_q4_0.argtypes = [u8p, u16p, i64, i64, f32p]
        lib.quantize_q8_0.argtypes = [f32p, i64, i64, i8p, u16p]
        lib.dequantize_q8_0.argtypes = [i8p, u16p, i64, i64, f32p]
        lib.matmul_q4_0.argtypes = [f32p, u8p, u16p, i64, i64, i64, f32p]
        for fn in ("quantize_q4_0", "dequantize_q4_0", "quantize_q8_0",
                   "dequantize_q8_0", "matmul_q4_0"):
            getattr(lib, fn).restype = None
        _lib = lib
        return _lib


def available() -> bool:
    return get_lib() is not None
