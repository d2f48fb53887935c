"""ctypes bindings for the native host data plane (``native/dknative.cpp``).

Loads ``libdknative.so`` — always through ``make -C native``, a no-op
when the library is newer than ``dknative.cpp``, so a stale binary is
never what runs (the library is not committed) — and exposes:

* ``fused_add(a, b, scale)``   — ``a + scale·b`` in one multithreaded pass
  (the PS commit rule; ctypes releases the GIL for the duration).
* ``axpy_inplace(dst, src, scale)`` — in-place variant.
* ``parse_csv(path)``          — multithreaded CSV → float32 array.

Every entry point has a NumPy fallback, so the framework works without a
toolchain; ``available()`` reports which path is active.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libdknative.so")

_lib = None
_tried = False
_lock = threading.Lock()


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        try:
            subprocess.run(["make", "-C", _NATIVE_DIR], check=True,
                           capture_output=True, timeout=120)
            lib = ctypes.CDLL(_LIB_PATH)
            lib.dk_fused_add_f32.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_float, ctypes.c_size_t, ctypes.c_int]
            lib.dk_axpy_inplace_f32.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_float,
                ctypes.c_size_t, ctypes.c_int]
            lib.dk_fused_add_f64.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_double, ctypes.c_size_t, ctypes.c_int]
            lib.dk_parse_csv_f32.argtypes = [
                ctypes.c_char_p, ctypes.c_size_t, ctypes.c_void_p,
                ctypes.c_size_t, ctypes.c_int]
            lib.dk_parse_csv_f32.restype = ctypes.c_size_t
            assert lib.dk_version() == 1
            _lib = lib
        except (OSError, subprocess.SubprocessError, AssertionError):
            _lib = None
    return _lib


def available() -> bool:
    return _load() is not None


def fused_add(a: np.ndarray, b: np.ndarray, scale: float = 1.0,
              nthreads: int = 0) -> np.ndarray:
    """``a + scale·b`` — fused native pass when possible, NumPy otherwise.

    Always returns a NEW array (replace semantics: safe for the PS's
    lock-free pull snapshots)."""
    lib = _load()
    if (lib is None or a.dtype != b.dtype or a.shape != b.shape
            or a.dtype not in (np.float32, np.float64)):
        return (a + np.asarray(b, a.dtype) * scale).astype(a.dtype, copy=False)
    a = np.ascontiguousarray(a)
    b = np.ascontiguousarray(b)
    out = np.empty_like(a)
    fn = (lib.dk_fused_add_f32 if a.dtype == np.float32
          else lib.dk_fused_add_f64)
    fn(out.ctypes.data, a.ctypes.data, b.ctypes.data, scale, a.size, nthreads)
    return out


def axpy_inplace(dst: np.ndarray, src: np.ndarray, scale: float = 1.0,
                 nthreads: int = 0) -> None:
    """``dst += scale·src`` in place (dst must be writable f32)."""
    lib = _load()
    if (lib is None or dst.dtype != np.float32 or src.dtype != np.float32
            or not dst.flags.writeable or not dst.flags.c_contiguous):
        dst += np.asarray(src, dst.dtype) * scale
        return
    src = np.ascontiguousarray(src)
    lib.dk_axpy_inplace_f32(dst.ctypes.data, src.ctypes.data, scale,
                            dst.size, nthreads)


def parse_csv(path: str, nthreads: int = 0) -> np.ndarray:
    """All numeric values in a CSV file as one float32 vector (caller
    reshapes).  Native multithreaded parse, NumPy fallback."""
    with open(path, "rb") as f:
        buf = f.read()
    lib = _load()
    if lib is None:
        # fallback with the SAME token semantics as the native parser:
        # split on , \n \r space \t; keep numeric-start tokens only
        import re
        vals = []
        for tok in re.split(rb"[,\r\n \t]+", buf):
            if tok and (tok[0:1].isdigit() or tok[0:1] in (b"-", b"+", b".")):
                try:
                    vals.append(float(tok))
                except ValueError:
                    # strtof semantics: parse the leading numeric prefix
                    m = re.match(rb"[-+.]?[0-9]*\.?[0-9]*(?:[eE][-+]?[0-9]+)?",
                                 tok)
                    if m and m.group():
                        try:
                            vals.append(float(m.group()))
                        except ValueError:
                            pass
        return np.asarray(vals, dtype=np.float32)
    # upper bound on value count: one per separator byte + 1
    max_vals = sum(buf.count(s) for s in (b",", b"\n", b"\r", b" ", b"\t")) + 2
    out = np.empty(max_vals, np.float32)
    n = lib.dk_parse_csv_f32(buf, len(buf), out.ctypes.data, max_vals, nthreads)
    return out[:n].copy()
