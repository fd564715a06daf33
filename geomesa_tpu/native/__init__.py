"""Native (C++) runtime kernels for host-side hot loops.

The TPU compute path is JAX/XLA; this package holds the *host* runtime work
that the reference implements on the JVM — the ingest key-encode hot loop
(Z3IndexKeySpace.toIndexKey, SURVEY.md §3.2) — as a fused C++ pass bound via
ctypes (no pybind11 in this image). The shared object compiles on first use
with g++ and is cached next to the source; every entry point has a numpy
fallback, so the package works (slower) without a toolchain.

Parity contract: bit-identical outputs to the numpy paths (device.py fp62,
curves/normalize.py, curves/binnedtime.py, curves/zorder.py), pinned by
tests/test_native.py.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
import threading
import warnings
from typing import Optional

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "encode.cpp")
_SO = os.path.join(_DIR, "_encode.so")

_lib = None
_lock = threading.Lock()
_load_failed = False
_fallback_reason: Optional[str] = None   # why _load gave up (None: it didn't)


def _nthreads() -> int:
    try:
        return max(1, min(os.cpu_count() or 1, 16))
    except Exception:
        return 1


def _cpu_identity() -> str:
    """What ``-march=native`` compiled for: the first processor's model and
    feature flags (a binary copied from another host must not load as
    fresh here)."""
    ident = platform.machine()
    try:
        with open("/proc/cpuinfo") as f:
            first = f.read().split("\n\n", 1)[0]
        ident += "".join(
            line for line in first.splitlines(True)
            if line.split(":", 1)[0].strip() in ("model name", "flags",
                                                  "Features"))
    except OSError:
        ident += platform.processor()
    return hashlib.sha256(ident.encode()).hexdigest()[:16]


def _stamp_prefix() -> str:
    """Freshness stamp minus the flags: source digest + this host's CPU."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return f"{digest} {_cpu_identity()} "


def _build() -> None:
    """Compile the shared object (per-process temp name + atomic rename, so
    concurrent first-use from several processes can't install a torn file)
    and stamp it with source digest, CPU identity and flags. Raises
    RuntimeError carrying every attempt's command and compiler stderr."""
    cxx = os.environ.get("CXX", "g++")
    tmp = f"{_SO}.{os.getpid()}.tmp"
    errors = []
    for flags in (["-O3", "-march=native"], ["-O3"]):  # native may not exist
        cmd = [cxx, *flags, "-shared", "-fPIC", "-std=c++17", "-pthread",
               _SRC, "-o", tmp]
        try:
            subprocess.run(cmd, check=True, capture_output=True, text=True,
                           timeout=120)
            os.replace(tmp, _SO)
            with open(_SO + ".sha", "w") as f:
                f.write(_stamp_prefix() + " ".join(flags))
            return
        except (OSError, subprocess.SubprocessError) as e:
            stderr = (getattr(e, "stderr", None) or "").strip()
            errors.append(f"{' '.join(cmd)}: {e}"
                          + (f"\n{stderr}" if stderr else ""))
            try:
                os.unlink(tmp)
            except OSError:
                pass
    raise RuntimeError("\n".join(errors))


def _load():
    """The compiled library, or None when unavailable (numpy fallback —
    slower; the reason is warned once and kept in ``fallback_reason()``)."""
    global _lib, _load_failed, _fallback_reason
    if _lib is not None or _load_failed:
        return _lib
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        from geomesa_tpu import config
        if config.NO_NATIVE.get():
            _load_failed = True
            return None
        try:
            try:
                with open(_SO + ".sha") as f:
                    fresh = os.path.exists(_SO) \
                        and f.read().startswith(_stamp_prefix())
            except OSError:
                fresh = False
            if not fresh:
                _build()
            try:
                lib = ctypes.CDLL(_SO)
            except OSError:
                # stale/foreign binary (different arch or glibc): rebuild once
                _build()
                lib = ctypes.CDLL(_SO)
            i64, i32, i16, u32, f64, f32 = (
                np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.int16, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.uint32, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
            )
            lib.gm_z3_encode.argtypes = [
                f64, f64, i64, ctypes.c_int64, ctypes.c_int32,
                i32, i32, i32, i32, f32, f32, i16, i32, u32, u32, i64,
                ctypes.c_int32]
            lib.gm_z2_encode.argtypes = [
                f64, f64, ctypes.c_int64,
                i32, i32, i32, i32, f32, f32, u32, u32, i64, ctypes.c_int32]
            lib.gm_fp62.argtypes = [
                f64, ctypes.c_int64, ctypes.c_double, ctypes.c_double,
                i32, i32, ctypes.c_int32]
            u8 = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
            lib.gm_zranges.argtypes = [
                i64, i64, ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
                ctypes.c_int64, ctypes.c_int32, i64, i64, u8, ctypes.c_int64]
            lib.gm_zranges.restype = ctypes.c_int64
            _lib = lib
        except (OSError, RuntimeError, AttributeError) as e:
            # no toolchain / unloadable binary / missing symbol: the numpy
            # paths serve (bit-identical, slower) — said once, with the cause
            _load_failed = True
            _fallback_reason = f"{type(e).__name__}: {e}"
            warnings.warn(
                "geomesa_tpu.native: C++ encoder unavailable, using the "
                f"numpy paths ({_fallback_reason})", RuntimeWarning,
                stacklevel=2)
    return _lib


def fallback_reason() -> Optional[str]:
    """Why the native library is not in use (None while it is, or before
    first use, or when GEOMESA_TPU_NO_NATIVE turned it off)."""
    return _fallback_reason


def available() -> bool:
    return _load() is not None


_PERIOD_CODES = {"day": 0, "week": 1}


def z3_encode(x: np.ndarray, y: np.ndarray, ms: np.ndarray, period: str):
    """Fused Z3 build encode. Returns a dict of all build planes, or None
    when the native library or the period (calendar months/years) is
    unsupported — callers fall back to the numpy path."""
    lib = _load()
    code = _PERIOD_CODES.get(str(period).lower())
    if lib is None or code is None:
        return None
    x = np.ascontiguousarray(x, dtype=np.float64)
    y = np.ascontiguousarray(y, dtype=np.float64)
    ms = np.ascontiguousarray(ms, dtype=np.int64)
    n = len(x)
    if n:
        # bins ride as int16 (the reference's Short bins, BinnedTime.MAX_BIN);
        # out-of-range epochs (pre-1970 / >2059 for days) take the numpy path
        period_ms = 86_400_000 if code == 0 else 604_800_000
        if not (0 <= int(ms.min()) and int(ms.max()) // period_ms <= 32767):
            return None
    out = {
        "xi": np.empty(n, np.int32), "xl": np.empty(n, np.int32),
        "yi": np.empty(n, np.int32), "yl": np.empty(n, np.int32),
        "xf": np.empty(n, np.float32), "yf": np.empty(n, np.float32),
        "bin16": np.empty(n, np.int16), "off": np.empty(n, np.int32),
        "zhi": np.empty(n, np.uint32), "zlo": np.empty(n, np.uint32),
        "z": np.empty(n, np.int64),
    }
    lib.gm_z3_encode(x, y, ms, n, code, out["xi"], out["xl"], out["yi"],
                     out["yl"], out["xf"], out["yf"], out["bin16"],
                     out["off"], out["zhi"], out["zlo"], out["z"],
                     _nthreads())
    return out


def z2_encode(x: np.ndarray, y: np.ndarray):
    lib = _load()
    if lib is None:
        return None
    x = np.ascontiguousarray(x, dtype=np.float64)
    y = np.ascontiguousarray(y, dtype=np.float64)
    n = len(x)
    out = {
        "xi": np.empty(n, np.int32), "xl": np.empty(n, np.int32),
        "yi": np.empty(n, np.int32), "yl": np.empty(n, np.int32),
        "xf": np.empty(n, np.float32), "yf": np.empty(n, np.float32),
        "zhi": np.empty(n, np.uint32), "zlo": np.empty(n, np.uint32),
        "z": np.empty(n, np.int64),
    }
    lib.gm_z2_encode(x, y, n, out["xi"], out["xl"], out["yi"], out["yl"],
                     out["xf"], out["yf"], out["zhi"], out["zlo"], out["z"],
                     _nthreads())
    return out


def zranges(blo: np.ndarray, bhi: np.ndarray, dims: int, bits: int,
            max_ranges: int, max_levels: int):
    """Morton range cover (≙ sfcurve zranges on the query-planning path).
    (lo, hi, contained) merged inclusive z-interval arrays, or None for the
    Python fallback. blo/bhi: (n_boxes, dims) inclusive normalized ints."""
    lib = _load()
    if lib is None:
        return None
    blo = np.ascontiguousarray(blo, dtype=np.int64)
    bhi = np.ascontiguousarray(bhi, dtype=np.int64)
    cap = 2 * int(max_ranges) + 4 * (1 << dims)
    lo = np.empty(cap, np.int64)
    hi = np.empty(cap, np.int64)
    cont = np.empty(cap, np.uint8)
    n = lib.gm_zranges(blo, bhi, blo.shape[0], dims, bits, int(max_ranges),
                       int(max_levels), lo, hi, cont, cap)
    if n < 0:
        return None
    return lo[:n], hi[:n], cont[:n].astype(bool)


def fp62_planes(x: np.ndarray, lo: float, hi: float):
    """(hi_plane, lo_plane) int32 — native fp62, or None for numpy fallback."""
    lib = _load()
    if lib is None:
        return None
    x = np.ascontiguousarray(x, dtype=np.float64)
    n = len(x)
    phi = np.empty(n, np.int32)
    plo = np.empty(n, np.int32)
    lib.gm_fp62(x, n, lo, hi, phi, plo, _nthreads())
    return phi, plo
