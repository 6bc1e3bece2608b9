"""ctypes bindings to the optional native C++ helper library (native/).

The library accelerates host-side columnar chores that sit off the device path:
string hashing for dictionary encoding and CSV newline-boundary scans.  Pure
Python fallbacks exist everywhere, so the package works without a compiler.
"""

from __future__ import annotations

import ctypes
import os
from typing import Optional, Sequence

import numpy as np

_LIB = None
_TRIED = False
# what the first-use build did: the helper is optional, but a missing
# compiler is reported (status()), not hidden
_BUILD_NOTE = "not attempted"


def _build_lib(native_dir: str) -> None:
    """Best-effort auto-build of the native helper on first use."""
    import subprocess

    global _BUILD_NOTE
    src = os.path.join(native_dir, "columnar.cpp")
    out = os.path.join(native_dir, "libquokka_native.so")
    if not os.path.exists(src):
        _BUILD_NOTE = f"no source at {src}"
        return
    if os.path.exists(out) and os.path.getmtime(out) >= os.path.getmtime(src):
        _BUILD_NOTE = "up to date"  # rebuild only when the source is newer
        return
    tmp = out + f".build-{os.getpid()}"
    try:
        subprocess.run(
            ["g++", "-O3", "-fPIC", "-shared", "-std=c++17", "-o", tmp, src],
            check=True,
            capture_output=True,
            timeout=120,
        )
        os.replace(tmp, out)  # atomic: never leave a torn .so behind
        _BUILD_NOTE = "built from columnar.cpp"
    except Exception as e:
        _BUILD_NOTE = f"build failed: {e!r}"
        try:
            os.remove(tmp)
        except OSError:
            pass


def status() -> str:
    """One line for bring-up logs: did the helper load, and what did its
    first-use build do."""
    how = "loaded" if _find_lib() is not None else "absent (Python paths)"
    return f"native helper {how}; build: {_BUILD_NOTE}"


def _find_lib():
    global _LIB, _TRIED
    if _TRIED:
        return _LIB
    _TRIED = True
    here = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    _build_lib(os.path.join(here, "native"))
    for cand in (
        os.path.join(here, "native", "libquokka_native.so"),
        os.environ.get("QUOKKA_TPU_NATIVE_LIB", ""),
    ):
        if cand and os.path.exists(cand):
            try:
                lib = ctypes.CDLL(cand)
                lib.qk_fnv1a64_many.restype = None
                lib.qk_fnv1a64_many.argtypes = [
                    ctypes.c_void_p,  # concatenated utf8 bytes
                    ctypes.c_void_p,  # int64 offsets (n+1)
                    ctypes.c_int64,  # n strings
                    ctypes.c_void_p,  # out uint64[n]
                ]
                lib.qk_find_newline.restype = ctypes.c_int64
                lib.qk_find_newline.argtypes = [ctypes.c_void_p, ctypes.c_int64]
                # newer symbols may be absent from a stale/external .so (no
                # compiler to rebuild): keep the lib for the old entry points
                # and let the new consumers fall back
                try:
                    for fn in ("qk_asof_backward", "qk_asof_forward"):
                        f = getattr(lib, fn)
                        f.restype = None
                        f.argtypes = [
                            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
                            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
                            ctypes.c_void_p,
                        ]
                    lib.qk_is_sorted_i64.restype = ctypes.c_int32
                    lib.qk_is_sorted_i64.argtypes = [
                        ctypes.c_void_p, ctypes.c_int64,
                    ]
                    lib._qk_has_asof = True
                except AttributeError:
                    lib._qk_has_asof = False
                _LIB = lib
            except OSError:
                _LIB = None
            break
    return _LIB


def fnv1a64_many(values: Sequence) -> Optional[np.ndarray]:
    """Hash a sequence of strings with the native lib; None if unavailable."""
    lib = _find_lib()
    if lib is None:
        return None
    encoded = [
        bytes(v) if isinstance(v, (bytes, bytearray))
        else (v if v is not None else "").encode("utf-8", errors="surrogatepass")
        for v in values
    ]
    n = len(encoded)
    offsets = np.zeros(n + 1, dtype=np.int64)
    for i, b in enumerate(encoded):
        offsets[i + 1] = offsets[i] + len(b)
    blob = b"".join(encoded)
    buf = np.frombuffer(blob, dtype=np.uint8) if blob else np.zeros(0, dtype=np.uint8)
    out = np.zeros(n, dtype=np.uint64)
    lib.qk_fnv1a64_many(
        buf.ctypes.data if buf.size else 0,
        offsets.ctypes.data,
        n,
        out.ctypes.data,
    )
    # null entries hash to 0 to match the Python fallback
    for i, v in enumerate(values):
        if v is None:
            out[i] = 0
    return out


def has_asof() -> bool:
    """Whether the loaded native library provides the as-of merge symbols."""
    lib = _find_lib()
    return lib is not None and getattr(lib, "_qk_has_asof", False)


def asof_merge(t_time: np.ndarray, t_key: np.ndarray,
               q_time: np.ndarray, q_key: np.ndarray,
               direction: str = "backward") -> Optional[np.ndarray]:
    """Sequential as-of merge over host arrays (the CPU-backend fast path of
    ops/asof.asof_join).  All inputs int64 and C-contiguous; each side must
    be time-sorted ascending — the CALLER sorts/compacts first.  Returns
    int32 quote indices (-1 = unmatched) per trade, or None when the native
    library is unavailable (callers fall back to the XLA kernel)."""
    lib = _find_lib()
    if lib is None or not getattr(lib, "_qk_has_asof", False):
        return None
    nt, nq = len(t_time), len(q_time)
    out = np.empty(nt, dtype=np.int32)
    if nt == 0:
        return out
    fn = lib.qk_asof_backward if direction == "backward" else lib.qk_asof_forward
    fn(
        t_time.ctypes.data, t_key.ctypes.data, nt,
        q_time.ctypes.data if nq else 0, q_key.ctypes.data if nq else 0, nq,
        out.ctypes.data,
    )
    return out


def is_sorted_i64(a: np.ndarray) -> bool:
    lib = _find_lib()
    if lib is None or not getattr(lib, "_qk_has_asof", False) or len(a) < 2:
        return bool(np.all(a[1:] >= a[:-1])) if len(a) >= 2 else True
    return bool(lib.qk_is_sorted_i64(a.ctypes.data, len(a)))


def find_newline(data: bytes) -> int:
    """Index of first b'\\n' in data, or -1.  Native when available."""
    lib = _find_lib()
    if lib is None:
        return data.find(b"\n")
    buf = np.frombuffer(data, dtype=np.uint8)
    return int(lib.qk_find_newline(buf.ctypes.data if buf.size else 0, len(data)))
