"""Native (C++) runtime components, loaded via ctypes.

Reference 2.10 note: the reference's whole runtime is native (Rust);
here the JAX/XLA compute plane stays Python-orchestrated, and the
host-side hot paths (MV row map; more to come) are C++ compiled
on first use into a cached shared library. Everything has a pure-
Python fallback, so a missing toolchain only costs speed.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_SRC_DIR = os.path.join(os.path.dirname(__file__), "native_src")
_BUILD_DIR = os.path.join(_SRC_DIR, "_build")
_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_TRIED = False


def _build_and_load() -> Optional[ctypes.CDLL]:
    src = os.path.join(_SRC_DIR, "mv_map.cpp")
    try:
        # Rebuilds are gated on a source-content hash (not mtime): git
        # does not preserve mtimes, so a stale checked-out .so could
        # otherwise load silently after a clone (ADVICE r2, medium).
        import hashlib

        with open(src, "rb") as f:
            tag = hashlib.sha256(f.read()).hexdigest()[:12]
        so = os.path.join(_BUILD_DIR, f"librw_native_{tag}.so")
        if not os.path.exists(so):
            os.makedirs(_BUILD_DIR, exist_ok=True)
            # a temporary of this process's own: several processes may
            # build at once from a fresh checkout (pytest -n 6), and a
            # shared name had one linker write into the file another
            # had already moved into place, or find its output gone;
            # the loser then fell back to Python for its whole life
            tmp = f"{so}.{os.getpid()}.tmp"
            try:
                subprocess.run(
                    ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", src,
                     "-o", tmp],
                    check=True,
                    capture_output=True,
                )
                os.replace(tmp, so)
            except (OSError, subprocess.CalledProcessError):
                if not os.path.exists(so):  # nobody else landed it either
                    raise
            finally:
                if os.path.exists(tmp):
                    os.remove(tmp)
            # only after the new build landed: drop artifacts of prior
            # source versions (a failed compile must not delete the
            # last working library)
            import glob

            for old in glob.glob(
                os.path.join(_BUILD_DIR, "librw_native*.so")
            ):
                if old != so:
                    try:
                        os.remove(old)
                    except OSError:
                        pass
        lib = ctypes.CDLL(so)
        lib.mv_new.restype = ctypes.c_void_p
        lib.mv_new.argtypes = [ctypes.c_int64, ctypes.c_int64]
        lib.mv_free.argtypes = [ctypes.c_void_p]
        lib.mv_apply.argtypes = [
            ctypes.c_void_p,
            ctypes.c_void_p,
            ctypes.c_void_p,
            ctypes.c_void_p,
            ctypes.c_int64,
        ]
        lib.mv_len.restype = ctypes.c_int64
        lib.mv_len.argtypes = [ctypes.c_void_p]
        lib.mv_dump.argtypes = [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p]
        lib.mv_get.restype = ctypes.c_int32
        lib.mv_get.argtypes = [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p]
        return lib
    except (OSError, subprocess.CalledProcessError):
        return None


def get_lib() -> Optional[ctypes.CDLL]:
    global _LIB, _TRIED
    with _LOCK:
        if not _TRIED:
            _LIB = _build_and_load()
            _TRIED = True
        return _LIB


class NativeMvMap:
    """int64-lane MV row map backed by the C++ unordered_map."""

    def __init__(self, k_arity: int, v_arity: int):
        self._lib = get_lib()
        if self._lib is None:
            raise RuntimeError("native library unavailable")
        self.k_arity = k_arity
        self.v_arity = v_arity
        self._h = self._lib.mv_new(k_arity, v_arity)

    def __del__(self):
        if getattr(self, "_h", None) and self._lib is not None:
            self._lib.mv_free(self._h)
            self._h = None

    def __len__(self) -> int:
        return int(self._lib.mv_len(self._h))

    def apply(self, keys: np.ndarray, vals: np.ndarray, is_del: np.ndarray):
        n = len(is_del)
        if n == 0:
            return
        keys = np.ascontiguousarray(keys, np.int64).reshape(n, self.k_arity)
        vals = (
            np.ascontiguousarray(vals, np.int64).reshape(n, self.v_arity)
            if self.v_arity
            else np.zeros((n, 0), np.int64)
        )
        is_del = np.ascontiguousarray(is_del, np.uint8)
        self._lib.mv_apply(
            self._h,
            keys.ctypes.data,
            vals.ctypes.data,
            is_del.ctypes.data,
            n,
        )

    def dump(self):
        n = len(self)
        keys = np.empty((n, self.k_arity), np.int64)
        vals = np.empty((n, self.v_arity), np.int64)
        if n:
            self._lib.mv_dump(self._h, keys.ctypes.data, vals.ctypes.data)
        return keys, vals

    def get(self, key) -> Optional[tuple]:
        k = np.asarray(key, np.int64)
        out = np.empty(self.v_arity, np.int64)
        if self._lib.mv_get(self._h, k.ctypes.data, out.ctypes.data):
            return tuple(out.tolist())
        return None
