"""ctypes bindings for the native loader core (``loader.cpp``).

``libddl_loader.so`` is not committed: it is built from ``loader.cpp``
with the Makefile beside it on first use, if a toolchain is present.
Every caller must handle ``loader_lib() is None`` and fall back to the
pure-Python path (PIL), so the framework works with no compiler at all.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from pathlib import Path

import numpy as np

__all__ = ["loader_lib", "load_batch", "native_available", "image_size"]

_HERE = Path(__file__).parent
_SO = _HERE / "libddl_loader.so"
_lib = None
_tried = False


def _build() -> bool:
    try:
        subprocess.run(
            ["make", "-C", str(_HERE), "-s"],
            check=True,
            capture_output=True,
            timeout=120,
        )
        return _SO.exists()
    except Exception:
        return False


def loader_lib():
    """The loaded shared library, or None if unavailable."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    _tried = True
    if not _SO.exists() and not _build():
        return None
    try:
        lib = ctypes.CDLL(str(_SO))
        lib.ddl_pool_init.argtypes = [ctypes.c_int]
        lib.ddl_load_batch.argtypes = [
            ctypes.c_char_p,
            ctypes.c_int,
            ctypes.c_int,
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_uint8),
        ]
        lib.ddl_load_batch.restype = ctypes.c_int
        lib.ddl_image_size.argtypes = [
            ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_int),
        ]
        lib.ddl_pool_init(max(2, (os.cpu_count() or 4) // 2))
        _lib = lib
    except OSError:
        _lib = None
    return _lib


def native_available() -> bool:
    return loader_lib() is not None


def image_size(path: str | os.PathLike) -> tuple[int, int] | None:
    """(height, width) of a PNG via the native probe, or None."""
    lib = loader_lib()
    if lib is None:
        return None
    h, w = ctypes.c_int(0), ctypes.c_int(0)
    if lib.ddl_image_size(str(path).encode(), ctypes.byref(h), ctypes.byref(w)) != 0:
        return None
    return h.value, w.value


def load_batch(paths: list[str | os.PathLike], height: int, width: int) -> np.ndarray | None:
    """Decode a batch of image files into one (N, H, W, 3) uint8 array using
    the native thread pool.  Returns None if the native core is unavailable
    or any image failed to decode (caller falls back to PIL)."""
    lib = loader_lib()
    if lib is None:
        return None
    n = len(paths)
    out = np.empty((n, height, width, 3), dtype=np.uint8)
    joined = "\n".join(str(p) for p in paths).encode()
    ok = lib.ddl_load_batch(
        joined, n, height, width, out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
    )
    return out if ok == n else None
