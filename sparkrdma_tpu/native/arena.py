"""ctypes binding for the native off-heap arena (arena.cpp).

Builds the shared library on first use with g++, cached next to the
source under a name keyed by the source and flags (transport_lib's
``build_library``). If the build fails the caller falls back to
anonymous ``mmap`` allocations (sparkrdma_tpu.memory.buffer) — same
semantics, same page alignment, slightly slower alloc path — and g++'s
stderr is logged once.
"""

from __future__ import annotations

import ctypes
import logging
import os
import threading
from typing import Optional, Tuple

# same build contract as transport_lib.py (sanitizer flags included)
from sparkrdma_tpu.native.transport_lib import NativeBuildError, build_library

logger = logging.getLogger(__name__)

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "arena.cpp")

_lib = None
_lib_lock = threading.Lock()
_build_failed = False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _build_failed
    if _lib is not None or _build_failed:
        return _lib
    with _lib_lock:
        if _lib is not None or _build_failed:
            return _lib
        try:
            lib = ctypes.CDLL(build_library("_libsrt_arena", _SRC))
        except (NativeBuildError, OSError):
            logger.warning("native arena unavailable", exc_info=True)
            _build_failed = True
            return None
        lib.srt_arena_create.restype = ctypes.c_void_p
        lib.srt_arena_destroy.argtypes = [ctypes.c_void_p]
        lib.srt_alloc.restype = ctypes.c_uint64
        lib.srt_alloc.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.srt_addr.restype = ctypes.c_void_p
        lib.srt_addr.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.srt_size.restype = ctypes.c_uint64
        lib.srt_size.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.srt_free.restype = ctypes.c_int
        lib.srt_free.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.srt_copy.argtypes = [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_uint64]
        lib.srt_arena_stats.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64),
        ]
        _lib = lib
    return _lib


def native_arena_available() -> bool:
    return _load() is not None


class NativeArena:
    """One native arena; usually the process-wide shared instance."""

    _shared: Optional["NativeArena"] = None
    _shared_lock = threading.Lock()

    def __init__(self):
        lib = _load()
        if lib is None:
            raise RuntimeError("native arena unavailable (g++ build failed)")
        self._lib = lib
        self._arena = ctypes.c_void_p(lib.srt_arena_create())

    @classmethod
    def shared(cls) -> "NativeArena":
        with cls._shared_lock:
            if cls._shared is None:
                cls._shared = cls()
            return cls._shared

    def alloc(self, size: int) -> Tuple[int, memoryview]:
        alloc_id = self._lib.srt_alloc(self._arena, size)
        if alloc_id == 0:
            raise MemoryError(f"native arena failed to allocate {size} bytes")
        addr = self._lib.srt_addr(self._arena, alloc_id)
        buf = (ctypes.c_char * size).from_address(addr)
        return alloc_id, memoryview(buf).cast("B")

    def free(self, alloc_id: int) -> None:
        self._lib.srt_free(self._arena, alloc_id)

    def stats(self) -> Tuple[int, int, int]:
        """(total_allocs, live_bytes, live_count)."""
        t = ctypes.c_uint64()
        b = ctypes.c_uint64()
        c = ctypes.c_uint64()
        self._lib.srt_arena_stats(self._arena, ctypes.byref(t), ctypes.byref(b), ctypes.byref(c))
        return t.value, b.value, c.value
