"""ctypes binding for the native transport data plane (transport.cpp).

Builds on first use with g++, exactly like the arena binding. The
shared object is cached next to the source under a name keyed by a
hash of the source and the compiler flags, so only a binary built from
the ``.cpp`` on disk can load — a stale one copied along with the tree
never matches. When the build fails, ``load()`` returns None and
:func:`build_error` keeps g++'s stderr: ``transport=auto`` then picks
the pure-Python transport (same wire format), while an explicit
``transport=native`` fails with that stderr (transport.create_node).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import shutil
import subprocess
import threading
from typing import Optional

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "transport.cpp")

# SPARKRDMA_NATIVE_SANITIZE="thread,undefined" rebuilds the plane with
# -fsanitize=... into a separately cached .so (the CI native-tsan job;
# see docs/ANALYSIS.md). TSan-instrumented objects need the runtime
# loaded first: run under LD_PRELOAD=$(g++ -print-file-name=libtsan.so)
# or dlopen dies allocating static TLS.
_SANITIZE = os.environ.get("SPARKRDMA_NATIVE_SANITIZE", "").strip()

# SPARKRDMA_NATIVE_NO_IOURING=1 compiles the io_uring read backend OUT
# (-DSRT_NO_IOURING) into a separately cached .so — the CI matrix leg
# proving the submission plane stays tier-1-green and reports the pread
# fallback when the uapi header (or kernel) is absent.
_NO_IOURING = os.environ.get(
    "SPARKRDMA_NATIVE_NO_IOURING", ""
).strip() not in ("", "0")


def _build_flags() -> list:
    flags = ["-O2", "-std=c++17", "-shared", "-fPIC", "-pthread"]
    if _SANITIZE:
        flags += [f"-fsanitize={_SANITIZE}", "-fno-sanitize-recover=all", "-g"]
    if _NO_IOURING:
        flags.append("-DSRT_NO_IOURING")
    return flags


class NativeBuildError(RuntimeError):
    """g++ could not build a native library; the message carries its
    stderr."""


def so_path(base: str, src: str) -> str:
    """Cache path of ``src``'s shared object: ``<base>.<hash>.so``,
    the hash covering the source bytes and the build flags."""
    h = hashlib.sha256()
    with open(src, "rb") as f:
        h.update(f.read())
    h.update("\0".join(_build_flags()).encode())
    return os.path.join(_HERE, f"{base}.{h.hexdigest()[:16]}.so")


def build_library(base: str, src: str) -> str:
    """Return the path of ``src``'s shared object, building it first
    when no object of this source and these flags is cached. Raises
    :class:`NativeBuildError` with g++'s stderr on failure."""
    so = so_path(base, src)
    if os.path.exists(so):
        return so
    # build beside the target and rename into place: concurrent
    # builders (test workers) never load a half-written object
    tmp = f"{so}.{os.getpid()}.tmp"
    try:
        proc = subprocess.run(
            ["g++", *_build_flags(), "-o", tmp, src],
            capture_output=True, text=True,
        )
    except OSError as e:
        raise NativeBuildError(f"cannot run g++ for {src}: {e}") from e
    if proc.returncode != 0:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise NativeBuildError(
            f"g++ failed building {src} (rc={proc.returncode}):\n"
            f"{proc.stderr}"
        )
    os.replace(tmp, so)
    return so


_lib = None
_lib_lock = threading.Lock()
_build_error: Optional[str] = None

# completion kinds (transport.cpp)
COMP_SEND_DONE = 1
COMP_READ_DONE = 2
COMP_RECV = 3
COMP_CHANNEL_DOWN = 4
COMP_ACCEPT = 5

ST_OK = 0
ST_ERR = 1
ST_REMOTE_ERR = 2

# tpu.shuffle.native.readBackend values -> srt_set_read_backend codes
# (RB_* enum in transport.cpp)
READ_BACKENDS = {"auto": 0, "iouring": 1, "pread": 2, "mapped": 3}


class SrtComp(ctypes.Structure):
    _fields_ = [
        ("kind", ctypes.c_uint32),
        ("status", ctypes.c_uint32),
        ("channel", ctypes.c_uint64),
        ("wr_id", ctypes.c_uint64),
        ("payload", ctypes.c_void_p),
        ("payload_len", ctypes.c_uint64),
        ("aux", ctypes.c_uint32),
        ("_pad", ctypes.c_uint32),
    ]


def load() -> Optional[ctypes.CDLL]:
    global _lib, _build_error
    if _lib is not None or _build_error is not None:
        return _lib
    with _lib_lock:
        if _lib is not None or _build_error is not None:
            return _lib
        try:
            lib = ctypes.CDLL(build_library("_libsrt_transport", _SRC))
        except (NativeBuildError, OSError) as e:
            _build_error = str(e)
            return None
        lib.srt_node_create.restype = ctypes.c_void_p
        lib.srt_node_create.argtypes = [ctypes.c_char_p, ctypes.c_uint16, ctypes.c_int]
        lib.srt_node_port.restype = ctypes.c_uint16
        lib.srt_node_port.argtypes = [ctypes.c_void_p]
        lib.srt_reg.restype = ctypes.c_uint32
        lib.srt_reg.argtypes = [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_uint64]
        lib.srt_reg_file.restype = ctypes.c_uint32
        lib.srt_reg_file.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_uint64,
            ctypes.c_char_p, ctypes.c_uint64,
            # backing-file identity from the caller's fstat of the
            # mapping fd: dev, ino, size, mtime_ns
            ctypes.c_uint64, ctypes.c_uint64, ctypes.c_uint64,
            ctypes.c_uint64,
        ]
        lib.srt_dereg.restype = ctypes.c_int
        lib.srt_dereg.argtypes = [ctypes.c_void_p, ctypes.c_uint32]
        lib.srt_region_count.restype = ctypes.c_uint64
        lib.srt_region_count.argtypes = [ctypes.c_void_p]
        lib.srt_stat_file_reads.restype = ctypes.c_uint64
        lib.srt_stat_file_reads.argtypes = [ctypes.c_void_p]
        lib.srt_stat_streamed_reads.restype = ctypes.c_uint64
        lib.srt_stat_streamed_reads.argtypes = [ctypes.c_void_p]
        lib.srt_stat_split_parts.restype = ctypes.c_uint64
        lib.srt_stat_split_parts.argtypes = [ctypes.c_void_p]
        lib.srt_stat_block_stripes.restype = ctypes.c_uint64
        lib.srt_stat_block_stripes.argtypes = [ctypes.c_void_p]
        # submission plane: backend knob, availability probe, SQ stats
        lib.srt_set_read_backend.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.srt_uring_compiled.restype = ctypes.c_int
        lib.srt_uring_compiled.argtypes = []
        lib.srt_read_backend_effective.restype = ctypes.c_int
        lib.srt_read_backend_effective.argtypes = [ctypes.c_void_p]
        lib.srt_sq_force_probe_fail.argtypes = [ctypes.c_void_p, ctypes.c_int]
        for _stat in ("submits", "batches", "depth_hwm", "completions",
                      "backend_fallbacks"):
            fn = getattr(lib, f"srt_stat_sq_{_stat}")
            fn.restype = ctypes.c_uint64
            fn.argtypes = [ctypes.c_void_p]
        lib.srt_connect.restype = ctypes.c_uint64
        lib.srt_connect.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint16,
            ctypes.c_uint16, ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
        ]
        lib.srt_post_send.restype = ctypes.c_int
        lib.srt_post_send.argtypes = [
            ctypes.c_void_p, ctypes.c_uint64, ctypes.c_void_p,
            ctypes.c_uint64, ctypes.c_uint64, ctypes.c_int,
        ]
        lib.srt_post_read.restype = ctypes.c_int
        lib.srt_post_read.argtypes = [
            ctypes.c_void_p, ctypes.c_uint64, ctypes.c_uint64, ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_uint64), ctypes.c_uint32,
        ]
        lib.srt_post_read_mapped.restype = ctypes.c_int
        lib.srt_post_read_mapped.argtypes = [
            ctypes.c_void_p, ctypes.c_uint64, ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint64), ctypes.c_uint32,
        ]
        lib.srt_unmap.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.srt_set_file_fastpath.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.srt_set_file_workers.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.srt_set_force_sendfile.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.srt_close_channel.restype = ctypes.c_int
        lib.srt_close_channel.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.srt_poll_cq.restype = ctypes.c_int
        lib.srt_poll_cq.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(SrtComp), ctypes.c_int, ctypes.c_int,
        ]
        lib.srt_free_payload.argtypes = [ctypes.c_void_p]
        lib.srt_node_stop.argtypes = [ctypes.c_void_p]
        _lib = lib
    return _lib


def available() -> bool:
    return load() is not None


def build_error() -> Optional[str]:
    """Why the native plane is unavailable (g++'s stderr), or None."""
    load()
    return _build_error


def toolchain_available() -> bool:
    """True when the native plane is *buildable* here: g++ on PATH or a
    prebuilt .so already cached. Distinct from ``available()``, which
    also returns False when the build itself fails — tests must gate
    their skip on THIS so a transport.cpp compile breakage fails
    loudly instead of silently skipping. Cheap (no build triggered),
    so safe to call at pytest collection time."""
    return shutil.which("g++") is not None or os.path.exists(
        so_path("_libsrt_transport", _SRC)
    )
