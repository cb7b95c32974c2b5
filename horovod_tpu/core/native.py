"""ctypes binding for the native host-path library (csrc/fusion.cpp).

The reference binds its native core with ctypes the same way
(``horovod/common/basics.py:29`` loads the shared lib).  If the
library is missing it is built once with g++ (the toolchain is part of
the image); failing that, a numpy fallback keeps everything working —
the choice is logged once at WARNING and reported by :func:`status`.
"""

import ctypes
import hashlib
import logging
import os
import subprocess
import threading

import numpy as np

logger = logging.getLogger("horovod_tpu")

_lock = threading.Lock()
_lib = None
_tried = False
_status = "numpy-fallback"

_PKG_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_LIB_DIR = os.path.join(_PKG_DIR, "_native")
_SRC_DIR = os.path.join(os.path.dirname(_PKG_DIR), "csrc")
_SRC_NAMES = ("fusion.cpp", "arena.cpp", "timeline.cpp")


def _srcs():
    return [os.path.join(_SRC_DIR, s) for s in _SRC_NAMES]


def _lib_path():
    """The library's file name carries a hash of its sources' CONTENT:
    ``_native/`` is git-ignored, so a copied tree can bring along a
    library built from other sources (or with any mtime), and it must
    never be the one that loads."""
    digest = hashlib.sha256()
    for src in _srcs():
        with open(src, "rb") as f:
            digest.update(f.read())
    return os.path.join(_LIB_DIR,
                        f"libhvdnative.{digest.hexdigest()[:16]}.so")


def _build(lib_path):
    os.makedirs(_LIB_DIR, exist_ok=True)
    # compile to a per-process temp file and rename atomically so
    # concurrently launched workers never dlopen a half-written .so
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-fPIC", "-std=c++17", "-shared",
           "-o", tmp] + _srcs() + ["-lpthread"]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, lib_path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def status() -> str:
    """How this process got its host path: ``"built"`` (compiled just
    now), ``"loaded"`` (a library of these sources was already there)
    or ``"numpy-fallback"``."""
    get_lib()
    return _status


def get_lib():
    """Load (building if needed) the native lib; None on failure."""
    global _lib, _tried, _status
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        try:
            lib_path = _lib_path()
            found = os.path.exists(lib_path)
            if not found:
                _build(lib_path)
            lib = ctypes.CDLL(lib_path)
            lib.hvd_pack.argtypes = [
                ctypes.POINTER(ctypes.c_void_p),
                ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_int64),
                ctypes.c_int64, ctypes.c_char_p]
            lib.hvd_unpack.argtypes = [
                ctypes.c_char_p,
                ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_int64),
                ctypes.c_int64,
                ctypes.POINTER(ctypes.c_void_p)]
            if hasattr(lib, "hvd_pack_mt"):
                lib.hvd_pack_mt.argtypes = \
                    lib.hvd_pack.argtypes + [ctypes.c_int64]
            if hasattr(lib, "hvd_arena_new"):
                lib.hvd_arena_new.restype = ctypes.c_void_p
                lib.hvd_arena_acquire.restype = ctypes.c_void_p
                lib.hvd_arena_acquire.argtypes = [ctypes.c_void_p,
                                                  ctypes.c_int64]
                lib.hvd_arena_release.argtypes = [ctypes.c_void_p,
                                                  ctypes.c_void_p]
                lib.hvd_arena_bytes.restype = ctypes.c_int64
                lib.hvd_arena_bytes.argtypes = [ctypes.c_void_p]
                lib.hvd_arena_destroy.argtypes = [ctypes.c_void_p]
            if hasattr(lib, "hvd_tl_open"):
                lib.hvd_tl_open.restype = ctypes.c_void_p
                lib.hvd_tl_open.argtypes = [ctypes.c_char_p]
                lib.hvd_tl_event.argtypes = [
                    ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
                    ctypes.c_int64, ctypes.c_double]
                if hasattr(lib, "hvd_tl_counter"):
                    lib.hvd_tl_counter.argtypes = [
                        ctypes.c_void_p, ctypes.c_char_p,
                        ctypes.c_char_p, ctypes.c_double]
                if hasattr(lib, "hvd_tl_set_pid"):
                    lib.hvd_tl_set_pid.argtypes = [
                        ctypes.c_void_p, ctypes.c_int64]
                if hasattr(lib, "hvd_tl_meta"):
                    lib.hvd_tl_meta.argtypes = [
                        ctypes.c_void_p, ctypes.c_char_p,
                        ctypes.c_char_p, ctypes.c_int64]
                if hasattr(lib, "hvd_tl_flow"):
                    lib.hvd_tl_flow.argtypes = [
                        ctypes.c_void_p, ctypes.c_char_p,
                        ctypes.c_int64, ctypes.c_int64,
                        ctypes.c_double]
                lib.hvd_tl_close.argtypes = [ctypes.c_void_p]
            _lib = lib
            _status = "loaded" if found else "built"
        except Exception as exc:  # noqa: BLE001 — fall back to numpy
            # (no compiler, no sources in an installed package, a
            # library this machine cannot load): slower, same results
            logger.warning("native host library unavailable (%r); the "
                           "numpy path packs fusion buckets and writes "
                           "timelines instead", exc)
            _lib = None
        return _lib


def available() -> bool:
    return get_lib() is not None


def pack(arrays, dst: np.ndarray, offsets_bytes) -> None:
    """Pack flat arrays into the contiguous dst buffer at byte offsets
    (one native call per fusion bucket; reference batched-D2D)."""
    lib = get_lib()
    n = len(arrays)
    if lib is None or n == 0:
        for a, off in zip(arrays, offsets_bytes):
            nb = a.nbytes
            dst.view(np.uint8)[off:off + nb] = \
                np.ascontiguousarray(a).view(np.uint8).ravel()
        return
    arrays = [np.ascontiguousarray(a) for a in arrays]
    srcs = (ctypes.c_void_p * n)(
        *[a.ctypes.data for a in arrays])
    sizes = (ctypes.c_int64 * n)(*[a.nbytes for a in arrays])
    offs = (ctypes.c_int64 * n)(*offsets_bytes)
    lib.hvd_pack(srcs, sizes, offs, n,
                 dst.ctypes.data_as(ctypes.c_char_p))


def unpack(src: np.ndarray, arrays, offsets_bytes) -> None:
    """Scatter the contiguous src buffer back into writable arrays."""
    lib = get_lib()
    n = len(arrays)
    if lib is None or n == 0:
        for a, off in zip(arrays, offsets_bytes):
            nb = a.nbytes
            a.view(np.uint8).ravel()[:] = \
                src.view(np.uint8)[off:off + nb]
        return
    for a in arrays:
        assert a.flags["C_CONTIGUOUS"] and a.flags["WRITEABLE"]
    dsts = (ctypes.c_void_p * n)(*[a.ctypes.data for a in arrays])
    sizes = (ctypes.c_int64 * n)(*[a.nbytes for a in arrays])
    offs = (ctypes.c_int64 * n)(*offsets_bytes)
    lib.hvd_unpack(src.ctypes.data_as(ctypes.c_char_p),
                   sizes, offs, n, dsts)


def pack_mt(arrays, dst: np.ndarray, offsets_bytes,
            nthreads: int = 4) -> None:
    """Multithreaded pack for large buckets (csrc hvd_pack_mt); falls
    back to the single-threaded path."""
    lib = get_lib()
    n = len(arrays)
    if lib is None or n == 0 or not hasattr(lib, "hvd_pack_mt"):
        return pack(arrays, dst, offsets_bytes)
    arrays = [np.ascontiguousarray(a) for a in arrays]
    srcs = (ctypes.c_void_p * n)(*[a.ctypes.data for a in arrays])
    sizes = (ctypes.c_int64 * n)(*[a.nbytes for a in arrays])
    offs = (ctypes.c_int64 * n)(*offsets_bytes)
    lib.hvd_pack_mt(srcs, sizes, offs, n,
                    dst.ctypes.data_as(ctypes.c_char_p), nthreads)


class Arena:
    """Size-class staging-buffer arena (csrc/arena.cpp — the
    reference FusionBufferManager's persistent-buffer role).  Buffers
    come back as numpy views over 64-byte-aligned native slabs; a
    numpy freelist stands in when the native lib is unavailable."""

    def __init__(self):
        self._lib = get_lib()
        self._native = self._lib is not None and \
            hasattr(self._lib, "hvd_arena_new")
        self._handle = self._lib.hvd_arena_new() if self._native else None
        self._py_free = {}      # size-class -> [ndarray]
        self._live = {}         # data address -> release token
        self._lock = threading.Lock()

    @staticmethod
    def _cls(nbytes):
        c = 4096
        while c < nbytes:
            c <<= 1
        return c

    def acquire(self, nbytes: int, dtype=np.uint8) -> np.ndarray:
        """A reusable buffer of >= nbytes, viewed as `dtype`
        (element count = nbytes // itemsize).  Release by passing the
        SAME array (tracked by data address — ndarrays don't accept
        attributes)."""
        itemsize = np.dtype(dtype).itemsize
        if self._native:
            ptr = self._lib.hvd_arena_acquire(self._handle, nbytes)
            if ptr:
                raw = (ctypes.c_char * nbytes).from_address(ptr)
                arr = np.frombuffer(raw, dtype=np.uint8, count=nbytes) \
                    .view(dtype)[: nbytes // itemsize]
                with self._lock:
                    self._live[int(ptr)] = ("native", int(ptr))
                return arr
        cls = self._cls(nbytes)
        with self._lock:
            slabs = self._py_free.setdefault(cls, [])
            base = slabs.pop() if slabs else np.empty(cls, np.uint8)
        arr = base[:nbytes].view(dtype)[: nbytes // itemsize]
        with self._lock:
            self._live[int(base.ctypes.data)] = ("py", base)
        return arr

    def release(self, arr: np.ndarray):
        addr = int(arr.ctypes.data)
        with self._lock:
            token = self._live.pop(addr, None)
        if token is None:
            return
        kind, val = token
        if kind == "native":
            self._lib.hvd_arena_release(self._handle, val)
        else:
            with self._lock:
                self._py_free.setdefault(len(val), []).append(val)

    def total_bytes(self) -> int:
        if self._native:
            return int(self._lib.hvd_arena_bytes(self._handle))
        with self._lock:
            return sum(len(b) for slabs in self._py_free.values()
                       for b in slabs)

    def __del__(self):  # pragma: no cover — interpreter teardown
        try:
            if self._native and self._handle:
                self._lib.hvd_arena_destroy(self._handle)
                self._handle = None
        except Exception:  # noqa: BLE001
            pass


def timeline_writer(path: str):
    """Native async chrome-trace writer handle, or None when the lib
    lacks it (utils/timeline.py then uses its python writer thread)."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "hvd_tl_open"):
        return None
    handle = lib.hvd_tl_open(path.encode())
    return (lib, handle) if handle else None
