"""Native runtime loader: compiles native.cc via the system toolchain and
binds it through ctypes.  The library's file name carries a hash of
native.cc, so the one loaded is always the one the source in this tree
builds (an mtime means nothing after a copy or a checkout).

The reference's runtime-critical components are C++ (SURVEY.md §2: "everything
runtime-critical is C++"); this package is their TPU-framework equivalent —
recordio, the blocking queue, the buddy allocator, and the threaded prefetch
reader all run in native code with the GIL released (ctypes drops it for the
call's duration).  ``available()`` is False when no toolchain exists; callers
(paddle_tpu.recordio, fluid.core_shim) fall back to pure python.
"""

import ctypes
import glob
import hashlib
import os
import subprocess
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "native.cc")

_lib = None
_tried = False
_lock = threading.Lock()


def _lib_path():
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_HERE, "libpaddle_tpu_native.%s.so" % digest)


def _build(lib_path):
    # compile to a private temp path, then atomic-rename into place:
    # concurrent processes (subprocess tests, multi-worker launch) must
    # never dlopen a half-written .so
    tmp = "%s.tmp.%d" % (lib_path, os.getpid())
    cmd = ["g++", "-O2", "-std=c++17", "-shared", "-fPIC",
           "-fvisibility=hidden", _SRC, "-o", tmp, "-lz", "-lpthread"]
    try:
        subprocess.run(cmd, check=True, capture_output=True)
        os.replace(tmp, lib_path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    # libraries built from earlier versions of native.cc: nothing can load
    # them by name any more (unlinking one a live process has mapped is safe)
    for stale in glob.glob(os.path.join(_HERE, "libpaddle_tpu_native*.so")):
        if stale != lib_path:
            try:
                os.unlink(stale)
            except FileNotFoundError:   # a concurrent builder got there first
                pass


def _bind(lib):
    u32p = ctypes.POINTER(ctypes.c_uint32)
    charpp = ctypes.POINTER(ctypes.c_char_p)
    sigs = {
        "recordio_writer_open": ([ctypes.c_char_p, ctypes.c_int,
                                  ctypes.c_uint32], ctypes.c_void_p),
        "recordio_writer_write": ([ctypes.c_void_p, ctypes.c_char_p,
                                   ctypes.c_uint32], ctypes.c_int),
        "recordio_writer_close": ([ctypes.c_void_p], ctypes.c_int),
        "recordio_scanner_open": ([ctypes.c_char_p], ctypes.c_void_p),
        "recordio_scanner_next": ([ctypes.c_void_p, u32p], ctypes.c_void_p),
        "recordio_scanner_close": ([ctypes.c_void_p], None),
        "bq_create": ([ctypes.c_uint32], ctypes.c_void_p),
        "bq_push": ([ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint32,
                     ctypes.c_int], ctypes.c_int),
        "bq_pop": ([ctypes.c_void_p, ctypes.c_int,
                    ctypes.POINTER(ctypes.c_void_p), u32p], ctypes.c_int),
        "bq_size": ([ctypes.c_void_p], ctypes.c_uint32),
        "bq_close": ([ctypes.c_void_p], None),
        "bq_destroy": ([ctypes.c_void_p], None),
        "buddy_create": ([ctypes.c_size_t, ctypes.c_size_t],
                         ctypes.c_void_p),
        "buddy_alloc": ([ctypes.c_void_p, ctypes.c_size_t], ctypes.c_void_p),
        "buddy_free": ([ctypes.c_void_p, ctypes.c_void_p], ctypes.c_int),
        "buddy_in_use": ([ctypes.c_void_p], ctypes.c_size_t),
        "buddy_destroy": ([ctypes.c_void_p], None),
        "prefetch_open": ([charpp, ctypes.c_uint32, ctypes.c_uint32,
                           ctypes.c_uint32], ctypes.c_void_p),
        "prefetch_next": ([ctypes.c_void_p,
                           ctypes.POINTER(ctypes.c_void_p), u32p],
                          ctypes.c_int),
        "prefetch_close": ([ctypes.c_void_p], None),
        "multislot_parse_line": (
            [ctypes.c_char_p, ctypes.c_uint32,
             ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_float),
             ctypes.POINTER(ctypes.c_longlong), u32p, ctypes.c_uint32],
            ctypes.c_int),
    }
    for name, (argtypes, restype) in sigs.items():
        fn = getattr(lib, name)
        fn.argtypes = argtypes
        fn.restype = restype
    return lib


def get_lib():
    """The bound native library, building it on first use; None if the
    toolchain is unavailable."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        try:
            lib_path = _lib_path()
            if not os.path.exists(lib_path):
                _build(lib_path)
            _lib = _bind(ctypes.CDLL(lib_path))
        except (OSError, subprocess.CalledProcessError):
            _lib = None
    return _lib


def available():
    return get_lib() is not None


# ---------------------------------------------------------------------------
# pythonic wrappers
# ---------------------------------------------------------------------------

class BlockingQueue:
    """Bounded byte queue in native code (LoDTensorBlockingQueue contract:
    push/pop block, close() wakes everyone; GIL released while blocked)."""

    def __init__(self, capacity=64):
        self._lib = get_lib()
        if self._lib is None:
            raise RuntimeError("native runtime unavailable")
        self._h = self._lib.bq_create(capacity)

    def push(self, data, timeout_ms=-1):
        rc = self._lib.bq_push(self._h, data, len(data), timeout_ms)
        if rc == 1:
            raise EOFError("queue closed")
        return rc == 0

    def pop(self, timeout_ms=-1):
        out = ctypes.c_void_p()
        ln = ctypes.c_uint32()
        rc = self._lib.bq_pop(self._h, timeout_ms, ctypes.byref(out),
                              ctypes.byref(ln))
        if rc == 1:
            raise EOFError("queue closed and drained")
        if rc == 2:
            return None
        return ctypes.string_at(out.value, ln.value)

    def size(self):
        return self._lib.bq_size(self._h)

    def close(self):
        self._lib.bq_close(self._h)

    def __del__(self):
        try:
            if getattr(self, "_h", None):
                self._lib.bq_close(self._h)
                self._lib.bq_destroy(self._h)
                self._h = None
        except Exception:
            pass


class BuddyAllocator:
    """Host memory arena with buddy split/merge
    (memory/detail/buddy_allocator.cc parity)."""

    def __init__(self, total_bytes, min_block=64):
        self._lib = get_lib()
        if self._lib is None:
            raise RuntimeError("native runtime unavailable")
        self._h = self._lib.buddy_create(total_bytes, min_block)
        if not self._h:
            raise MemoryError("arena reservation failed")

    def alloc(self, size):
        p = self._lib.buddy_alloc(self._h, size)
        return p  # address (int) or None

    def free(self, ptr):
        if self._lib.buddy_free(self._h, ptr) != 0:
            raise ValueError("invalid free (not a live allocation)")

    @property
    def in_use(self):
        return self._lib.buddy_in_use(self._h)

    def __del__(self):
        try:
            if getattr(self, "_h", None):
                self._lib.buddy_destroy(self._h)
                self._h = None
        except Exception:
            pass
