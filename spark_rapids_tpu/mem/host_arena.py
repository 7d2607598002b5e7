"""ctypes binding for the native host staging arena (native/arena.cpp).

Role analog: the RMM arena allocator + pinned host pool of the reference
(reference: GpuDeviceManager.scala:196-270), managing *host* staging memory
under TPU/XLA (which owns HBM itself).  Builds the shared library on first
use with g++ (a failed build raises); where there is no g++ at all a
pure-Python malloc-per-allocation shim keeps the API identical.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_LIB = None
_LIB_LOCK = threading.Lock()
_SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native", "arena.cpp")


def _build_lib() -> Optional[ctypes.CDLL]:
    # the library's name carries the hash of the source it was built
    # from: what loads is always built from the committed arena.cpp,
    # never a stale binary left in the tree (file times do not survive
    # a copy or a checkout, so they cannot decide this)
    with open(_SRC, "rb") as f:
        digest = hashlib.sha1(f.read()).hexdigest()[:12]
    so_path = os.path.join(os.path.dirname(_SRC),
                           f"libarena-{digest}.so")
    if not os.path.exists(so_path):
        tmp = f"{so_path}.{os.getpid()}.tmp"
        try:
            subprocess.run(
                ["g++", "-O2", "-shared", "-fPIC", "-std=c++17",
                 _SRC, "-o", tmp],
                check=True, capture_output=True)
        except FileNotFoundError:
            return None         # no toolchain: the Python shim
        os.replace(tmp, so_path)    # atomic under concurrent builders
    lib = ctypes.CDLL(so_path)
    lib.arena_create.restype = ctypes.c_void_p
    lib.arena_create.argtypes = [ctypes.c_size_t, ctypes.c_size_t]
    lib.arena_destroy.argtypes = [ctypes.c_void_p]
    lib.arena_alloc.restype = ctypes.c_void_p
    lib.arena_alloc.argtypes = [ctypes.c_void_p, ctypes.c_size_t]
    lib.arena_free.restype = ctypes.c_int
    lib.arena_free.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    for fn in ("arena_allocated", "arena_peak", "arena_capacity",
               "arena_largest_free"):
        getattr(lib, fn).restype = ctypes.c_size_t
        getattr(lib, fn).argtypes = [ctypes.c_void_p]
    lib.arena_num_live.restype = ctypes.c_int
    lib.arena_num_live.argtypes = [ctypes.c_void_p]
    return lib


def _get_lib() -> Optional[ctypes.CDLL]:
    global _LIB
    with _LIB_LOCK:
        if _LIB is None:
            _LIB = _build_lib() or False
    return _LIB or None


def native_available() -> bool:
    """Is the arena the compiled ``native/arena.cpp`` (built on first
    use) or the Python shim (no ``g++`` on this machine)?"""
    return _get_lib() is not None


class ArenaAllocation:
    """One allocation; exposes a zero-copy numpy view."""

    def __init__(self, arena: "HostArena", ptr: int, size: int):
        self._arena = arena
        self._ptr = ptr
        self.size = size
        self._closed = False

    def as_numpy(self, dtype=np.uint8, shape=None) -> np.ndarray:
        assert not self._closed
        n = self.size // np.dtype(dtype).itemsize
        buf = (ctypes.c_char * self.size).from_address(self._ptr)
        arr = np.frombuffer(buf, dtype=dtype, count=n)
        return arr.reshape(shape) if shape is not None else arr

    def close(self) -> None:
        if not self._closed:
            self._arena._free(self._ptr)
            self._closed = True

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.close()


class HostArena:
    """First-fit host arena; alloc failure returns None (spill + retry)."""

    def __init__(self, capacity: int, alignment: int = 64):
        self.capacity = capacity
        self._lib = _get_lib()
        if self._lib is not None:
            self._handle = self._lib.arena_create(capacity, alignment)
            if not self._handle:
                raise MemoryError(f"cannot reserve {capacity} byte arena")
            self.native = True
        else:  # pure-python fallback: plain malloc per allocation
            self._handle = None
            self._fallback = {}
            self._fallback_bytes = 0
            self._peak = 0
            self.native = False
        self._lock = threading.Lock()

    def alloc(self, size: int) -> Optional[ArenaAllocation]:
        if self.native:
            ptr = self._lib.arena_alloc(self._handle, size)
            if not ptr:
                return None
            return ArenaAllocation(self, ptr, size)
        with self._lock:
            if self._fallback_bytes + size > self.capacity:
                return None
            buf = ctypes.create_string_buffer(size)
            ptr = ctypes.addressof(buf)
            self._fallback[ptr] = buf
            self._fallback_bytes += size
            self._peak = max(self._peak, self._fallback_bytes)
        return ArenaAllocation(self, ptr, size)

    def _free(self, ptr: int) -> None:
        if self.native:
            self._lib.arena_free(self._handle, ptr)
        else:
            with self._lock:
                buf = self._fallback.pop(ptr, None)
                if buf is not None:
                    self._fallback_bytes -= len(buf)

    @property
    def allocated(self) -> int:
        if self.native:
            return self._lib.arena_allocated(self._handle)
        return self._fallback_bytes

    @property
    def peak(self) -> int:
        if self.native:
            return self._lib.arena_peak(self._handle)
        return self._peak

    @property
    def largest_free(self) -> int:
        if self.native:
            return self._lib.arena_largest_free(self._handle)
        return self.capacity - self._fallback_bytes

    @property
    def num_live(self) -> int:
        if self.native:
            return self._lib.arena_num_live(self._handle)
        return len(self._fallback)

    def close(self) -> None:
        if self.native and self._handle:
            self._lib.arena_destroy(self._handle)
            self._handle = None
