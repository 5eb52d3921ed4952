"""Native runtime bindings (C++ via ctypes).

Builds ``librecordio.so`` from runtime/recordio.cpp on first use (g++ -O3
-fopenmp; no pybind11 in this image) and exposes:

* ``RecordFile`` — mmap'd RecordIO random access (replaces dmlc RecordIO
  reader + the .idx sidecar for reading)
* ``assemble_batch`` — parallel uint8 HWC → float32 NCHW batch assembly
  with mean/std/mirror/crop (the hot inner loop of the reference's
  iter_normalize.h + iter_batchloader.h)

The paths that ask for the native lib need it: a build that fails raises
``NativeBuildError`` (with the compiler's output) instead of falling back
to a slower implementation without a word.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as onp

from ._native_build import load_native

_LIB = None
_LOCK = threading.Lock()


def get_lib():
    """Load (building if needed) the native lib; raises
    ``NativeBuildError`` when it cannot be built on this host."""
    global _LIB
    with _LOCK:
        if _LIB is not None:
            return _LIB
        lib = load_native("recordio.cpp", "librecordio.so",
                          extra_flags=("-march=native", "-fopenmp"))
        lib.ri_open.restype = ctypes.c_void_p
        lib.ri_open.argtypes = [ctypes.c_char_p]
        lib.ri_count.restype = ctypes.c_int64
        lib.ri_count.argtypes = [ctypes.c_void_p]
        lib.ri_get.restype = ctypes.POINTER(ctypes.c_uint8)
        lib.ri_get.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                               ctypes.POINTER(ctypes.c_int64)]
        lib.ri_close.argtypes = [ctypes.c_void_p]
        lib.assemble_batch.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int64, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_float)]
        _LIB = lib
        return lib


class RecordFile(object):
    """mmap'd random-access RecordIO reader (native; a file the native
    reader cannot map is scanned in python)."""

    def __init__(self, path):
        self.path = path
        self._lib = get_lib()
        self._py_offsets = None
        self._handle = self._lib.ri_open(path.encode())
        if not self._handle:
            self._scan_python()

    def _scan_python(self):
        import struct
        self._py_data = open(self.path, "rb").read()
        self._py_offsets = []
        pos = 0
        data = self._py_data
        while pos + 8 <= len(data):
            magic, lrec = struct.unpack_from("<II", data, pos)
            if magic != 0xced7230a:
                break
            length = lrec & 0x1fffffff
            self._py_offsets.append((pos + 8, length))
            pos += 8 + ((length + 3) & ~3)

    def __len__(self):
        if self._handle:
            return int(self._lib.ri_count(self._handle))
        return len(self._py_offsets)

    def read(self, i):
        """Record payload bytes at index i."""
        if self._handle:
            ln = ctypes.c_int64()
            ptr = self._lib.ri_get(self._handle, i, ctypes.byref(ln))
            if not ptr:
                raise IndexError(i)
            return ctypes.string_at(ptr, ln.value)
        off, length = self._py_offsets[i]
        return self._py_data[off:off + length]

    def close(self):
        if self._handle:
            self._lib.ri_close(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def aligned_empty(shape, dtype=onp.float32):
    """An uninitialised array whose data starts on a 64-byte boundary:
    what XLA's CPU client asks of host memory before ``jax.device_put``
    aliases it. numpy's own large blocks start 16 bytes past a page,
    and the client then copies them, later and on one thread — 154 MB
    in 220 ms on the v5e's host, which the batch's staging waited out
    (PERF.md, PR 26)."""
    dtype = onp.dtype(dtype)
    nbytes = int(onp.prod(shape)) * dtype.itemsize
    raw = onp.empty(nbytes + 64, onp.uint8)
    start = -raw.ctypes.data % 64
    return raw[start:start + nbytes].view(dtype).reshape(shape)


def assemble_batch(images, mean=None, std=None, mirror=None, crop_yx=None,
                   out_hw=None, out=None):
    """uint8 (n,h,w,c) HWC images -> float32 (n,c,oh,ow) NCHW batch.

    Runs the native OpenMP loop. ``out`` lets the caller supply a
    staging buffer (e.g. a pooled HostPool array, the iter_prefetcher.h
    double-buffer pattern) instead of allocating; the batch allocated
    here is 64-byte aligned (:func:`aligned_empty`), so ``nd.array`` of
    it on the CPU backend is the same memory, not a copy.
    """
    images = onp.ascontiguousarray(images, dtype=onp.uint8)
    n, h, w, c = images.shape
    oh, ow = out_hw if out_hw is not None else (h, w)
    if out is not None:
        assert out.shape == (n, c, oh, ow) and out.dtype == onp.float32 \
            and out.flags.c_contiguous, "bad staging buffer"
    lib = get_lib()
    if out is None:
        out = aligned_empty((n, c, oh, ow))
    meanp = stdp = None
    if mean is not None:
        mean = onp.ascontiguousarray(mean, dtype=onp.float32)
        meanp = mean.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
    if std is not None:
        std_inv = onp.ascontiguousarray(1.0 / onp.asarray(std),
                                        dtype=onp.float32)
        stdp = std_inv.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
    mirp = cyp = cxp = None
    if mirror is not None:
        mirror = onp.ascontiguousarray(mirror, dtype=onp.uint8)
        mirp = mirror.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
    if crop_yx is not None:
        cy = onp.ascontiguousarray(crop_yx[0], dtype=onp.int32)
        cx = onp.ascontiguousarray(crop_yx[1], dtype=onp.int32)
        cyp = cy.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
        cxp = cx.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
    lib.assemble_batch(
        images.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        n, h, w, c, meanp, stdp, mirp, cyp, cxp, oh, ow,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
    return out
