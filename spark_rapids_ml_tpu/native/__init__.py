"""ctypes bindings to the native host runtime (libtpuml_host.so).

Loader parity with the reference's ``JniRAPIDSML`` (JniRAPIDSML.java:26-58):
a lazily-initialized per-process singleton that locates the shared library
shipped inside the package directory and binds its C ABI. If the library is
absent, it is built on the fly with the in-tree Makefile when a toolchain is
available; otherwise ``available()`` returns False and callers fall back to
the pure-JAX/numpy paths — the native layer accelerates, never gates.

Surface (native/src/tpuml_host.cpp):
  - SprAccumulator  — fp64 Kahan-compensated streaming covariance
    (packed-upper cublasDspr layout; the reference's spr/treeAggregate path)
  - csr_to_dense    — sparse batch assembly ("concat before cov" hot loop)
  - center_scale    — fused fp64 center + fp32 narrow
  - trace push/pop  — NVTX-parity host ranges
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Optional

import numpy as np

from spark_rapids_ml_tpu.utils.lockcheck import make_lock

_LIB_NAME = "libtpuml_host.so"
_lock = make_lock("native.loader")
_lib: Optional[ctypes.CDLL] = None
_load_attempted = False


def _package_lib_path() -> str:
    return os.path.join(os.path.dirname(__file__), _LIB_NAME)


def _try_build() -> bool:
    """Build the library from native/ if a toolchain is present."""
    native_dir = os.path.join(os.path.dirname(__file__), "..", "..", "native")
    native_dir = os.path.abspath(native_dir)
    src = os.path.join(native_dir, "src", "tpuml_host.cpp")
    if not os.path.exists(src):
        return False
    try:
        # Direct g++ invocation: faster and fewer moving parts than the CMake
        # path (which remains the documented/official build).
        out = _package_lib_path()
        subprocess.run(
            ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-o", out, src],
            check=True,
            capture_output=True,
            timeout=120,
        )
        return os.path.exists(out)
    except (OSError, subprocess.SubprocessError):
        return False


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    i8, i32, i64 = ctypes.c_int8, ctypes.c_int32, ctypes.c_int64
    p = ctypes.POINTER
    lib.tpuml_abi_version.restype = i32
    lib.tpuml_spr_create.restype = ctypes.c_void_p
    lib.tpuml_spr_create.argtypes = [i64]
    lib.tpuml_spr_destroy.argtypes = [ctypes.c_void_p]
    lib.tpuml_spr_add_block.restype = i32
    lib.tpuml_spr_add_block.argtypes = [ctypes.c_void_p, p(ctypes.c_double), i64]
    lib.tpuml_spr_merge.restype = i32
    lib.tpuml_spr_merge.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    lib.tpuml_spr_rows.restype = i64
    lib.tpuml_spr_rows.argtypes = [ctypes.c_void_p]
    lib.tpuml_spr_finalize.restype = i32
    lib.tpuml_spr_finalize.argtypes = [
        ctypes.c_void_p,
        p(ctypes.c_double),
        p(ctypes.c_double),
        i32,
    ]
    lib.tpuml_csr_to_dense_f64.restype = i32
    lib.tpuml_csr_to_dense_f64.argtypes = [
        p(i64), p(i32), p(ctypes.c_double), i64, i64, p(ctypes.c_double)
    ]
    lib.tpuml_csr_to_dense_f32.restype = i32
    lib.tpuml_csr_to_dense_f32.argtypes = [
        p(i64), p(i32), p(ctypes.c_double), i64, i64, p(ctypes.c_float)
    ]
    lib.tpuml_center_scale_f32.restype = i32
    lib.tpuml_center_scale_f32.argtypes = [
        p(ctypes.c_double), p(ctypes.c_double), ctypes.c_double, i64, i64,
        p(ctypes.c_float),
    ]
    lib.tpuml_trace_push.argtypes = [ctypes.c_char_p]
    lib.tpuml_trace_pop.argtypes = []
    try:
        _bind_npy(lib)
        lib._tpuml_has_npy = True
    except AttributeError:  # stale library predating the npy loader
        lib._tpuml_has_npy = False
    return lib


def get_lib() -> Optional[ctypes.CDLL]:
    """Lazily load (building if needed) the native library; None if unavailable."""
    global _lib, _load_attempted
    with _lock:
        if _lib is not None or _load_attempted:
            return _lib
        _load_attempted = True
        path = _package_lib_path()
        if not os.path.exists(path) and not _try_build():
            return None
        try:
            lib = ctypes.CDLL(path)
            if lib.tpuml_abi_version() != 1:
                return None
            _lib = _bind(lib)
        except OSError:
            return None
        return _lib


def available() -> bool:
    return get_lib() is not None


def loaded() -> bool:
    """Whether this process has the library loaded — a pure query: unlike
    :func:`available` it never loads, and so never builds, anything."""
    return _lib is not None


def _as_c(arr: np.ndarray, ctype):
    return arr.ctypes.data_as(ctypes.POINTER(ctype))


class SprAccumulator:
    """fp64 streaming covariance accumulator (native; Kahan-compensated).

    The host-side equivalent of the reference's spr/treeAggregate covariance
    (RapidsRowMatrix.scala:202-251) with true fp64 — the numerics oracle for
    the TPU fp32 paths, and the CPU fallback when no accelerator is present.
    """

    def __init__(self, n_cols: int):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._handle = lib.tpuml_spr_create(n_cols)
        if not self._handle:
            raise ValueError(f"invalid n_cols {n_cols} (must be 1..65535)")
        self.n_cols = n_cols

    def add_block(self, block: np.ndarray) -> "SprAccumulator":
        block = np.ascontiguousarray(block, dtype=np.float64)
        if block.ndim != 2 or block.shape[1] != self.n_cols:
            raise ValueError(f"block must be (rows, {self.n_cols})")
        rc = self._lib.tpuml_spr_add_block(
            self._handle, _as_c(block, ctypes.c_double), block.shape[0]
        )
        if rc != 0:
            raise RuntimeError(f"spr_add_block failed: {rc}")
        return self

    def merge(self, other: "SprAccumulator") -> "SprAccumulator":
        rc = self._lib.tpuml_spr_merge(self._handle, other._handle)
        if rc != 0:
            raise RuntimeError(f"spr_merge failed: {rc}")
        return self

    @property
    def n_rows(self) -> int:
        return int(self._lib.tpuml_spr_rows(self._handle))

    def finalize(self, center: bool = True):
        """Return (covariance (n, n), column means (n,))."""
        n = self.n_cols
        cov = np.empty((n, n), dtype=np.float64)
        mean = np.empty(n, dtype=np.float64)
        rc = self._lib.tpuml_spr_finalize(
            self._handle,
            _as_c(cov, ctypes.c_double),
            _as_c(mean, ctypes.c_double),
            1 if center else 0,
        )
        if rc == -2:
            raise ValueError(f"need at least 2 rows, got {self.n_rows}")
        if rc != 0:
            raise RuntimeError(f"spr_finalize failed: {rc}")
        return cov, mean

    def __del__(self):
        handle = getattr(self, "_handle", None)
        if handle:
            self._lib.tpuml_spr_destroy(handle)
            self._handle = None


def csr_to_dense(indptr, indices, values, n_cols: int, dtype=np.float64) -> np.ndarray:
    """Native CSR -> dense row block ("concat before cov" assembly)."""
    lib = get_lib()
    indptr = np.ascontiguousarray(indptr, dtype=np.int64)
    indices = np.ascontiguousarray(indices, dtype=np.int32)
    values = np.ascontiguousarray(values, dtype=np.float64)
    n_rows = indptr.shape[0] - 1
    if lib is None:
        out = np.zeros((n_rows, n_cols), dtype=dtype)
        for r in range(n_rows):
            sl = slice(indptr[r], indptr[r + 1])
            out[r, indices[sl]] = values[sl]
        return out
    if dtype == np.float32:
        out32 = np.empty((n_rows, n_cols), dtype=np.float32)
        rc = lib.tpuml_csr_to_dense_f32(
            _as_c(indptr, ctypes.c_int64), _as_c(indices, ctypes.c_int32),
            _as_c(values, ctypes.c_double), n_rows, n_cols,
            _as_c(out32, ctypes.c_float),
        )
        if rc != 0:
            raise ValueError(f"csr_to_dense failed: {rc} (bad column index?)")
        return out32
    out = np.empty((n_rows, n_cols), dtype=np.float64)
    rc = lib.tpuml_csr_to_dense_f64(
        _as_c(indptr, ctypes.c_int64), _as_c(indices, ctypes.c_int32),
        _as_c(values, ctypes.c_double), n_rows, n_cols,
        _as_c(out, ctypes.c_double),
    )
    if rc != 0:
        raise ValueError(f"csr_to_dense failed: {rc} (bad column index?)")
    return out


def center_scale_f32(x: np.ndarray, mean: np.ndarray, scale: float) -> np.ndarray:
    """Fused (x - mean) * scale with fp64 math, fp32 output."""
    lib = get_lib()
    x = np.ascontiguousarray(x, dtype=np.float64)
    mean = np.ascontiguousarray(mean, dtype=np.float64)
    if lib is None:
        return ((x - mean) * scale).astype(np.float32)
    out = np.empty(x.shape, dtype=np.float32)
    rc = lib.tpuml_center_scale_f32(
        _as_c(x, ctypes.c_double), _as_c(mean, ctypes.c_double),
        float(scale), x.shape[0], x.shape[1], _as_c(out, ctypes.c_float),
    )
    if rc != 0:
        raise RuntimeError(f"center_scale failed: {rc}")
    return out


def trace_push(name: str) -> None:
    lib = get_lib()
    if lib is not None:
        lib.tpuml_trace_push(name.encode())


def trace_pop() -> None:
    lib = get_lib()
    if lib is not None:
        lib.tpuml_trace_pop()


class NpyBlockReader:
    """Streaming block reader over a ``.npy`` file — the native data loader.

    The mmap + madvise readahead lives in C++ (``tpuml_npy_*``): the OS page
    cache double-buffers, :meth:`iter_blocks` warms the NEXT block while
    yielding the current one, and each read is one memcpy out of the
    mapping. Blocks are plain ``(rows, d)`` ndarrays. Pass the READER (or
    its block generator) straight to an estimator for a constant-memory
    fit — one block resident at a time, never the whole file:

        reader = NpyBlockReader("data.npy", block_rows=1 << 20)
        PCA().setK(8).fit(reader)                       # constant memory
        LinearRegression().fit((reader.iter_blocks(), y))
    """

    def __init__(self, path: str, block_rows: int = 1 << 20):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native library unavailable (no toolchain?)")
        if not getattr(lib, "_tpuml_has_npy", False):
            raise RuntimeError(
                "native library predates the npy loader; rebuild via "
                "`make -C native` (or delete the stale .so)"
            )
        self._lib = lib
        self._handle = lib.tpuml_npy_open(path.encode())
        if not self._handle:
            raise ValueError(
                f"cannot open {path!r}: not a C-order float32/float64 .npy"
            )
        rows = ctypes.c_int64()
        cols = ctypes.c_int64()
        dtype = ctypes.c_int32()
        lib.tpuml_npy_info(
            self._handle, ctypes.byref(rows), ctypes.byref(cols), ctypes.byref(dtype)
        )
        self.shape = (rows.value, cols.value)
        self.dtype = np.float32 if dtype.value == 0 else np.float64
        self.block_rows = int(block_rows)

    def read_block(self, start: int, n_rows: int) -> np.ndarray:
        n_rows = min(n_rows, self.shape[0] - start)
        out = np.empty((n_rows, self.shape[1]), dtype=self.dtype)
        rc = self._lib.tpuml_npy_read_block(
            self._handle, start, n_rows, out.ctypes.data_as(ctypes.c_void_p)
        )
        if rc != 0:
            raise ValueError(f"read_block({start}, {n_rows}) failed: {rc}")
        return out

    def iter_blocks(self):
        n = self.shape[0]
        b = self.block_rows
        release = getattr(self._lib, "_tpuml_has_npy_release", False)
        for start in range(0, n, b):
            if start + b < n:  # warm the next block while this one is used
                self._lib.tpuml_npy_prefetch(self._handle, start + b, b)
            yield self.read_block(start, b)
            if release and self._handle:
                # The block was memcpy'd out; drop its mapped pages so a
                # full-file pass stays resident-bounded by ~one block.
                self._lib.tpuml_npy_release(self._handle, start, b)

    def close(self) -> None:
        if getattr(self, "_handle", None):
            self._lib.tpuml_npy_close(self._handle)
            self._handle = None

    def __enter__(self) -> "NpyBlockReader":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):  # pragma: no cover - GC timing
        try:
            self.close()
        except Exception:
            pass


def _bind_npy(lib: ctypes.CDLL) -> None:
    i32, i64 = ctypes.c_int32, ctypes.c_int64
    lib.tpuml_npy_open.restype = ctypes.c_void_p
    lib.tpuml_npy_open.argtypes = [ctypes.c_char_p]
    lib.tpuml_npy_info.restype = i32
    lib.tpuml_npy_info.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(i64),
        ctypes.POINTER(i64),
        ctypes.POINTER(i32),
    ]
    lib.tpuml_npy_prefetch.restype = i32
    lib.tpuml_npy_prefetch.argtypes = [ctypes.c_void_p, i64, i64]
    lib.tpuml_npy_read_block.restype = i32
    lib.tpuml_npy_read_block.argtypes = [ctypes.c_void_p, i64, i64, ctypes.c_void_p]
    lib.tpuml_npy_close.argtypes = [ctypes.c_void_p]
    try:
        # Added after the first npy ABI shipped; stale builds degrade to
        # streaming without page release rather than losing the loader.
        lib.tpuml_npy_release.restype = i32
        lib.tpuml_npy_release.argtypes = [ctypes.c_void_p, i64, i64]
        lib._tpuml_has_npy_release = True
    except AttributeError:
        lib._tpuml_has_npy_release = False
