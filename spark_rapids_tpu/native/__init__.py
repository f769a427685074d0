"""ctypes bindings for the native host runtime (native/sparktpu_runtime.cpp)
— the engine's replacement for the reference's cuDF-Java/JNI host surface
(SURVEY.md section 2.12). Built on demand with g++ (no pybind11 in this
image) from the committed source, ON the machine that runs it: the
output is keyed by the source's hash and the host's CPU, so a library
built with `-march=native` elsewhere and carried along in the
git-ignored build directory is never loaded. Everything degrades to
pure-Python fallbacks when the toolchain is unavailable so the engine
never hard-depends on the native path; `runtime_in_use()` says which
one a process got.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
import tempfile
import threading
from typing import List, Optional, Sequence, Tuple

import numpy as np

_lib = None
_lib_lock = threading.Lock()
_build_failed = False

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_SRC = os.path.join(_REPO_ROOT, "native", "sparktpu_runtime.cpp")
_OUT_DIR = os.path.join(_REPO_ROOT, "native", "build")

u8p = ctypes.POINTER(ctypes.c_uint8)
i32p = ctypes.POINTER(ctypes.c_int32)
i64p = ctypes.POINTER(ctypes.c_int64)
u64p = ctypes.POINTER(ctypes.c_uint64)


def compile_runtime(src: str, out_so: str, timeout: int = 120,
                    native_arch: bool = True) -> Optional[str]:
    """THE compile command for the native runtime — shared by the
    import-time builder, setup.py, and tools/package_dist so flags
    cannot drift. Returns the .so path or None (toolchain missing /
    compile failure); never raises."""
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC"]
    if native_arch:
        cmd.append("-march=native")
    cmd += [src, "-o", out_so]
    try:
        subprocess.run(cmd, check=True, capture_output=True,
                       timeout=timeout)
        return out_so
    except (subprocess.SubprocessError, OSError):
        if native_arch:
            # retry without -march=native (portability)
            return compile_runtime(src, out_so, timeout,
                                   native_arch=False)
        return None


def _build_key() -> str:
    """What a `-march=native` build of the committed source depends on:
    the source bytes and this host's CPU (model + feature flags)."""
    h = hashlib.sha256()
    with open(_SRC, "rb") as f:
        h.update(f.read())
    h.update(platform.machine().encode())
    try:
        seen = set()
        with open("/proc/cpuinfo") as f:
            for line in f:
                name = line.split(":", 1)[0].strip()
                if name in ("model name", "flags", "Features") \
                        and name not in seen:
                    seen.add(name)
                    h.update(line.encode())
    except OSError:
        h.update(platform.processor().encode())
    return h.hexdigest()[:16]


def _build() -> Optional[str]:
    # prebuilt library shipped inside the wheel (setup.py build_py,
    # portable flags)
    packaged = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "libsparktpu.so")
    if os.path.exists(packaged):
        return packaged
    try:
        so_dir = os.path.join(_OUT_DIR, _build_key())
        so = os.path.join(so_dir, "libsparktpu.so")
        if os.path.exists(so):
            return so
        os.makedirs(so_dir, exist_ok=True)
        # several processes (test workers) may build at once: each
        # compiles to its own file and renames it into place
        fd, tmp = tempfile.mkstemp(dir=so_dir, suffix=".so.tmp")
        os.close(fd)
    except OSError:
        return None  # no source, or no place to build
    try:
        if compile_runtime(_SRC, tmp) is None:
            return None
        os.replace(tmp, so)
        return so
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def _declare(lib):
    lib.stpu_packed_size.restype = ctypes.c_int64
    lib.stpu_packed_size.argtypes = [i64p, ctypes.c_int32]
    lib.stpu_pack.restype = ctypes.c_int64
    lib.stpu_pack.argtypes = [ctypes.POINTER(u8p), i64p, ctypes.c_int32,
                              u8p]
    lib.stpu_unpack_count.restype = ctypes.c_int32
    lib.stpu_unpack_count.argtypes = [u8p]
    lib.stpu_unpack_offsets.restype = ctypes.c_int64
    lib.stpu_unpack_offsets.argtypes = [u8p, i64p, i64p]
    for name, vp in (("int", i32p), ("long", i64p),
                     ("float", ctypes.POINTER(ctypes.c_float)),
                     ("double", ctypes.POINTER(ctypes.c_double))):
        fn = getattr(lib, f"stpu_murmur3_{name}")
        fn.restype = None
        fn.argtypes = [vp, u8p, ctypes.c_int64, i32p]
    lib.stpu_murmur3_bytes.restype = None
    lib.stpu_murmur3_bytes.argtypes = [u8p, i32p, ctypes.c_int64, u8p,
                                       ctypes.c_int64, i32p]
    for name, vp in (("int", i32p), ("long", i64p),
                     ("float", ctypes.POINTER(ctypes.c_float)),
                     ("double", ctypes.POINTER(ctypes.c_double))):
        fn = getattr(lib, f"stpu_xxhash64_{name}")
        fn.restype = None
        fn.argtypes = [vp, u8p, ctypes.c_int64, u64p]
    lib.stpu_xxhash64_bytes.restype = None
    lib.stpu_xxhash64_bytes.argtypes = [u8p, i32p, ctypes.c_int64, u8p,
                                        ctypes.c_int64, u64p]
    lib.stpu_columns_to_rows.restype = None
    lib.stpu_columns_to_rows.argtypes = [
        ctypes.c_int32, ctypes.POINTER(u8p), i32p, ctypes.POINTER(u8p),
        ctypes.c_int64, u8p, ctypes.c_int64]
    lib.stpu_rows_to_columns.restype = None
    lib.stpu_rows_to_columns.argtypes = [
        ctypes.c_int32, ctypes.POINTER(u8p), i32p, ctypes.POINTER(u8p),
        ctypes.c_int64, u8p, ctypes.c_int64]
    lib.stpu_row_stride.restype = ctypes.c_int64
    lib.stpu_row_stride.argtypes = [ctypes.c_int32, i32p]
    lib.stpu_pool_create.restype = ctypes.c_void_p
    lib.stpu_pool_create.argtypes = [ctypes.c_int64]
    lib.stpu_pool_destroy.restype = None
    lib.stpu_pool_destroy.argtypes = [ctypes.c_void_p]
    lib.stpu_pool_alloc.restype = ctypes.c_void_p
    lib.stpu_pool_alloc.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.stpu_pool_free.restype = None
    lib.stpu_pool_free.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    for f in ("in_use", "peak", "alloc_count"):
        fn = getattr(lib, f"stpu_pool_{f}")
        fn.restype = ctypes.c_int64
        fn.argtypes = [ctypes.c_void_p]


def get_lib():
    """The loaded native library, building it on first use; None if the
    toolchain is unavailable."""
    global _lib, _build_failed
    if _lib is not None or _build_failed:
        return _lib
    with _lib_lock:
        if _lib is not None or _build_failed:
            return _lib
        so = _build()
        if so is None:
            _build_failed = True
            return None
        try:
            lib = ctypes.CDLL(so)
            _declare(lib)
            _lib = lib
        except OSError:
            _build_failed = True
    return _lib


def available() -> bool:
    return get_lib() is not None


def runtime_in_use() -> str:
    """"native" when the C++ runtime loaded (building it if needed),
    "python" when this process runs the pure-Python fallbacks."""
    return "native" if available() else "python"


# ----------------------------------------------------------- wire format

def pack_buffers(bufs: Sequence[np.ndarray]) -> np.ndarray:
    """Pack raw numpy buffers into one contiguous framed uint8 buffer
    (JCudfSerialization analog). Falls back to a Python implementation."""
    lib = get_lib()
    flat = [np.ascontiguousarray(b).view(np.uint8).reshape(-1)
            for b in bufs]
    sizes = np.array([b.nbytes for b in flat], dtype=np.int64)
    n = len(flat)
    if lib is None:
        return _py_pack(flat, sizes)
    total = lib.stpu_packed_size(sizes.ctypes.data_as(i64p), n)
    out = np.zeros(total, dtype=np.uint8)  # deterministic padding bytes
    ptrs = (u8p * n)(*[b.ctypes.data_as(u8p) for b in flat])
    lib.stpu_pack(ptrs, sizes.ctypes.data_as(i64p), n,
                  out.ctypes.data_as(u8p))
    return out


def unpack_buffers(data: np.ndarray) -> List[np.ndarray]:
    """Inverse of pack_buffers: zero-copy uint8 views into `data`."""
    lib = get_lib()
    data = np.ascontiguousarray(data).view(np.uint8).reshape(-1)
    if lib is None:
        return _py_unpack(data)
    n = lib.stpu_unpack_count(data.ctypes.data_as(u8p))
    if n < 0:
        raise ValueError("bad magic in packed buffer")
    offs = np.empty(n, dtype=np.int64)
    sizes = np.empty(n, dtype=np.int64)
    total = lib.stpu_unpack_offsets(data.ctypes.data_as(u8p),
                                    offs.ctypes.data_as(i64p),
                                    sizes.ctypes.data_as(i64p))
    if total < 0 or total > data.nbytes:
        raise ValueError("truncated packed buffer")
    return [data[offs[i]:offs[i] + sizes[i]] for i in range(n)]


_MAGIC = (0x53545055434F4C31).to_bytes(8, "little")
_ALIGN = 64


def _py_pack(flat, sizes) -> np.ndarray:
    import struct

    n = len(flat)
    header = _MAGIC + struct.pack("<ii", 1, n) + sizes.tobytes()
    hsize = (len(header) + _ALIGN - 1) // _ALIGN * _ALIGN
    total = hsize + int(sum((int(s) + _ALIGN - 1) // _ALIGN * _ALIGN
                            for s in sizes))
    out = np.zeros(total, dtype=np.uint8)
    out[:len(header)] = np.frombuffer(header, dtype=np.uint8)
    off = hsize
    for b, s in zip(flat, sizes):
        out[off:off + int(s)] = b
        off += (int(s) + _ALIGN - 1) // _ALIGN * _ALIGN
    return out


def _py_unpack(data: np.ndarray) -> List[np.ndarray]:
    import struct

    if bytes(data[:8]) != _MAGIC:
        raise ValueError("bad magic in packed buffer")
    _, n = struct.unpack("<ii", bytes(data[8:16]))
    sizes = np.frombuffer(bytes(data[16:16 + 8 * n]), dtype=np.int64)
    hsize = (16 + 8 * n + _ALIGN - 1) // _ALIGN * _ALIGN
    out = []
    off = hsize
    for s in sizes:
        out.append(data[off:off + int(s)])
        off += (int(s) + _ALIGN - 1) // _ALIGN * _ALIGN
    return out


# --------------------------------------------------------------- hashing

def _valid_ptr(valid: Optional[np.ndarray]):
    if valid is None:
        return ctypes.cast(None, u8p)
    return np.ascontiguousarray(valid, dtype=np.uint8).ctypes.data_as(u8p)


def murmur3_host(columns, seed: int = 42) -> np.ndarray:
    """Spark-exact murmur3 over host numpy columns. Each column is either
    (values, validity) with a numeric np array, or
    (byte_matrix, lengths, validity) for strings/binary."""
    lib = get_lib()
    if lib is None:
        raise RuntimeError("native library unavailable")
    n = len(columns[0][0])
    h = np.full(n, np.int32(seed), dtype=np.int32)
    hp = h.ctypes.data_as(i32p)
    for col in columns:
        if len(col) == 3:
            data, lens, valid = col
            data = np.ascontiguousarray(data, dtype=np.uint8)
            lens = np.ascontiguousarray(lens, dtype=np.int32)
            lib.stpu_murmur3_bytes(
                data.ctypes.data_as(u8p), lens.ctypes.data_as(i32p),
                data.shape[1] if data.ndim == 2 else 0,
                _valid_ptr(valid), n, hp)
            continue
        vals, valid = col
        vals = np.ascontiguousarray(vals)
        vp = _valid_ptr(valid)
        if vals.dtype == np.float64:
            lib.stpu_murmur3_double(vals.ctypes.data_as(
                ctypes.POINTER(ctypes.c_double)), vp, n, hp)
        elif vals.dtype == np.float32:
            lib.stpu_murmur3_float(vals.ctypes.data_as(
                ctypes.POINTER(ctypes.c_float)), vp, n, hp)
        elif vals.dtype.itemsize <= 4:
            v32 = vals.astype(np.int32, copy=False)
            v32 = np.ascontiguousarray(v32)
            lib.stpu_murmur3_int(v32.ctypes.data_as(i32p), vp, n, hp)
        else:
            v64 = np.ascontiguousarray(vals.astype(np.int64, copy=False))
            lib.stpu_murmur3_long(v64.ctypes.data_as(i64p), vp, n, hp)
    return h


def xxhash64_host(columns, seed: int = 42) -> np.ndarray:
    """Spark-exact xxhash64 over host numpy columns (same column spec as
    murmur3_host)."""
    lib = get_lib()
    if lib is None:
        raise RuntimeError("native library unavailable")
    n = len(columns[0][0])
    h = np.full(n, np.uint64(seed), dtype=np.uint64)
    hp = h.ctypes.data_as(u64p)
    for col in columns:
        if len(col) == 3:
            data, lens, valid = col
            data = np.ascontiguousarray(data, dtype=np.uint8)
            lens = np.ascontiguousarray(lens, dtype=np.int32)
            lib.stpu_xxhash64_bytes(
                data.ctypes.data_as(u8p), lens.ctypes.data_as(i32p),
                data.shape[1] if data.ndim == 2 else 0,
                _valid_ptr(valid), n, hp)
            continue
        vals, valid = col
        vals = np.ascontiguousarray(vals)
        vp = _valid_ptr(valid)
        if vals.dtype == np.float64:
            lib.stpu_xxhash64_double(vals.ctypes.data_as(
                ctypes.POINTER(ctypes.c_double)), vp, n, hp)
        elif vals.dtype == np.float32:
            lib.stpu_xxhash64_float(vals.ctypes.data_as(
                ctypes.POINTER(ctypes.c_float)), vp, n, hp)
        elif vals.dtype.itemsize <= 4:
            v32 = np.ascontiguousarray(vals.astype(np.int32, copy=False))
            lib.stpu_xxhash64_int(v32.ctypes.data_as(i32p), vp, n, hp)
        else:
            v64 = np.ascontiguousarray(vals.astype(np.int64, copy=False))
            lib.stpu_xxhash64_long(v64.ctypes.data_as(i64p), vp, n, hp)
    return h.view(np.int64)


# --------------------------------------------------- row <-> column bridge

def columns_to_rows(cols: List[Tuple[np.ndarray, Optional[np.ndarray]]]
                    ) -> Tuple[np.ndarray, int]:
    """Fixed-width columns -> packed row-major bytes (RowConversion
    analog). Returns (rows[n, stride] uint8, stride)."""
    lib = get_lib()
    if lib is None:
        raise RuntimeError("native library unavailable")
    ncols = len(cols)
    n = len(cols[0][0])
    datas = [np.ascontiguousarray(c[0]) for c in cols]
    widths = np.array([d.dtype.itemsize for d in datas], dtype=np.int32)
    valids = [None if c[1] is None else
              np.ascontiguousarray(c[1], dtype=np.uint8) for c in cols]
    stride = lib.stpu_row_stride(ncols, widths.ctypes.data_as(i32p))
    rows = np.zeros((n, stride), dtype=np.uint8)
    dptrs = (u8p * ncols)(*[d.view(np.uint8).reshape(-1)
                            .ctypes.data_as(u8p) for d in datas])
    vptrs = (u8p * ncols)(*[
        ctypes.cast(None, u8p) if v is None else v.ctypes.data_as(u8p)
        for v in valids])
    lib.stpu_columns_to_rows(ncols, dptrs,
                             widths.ctypes.data_as(i32p), vptrs, n,
                             rows.ctypes.data_as(u8p), stride)
    return rows, int(stride)


def rows_to_columns(rows: np.ndarray, dtypes: List[np.dtype]
                    ) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Packed rows -> (values, validity) columns."""
    lib = get_lib()
    if lib is None:
        raise RuntimeError("native library unavailable")
    n, stride = rows.shape
    ncols = len(dtypes)
    datas = [np.zeros(n, dtype=dt) for dt in dtypes]
    valids = [np.zeros(n, dtype=np.uint8) for _ in dtypes]
    widths = np.array([np.dtype(dt).itemsize for dt in dtypes],
                      dtype=np.int32)
    rows = np.ascontiguousarray(rows, dtype=np.uint8)
    dptrs = (u8p * ncols)(*[d.view(np.uint8).reshape(-1)
                            .ctypes.data_as(u8p) for d in datas])
    vptrs = (u8p * ncols)(*[v.ctypes.data_as(u8p) for v in valids])
    lib.stpu_rows_to_columns(ncols, dptrs,
                             widths.ctypes.data_as(i32p), vptrs, n,
                             rows.ctypes.data_as(u8p), stride)
    return [(d, v.astype(bool)) for d, v in zip(datas, valids)]


# ----------------------------------------------------------- host pool

class HostBufferPool:
    """Bounded native host pool with freelist reuse (HostAlloc analog,
    reference HostAlloc.scala). Python holds numpy views over pool
    blocks; `alloc` returns None when the budget is exhausted (callers
    spill and retry)."""

    def __init__(self, capacity: int):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._pool = lib.stpu_pool_create(capacity)
        self._live = {}

    def alloc(self, nbytes: int) -> Optional[np.ndarray]:
        p = self._lib.stpu_pool_alloc(self._pool, nbytes)
        if not p:
            return None
        buf = np.ctypeslib.as_array(
            ctypes.cast(p, u8p), shape=(nbytes,))
        self._live[buf.ctypes.data] = p
        return buf

    def free(self, buf: np.ndarray):
        p = self._live.pop(buf.ctypes.data, None)
        if p:
            self._lib.stpu_pool_free(self._pool, p)

    @property
    def in_use(self) -> int:
        return self._lib.stpu_pool_in_use(self._pool)

    @property
    def peak(self) -> int:
        return self._lib.stpu_pool_peak(self._pool)

    @property
    def alloc_count(self) -> int:
        return self._lib.stpu_pool_alloc_count(self._pool)

    def close(self):
        if self._pool:
            self._lib.stpu_pool_destroy(self._pool)
            self._pool = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
