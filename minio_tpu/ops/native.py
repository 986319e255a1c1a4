"""ctypes loader for the native host kernels (native/minio_native.cpp).

Builds the shared library on first use if g++ is available (no pip deps);
callers fall back to numpy when the toolchain is missing -- about 10x slower
on host tails and GET verification, so the fallback is logged, exported
(minio_tpu_native_codec_available) and refused by chip_smoke.py. The library
is compiled -march=native and git-ignored: it belongs to the machine that
built it (build() recompiles in place; .chiprunignore keeps a sandbox binary
off the chip's host). The native kernels are bit-exact with the Python ones
-- tests cross-check all three paths (numpy / native / JAX) against the
reference golden vectors.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np
from ..control.sanitizer import san_lock, san_rlock

_NATIVE_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "native")
_LIB_PATH = os.path.abspath(os.path.join(_NATIVE_DIR, "libminio_native.so"))

_lib: ctypes.CDLL | None = None
_lock = san_lock("native._lock")
_tried = False


def build() -> str | None:
    """Compile native/*.cpp for THIS machine's CPU and install the library;
    None on success, else why not (the compiler's own words). A process that
    already loaded the library keeps the mapping it has."""
    kernel = os.path.join(_NATIVE_DIR, "minio_native.cpp")
    if not os.path.isfile(kernel):
        return f"{kernel} missing"  # the RS/HH kernels are mandatory; IO layer is additive
    srcs = [kernel]
    io_src = os.path.join(_NATIVE_DIR, "minio_io.cpp")
    if os.path.isfile(io_src):
        srcs.append(io_src)
    # Build to a per-process temp path and rename: overwriting a .so that a
    # running server has mapped corrupts that process, and a shared temp
    # name would let a concurrent builder scribble into the freshly
    # installed library through its still-open fd.
    tmp = f"{_LIB_PATH}.build.{os.getpid()}"
    try:
        subprocess.run(
            ["g++", "-O3", "-march=native", "-fPIC", "-shared", "-o", tmp, *srcs],
            check=True,
            capture_output=True,
            timeout=120,
        )
        os.replace(tmp, _LIB_PATH)
        return None
    except subprocess.CalledProcessError as e:
        return f"g++ exit {e.returncode}: {e.stderr.decode(errors='replace')[-2000:]}"
    except (subprocess.SubprocessError, FileNotFoundError, OSError) as e:
        return f"{type(e).__name__}: {e}"


def _stale() -> bool:
    """True when the prebuilt .so predates any native source (a stale lib
    would silently serve yesterday's kernels after a source edit)."""
    try:
        lib_m = os.path.getmtime(_LIB_PATH)
    except OSError:
        return True
    for name in ("minio_native.cpp", "minio_io.cpp"):
        p = os.path.join(_NATIVE_DIR, name)
        if os.path.isfile(p) and os.path.getmtime(p) > lib_m:
            return True
    return False


def load() -> ctypes.CDLL | None:
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        why = build() if _stale() else None
        if why is not None and not os.path.isfile(_LIB_PATH):
            return _numpy_serves(why)
        try:
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError as e:
            return _numpy_serves(f"cannot load {_LIB_PATH}: {e}")
        u8p = ctypes.POINTER(ctypes.c_uint8)
        lib.rs_encode.argtypes = [ctypes.c_int, ctypes.c_int, u8p, u8p, u8p, ctypes.c_size_t]
        lib.rs_apply.argtypes = lib.rs_encode.argtypes
        lib.hh256.argtypes = [u8p, u8p, ctypes.c_size_t, u8p]
        lib.hh256_batch.argtypes = [
            u8p, u8p, ctypes.c_size_t, ctypes.c_size_t, ctypes.c_size_t, u8p,
        ]
        lib.hh256_frame.argtypes = lib.hh256_batch.argtypes
        try:
            lib.hh256_verify_frames.argtypes = [
                u8p, u8p, ctypes.c_size_t, ctypes.c_size_t, u8p,
            ]
        except AttributeError:  # stale prebuilt .so without the verifier
            pass
        # Snappy codec (control/compress.py); absent in stale prebuilt libs.
        try:
            lib.sn_max_compressed.argtypes = [ctypes.c_size_t]
            lib.sn_max_compressed.restype = ctypes.c_size_t
            lib.sn_compress.argtypes = [u8p, ctypes.c_size_t, u8p]
            lib.sn_compress.restype = ctypes.c_longlong
            lib.sn_uncompressed_len.argtypes = [u8p, ctypes.c_size_t]
            lib.sn_uncompressed_len.restype = ctypes.c_longlong
            lib.sn_decompress.argtypes = [u8p, ctypes.c_size_t, u8p, ctypes.c_size_t]
            lib.sn_decompress.restype = ctypes.c_longlong
        except AttributeError:
            pass
        # IO layer (native/minio_io.cpp); absent in stale prebuilt libraries.
        try:
            lib.mt_write_file.argtypes = [
                ctypes.c_char_p, u8p, ctypes.c_size_t, ctypes.c_int, ctypes.c_int,
            ]
            lib.mt_write_file.restype = ctypes.c_longlong
            lib.mt_read_file.argtypes = [
                ctypes.c_char_p, u8p, ctypes.c_size_t, ctypes.c_size_t, ctypes.c_int,
            ]
            lib.mt_read_file.restype = ctypes.c_longlong
            lib.mt_odirect_supported.argtypes = [ctypes.c_char_p]
            lib.mt_odirect_supported.restype = ctypes.c_int
        except AttributeError:
            pass
        _lib = lib
        return _lib


def _numpy_serves(why: str) -> None:
    from ..control.logging import GLOBAL_LOGGER

    GLOBAL_LOGGER.error(
        "native host kernels unavailable; numpy serves host-codec blocks and "
        f"GET verification about 10x slower: {why}"
    )
    return None


def available() -> bool:
    return load() is not None


def status() -> tuple[bool, bool]:
    """(probe_attempted, loaded) WITHOUT triggering a load.

    The metrics scrape needs a device-vs-CPU fallback gauge; calling
    available() there could kick off a 120s g++ build inside a scrape.
    """
    return _tried, _lib is not None


def _ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def rs_encode(
    data: np.ndarray, matrix: np.ndarray, out: np.ndarray | None = None
) -> np.ndarray:
    """data [K, S] u8, matrix [M, K] u8 -> parity [M, S] u8.

    `out` (contiguous [M, S] view) writes parity in place -- callers
    assembling a [G, K+M, S] frame buffer skip a copy per block."""
    lib = load()
    assert lib is not None
    k, s = data.shape
    m = matrix.shape[0]
    data = np.ascontiguousarray(data)
    matrix = np.ascontiguousarray(matrix)
    if out is None:
        out = np.empty((m, s), dtype=np.uint8)
    else:
        assert out.shape == (m, s) and out.flags.c_contiguous
    lib.rs_encode(k, m, _ptr(matrix), _ptr(data), _ptr(out), s)
    return out


def rs_apply(data: np.ndarray, matrix: np.ndarray) -> np.ndarray:
    """Arbitrary coefficient application (reconstruct): same shape contract."""
    return rs_encode(data, matrix)


def hh256(data: bytes | np.ndarray, key: bytes) -> bytes:
    lib = load()
    assert lib is not None
    arr = np.frombuffer(data, dtype=np.uint8) if isinstance(data, (bytes, bytearray)) else data
    arr = np.ascontiguousarray(arr)
    keya = np.frombuffer(key, dtype=np.uint8)
    out = np.empty(32, dtype=np.uint8)
    lib.hh256(_ptr(keya), _ptr(arr), arr.size, _ptr(out))
    return out.tobytes()


def hh256_batch(data: np.ndarray, key: bytes) -> np.ndarray:
    """[N, L] u8 -> [N, 32] u8."""
    lib = load()
    assert lib is not None
    data = np.ascontiguousarray(data)
    n, length = data.shape
    keya = np.frombuffer(key, dtype=np.uint8)
    out = np.empty((n, 32), dtype=np.uint8)
    lib.hh256_batch(_ptr(keya), _ptr(data), length, length, n, _ptr(out))
    return out


def hh256_frame(data: np.ndarray, key: bytes) -> bytes:
    """[N, L] u8 chunks -> interleaved H(chunk)||chunk stream bytes."""
    lib = load()
    assert lib is not None
    data = np.ascontiguousarray(data)
    n, length = data.shape
    keya = np.frombuffer(key, dtype=np.uint8)
    out = np.empty(n * (32 + length), dtype=np.uint8)
    lib.hh256_frame(_ptr(keya), _ptr(data), length, length, n, _ptr(out))
    return out.tobytes()


def verify_frames_available() -> bool:
    lib = load()
    return lib is not None and hasattr(lib, "hh256_verify_frames")


def hh256_verify_frames(blob, chunk_len: int, n: int, key: bytes) -> np.ndarray:
    """Verify n uniform H(chunk)||chunk frames inside a raw shard-file image
    without slicing a single chunk in Python: [n] u8 flags (1 = digest ok).

    `blob` is any C-contiguous buffer (bytes / memoryview) whose first
    n*(32+chunk_len) bytes are the frames (the read side of hh256_frame)."""
    lib = load()
    assert lib is not None
    arr = np.frombuffer(blob, dtype=np.uint8, count=n * (32 + chunk_len))
    keya = np.frombuffer(key, dtype=np.uint8)
    ok = np.empty(n, dtype=np.uint8)
    lib.hh256_verify_frames(_ptr(keya), _ptr(arr), chunk_len, n, _ptr(ok))
    return ok


def hh256_frame_rows(stacked: np.ndarray, key: bytes) -> "list[memoryview]":
    """[G, T, S] C-contiguous shard groups -> T per-row frame streams,
    returned as memoryviews (buffer protocol, NOT bytes -- fine for file
    writes and HTTP bodies, not hashable/msgpack-able).

    One strided C call per shard row: the kernel walks row r's chunks at
    stride T*S directly inside the group buffer, so framing a whole encode
    group costs zero numpy row copies (the `ascontiguousarray` per row that
    a [G, S] slice would need)."""
    lib = load()
    assert lib is not None
    assert stacked.flags.c_contiguous and stacked.dtype == np.uint8
    g, t, s = stacked.shape
    keya = np.frombuffer(key, dtype=np.uint8)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    rows: list[memoryview] = []
    for row in range(t):
        out = np.empty(g * (32 + s), dtype=np.uint8)
        base = ctypes.cast(stacked.ctypes.data + row * s, u8p)
        lib.hh256_frame(_ptr(keya), base, t * s, s, g, _ptr(out))
        # memoryview, not tobytes(): the caller appends these to drive files /
        # HTTP bodies, both buffer-protocol consumers -- skipping the copy
        # saves G x S bytes of memcpy per row.
        rows.append(out.data)
    return rows


# -- snappy codec (control/compress.py fast path; S2 role) -------------------


def snappy_available() -> bool:
    lib = load()
    return lib is not None and hasattr(lib, "sn_compress")


def snappy_compress(data: bytes | np.ndarray) -> bytes:
    lib = load()
    assert lib is not None and hasattr(lib, "sn_compress")
    arr = np.frombuffer(data, dtype=np.uint8) if not isinstance(data, np.ndarray) else data
    arr = np.ascontiguousarray(arr)
    out = np.empty(lib.sn_max_compressed(arr.size), dtype=np.uint8)
    n = lib.sn_compress(_ptr(arr) if arr.size else None, arr.size, _ptr(out))
    return out[:n].tobytes()


def snappy_decompress(blob: bytes | np.ndarray) -> bytes:
    """Raises ValueError on corrupt input (decoder validates every element)."""
    lib = load()
    assert lib is not None and hasattr(lib, "sn_decompress")
    arr = np.frombuffer(blob, dtype=np.uint8) if not isinstance(blob, np.ndarray) else blob
    arr = np.ascontiguousarray(arr)
    want = lib.sn_uncompressed_len(_ptr(arr) if arr.size else None, arr.size)
    # Bound the allocation BEFORE trusting the preamble: a corrupt length
    # must raise ValueError, not MemoryError (or reserve half the address
    # space). No valid stream expands more than ~21x (a 3-byte copy-2 tag
    # emits at most 64 bytes), so 24x + slack is unreachable by real data.
    if want < 0 or want > arr.size * 24 + 64:
        raise ValueError("snappy: bad length preamble")
    # +16 slop: the decoder's 8-byte overlap blasts may overshoot a copy's
    # length by up to 7 bytes (never past cap); output is sliced to `want`.
    out = np.empty(int(want) + 16, dtype=np.uint8)
    n = lib.sn_decompress(_ptr(arr) if arr.size else None, arr.size, _ptr(out), out.size)
    if n < 0:
        raise ValueError(f"snappy: corrupt stream (code {n})")
    return out[: int(n)].tobytes()


# -- native IO (O_DIRECT aligned file path; xl-storage.go CreateFile role) ---


def io_available() -> bool:
    lib = load()
    return lib is not None and hasattr(lib, "mt_write_file")


def odirect_supported(dirpath: str) -> bool:
    lib = load()
    if lib is None or not hasattr(lib, "mt_odirect_supported"):
        return False
    return bool(lib.mt_odirect_supported(dirpath.encode()))


def write_file(path: str, data: bytes, use_odirect: bool = True, fsync: bool = False) -> None:
    """Native aligned write; raises OSError on failure."""
    lib = load()
    assert lib is not None and hasattr(lib, "mt_write_file")
    arr = np.frombuffer(data, dtype=np.uint8) if data else np.empty(0, dtype=np.uint8)
    rc = lib.mt_write_file(
        path.encode(), _ptr(arr) if len(arr) else None, len(data),
        1 if use_odirect else 0, 1 if fsync else 0,
    )
    if rc < 0:
        raise OSError(-rc, os.strerror(-rc), path)


def read_file(path: str, size: int, offset: int = 0, use_odirect: bool = True) -> bytes:
    """Native read (possibly short at EOF); raises OSError on failure."""
    lib = load()
    assert lib is not None and hasattr(lib, "mt_read_file")
    out = np.empty(max(size, 1), dtype=np.uint8)
    rc = lib.mt_read_file(
        path.encode(), _ptr(out), size, offset, 1 if use_odirect else 0
    )
    if rc < 0:
        raise OSError(-rc, os.strerror(-rc), path)
    return out[: int(rc)].tobytes()
