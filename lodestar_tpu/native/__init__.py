"""Native runtime kernels: build-on-first-import C library + ctypes bindings.

The reference ships its host hot loops as native/WASM deps (SURVEY §2.3:
@chainsafe/as-sha256 for merkleization, xxhash-wasm for gossip message
ids, snappy for wire compression).  Here they are one dependency-free C
translation unit (csrc/lodestar_native.c) compiled to a shared library
with the system compiler the first time it's needed and bound via ctypes
(the environment has no pybind11; ctypes keeps the binding zero-build).

Every consumer keeps a pure-Python fallback: `available()` gates use, and
LODESTAR_TPU_NO_NATIVE=1 disables the native path entirely (useful for
differential tests of the fallbacks).  A build or load that fails is
logged as a warning with the compiler's error, never passed over.
"""
from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import sys
import threading
from typing import Optional

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRCS = [
    os.path.join(_HERE, "csrc", "lodestar_native.c"),
    os.path.join(_HERE, "csrc", "bls_h2c.c"),
]
_SRC_DEPS = _SRCS + [os.path.join(_HERE, "csrc", "bls_h2c_constants.h")]
_LIB_PATH = os.path.join(_HERE, f"_lodestar_native_{sys.platform}.so")

_log = logging.getLogger(__name__)
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _build() -> Optional[str]:
    """Compile the library; returns None on success, else why not."""
    cc = os.environ.get("CC", "cc")
    cmd = [cc, "-O3", "-shared", "-fPIC", "-fvisibility=hidden",
           "-o", _LIB_PATH, *_SRCS]
    try:
        proc = subprocess.run(cmd, capture_output=True, timeout=120)
    except (OSError, subprocess.TimeoutExpired) as e:
        return f"{type(e).__name__}: {e}"
    if proc.returncode != 0 or not os.path.exists(_LIB_PATH):
        return f"{cc} rc={proc.returncode}: {proc.stderr.decode()[-2000:]}"
    return None


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.ls_sha256.argtypes = [ctypes.c_char_p, ctypes.c_size_t, u8p]
    lib.ls_sha256.restype = None
    lib.ls_hash_pairs.argtypes = [ctypes.c_char_p, u8p, ctypes.c_size_t]
    lib.ls_hash_pairs.restype = None
    lib.ls_hash_layer.argtypes = [ctypes.c_char_p, ctypes.c_size_t,
                                  ctypes.c_char_p, u8p]
    lib.ls_hash_layer.restype = None
    lib.ls_xxh64.argtypes = [ctypes.c_char_p, ctypes.c_size_t, ctypes.c_uint64]
    lib.ls_xxh64.restype = ctypes.c_uint64
    lib.ls_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
    lib.ls_crc32c.restype = ctypes.c_uint32
    lib.ls_snappy_max_compressed.argtypes = [ctypes.c_size_t]
    lib.ls_snappy_max_compressed.restype = ctypes.c_size_t
    lib.ls_snappy_compress.argtypes = [ctypes.c_char_p, ctypes.c_size_t, u8p]
    lib.ls_snappy_compress.restype = ctypes.c_long
    lib.ls_snappy_uncompressed_length.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
    lib.ls_snappy_uncompressed_length.restype = ctypes.c_long
    lib.ls_snappy_uncompress.argtypes = [ctypes.c_char_p, ctypes.c_size_t,
                                         u8p, ctypes.c_size_t]
    lib.ls_snappy_uncompress.restype = ctypes.c_long
    try:  # absent in pre-h2c builds of the .so (rebuilt on mtime anyway)
        lib.ls_hash_to_g2.argtypes = [ctypes.c_char_p, ctypes.c_size_t,
                                      ctypes.c_char_p, ctypes.c_size_t, u8p]
        lib.ls_hash_to_g2.restype = ctypes.c_int
        lib.ls_h2c_warmup.argtypes = []
        lib.ls_h2c_warmup.restype = None
        lib.ls_h2c_warmup()  # init derived constants once, single-threaded
    except AttributeError:
        pass
    return lib


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    if _lib is not None:
        return _lib
    if _tried:
        return None
    # Reviewed exception: double-checked one-time init — after the first
    # load every call returns on the lock-free fast path above; the one
    # locked section (which may compile the .so) runs once at startup.
    with _lock:  # lodelint: disable=transitive-blocking
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.environ.get("LODESTAR_TPU_NO_NATIVE") == "1":
            return None
        try:
            if not os.path.exists(_LIB_PATH) or any(
                os.path.getmtime(_LIB_PATH) < os.path.getmtime(src)
                for src in _SRC_DEPS
            ):
                err = _build()
                if err is not None:
                    _log.warning(
                        "native library build failed, pure-Python "
                        "fallbacks in use: %s", err,
                    )
                    return None
            _lib = _bind(ctypes.CDLL(_LIB_PATH))
        except OSError as e:
            _log.warning(
                "native library failed to load, pure-Python fallbacks "
                "in use: %s", e,
            )
            _lib = None
    return _lib


def available() -> bool:
    return _load() is not None


# ---------------------------------------------------------------------------
# typed wrappers
# ---------------------------------------------------------------------------


def sha256(data: bytes) -> bytes:
    lib = _load()
    out = (ctypes.c_uint8 * 32)()
    lib.ls_sha256(data, len(data), out)
    return bytes(out)


def hash_pairs(data: bytes) -> bytes:
    """n*64 bytes of concatenated node pairs -> n*32 bytes of parents."""
    n = len(data) // 64
    out = (ctypes.c_uint8 * (32 * n))()
    lib = _load()
    lib.ls_hash_pairs(data, out, n)
    return bytes(out)


def hash_layer(nodes: bytes, zero: bytes) -> bytes:
    """A merkle layer of len(nodes)/32 nodes -> ceil(n/2) parent nodes;
    an odd tail is paired with `zero`."""
    n = len(nodes) // 32
    out_n = (n + 1) // 2
    out = (ctypes.c_uint8 * (32 * out_n))()
    lib = _load()
    lib.ls_hash_layer(nodes, n, zero, out)
    return bytes(out)


def xxh64(data: bytes, seed: int = 0) -> int:
    return int(_load().ls_xxh64(data, len(data), seed))


def crc32c(data: bytes) -> int:
    return int(_load().ls_crc32c(data, len(data)))


def snappy_compress(data: bytes) -> bytes:
    lib = _load()
    cap = lib.ls_snappy_max_compressed(len(data))
    out = (ctypes.c_uint8 * cap)()
    n = lib.ls_snappy_compress(data, len(data), out)
    if n < 0:
        raise ValueError("snappy compression failed")
    return bytes(out[:n])


def has_h2c() -> bool:
    lib = _load()
    return lib is not None and hasattr(lib, "ls_hash_to_g2")


def hash_to_g2_affine(msg: bytes, dst: bytes):
    """Native RFC-9380 hash_to_curve for G2; returns the oracle's affine
    format ((x0, x1), (y0, y1)) of python ints.  ~100x the pure-Python
    oracle's speed (the role blst's in-C h2c plays for the reference)."""
    lib = _load()
    out = (ctypes.c_uint8 * 192)()
    rc = lib.ls_hash_to_g2(msg, len(msg), dst, len(dst), out)
    if rc != 0:
        raise ValueError(f"ls_hash_to_g2 failed rc={rc}")
    b = bytes(out)
    x0, x1, y0, y1 = (
        int.from_bytes(b[i * 48 : (i + 1) * 48], "big") for i in range(4)
    )
    return ((x0, x1), (y0, y1))


def snappy_uncompress(data: bytes, max_len: int = 1 << 27) -> bytes:
    lib = _load()
    n = lib.ls_snappy_uncompressed_length(data, len(data))
    if n < 0 or n > max_len:
        raise ValueError("invalid snappy length")
    out = (ctypes.c_uint8 * n)() if n else (ctypes.c_uint8 * 1)()
    got = lib.ls_snappy_uncompress(data, len(data), out, n)
    if got != n:
        raise ValueError("corrupt snappy data")
    return bytes(out[:n])
