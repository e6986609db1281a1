"""ctypes bindings + build-on-demand for native/postings_codec.cpp,
with a pure-NumPy fallback of identical semantics.

The library is built from native/postings_codec.cpp and nothing else:
its file name carries a hash of that source, so a binary built from
another source (a stale copy from another tree, an older checkout) has
another name and is never loaded. When no library can be built or
loaded, the NumPy codec serves and says so once on stderr."""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
import threading
from typing import Optional, Tuple

import numpy as np

_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_TRIED = False


def _source_path() -> str:
    here = os.path.dirname(os.path.dirname(os.path.dirname(__file__)))
    return os.path.join(here, "native", "postings_codec.cpp")


def _lib_path(src: str) -> str:
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(
        os.path.dirname(__file__), f"_libpostings_{digest}.so"
    )


def _load() -> Optional[ctypes.CDLL]:
    global _LIB, _TRIED
    if _LIB is not None or _TRIED:
        return _LIB
    with _LOCK:
        if _LIB is not None or _TRIED:
            return _LIB
        _TRIED = True
        src = _source_path()
        try:
            lib = _lib_path(src)
            if not os.path.exists(lib):
                # build aside, then rename: concurrent processes (xdist
                # workers) never load a half-written library
                tmp = f"{lib}.{os.getpid()}.tmp"
                subprocess.run(
                    ["g++", "-O3", "-shared", "-fPIC", src, "-o", tmp],
                    check=True,
                    capture_output=True,
                    timeout=120,
                )
                os.replace(tmp, lib)
            dll = ctypes.CDLL(lib)
            for name, argtypes in (
                ("vb_encode", [ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p]),
                ("vb_decode", [ctypes.c_void_p, ctypes.c_int64,
                               ctypes.c_void_p, ctypes.c_int64]),
                ("tiles_encode", [ctypes.c_void_p, ctypes.c_int64,
                                  ctypes.c_int64, ctypes.c_void_p]),
                ("tiles_decode", [ctypes.c_void_p, ctypes.c_int64,
                                  ctypes.c_void_p, ctypes.c_int64,
                                  ctypes.c_int64]),
            ):
                fn = getattr(dll, name)
                fn.argtypes = argtypes
                fn.restype = ctypes.c_int64
            _LIB = dll
        except (OSError, subprocess.SubprocessError) as e:
            _LIB = None
            print(
                "elasticsearch_tpu.native: postings codec not built from "
                f"{src} ({e!r}); using the NumPy codec",
                file=sys.stderr,
            )
        return _LIB


def native_available() -> bool:
    return _load() is not None


# ---------------------------------------------------------------------------
# zigzag varints (LEB128)
# ---------------------------------------------------------------------------


def _zz_enc(v: np.ndarray) -> np.ndarray:
    return ((v.astype(np.int64) << 1) ^ (v.astype(np.int64) >> 31)).astype(
        np.uint64
    )


def _py_vb_encode(vals: np.ndarray) -> bytes:
    out = bytearray()
    for u in _zz_enc(vals.astype(np.int32)):
        u = int(u)
        while u >= 0x80:
            out.append((u & 0x7F) | 0x80)
            u >>= 7
        out.append(u)
    return bytes(out)


def _py_vb_decode(data: bytes, n: int) -> np.ndarray:
    out = np.empty(n, np.int32)
    p = 0
    ln = len(data)
    for i in range(n):
        u = 0
        shift = 0
        while True:
            if p >= ln or shift > 28:
                raise ValueError("corrupt varint stream")
            b = data[p]
            p += 1
            u |= (b & 0x7F) << shift
            if not b & 0x80:
                break
            shift += 7
        out[i] = np.int32((u >> 1) ^ -(u & 1))
    return out


def vb_encode(vals: np.ndarray) -> bytes:
    vals = np.ascontiguousarray(vals, np.int32)
    lib = _load()
    if lib is None:
        return _py_vb_encode(vals)
    out = np.empty(len(vals) * 5, np.uint8)
    n = lib.vb_encode(
        vals.ctypes.data, len(vals), out.ctypes.data
    )
    return out[:n].tobytes()


def vb_decode(data: bytes, n: int) -> np.ndarray:
    lib = _load()
    if lib is None:
        return _py_vb_decode(data, n)
    buf = np.frombuffer(data, np.uint8)
    out = np.empty(n, np.int32)
    used = lib.vb_decode(buf.ctypes.data, len(buf), out.ctypes.data, n)
    if used < 0:
        raise ValueError("corrupt varint stream")
    return out


# ---------------------------------------------------------------------------
# tile delta codec (doc-id rows: absolute first value, ascending deltas,
# -1 padding kept absolute)
# ---------------------------------------------------------------------------


def _py_tiles_encode(tiles: np.ndarray) -> bytes:
    out = bytearray()
    for row in tiles:
        prev = 0
        first = True
        for v in row.tolist():
            if v < 0:
                enc = -1
            elif first:
                enc = v
                prev = v
                first = False
            else:
                if v < prev:
                    # an unsorted row must fail LOUDLY: its negative
                    # delta would alias the -1 padding sentinel and
                    # round-trip silently corrupted
                    raise ValueError(
                        "tiles_encode: doc ids not ascending within row"
                    )
                enc = v - prev
                prev = v
            u = ((enc << 1) ^ (enc >> 31)) & 0xFFFFFFFF
            while u >= 0x80:
                out.append((u & 0x7F) | 0x80)
                u >>= 7
            out.append(u)
    return bytes(out)


def _py_tiles_decode(data: bytes, n_tiles: int, width: int) -> np.ndarray:
    out = np.empty((n_tiles, width), np.int32)
    p = 0
    ln = len(data)
    for t in range(n_tiles):
        prev = 0
        first = True
        for i in range(width):
            u = 0
            shift = 0
            while True:
                if p >= ln or shift > 28:
                    raise ValueError("corrupt tile stream")
                b = data[p]
                p += 1
                u |= (b & 0x7F) << shift
                if not b & 0x80:
                    break
                shift += 7
            v = int(np.int32((u >> 1) ^ -(u & 1)))
            if v == -1:
                out[t, i] = -1
            elif first:
                out[t, i] = v
                prev = v
                first = False
            else:
                prev += v
                out[t, i] = prev
    return out


def tiles_encode(tiles: np.ndarray) -> bytes:
    tiles = np.ascontiguousarray(tiles, np.int32)
    lib = _load()
    if lib is None:
        return _py_tiles_encode(tiles)
    n_tiles, width = tiles.shape
    out = np.empty(tiles.size * 5, np.uint8)
    n = lib.tiles_encode(
        tiles.ctypes.data, n_tiles, width, out.ctypes.data
    )
    if n < 0:
        raise ValueError("tiles_encode: doc ids not ascending within row")
    return out[:n].tobytes()


def tiles_decode(data: bytes, n_tiles: int, width: int) -> np.ndarray:
    lib = _load()
    if lib is None:
        return _py_tiles_decode(data, n_tiles, width)
    buf = np.frombuffer(data, np.uint8)
    out = np.empty((n_tiles, width), np.int32)
    used = lib.tiles_decode(
        buf.ctypes.data, len(buf), out.ctypes.data, n_tiles, width
    )
    if used < 0:
        raise ValueError("corrupt tile stream")
    return out
