"""Native (C++) components: build-on-first-import, ctypes ABI.

The reference carries its native axis in c-deps/ built by Bazel; here
the native hotspots are bulk key encoding (keyenc.cpp) and the OLTP
row plane (oltp.cpp). Each shared library compiles lazily with g++
and loads via ctypes — pybind11 isn't in the image, and the ABI is a
few flat functions. The cached `.so` is named by the SHA-256 of its
source and compile flags, so an edited source can never load a stale
library (and a library that loads has every symbol the source
declares). Without a toolchain the callers run their pure-Python
codecs; `status()` says which plane each component is on and keeps
the compiler's message.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_CXX = ("g++", "-O3", "-shared", "-fPIC", "-std=c++17")

_lock = threading.Lock()
_lib = None
_tried = False
# component -> {"plane": "native"|"python", "so": path|None,
#               "built": compiled by this process (False = reused a
#               cached library whose name matches the source hash),
#               "error": compiler/loader message|None}
_STATUS: dict[str, dict] = {}


def status() -> dict:
    """Per-component plane report (empty until first use)."""
    with _lock:
        return {k: dict(v) for k, v in _STATUS.items()}


def _load(stem: str):
    """Build (unless a library for exactly this source is cached) and
    dlopen `<stem>.cpp`; None when there is no toolchain. Caller holds
    _lock."""
    src = os.path.join(_HERE, stem + ".cpp")
    with open(src, "rb") as f:
        digest = hashlib.sha256(
            " ".join(_CXX).encode() + b"\0" + f.read()).hexdigest()[:16]
    so = os.path.join(_HERE, f"_{stem}-{digest}.so")
    st = _STATUS[stem] = {"plane": "python", "so": None,
                          "built": False, "error": None}
    try:
        if not os.path.exists(so):
            tmp = so + f".tmp{os.getpid()}"
            subprocess.run([*_CXX, "-o", tmp, src], check=True,
                           capture_output=True, timeout=120)
            os.replace(tmp, so)
            st["built"] = True
            for old in glob.glob(os.path.join(_HERE, f"_{stem}*.so")):
                if old != so:
                    os.unlink(old)  # libraries of earlier sources
        lib = ctypes.CDLL(so)
    except subprocess.CalledProcessError as e:
        st["error"] = e.stderr.decode(errors="replace")[-2000:]
        return None
    except (OSError, subprocess.SubprocessError) as e:
        st["error"] = f"{type(e).__name__}: {e}"
        return None
    st.update(plane="native", so=so)
    return lib


def get_lib():
    """The loaded keyenc library, or None (callers fall back)."""
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        lib = _load("keyenc")
        if lib is None:
            return None
        u8p = ctypes.POINTER(ctypes.c_uint8)
        i64p = ctypes.POINTER(ctypes.c_int64)
        lib.keyenc_batch_int.argtypes = [
            u8p, ctypes.c_int64, i64p, ctypes.c_int64, u8p, i64p]
        lib.keyenc_batch_int.restype = None
        lib.keyenc_batch_bytes.argtypes = [
            u8p, ctypes.c_int64, u8p, i64p, ctypes.c_int64, u8p, i64p]
        lib.keyenc_batch_bytes.restype = ctypes.c_int64
        lib.keyenc_int64.argtypes = [ctypes.c_int64, u8p]
        lib.keyenc_float64.argtypes = [ctypes.c_double, u8p]
        lib.keyenc_bytes.argtypes = [u8p, ctypes.c_int64, u8p]
        lib.keyenc_bytes.restype = ctypes.c_int64
        _lib = lib
        return _lib


_oltp_lib = None
_oltp_tried = False


def get_oltp():
    """The native OLTP row plane (oltp.cpp), or None (callers fall
    back to the Python fastpath)."""
    global _oltp_lib, _oltp_tried
    with _lock:
        if _oltp_tried:
            return _oltp_lib
        _oltp_tried = True
        lib = _load("oltp")
        if lib is None:
            return None
        i64 = ctypes.c_int64
        i64p = ctypes.POINTER(i64)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        vp = ctypes.c_void_p
        lib.oltp_create.argtypes = [i64]
        lib.oltp_create.restype = vp
        lib.oltp_destroy.argtypes = [vp]
        lib.oltp_destroy.restype = None
        lib.oltp_nversions.argtypes = [vp]
        lib.oltp_nversions.restype = i64
        lib.oltp_bulk.argtypes = [vp, i64, i64p, i64p, i64p, i64p, u8p]
        lib.oltp_bulk.restype = None
        lib.oltp_put.argtypes = [vp, i64, i64, i64p, u8p]
        lib.oltp_put.restype = ctypes.c_int
        lib.oltp_del.argtypes = [vp, i64, i64]
        lib.oltp_del.restype = ctypes.c_int
        lib.oltp_live.argtypes = [vp, i64, i64]
        lib.oltp_live.restype = ctypes.c_int
        lib.oltp_read.argtypes = [vp, i64, i64, i64p, u8p]
        lib.oltp_read.restype = ctypes.c_int
        # batch-window gather
        lib.oltp_multiread.argtypes = [vp, i64, i64p, i64, i64p,
                                       u8p, u8p]
        lib.oltp_multiread.restype = i64
        lib.oltp_scan.argtypes = [vp, i64, ctypes.c_int, ctypes.c_int,
                                  i64, ctypes.c_int, ctypes.c_int,
                                  i64, i64, i64p, i64p, u8p]
        lib.oltp_scan.restype = i64
        _oltp_lib = lib
        return _oltp_lib


def batch_encode_int_keys(prefix: bytes, vals) -> list[bytes]:
    """n keys of prefix+int64 via the native encoder; None if no lib."""
    import numpy as np
    lib = get_lib()
    if lib is None:
        return None
    vals = np.ascontiguousarray(vals, dtype=np.int64)
    n = len(vals)
    stride = len(prefix) + 8
    out = np.empty(n * stride, dtype=np.uint8)
    offs = np.empty(n + 1, dtype=np.int64)
    pbuf = (ctypes.c_uint8 * len(prefix)).from_buffer_copy(prefix)
    lib.keyenc_batch_int(
        ctypes.cast(pbuf, ctypes.POINTER(ctypes.c_uint8)),
        len(prefix),
        vals.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), n,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
    raw = out.tobytes()
    return [raw[i * stride:(i + 1) * stride] for i in range(n)]


def batch_encode_str_keys(prefix: bytes, strs: list[str]) -> list[bytes]:
    """n keys of prefix+escaped-utf8; None if no lib."""
    import numpy as np
    lib = get_lib()
    if lib is None:
        return None
    blobs = [s.encode("utf-8") for s in strs]
    n = len(blobs)
    data = b"".join(blobs)
    doffs = np.zeros(n + 1, dtype=np.int64)
    np.cumsum([len(b) for b in blobs], out=doffs[1:])
    cap = n * len(prefix) + 2 * len(data) + 2 * n
    out = np.empty(max(cap, 1), dtype=np.uint8)
    offs = np.empty(n + 1, dtype=np.int64)
    pbuf = (ctypes.c_uint8 * len(prefix)).from_buffer_copy(prefix)
    dbuf = (ctypes.c_uint8 * max(len(data), 1)).from_buffer_copy(
        data or b"\x00")
    lib.keyenc_batch_bytes(
        ctypes.cast(pbuf, ctypes.POINTER(ctypes.c_uint8)),
        len(prefix),
        ctypes.cast(dbuf, ctypes.POINTER(ctypes.c_uint8)),
        doffs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), n,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
    raw = out.tobytes()
    return [raw[offs[i]:offs[i + 1]] for i in range(n)]
