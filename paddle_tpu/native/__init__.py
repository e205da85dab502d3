"""Native runtime components (C++, ctypes-bound).

The reference's runtime is C++ where it matters for throughput — the
ingest stack above all (framework/data_set.h, data_feed.h run the whole
file→shuffle→batch path without Python in the loop).  This package holds
the TPU framework's native equivalents.  pybind11 isn't available in this
image, so the ABI is plain C over ctypes.

The shared library builds from the in-tree source on first use (g++ -O2)
and is cached under ``<checkout>/.cache/native`` keyed by a source hash —
the same "compile on first touch, cache after" contract as XLA kernels.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

from ..sysconfig import cache_root as _cache_root

__all__ = ["ingest_lib", "c_api_path", "NativeBuildError"]

_CACHE_DIR = os.path.join(_cache_root(), "native")
_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "ingest.cc")

_lock = threading.Lock()
_lib = None


class NativeBuildError(RuntimeError):
    pass


def _build(src: str, tag: str, extra_flags=(), extra_srcs=()) -> str:
    h = hashlib.sha256()
    for p in (src,) + tuple(extra_srcs):
        with open(p, "rb") as f:
            h.update(f.read())
    digest = h.hexdigest()[:16]
    out = os.path.join(_CACHE_DIR, f"{tag}-{digest}.so")
    if os.path.exists(out):
        return out
    os.makedirs(_CACHE_DIR, exist_ok=True)
    tmp = out + f".tmp{os.getpid()}-{threading.get_ident()}"
    cmd = (["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-pthread",
            src] + list(extra_flags) + ["-o", tmp])
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True)
    except FileNotFoundError as e:
        raise NativeBuildError(f"g++ not available: {e}")
    if proc.returncode != 0:
        raise NativeBuildError(
            f"native build failed ({' '.join(cmd)}):\n{proc.stderr[-2000:]}")
    os.replace(tmp, out)  # atomic publish; concurrent builders converge
    return out


def ingest_lib() -> ctypes.CDLL:
    """The ingest engine library, built/cached on first call."""
    global _lib
    with _lock:
        if _lib is not None:
            return _lib
        path = _build(_SRC, "ingest")
        lib = ctypes.CDLL(path)
        lib.ingest_create.restype = ctypes.c_void_p
        lib.ingest_create.argtypes = [ctypes.c_int64]
        lib.ingest_destroy.argtypes = [ctypes.c_void_p]
        lib.ingest_load.restype = ctypes.c_int64
        lib.ingest_load.argtypes = [ctypes.c_void_p,
                                    ctypes.POINTER(ctypes.c_char_p),
                                    ctypes.c_int64, ctypes.c_int64]
        lib.ingest_size.restype = ctypes.c_int64
        lib.ingest_size.argtypes = [ctypes.c_void_p]
        lib.ingest_error.restype = ctypes.c_char_p
        lib.ingest_error.argtypes = [ctypes.c_void_p]
        lib.ingest_shuffle.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.ingest_copy_rows.restype = ctypes.c_int64
        lib.ingest_copy_rows.argtypes = [ctypes.c_void_p,
                                         ctypes.POINTER(ctypes.c_double),
                                         ctypes.c_int64, ctypes.c_int64]
        lib.ingest_clear.argtypes = [ctypes.c_void_p]
        lib.ingest_create_multislot.restype = ctypes.c_void_p
        lib.ingest_create_multislot.argtypes = [
            ctypes.c_int64, ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64)]
        lib.ingest_copy_slot.restype = ctypes.c_int64
        lib.ingest_copy_slot.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64)]
        _lib = lib
        return _lib


_CAPI_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "capi.cc")


def c_api_path() -> str:
    """Build (once, cached) and return the C inference ABI shared library
    (paddle_tpu_c.h).  Unlike :func:`ingest_lib` this is linked by C/Go
    programs, not loaded via ctypes here — the embedded interpreter would
    clash with the running one."""
    # flags from the RUNNING interpreter (sysconfig), not whatever
    # python3-config is on PATH — a mismatched system interpreter would
    # embed a runtime that cannot import this package
    import sysconfig

    inc = sysconfig.get_paths()["include"]
    libdir = sysconfig.get_config_var("LIBDIR") or ""
    ldver = sysconfig.get_config_var("LDVERSION") \
        or sysconfig.get_config_var("VERSION")
    syslibs = ((sysconfig.get_config_var("LIBS") or "").split()
               + (sysconfig.get_config_var("SYSLIBS") or "").split())
    flags = [f"-I{inc}", f"-I{os.path.dirname(_CAPI_SRC)}"]
    if libdir:
        flags.append(f"-L{libdir}")
    flags.append(f"-lpython{ldver}")
    flags += syslibs
    hdr = os.path.join(os.path.dirname(_CAPI_SRC), "paddle_tpu_c.h")
    with _lock:
        return _build(_CAPI_SRC, "capi", extra_flags=flags,
                      extra_srcs=(hdr,))
