"""Native (C++) runtime components, built on demand and loaded via ctypes.

The reference's runtime leans on external C++ (ADIOS2, DDStore, GPTL —
SURVEY §2.9); this package holds the TPU build's own native pieces. The
shared objects are build outputs, never inputs: they are git-ignored, built
from the tracked ``.cpp`` on first use (or by :func:`rebuild`), and rebuilt
when older than their source. Without a ``g++`` the pure-numpy paths run
instead; WITH one, a build that fails raises — a compiler error must not
quietly become the slow path.
"""

from __future__ import annotations

import ctypes
import os
import shutil
import subprocess

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))


def _declare_packed_gather(lib) -> None:
    lib.gpk_gather.argtypes = [
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64),
        ctypes.c_char_p,
        ctypes.c_int64,
    ]
    lib.gpk_gather_mt.argtypes = lib.gpk_gather.argtypes + [ctypes.c_int]


def _declare_radius_graph(lib) -> None:
    lib.pairs_within.argtypes = [
        ctypes.POINTER(ctypes.c_double), ctypes.c_int64,
        ctypes.POINTER(ctypes.c_double), ctypes.c_int64,
        ctypes.c_double,
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64, ctypes.c_int,
    ]
    lib.pairs_within.restype = ctypes.c_int64


# name -> (source, extra compiler flags, ctypes signature declaration)
_SOURCES = {
    "packed_gather": ("packed_gather.cpp", (), _declare_packed_gather),
    "radius_graph": ("radius_graph.cpp", ("-std=c++17",), _declare_radius_graph),
}
_libs: dict[str, ctypes.CDLL | None] = {}


def _paths(name: str) -> tuple[str, str]:
    src = os.path.join(_HERE, _SOURCES[name][0])
    return src, os.path.join(_HERE, f"lib{name}.so")


def _build(name: str) -> bool:
    """Compile ``name`` from its tracked source. False when there is no
    ``g++``; raises when there is one and it fails."""
    if shutil.which("g++") is None:
        return False
    src, so = _paths(name)
    tmp = f"{so}.tmp{os.getpid()}"  # concurrent builders never see a torn .so
    proc = subprocess.run(
        ["g++", "-O3", "-shared", "-fPIC", *_SOURCES[name][1], "-o", tmp, src,
         "-lpthread"],
        capture_output=True, text=True, timeout=120,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"native build of {os.path.basename(src)} failed "
            f"(g++ exit {proc.returncode}):\n{proc.stderr}"
        )
    os.replace(tmp, so)
    return True


def _load(name: str, force_build: bool = False) -> ctypes.CDLL | None:
    if name in _libs and not force_build:
        return _libs[name]
    src, so = _paths(name)
    stale = not os.path.exists(so) or os.path.getmtime(so) < os.path.getmtime(src)
    if (force_build or stale) and not _build(name):
        _libs[name] = None
        return None
    lib = ctypes.CDLL(so)
    _SOURCES[name][2](lib)
    _libs[name] = lib
    return lib


def rebuild() -> dict[str, str]:
    """Rebuild every native library from its tracked source and reload it:
    ``{name: "native" | "numpy"}`` (``numpy`` = no ``g++`` here). What
    ``chip_smoke.py`` calls, so a checkout runs on what git would commit
    rather than on a stray ``.so``."""
    return {
        name: "native" if _load(name, force_build=True) is not None else "numpy"
        for name in _SOURCES
    }


def get_lib():
    """The loaded packed-gather library, or None (numpy path)."""
    return _load("packed_gather")


def gather_blocks(
    src: np.ndarray,
    src_off: np.ndarray,
    nbytes: np.ndarray,
    dst_off: np.ndarray,
    dst: np.ndarray,
    threads: int = 0,
) -> None:
    """Copy variable-length byte blocks src->dst (native when available)."""
    n = len(src_off)
    lib = get_lib()
    if lib is None:
        sv = src.view(np.uint8)
        dv = dst.view(np.uint8)
        for i in range(n):
            dv[dst_off[i] : dst_off[i] + nbytes[i]] = sv[
                src_off[i] : src_off[i] + nbytes[i]
            ]
        return
    so = np.ascontiguousarray(src_off, np.int64)
    nb = np.ascontiguousarray(nbytes, np.int64)
    do = np.ascontiguousarray(dst_off, np.int64)
    src_p = src.ctypes.data_as(ctypes.c_char_p)
    dst_p = dst.ctypes.data_as(ctypes.c_char_p)
    i64p = lambda a: a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))
    if threads > 1:
        lib.gpk_gather_mt(src_p, i64p(so), i64p(nb), i64p(do), dst_p, n, threads)
    else:
        lib.gpk_gather(src_p, i64p(so), i64p(nb), i64p(do), dst_p, n)


# ---------------------------------------------------------------------------
# Cell-list neighbor search (the reference's vesin role)
# ---------------------------------------------------------------------------

def get_radius_lib():
    """The loaded cell-list library, or None (numpy path)."""
    return _load("radius_graph")


def pairs_within_native(
    query: np.ndarray, points: np.ndarray, radius: float, threads: int = 0
):
    """All (qi, pj) with ||points[pj] - query[qi]|| <= radius via the native
    cell list; None when the native library is unavailable."""
    lib = get_radius_lib()
    if lib is None:
        return None
    q = np.ascontiguousarray(query, np.float64)
    p = np.ascontiguousarray(points, np.float64)
    nq, npts = q.shape[0], p.shape[0]
    if threads <= 0:
        threads = min(os.cpu_count() or 1, 8)
    cap = max(64 * nq, 1024)
    f64p = lambda a: a.ctypes.data_as(ctypes.POINTER(ctypes.c_double))
    i64p = lambda a: a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))
    for _ in range(2):
        out_q = np.empty(cap, np.int64)
        out_p = np.empty(cap, np.int64)
        n = lib.pairs_within(
            f64p(q), nq, f64p(p), npts, float(radius),
            i64p(out_q), i64p(out_p), cap, int(threads),
        )
        if n >= 0:
            return out_q[:n], out_p[:n]
        cap = -n
    return None  # pragma: no cover — second pass always fits
