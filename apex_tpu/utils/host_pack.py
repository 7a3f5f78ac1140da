"""ctypes bindings for the native host packing engine (csrc/host_pack.cpp)
— the ``apex_C.flatten/unflatten`` runtime analog.

Compiled on first use by :mod:`apex_tpu.utils.native` (``g++`` into
``csrc/_build/``).  Where that fails the same API runs on numpy, after a
warning that says why:

    from apex_tpu.utils import host_pack
    flat = host_pack.pack(arrays, offsets, total)      # one buffer
    host_pack.unpack(flat, arrays_out, offsets)        # in-place fill
"""
from __future__ import annotations

import ctypes
import warnings
from typing import List, Optional, Sequence

import numpy as np

from . import native

_lib = None
_lib_tried = False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _lib_tried
    if _lib_tried:
        return _lib
    _lib_tried = True
    try:
        so, _ = native.build("host_pack.cpp", "libapex_tpu_host")
    except native.NativeBuildError as err:
        warnings.warn(f"host_pack: native library unavailable, packing "
                      f"with numpy instead ({err})", RuntimeWarning)
        return None
    lib = ctypes.CDLL(so)
    lib.apex_tpu_pack.argtypes = [
        ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
        ctypes.c_void_p, ctypes.c_int64]
    lib.apex_tpu_unpack.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
        ctypes.POINTER(ctypes.c_void_p), ctypes.c_int64]
    if lib.apex_tpu_host_pack_abi() != 1:
        raise RuntimeError(f"{so}: unexpected ABI version")
    _lib = lib
    return _lib


def native_available() -> bool:
    return _load() is not None


def _as_i64(vals) -> "ctypes.Array":
    return (ctypes.c_int64 * len(vals))(*vals)


def pack(arrays: Sequence[np.ndarray], offsets: Sequence[int], total: int,
         dtype=np.float32, out: Optional[np.ndarray] = None) -> np.ndarray:
    """Pack host arrays into one (total,) buffer at ELEMENT offsets.
    Arrays must already have the target dtype; padding gaps are zeroed.

    ``out``: optional reusable staging buffer — a fresh tens-of-MB
    0-init allocation per call costs more in page faults than the
    memcpys themselves (measured 31 ms vs 6 ms at 42 MB); callers on a
    steady-state step loop should allocate once and pass it back in.
    Gap elements keep whatever the buffer last held, which is zeros when
    the buffer started as ``np.zeros`` and only ever saw pack()."""
    dtype = np.dtype(dtype)
    if out is None:
        out = np.zeros((total,), dtype)
    elif out.shape != (total,) or out.dtype != dtype:
        raise ValueError(f"out buffer {out.shape}/{out.dtype} != "
                         f"({total},)/{dtype}")
    elif not out.flags["C_CONTIGUOUS"]:
        # the native path memcpys against out's base pointer assuming a
        # dense buffer; a strided view would be silently corrupted (the
        # numpy fallback handles views, so behavior would otherwise
        # diverge by toolchain) — same guard unpack() has on its targets
        raise ValueError("out buffer must be C-contiguous")
    arrays = [np.ascontiguousarray(a, dtype).reshape(-1) for a in arrays]
    if len(arrays) != len(offsets):
        raise ValueError(f"{len(arrays)} arrays vs {len(offsets)} offsets")
    for a, off in zip(arrays, offsets):
        if off < 0 or off + a.size > total:
            raise ValueError(
                f"span [{off}, {off + a.size}) exceeds total {total}")
    lib = _load()
    if lib is None:
        for a, off in zip(arrays, offsets):
            out[off:off + a.size] = a
        return out
    srcs = (ctypes.c_void_p * len(arrays))(
        *[a.ctypes.data_as(ctypes.c_void_p) for a in arrays])
    lib.apex_tpu_pack(srcs, _as_i64([a.size for a in arrays]),
                      _as_i64(list(offsets)), len(arrays),
                      out.ctypes.data_as(ctypes.c_void_p), dtype.itemsize)
    return out


def unpack(flat: np.ndarray, outs: List[np.ndarray],
           offsets: Sequence[int]) -> None:
    """Fill ``outs`` in place from ELEMENT offsets of ``flat`` (same
    dtype)."""
    flat = np.ascontiguousarray(flat)
    if len(outs) != len(offsets):
        raise ValueError(f"{len(outs)} outputs vs {len(offsets)} offsets")
    for o, off in zip(outs, offsets):
        if off < 0 or off + o.size > flat.size:
            raise ValueError(
                f"span [{off}, {off + o.size}) exceeds flat {flat.size}")
    lib = _load()
    if lib is None:
        for o, off in zip(outs, offsets):
            flat_part = flat[off:off + o.size]
            np.copyto(o.reshape(-1), flat_part)
        return
    for o in outs:
        if not o.flags["C_CONTIGUOUS"]:
            raise ValueError("unpack targets must be contiguous")
        if o.dtype.itemsize != flat.dtype.itemsize:
            raise ValueError("unpack dtype width mismatch")
    dsts = (ctypes.c_void_p * len(outs))(
        *[o.ctypes.data_as(ctypes.c_void_p) for o in outs])
    lib.apex_tpu_unpack(flat.ctypes.data_as(ctypes.c_void_p),
                        _as_i64([o.size for o in outs]),
                        _as_i64(list(offsets)), len(outs), dsts,
                        flat.dtype.itemsize)


def pack_like_flattener(arrays, flattener, dtype=np.float32,
                        out: Optional[np.ndarray] = None) -> np.ndarray:
    """Pack host arrays using a TreeFlattener's offsets/total layout — the
    staging buffer feeds ``step_flat`` after ONE host->device transfer."""
    offs = [int(o) for o in flattener.offsets[:-1]]
    return pack(arrays, offs, flattener.total, dtype, out=out)
