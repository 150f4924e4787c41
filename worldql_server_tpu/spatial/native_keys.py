"""Native query-key preparation (ctypes binding for native/spatial.cpp).

One C++ pass fuses cube quantization with both spatial hashes — the
per-tick host-side cost of the fan-out engine (~4 ms per 16K-query
batch in numpy, dominated by intermediate arrays the fused loop never
materializes). Falls back to the numpy twins transparently; the
property suite (tests/test_native_keys.py) pins bit-exact agreement
including NaN/±inf/exact-multiple/saturation edge cases.

Two entry points:

* :func:`query_keys` — quantize + both hashes for an [N] batch
  (``wql_query_keys``).
* :func:`encode_queries` — the full dispatch-ready encode
  (``wql_encode_queries``): quantize + hash + capacity-tier padding of
  all four query columns straight from the ticker's staging arrays, one
  GIL-releasing C call (ctypes drops the GIL for the duration), zero
  numpy intermediates. Padding lanes match spatial/hashing.py
  (PAD_KEY / QUERY_PAD_KEY2 / sender -1 / repl 0) — pinned by the
  parity suite. A stale ``.so`` built before this symbol existed keeps
  serving ``query_keys`` and the encode composes the two-step path.
"""

from __future__ import annotations

import ctypes
import logging

import numpy as np

from ..protocol.native_codec import resolve_lib_path
from .hashing import (
    KEY2_OFFSET, PAD_KEY, QUERY_PAD_KEY2, pad_to, spatial_keys,
    spatial_keys2,
)
from .quantize import cube_coords_batch

logger = logging.getLogger(__name__)

_U64_MASK = (1 << 64) - 1


class _NativeKeys:
    def __init__(self, lib: ctypes.CDLL):
        self._fn = lib.wql_query_keys
        self._fn.restype = None
        self._fn.argtypes = [
            ctypes.POINTER(ctypes.c_double),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int64, ctypes.c_int64,
            ctypes.c_uint64, ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64),
        ]
        # Reference-calibration probe (ROADMAP 5a): newer symbol,
        # probed separately like the encode below.
        self._areamap = getattr(lib, "wql_areamap_probe", None)
        if self._areamap is not None:
            self._areamap.restype = ctypes.c_int64
            self._areamap.argtypes = [
                ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_uint64,
                ctypes.POINTER(ctypes.c_double),
            ]
        # The fused batch encode is newer than wql_query_keys — probe
        # it separately so a stale library degrades to the two-step
        # path instead of losing the native keys entirely.
        self._encode = getattr(lib, "wql_encode_queries", None)
        if self._encode is not None:
            self._encode.restype = None
            self._encode.argtypes = [
                ctypes.POINTER(ctypes.c_double),
                ctypes.POINTER(ctypes.c_int32),
                ctypes.POINTER(ctypes.c_int32),
                ctypes.POINTER(ctypes.c_int8),
                ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_uint64, ctypes.c_uint64,
                ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_int32),
                ctypes.POINTER(ctypes.c_int8),
            ]

    def __call__(self, world_ids, positions, cube_size: int, seed: int):
        n = len(world_ids)
        pos = np.ascontiguousarray(positions, dtype=np.float64)
        wid = np.ascontiguousarray(world_ids, dtype=np.int32)
        if pos.shape != (n, 3):
            # the numpy twin raises a broadcast error here; the C call
            # would read past the buffer
            raise ValueError(
                f"positions shape {pos.shape} != ({n}, 3)"
            )
        k1 = np.empty(n, np.int64)
        k2 = np.empty(n, np.int64)
        self._fn(
            pos.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            wid.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            n, cube_size,
            ctypes.c_uint64(seed & _U64_MASK),
            ctypes.c_uint64((seed + KEY2_OFFSET) & _U64_MASK),
            k1.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            k2.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        )
        return k1, k2

    def encode(self, world_ids, positions, sender_ids, repls, cap: int,
               cube_size: int, seed: int):
        if self._encode is None:
            return None
        n = len(world_ids)
        pos = np.ascontiguousarray(positions, dtype=np.float64)
        wid = np.ascontiguousarray(world_ids, dtype=np.int32)
        sid = np.ascontiguousarray(sender_ids, dtype=np.int32)
        rep = np.ascontiguousarray(repls, dtype=np.int8)
        if pos.shape != (n, 3):
            raise ValueError(f"positions shape {pos.shape} != ({n}, 3)")
        if len(sid) != n or len(rep) != n or cap < n:
            raise ValueError("encode_queries column lengths disagree")
        k1 = np.empty(cap, np.int64)
        k2 = np.empty(cap, np.int64)
        sid_out = np.empty(cap, np.int32)
        rep_out = np.empty(cap, np.int8)
        self._encode(
            pos.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            wid.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            sid.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            rep.ctypes.data_as(ctypes.POINTER(ctypes.c_int8)),
            n, cap, cube_size,
            ctypes.c_uint64(seed & _U64_MASK),
            ctypes.c_uint64((seed + KEY2_OFFSET) & _U64_MASK),
            k1.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            k2.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            sid_out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            rep_out.ctypes.data_as(ctypes.POINTER(ctypes.c_int8)),
        )
        return k1, k2, sid_out, rep_out


def load() -> _NativeKeys | None:
    """Load the native key kernel, or None (numpy fallback)."""
    lib_path = resolve_lib_path()
    if lib_path is None or not lib_path.exists():
        return None
    try:
        lib = ctypes.CDLL(str(lib_path))
        if lib.wql_spatial_abi() != 1:
            logger.warning("native spatial ABI mismatch — using numpy")
            return None
        return _NativeKeys(lib)
    except (OSError, AttributeError) as exc:
        # a stale .so without the symbol must not kill the server
        logger.warning("native key kernel unavailable: %s", exc)
        return None


_native = load()


def query_keys(world_ids, positions, cube_size: int, seed: int):
    """[N] i32 world ids + [N, 3] f64 positions → (keys1, keys2), via
    the native fused kernel when built, numpy twins otherwise."""
    if _native is not None:
        return _native(world_ids, positions, cube_size, seed)
    cubes = cube_coords_batch(positions, cube_size)
    return (
        spatial_keys(world_ids, cubes, seed),
        spatial_keys2(world_ids, cubes, seed),
    )


def areamap_probe(n_subs: int, n_queries: int, cube_size: int = 16,
                  seed: int = 11) -> dict | None:
    """Reference-class CPU calibration (``wql_areamap_probe``): build
    a reference-shaped cube→peers hash map of ``n_subs`` rows and
    resolve ``n_queries`` lookups against it, single native thread.
    None when the native library predates the symbol (absent, never
    wrong)."""
    if _native is None or getattr(_native, "_areamap", None) is None:
        return None
    out = np.zeros(3, np.float64)
    rc = _native._areamap(
        int(n_subs), int(n_queries), int(cube_size),
        ctypes.c_uint64(seed & _U64_MASK),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
    )
    if rc != 0:
        return None
    return {
        "subs": int(n_subs),
        "queries": int(n_queries),
        "build_ms": round(float(out[0]), 3),
        "lookup_ns_per_query": round(float(out[1]), 1),
        "matched_rows": int(out[2]),
    }


def numpy_query_keys(world_ids, positions, cube_size: int, seed: int):
    """The pure-numpy path, exposed for the parity suite."""
    cubes = cube_coords_batch(positions, cube_size)
    return (
        spatial_keys(world_ids, cubes, seed),
        spatial_keys2(world_ids, cubes, seed),
    )


def encode_queries(world_ids, positions, sender_ids, repls, cap: int,
                   cube_size: int, seed: int):
    """Full dispatch-ready query encode: → ``(keys1[cap], keys2[cap],
    senders[cap] i32, repls[cap] i8)``, padded to the ``cap`` capacity
    tier. One fused native pass when the kernel is built; the composed
    query_keys + pad_to path otherwise (bit-identical, pinned by
    tests/test_native_keys.py)."""
    if _native is not None:
        out = _native.encode(
            world_ids, positions, sender_ids, repls, cap, cube_size, seed
        )
        if out is not None:
            return out
    return numpy_encode_queries(
        world_ids, positions, sender_ids, repls, cap, cube_size, seed
    )


def numpy_encode_queries(world_ids, positions, sender_ids, repls,
                         cap: int, cube_size: int, seed: int):
    """The composed two-step encode, exposed for the parity suite (and
    the fallback when the fused symbol is absent). Uses query_keys —
    which may itself be native — so a stale library still accelerates
    the hash leg."""
    keys, keys2 = query_keys(world_ids, positions, cube_size, seed)
    return (
        pad_to(keys, cap, PAD_KEY),
        pad_to(keys2, cap, QUERY_PAD_KEY2),
        pad_to(
            np.ascontiguousarray(sender_ids, dtype=np.int32), cap,
            np.int32(-1),
        ),
        pad_to(
            np.ascontiguousarray(repls, dtype=np.int8), cap, np.int8(0)
        ),
    )
