"""Columnar entity wire codec (ctypes binding for the PR 11 natives).

Three GIL-releasing siblings of ``wql_encode_queries`` live in
``native/codec.cpp`` (they need its FlatBuffers reader/writer):

* ``wql_decode_entities`` — batch-decode the ``entities`` lists of a
  whole recv batch straight into preallocated SoA columns (binary uuid
  keys, f32 positions/velocities, per-buffer envelope views). The
  entity vector is read directly off the wire, so this path has NO
  ``WQL_MAX_OBJS`` cap — its only bound is the column capacity, which
  grows pow2 on demand.
* ``wql_encode_entity_frames`` — serialize-once per-cohort neighbor
  frame encoding: N ``entity.frame`` LocalMessages sharing one world
  encode in one native pass, byte-identical to ``wql_encode`` of the
  equivalent ``Message``.
* ``wql_encode_interest_frames`` — a tick's interest-managed frames
  (``--interest on``: stamped parameter, NIL sender, n entities of one
  world a frame, tombstones) in ONE native pass over shared columns,
  entities stamped from fixed-layout records, byte-identical to
  ``serialize_message`` of each frame's ``Message``.

Symbol-probe discipline matches spatial/native_keys.py: each symbol is
probed independently so a stale ``.so`` built before PR 11 degrades
that leg to the object path — same semantics, slower — and never
breaks. ``load()`` returns None when the library itself is absent.

Scratch ownership: ``EntityWire.decode`` returns VIEWS into reusable
scratch columns — valid until the next ``decode`` call. The consumer
(entities/ingest.py) stages them into the plane's own columns in the
same event-loop turn, so nothing outlives the window.
"""

from __future__ import annotations

import ctypes
import logging
import struct

import numpy as np

from .native_codec import resolve_lib_path
from .types import Instruction

logger = logging.getLogger(__name__)

_c_i64p = ctypes.POINTER(ctypes.c_int64)
_c_i32p = ctypes.POINTER(ctypes.c_int32)
_c_i8p = ctypes.POINTER(ctypes.c_int8)
_c_u8p = ctypes.POINTER(ctypes.c_uint8)
_c_f32p = ctypes.POINTER(ctypes.c_float)
_c_f64p = ctypes.POINTER(ctypes.c_double)

#: initial entity-column capacity (rows); grows pow2 on demand
_MIN_ROWS = 4096

#: bounded transport recv drain (messages per loop iteration) — the
#: columnar decode amortizes across it; past this the loop yields
RECV_DRAIN_MAX = 256

WQL_E_CAPACITY = -4

_LOCAL_MESSAGE = int(Instruction.LOCAL_MESSAGE)
_u16 = struct.Struct("<H").unpack_from
_i32 = struct.Struct("<i").unpack_from
_u32 = struct.Struct("<I").unpack_from


def peek_update_rows(data: bytes) -> int:
    """Rows a wire buffer would stage if it is what it looks like: a
    LocalMessage with entities and no parameter, the one kind the
    transport may hold for the tick edge. 0 for every other buffer
    (another instruction, a removal, no entities, bytes that do not
    parse). A LOOK, a few reads at the table's head: it decides only
    whether a buffer waits, and ``wql_decode_entities`` still
    classifies every buffer exactly when the held batch is staged."""
    try:
        root = _u32(data, 0)[0]
        vtable = root - _i32(data, root)[0]
        if vtable < 0:
            return 0
        # slots 0 (instruction), 1 (parameter) and 6 (entities) of the
        # Message table: its vtable holds two u16 of sizes, then a u16
        # offset a slot
        if _u16(data, vtable)[0] < 18:
            return 0
        instr, param = _u16(data, vtable + 4)[0], _u16(data, vtable + 6)[0]
        if not instr or param or data[root + instr] != _LOCAL_MESSAGE:
            return 0
        entities = _u16(data, vtable + 16)[0]
        if not entities:
            return 0
        at = root + entities
        return _u32(data, at + _u32(data, at)[0])[0]
    except (struct.error, IndexError):
        return 0


class DecodedBatch:
    """One recv batch's columnar decode. Arrays are views into the
    decoder's scratch — consume before the next ``decode`` call."""

    __slots__ = (
        "status", "instr", "sender_keys", "world_off", "world_len",
        "ent_start", "ent_count", "uuid_keys", "pos", "vel", "has_vel",
        "total",
    )

    def __init__(self, status, instr, sender_keys, world_off, world_len,
                 ent_start, ent_count, uuid_keys, pos, vel, has_vel,
                 total):
        self.status = status
        self.instr = instr
        self.sender_keys = sender_keys
        self.world_off = world_off
        self.world_len = world_len
        self.ent_start = ent_start
        self.ent_count = ent_count
        self.uuid_keys = uuid_keys
        self.pos = pos
        self.vel = vel
        self.has_vel = has_vel
        self.total = total


class EntityWire:
    """Bound native entity codec. ``can_decode``/``can_encode_frames``
    reflect which symbols this build of the library actually has."""

    def __init__(self, lib: ctypes.CDLL):
        self._lib = lib
        self._decode = getattr(lib, "wql_decode_entities", None)
        if self._decode is not None:
            self._decode.restype = ctypes.c_int64
            self._decode.argtypes = [
                ctypes.POINTER(ctypes.c_char_p), _c_i64p, ctypes.c_int64,
                _c_i8p, _c_u8p, _c_u8p, _c_i64p, _c_i32p, _c_i64p,
                _c_i32p, ctypes.c_int64, _c_u8p, _c_f32p, _c_f32p,
                _c_u8p,
            ]
        self._encode_frames = getattr(lib, "wql_encode_entity_frames", None)
        if self._encode_frames is not None:
            self._encode_frames.restype = ctypes.c_int
            self._encode_frames.argtypes = [
                _c_u8p, _c_u8p, _c_f64p, ctypes.c_int64,
                ctypes.c_char_p, ctypes.c_int32,
                ctypes.POINTER(_c_u8p), _c_i64p, _c_i64p,
            ]
        self._encode_interest = getattr(
            lib, "wql_encode_interest_frames", None
        )
        if self._encode_interest is not None:
            self._encode_interest.restype = ctypes.c_int64
            self._encode_interest.argtypes = [
                ctypes.c_int64,
                ctypes.POINTER(ctypes.c_char_p), _c_i32p,
                ctypes.POINTER(ctypes.c_char_p), _c_i32p,
                _c_i64p, _c_u8p, _c_f64p, _c_u8p,
                ctypes.POINTER(_c_u8p), _c_i64p, _c_i64p, _c_i64p,
            ]
        self._free = lib.wql_buffer_free
        self._free.argtypes = [_c_u8p]
        self._free.restype = None
        # reusable entity-column scratch (pow2 rows)
        self._rows = _MIN_ROWS
        self._alloc_columns()

    def _alloc_columns(self) -> None:
        rows = self._rows
        self._uuid_keys = np.empty((rows, 16), np.uint8)
        self._pos = np.empty((rows, 3), np.float32)
        self._vel = np.empty((rows, 3), np.float32)
        self._has_vel = np.empty(rows, np.uint8)

    @property
    def can_decode(self) -> bool:
        return self._decode is not None

    @property
    def can_encode_frames(self) -> bool:
        return self._encode_frames is not None

    @property
    def can_encode_interest(self) -> bool:
        return self._encode_interest is not None

    # region: decode

    def decode(self, datas: list[bytes]) -> DecodedBatch:
        """Batch-decode a recv batch into columns (one GIL-releasing
        native call; retries with doubled columns on capacity)."""
        n = len(datas)
        bufs = (ctypes.c_char_p * n)(*datas)
        lens = np.fromiter(map(len, datas), np.int64, count=n)
        status = np.empty(n, np.int8)
        instr = np.empty(n, np.uint8)
        sender_keys = np.empty((n, 16), np.uint8)
        world_off = np.empty(n, np.int64)
        world_len = np.empty(n, np.int32)
        ent_start = np.empty(n, np.int64)
        ent_count = np.empty(n, np.int32)
        while True:
            total = self._decode(
                bufs,
                lens.ctypes.data_as(_c_i64p),
                n,
                status.ctypes.data_as(_c_i8p),
                instr.ctypes.data_as(_c_u8p),
                sender_keys.ctypes.data_as(_c_u8p),
                world_off.ctypes.data_as(_c_i64p),
                world_len.ctypes.data_as(_c_i32p),
                ent_start.ctypes.data_as(_c_i64p),
                ent_count.ctypes.data_as(_c_i32p),
                self._rows,
                self._uuid_keys.ctypes.data_as(_c_u8p),
                self._pos.ctypes.data_as(_c_f32p),
                self._vel.ctypes.data_as(_c_f32p),
                self._has_vel.ctypes.data_as(_c_u8p),
            )
            if total != WQL_E_CAPACITY:
                break
            self._rows *= 2
            self._alloc_columns()
        return DecodedBatch(
            status, instr, sender_keys, world_off, world_len, ent_start,
            ent_count, self._uuid_keys, self._pos, self._vel,
            self._has_vel, int(total),
        )

    # endregion

    # region: frame encode

    def encode_frames(self, sender_keys: np.ndarray,
                      ent_keys: np.ndarray, pos: np.ndarray,
                      world: bytes) -> list[bytes]:
        """Encode one cohort's neighbor frames in a single native pass:
        ``[n,16]u8`` sender/entity uuid keys + ``[n,3]f64`` positions +
        one shared world → per-frame wire bytes."""
        n = len(ent_keys)
        sk = np.ascontiguousarray(sender_keys, np.uint8)
        ek = np.ascontiguousarray(ent_keys, np.uint8)
        p = np.ascontiguousarray(pos, np.float64)
        off = np.empty(n, np.int64)
        lens = np.empty(n, np.int64)
        out = _c_u8p()
        rc = self._encode_frames(
            sk.ctypes.data_as(_c_u8p),
            ek.ctypes.data_as(_c_u8p),
            p.ctypes.data_as(_c_f64p),
            n, world, len(world),
            ctypes.byref(out),
            off.ctypes.data_as(_c_i64p),
            lens.ctypes.data_as(_c_i64p),
        )
        if rc != 0:
            raise RuntimeError(f"native frame encode failed (rc {rc})")
        try:
            blob = ctypes.string_at(out, int(off[-1] + lens[-1])) if n else b""
        finally:
            self._free(out)
        return [
            blob[o:o + ln]
            for o, ln in zip(off.tolist(), lens.tolist())
        ]

    def encode_interest_frames(self, params: list[bytes],
                               worlds: list[bytes], bounds: np.ndarray,
                               ent_keys: np.ndarray, pos: np.ndarray,
                               tomb: np.ndarray):
        """Encode a batch of interest-managed frames (ISSUE 18, 44) in
        ONE native pass. Frame ``f`` carries the stamped parameter
        ``params[f]``, the world ``worlds[f]`` and the entities
        ``[bounds[f], bounds[f + 1])`` of three shared columns:
        ``[N,16]u8`` uuid keys, ``[N,3]f64`` positions, ``[N]u8``
        tombstone flags. Returns ``(frames, param_at, recorded)``:
        ``frames[f]`` is a view of frame ``f``'s bytes (one buffer
        holds the batch), byte-identical to ``serialize_message`` of
        the equivalent Message whatever its neighbours in the batch,
        with its parameter's first byte at ``param_at[f]``;
        ``recorded`` is how many entities the encoder wrote from its
        fixed-layout records."""
        n = len(params)
        ek = np.ascontiguousarray(ent_keys, np.uint8)
        p = np.ascontiguousarray(pos, np.float64)
        tb = np.ascontiguousarray(tomb, np.uint8)
        bd = np.ascontiguousarray(bounds, np.int64)
        rows = len(tb)
        if (len(worlds) != n or bd.shape != (n + 1,) or bd[0] < 0
                or bd[-1] > rows or (np.diff(bd) < 0).any()
                or ek.shape != (rows, 16) or p.shape != (rows, 3)):
            raise ValueError("interest frame batch: columns do not fit")
        plens = np.fromiter(map(len, params), np.int32, count=n)
        wlens = np.fromiter(map(len, worlds), np.int32, count=n)
        meta = np.empty((3, n), np.int64)
        out = _c_u8p()
        rc = self._encode_interest(
            n,
            (ctypes.c_char_p * n)(*params), plens.ctypes.data_as(_c_i32p),
            (ctypes.c_char_p * n)(*worlds), wlens.ctypes.data_as(_c_i32p),
            bd.ctypes.data_as(_c_i64p),
            ek.ctypes.data_as(_c_u8p),
            p.ctypes.data_as(_c_f64p),
            tb.ctypes.data_as(_c_u8p),
            ctypes.byref(out),
            meta[0].ctypes.data_as(_c_i64p),
            meta[1].ctypes.data_as(_c_i64p),
            meta[2].ctypes.data_as(_c_i64p),
        )
        if rc < 0:
            raise RuntimeError(f"native interest encode failed (rc {rc})")
        try:
            # frames lie in order, frame 0 lowest: one copy of what the
            # encoder used of its buffer
            off, size, param_at = meta.tolist()
            base = off[0] if n else 0
            blob = memoryview(ctypes.string_at(
                ctypes.addressof(out.contents) + base,
                off[-1] + size[-1] - base,
            ) if n else b"")
        finally:
            self._free(out)
        return ([blob[o - base:o - base + ln] for o, ln in zip(off, size)],
                param_at, int(rc))

    # endregion


_shared: EntityWire | None = None
_shared_loaded = False


def shared() -> EntityWire | None:
    """Process-wide lazily-loaded instance (one CDLL + one scratch set
    per process; callers on the event loop share it safely)."""
    global _shared, _shared_loaded
    if not _shared_loaded:
        _shared = load()
        _shared_loaded = True
    return _shared


def load() -> EntityWire | None:
    """Load the native entity codec, or None (object-path fallback).
    Honors WQL_NATIVE_CODEC exactly like the message codec."""
    lib_path = resolve_lib_path()
    if lib_path is None or not lib_path.exists():
        return None
    try:
        lib = ctypes.CDLL(str(lib_path))
        abi = getattr(lib, "wql_entities_abi", None)
        if abi is None:
            # stale .so from before PR 11 — the object path still works
            logger.warning(
                "native library has no entity codec (stale build) — "
                "entity ingest stays on the object path"
            )
            return None
        abi.restype = ctypes.c_int64
        abi.argtypes = []
        if abi() != 1:
            logger.warning("native entity codec ABI mismatch — object path")
            return None
        return EntityWire(lib)
    except (OSError, AttributeError) as exc:
        logger.warning("native entity codec unavailable: %s", exc)
        return None
