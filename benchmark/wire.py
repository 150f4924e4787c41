"""The client side of the WorldQL wire, kept with the yardstick.

FlatBuffers `Message` envelope as upstream publishes it (schema
reconstructed from `WorldQLFB_generated.rs`, slots below), written with
the third-party `flatbuffers` runtime only: nothing of the program's
codec is imported, so a later change to the program cannot move what
the generators send or what the receivers read.

Hot path of the measured window: `Template.stamp` (patch id, due time
and position into a pre-built frame) and `peek` (find the stamp in a
received frame without parsing it). `parse` is the full read, used
after the window on a sample of frames.
"""

from __future__ import annotations

import struct
import uuid as uuid_mod

import flatbuffers
import numpy as np
from flatbuffers import number_types as N
from flatbuffers.table import Table

# Instruction / Replication enums (WorldQLFB_generated.rs:34-70, 169-190)
HEARTBEAT, HANDSHAKE, LOCAL_MESSAGE = 0, 1, 7
EXCEPT_SELF, INCLUDING_SELF = 0, 1
#: Handshake, PeerConnect, PeerDisconnect: what a peer is sent unasked
PROTOCOL_FRAMES = (1, 2, 3)

# Message vtable slots (WorldQLFB_generated.rs:939-947)
_INSTRUCTION, _PARAMETER, _SENDER, _WORLD, _REPLICATION = 0, 1, 2, 3, 4
_RECORDS, _ENTITIES, _POSITION, _FLEX = 5, 6, 7, 8
# Record/Entity slots (WorldQLFB_generated.rs:485-489)
_O_UUID, _O_POSITION, _O_WORLD, _O_DATA, _O_FLEX = 0, 1, 2, 3, 4

#: every stamped payload starts with this; `peek` finds it with one
#: C-speed search, wherever the server's encoder put the flex vector
MAGIC = b"WQLBENCH"
#: magic, message id (u64), due time (CLOCK_MONOTONIC ns, i64)
STAMP = struct.Struct("<8sQq")
#: message id = phase << 48 | index in the phase's plan
PHASE_SHIFT = 48
_SENTINEL = (-7.25e300, 3.5e300, -1.125e300)   # found again in the frame


def encode(instruction: int, sender: uuid_mod.UUID, world: str = "",
           parameter: str | None = None, replication: int = EXCEPT_SELF,
           position=None, flex: bytes | None = None,
           entities: list | None = None) -> bytes:
    """One Message -> wire bytes. `entities` is a list of
    (uuid, (x, y, z), world, flex-or-None)."""
    b = flatbuffers.Builder(256)
    ent_offs = []
    for e_uuid, e_pos, e_world, e_flex in entities or ():
        u = b.CreateString(str(e_uuid))
        w = b.CreateString(e_world)
        f = b.CreateByteVector(e_flex) if e_flex is not None else None
        b.StartObject(5)
        b.PrependUOffsetTRelativeSlot(_O_UUID, u, 0)
        b.Prep(8, 24)
        for c in reversed(e_pos):
            b.PrependFloat64(float(c))
        b.PrependStructSlot(_O_POSITION, b.Offset(), 0)
        b.PrependUOffsetTRelativeSlot(_O_WORLD, w, 0)
        if f is not None:
            b.PrependUOffsetTRelativeSlot(_O_FLEX, f, 0)
        ent_offs.append(b.EndObject())
    ent_vec = None
    if ent_offs:
        b.StartVector(4, len(ent_offs), 4)
        for off in reversed(ent_offs):
            b.PrependUOffsetTRelative(off)
        ent_vec = b.EndVector()
    p = b.CreateString(parameter) if parameter is not None else None
    s = b.CreateString(str(sender))
    w = b.CreateString(world)
    f = b.CreateByteVector(flex) if flex is not None else None
    b.StartObject(9)
    b.PrependUint8Slot(_INSTRUCTION, instruction, 0)
    if p is not None:
        b.PrependUOffsetTRelativeSlot(_PARAMETER, p, 0)
    b.PrependUOffsetTRelativeSlot(_SENDER, s, 0)
    b.PrependUOffsetTRelativeSlot(_WORLD, w, 0)
    b.PrependUint8Slot(_REPLICATION, replication, 0)
    if ent_vec is not None:
        b.PrependUOffsetTRelativeSlot(_ENTITIES, ent_vec, 0)
    if position is not None:
        b.Prep(8, 24)
        for c in reversed(position):
            b.PrependFloat64(float(c))
        b.PrependStructSlot(_POSITION, b.Offset(), 0)
    if f is not None:
        b.PrependUOffsetTRelativeSlot(_FLEX, f, 0)
    b.Finish(b.EndObject())
    return bytes(b.Output())


class Template:
    """A LocalMessage frame of one sender, built once; `stamp` patches
    message id, due time and position in place — a microsecond, where a
    FlatBuffers build in Python takes tens."""

    def __init__(self, sender: uuid_mod.UUID, world: str, replication: int,
                 payload_bytes: int):
        if payload_bytes < STAMP.size:
            raise ValueError(f"payload must hold the {STAMP.size}-byte stamp")
        flex = STAMP.pack(MAGIC, 0, 0).ljust(payload_bytes, b"\x00")
        frame = encode(LOCAL_MESSAGE, sender, world, replication=replication,
                       position=_SENTINEL, flex=flex)
        self._frame = bytearray(frame)
        self._stamp_at = frame.index(MAGIC) + len(MAGIC)
        self._pos_at = frame.index(struct.pack("<3d", *_SENTINEL))

    def stamp(self, msg_id: int, due_ns: int, x: float, y: float,
              z: float) -> bytes:
        buf = self._frame
        struct.pack_into("<Qq", buf, self._stamp_at, msg_id, due_ns)
        struct.pack_into("<3d", buf, self._pos_at, x, y, z)
        return bytes(buf)


def make_ids(phase: int, n: int):
    return (np.uint64(phase) << np.uint64(PHASE_SHIFT)) | np.arange(
        n, dtype=np.uint64)


def phase_of(msg_id):
    return (np.asarray(msg_id, np.uint64) >> np.uint64(PHASE_SHIFT)).astype(
        np.int64)


def index_of(msg_id):
    return (np.asarray(msg_id, np.uint64)
            & np.uint64((1 << PHASE_SHIFT) - 1)).astype(np.int64)


def peek(frame: bytes):
    """-> (message id, due ns) of a stamped frame, or None."""
    at = frame.find(MAGIC)
    if at < 0 or at + STAMP.size > len(frame):
        return None
    return struct.unpack_from("<Qq", frame, at + len(MAGIC))


# ---- full read ------------------------------------------------------


def _str(t: Table, slot: int):
    o = t.Offset(4 + 2 * slot)
    return t.String(o + t.Pos).decode() if o else None


def _bytes(t: Table, slot: int):
    o = t.Offset(4 + 2 * slot)
    if not o:
        return None
    start = t.Vector(o)
    return bytes(t.Bytes[start:start + t.VectorLen(o)])


def _u8(t: Table, slot: int) -> int:
    o = t.Offset(4 + 2 * slot)
    return t.Get(N.Uint8Flags, o + t.Pos) if o else 0


def _vec3(t: Table, slot: int):
    o = t.Offset(4 + 2 * slot)
    if not o:
        return None
    return struct.unpack_from("<3d", t.Bytes, o + t.Pos)


def parse(frame: bytes) -> dict:
    """Whole Message -> dict (entities as dicts with uuid, position,
    world, flex)."""
    buf = bytearray(frame)
    root = flatbuffers.encode.Get(N.UOffsetTFlags.packer_type, buf, 0)
    t = Table(buf, root)
    entities = []
    o = t.Offset(4 + 2 * _ENTITIES)
    if o:
        for i in range(t.VectorLen(o)):
            e = Table(buf, t.Indirect(t.Vector(o) + 4 * i))
            entities.append({
                "uuid": _str(e, _O_UUID), "position": _vec3(e, _O_POSITION),
                "world": _str(e, _O_WORLD), "flex": _bytes(e, _O_FLEX),
            })
    return {
        "instruction": _u8(t, _INSTRUCTION),
        "parameter": _str(t, _PARAMETER),
        "sender": _str(t, _SENDER),
        "world": _str(t, _WORLD),
        "replication": _u8(t, _REPLICATION),
        "position": _vec3(t, _POSITION),
        "flex": _bytes(t, _FLEX),
        "entities": entities,
    }
