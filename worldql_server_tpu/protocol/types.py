"""Domain types for the WorldQL wire protocol.

Mirrors the reference's idiomatic layer (worldql_server/src/structures/):
``Message`` is the universal envelope for every instruction
(message.rs:14-24); ``Record``/``Entity`` are positioned payloads
(record.rs:9-15, entity.rs:8-14); ``Vector3`` is an f64 triple
(vector3.rs:11-225).
"""

from __future__ import annotations

import enum
import math
import uuid as uuid_mod
from dataclasses import dataclass, field, replace

NIL_UUID = uuid_mod.UUID(int=0)


class Instruction(enum.IntEnum):
    """14-op instruction set (structures/instruction.rs:7-23).

    Wire values match the FlatBuffers enum exactly
    (WorldQLFB_generated.rs:56-70). Unknown is the catch-all default:
    out-of-range wire values decode to it rather than erroring.
    """

    HEARTBEAT = 0
    HANDSHAKE = 1
    PEER_CONNECT = 2
    PEER_DISCONNECT = 3
    AREA_SUBSCRIBE = 4
    AREA_UNSUBSCRIBE = 5
    GLOBAL_MESSAGE = 6
    LOCAL_MESSAGE = 7
    RECORD_CREATE = 8
    RECORD_READ = 9
    RECORD_UPDATE = 10
    RECORD_DELETE = 11
    RECORD_REPLY = 12
    UNKNOWN = 13

    @classmethod
    def from_wire(cls, value: int) -> "Instruction":
        try:
            return cls(value)
        except ValueError:
            return cls.UNKNOWN


class Replication(enum.IntEnum):
    """Per-message fan-out mode (structures/replication.rs:8-18)."""

    EXCEPT_SELF = 0  # default
    INCLUDING_SELF = 1
    ONLY_SELF = 2

    @classmethod
    def from_wire(cls, value: int) -> "Replication":
        try:
            return cls(value)
        except ValueError:
            return cls.EXCEPT_SELF


@dataclass(frozen=True, slots=True)
class Vector3:
    x: float = 0.0
    y: float = 0.0
    z: float = 0.0

    def __add__(self, other: "Vector3") -> "Vector3":
        return Vector3(self.x + other.x, self.y + other.y, self.z + other.z)

    def __sub__(self, other: "Vector3") -> "Vector3":
        return Vector3(self.x - other.x, self.y - other.y, self.z - other.z)

    def __mul__(self, scalar: float) -> "Vector3":
        return Vector3(self.x * scalar, self.y * scalar, self.z * scalar)

    def __neg__(self) -> "Vector3":
        return Vector3(-self.x, -self.y, -self.z)

    def length(self) -> float:
        return math.sqrt(self.x * self.x + self.y * self.y + self.z * self.z)

    def distance_to(self, other: "Vector3") -> float:
        return (self - other).length()

    def as_tuple(self) -> tuple[float, float, float]:
        return (self.x, self.y, self.z)

    @classmethod
    def zero(cls) -> "Vector3":
        return cls(0.0, 0.0, 0.0)


@dataclass(slots=True)
class Record:
    """Persistent positioned object (structures/record.rs:9-15).

    ``position`` is optional on the wire; records without position are
    accepted by the codec but (like the reference) not yet by the
    region-sharded store paths that require one.
    """

    uuid: uuid_mod.UUID = NIL_UUID
    position: Vector3 | None = None
    world_name: str = ""
    data: str | None = None
    flex: bytes | None = None


@dataclass(slots=True)
class Entity:
    """Live positioned object (structures/entity.rs:8-14); position required."""

    uuid: uuid_mod.UUID = NIL_UUID
    position: Vector3 = field(default_factory=Vector3.zero)
    world_name: str = ""
    data: str | None = None
    flex: bytes | None = None


@dataclass(slots=True)
class Message:
    """The universal wire envelope (structures/message.rs:14-24)."""

    instruction: Instruction = Instruction.UNKNOWN
    parameter: str | None = None
    sender_uuid: uuid_mod.UUID = NIL_UUID
    world_name: str = ""
    replication: Replication = Replication.EXCEPT_SELF
    records: list[Record] = field(default_factory=list)
    entities: list[Entity] = field(default_factory=list)
    position: Vector3 | None = None
    flex: bytes | None = None
    #: inbound wire bytes this Message was decoded from (set by the
    #: decoder; excluded from equality). Fan-out paths that re-broadcast
    #: a message VERBATIM (LocalMessage — the reference re-serializes
    #: the identical struct, message.rs:120-134) reuse these bytes and
    #: skip the encoder entirely. Never set on mutated/constructed
    #: messages; ``with_`` clears it.
    wire: bytes | None = field(default=None, compare=False, repr=False)
    #: cluster trace context ``(trace_id, t_router_ingress_ns)`` set by
    #: a shard's transport after stripping the router's framed prefix
    #: (cluster/tracectx.py); excluded from equality and never
    #: serialized. None everywhere outside a cluster shard — the
    #: single-process paths pay one attribute read at most.
    trace_ctx: tuple | None = field(default=None, compare=False, repr=False)
    #: ``time.monotonic_ns()`` at ``TickBatcher.enqueue`` (0 = never
    #: queued): the flush that takes the message observes enqueue →
    #: flush start into ``tick.queue_wait_ms``. Never serialized.
    t_enqueue_ns: int = field(default=0, compare=False, repr=False)

    def with_(self, **kwargs) -> "Message":
        """Copy with replacements (Rust struct-update syntax analog).
        The copy never inherits ``wire`` — it no longer matches the
        mutated content unless explicitly re-set."""
        kwargs.setdefault("wire", None)
        return replace(self, **kwargs)
