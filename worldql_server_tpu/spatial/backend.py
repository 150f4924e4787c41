"""The swappable spatial-subscription engine interface.

This is the seam the whole rebuild pivots on (BASELINE.json north
star): the reference hard-wires a ``WorldMap → AreaMap → CubeArea``
HashMap pipeline into its handlers (subscriptions/world_map.rs,
area_map.rs); here every subscription mutation and proximity query goes
through ``SpatialBackend``, so the dict-based CPU engine and the
batched JAX/TPU engine are interchangeable and property-tested against
each other.

Peers are identified by ``uuid.UUID`` at this boundary; backends may
intern them to dense ints internally. Positions are accepted either as
raw ``Vector3`` (quantized by the backend at the configured cube size)
or as already-quantized ``(cx, cy, cz)`` int tuples — mirroring the
reference's ``ToCubeArea`` trait (cube_area.rs:61-78).
"""

from __future__ import annotations

import abc
import uuid as uuid_mod
from dataclasses import dataclass
from typing import Iterable, Sequence

from ..protocol.types import Replication, Vector3
from .quantize import cube_coords

Cube = tuple[int, int, int]
PosOrCube = "Vector3 | Cube"


def to_cube(pos: Vector3 | Cube, cube_size: int) -> Cube:
    """ToCubeArea: a Vector3 quantizes; a cube passes through
    (cube_area.rs:61-78)."""
    if isinstance(pos, Vector3):
        return cube_coords(pos.x, pos.y, pos.z, cube_size)
    return pos


@dataclass(slots=True)
class LocalQuery:
    """One LocalMessage proximity query in a tick batch."""

    world: str  # sanitized world name
    position: Vector3
    sender: uuid_mod.UUID
    replication: Replication = Replication.EXCEPT_SELF
    #: query-library kind (queries/kinds.py): 0 = plain radius row,
    #: anything else routes through the kind-dispatched expansion with
    #: ``params`` carrying the parsed f64 parameter lanes
    kind: int = 0
    params: tuple = ()


class SpatialBackend(abc.ABC):
    """Subscription index + proximity query engine for all worlds."""

    #: query-library expansion clamps (engine/config.py wires the
    #: ``query_stencil_max`` / ``query_ray_steps`` flags through;
    #: oracles and device expansion read the SAME values, so the clamp
    #: is part of the query semantics on both paths)
    query_stencil_max: int = 3
    query_ray_steps: int = 64

    def __init__(self, cube_size: int):
        self.cube_size = cube_size

    # region: mutations

    @abc.abstractmethod
    def add_subscription(
        self, world: str, peer: uuid_mod.UUID, pos: Vector3 | Cube
    ) -> bool:
        """Subscribe peer to the cube containing ``pos`` in ``world``.
        Creates the world lazily. Returns True if newly added
        (area_map.rs:72-85)."""

    @abc.abstractmethod
    def remove_subscription(
        self, world: str, peer: uuid_mod.UUID, pos: Vector3 | Cube
    ) -> bool:
        """Unsubscribe peer from one cube. Returns True if a
        subscription was removed (area_map.rs:88-119)."""

    @abc.abstractmethod
    def remove_peer(self, peer: uuid_mod.UUID) -> bool:
        """Remove a disconnected peer from every world/cube
        (world_map.rs:41-61)."""

    def remove_peers(self, peers: Sequence[uuid_mod.UUID]) -> int:
        """``remove_peer`` for each of ``peers``; returns how many held
        a subscription. Device backends override with one vectorized
        pass."""
        return sum(self.remove_peer(peer) for peer in peers)

    # endregion

    # region: queries

    @abc.abstractmethod
    def query_cube(self, world: str, pos: Vector3 | Cube) -> set[uuid_mod.UUID]:
        """Peers subscribed to the cube containing ``pos``; empty set if
        the world has never been subscribed to (area_map.rs:52-60)."""

    @abc.abstractmethod
    def query_world(self, world: str) -> set[uuid_mod.UUID]:
        """Peers subscribed to at least one cube of ``world``
        (area_map.rs:65-67)."""

    def is_subscribed(
        self, world: str, peer: uuid_mod.UUID, pos: Vector3 | Cube
    ) -> bool:
        return peer in self.query_cube(world, pos)

    def is_subscribed_any(self, world: str, peer: uuid_mod.UUID) -> bool:
        return peer in self.query_world(world)

    # endregion

    # region: batched hot path

    def match_local_batch(
        self, queries: Sequence[LocalQuery]
    ) -> list[list[uuid_mod.UUID]]:
        """Resolve a tick's worth of LocalMessage queries to fan-out
        lists, applying each query's replication filter
        (local_message.rs:60-86).

        Base implementation loops ``query_cube``; accelerated backends
        override with one fused device batch. Kind queries (``q.kind``
        != 0) resolve through the library's CPU-parity oracles
        (queries/oracle.py) to a ``KindResult`` row — this IS the
        reference path the device expansion is pinned against, and the
        degraded path ResilientBackend's CPU mirror answers with.
        """
        out: list = []
        for q in queries:  # wql: allow(per-query-python-loop) — the CPU reference path IS per-query
            if q.kind:
                from ..queries.oracle import match_kind

                out.append(match_kind(
                    self, q, q.params,
                    stencil_max=self.query_stencil_max,
                    ray_steps_max=self.query_ray_steps,
                ))
                continue
            peers = self.query_cube(q.world, q.position)
            out.append(_apply_replication(peers, q.sender, q.replication))
        return out

    def export_rows(self):
        """→ (worlds, peers, row_wid, row_cube, row_pid): every live
        subscription as index rows for snapshotting (spatial/
        snapshot.py). Each backend implements this against its own
        internals — a backend without it loses its shutdown checkpoint,
        so fail loudly rather than silently."""
        raise NotImplementedError(
            f"{type(self).__name__} does not implement export_rows — "
            "its index cannot be snapshotted"
        )

    def flush(self) -> None:
        """Make all prior mutations visible to queries. No-op for
        immediate-mode backends; device-mirror backends sync here."""

    # Two-phase batch API for the tick batcher: ``dispatch`` runs on the
    # owning thread (may read mutable host state), ``collect`` only
    # waits for results and may run on a worker thread. Immediate-mode
    # backends resolve everything in dispatch.
    def dispatch_local_batch(self, queries: Sequence[LocalQuery]):
        return self.match_local_batch(queries)

    def collect_local_batch(self, handle) -> list[list[uuid_mod.UUID]]:
        return handle

    # Columnar staged dispatch (engine/staging.py): backends that can
    # launch a batch straight from preallocated columnar arrays
    # (world_id i32, pos f64[·,3], sender_id i32, repl i8 — interned at
    # enqueue time by the ticker's staging buffers) advertise it here,
    # killing the per-query Python encode loop at flush time. The
    # object-list API above remains the default path (CPU backend,
    # staging off) byte for byte.
    def supports_staged_dispatch(self) -> bool:
        return False

    def interning_maps(self):
        """→ ``(world_name → id, peer_uuid → id)`` dicts the staging
        buffers intern through at enqueue time. Only meaningful when
        :meth:`supports_staged_dispatch` is True; the dicts are owned
        (and only mutated) by the event-loop thread."""
        raise NotImplementedError(
            f"{type(self).__name__} has no interning tables"
        )

    def staging_epoch(self) -> int:
        """Monotone counter that changes whenever previously interned
        ids stop being valid (e.g. a resilience rebuild swapped the
        inner backend). The ticker falls back to the object-list path
        for any staged window whose epoch went stale."""
        return 0

    def dispatch_staged_batch(
        self, world_ids, positions, sender_ids, repls,
        kinds=None, params=None, fallback=None,
    ):
        """Launch a batch from staged columnar arrays (already
        interned). ``kinds``/``params`` are the query-library lanes
        (i8 kind + f64 parameter rows); ``None`` — or an all-zero kind
        column — is the pure-radius fast path, byte-for-byte the
        pre-library pipeline. ``fallback`` is an opaque sequence of
        ``(message, LocalQuery)`` pairs a degraded wrapper may use to
        re-resolve the batch without the columns (robustness/
        resilient.py); array backends ignore it."""
        raise NotImplementedError(
            f"{type(self).__name__} does not support staged dispatch"
        )

    # endregion


def _apply_replication(
    peers: Iterable[uuid_mod.UUID],
    sender: uuid_mod.UUID,
    replication: Replication,
) -> list[uuid_mod.UUID]:
    if replication == Replication.EXCEPT_SELF:
        return [p for p in peers if p != sender]
    if replication == Replication.ONLY_SELF:
        return [p for p in peers if p == sender]
    return list(peers)
