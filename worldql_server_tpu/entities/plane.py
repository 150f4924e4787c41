"""EntityPlane: the device-resident moving-object workload.

One plane owns every live entity as a slot in preallocated host SoA
columns (``pos f32[cap,3] | vel f32[cap,3] | wid i32 | pid i32``) plus
their device twin, an :class:`~worldql_server_tpu.ops.tick.EntityState`.
The host columns are the authority (the same discipline as
spatial/tpu_backend.py): wire ingest mutates them at message-arrival
time, each ticker flush runs ONE jitted ``simulation_tick`` (integrate
→ re-quantize → spatial-hash rebuild → stencil kNN, ops/tick.py), and
the collect fetches back integrated positions + per-entity neighbor
targets.

Columnar ingest (PR 11): updates of LIVE entities stage into fixed
preallocated double-buffered columns (``pos/vel/has_vel/touched`` per
slot) instead of writing per-entity — coalescing IS the column
overwrite (last write per slot wins, per field), and the pre-dispatch
drain is a buffer flip + one vectorized masked fold into the authority
columns. The wire fast path (``ingest_columns``, fed by
protocol/entity_wire.wql_decode_entities through entities/ingest.py)
maps a whole recv batch's uuid keys to slots in one C-level pass and
stages every owned row without constructing a single Entity object;
registrations, removals, and exotic messages keep the object path
(``ingest``) — identical semantics, per-entity cost, control-plane
rates. The device twin is maintained INCREMENTALLY: a dirty-slot
bitmap tracks rows whose host authority diverged from the twin
(client updates, registrations, removals), and each dispatch scatters
only those rows into device memory (ASH-style partial transfer,
arXiv:2110.00511) instead of re-shipping whole columns — the scatter
kernel registers with the retrace GUARD under ``entities.scatter`` and
its pow2 dirty-bucket ladder precompiles at boot.

Capacity is a power-of-two tier (``_MIN_CAP`` floor), so the jitted
tick sees a handful of shapes over a process lifetime — the tick
kernel registers with the retrace GUARD under ``entities.sim_tick``
and the e2e suite holds the steady-state budget.

Index coupling (the bounded-staleness contract): every entity also
owns ONE subscription row in the authoritative spatial index — its
owner peer subscribed at the entity's current cube — refcounted per
``(world, cube, peer)`` so co-located entities of one peer share a
row. Registration inserts the row IMMEDIATELY (a new entity is
queryable before its first tick); position churn flows through the
index's base+delta path (``bulk_move_subscriptions``) when the tick's
integrated position crosses a cube boundary. Subscription queries
therefore observe an entity's position with staleness bounded by ONE
applied tick: the cube registered in the index is the quantization of
the position the LAST applied tick integrated (plus any not-yet-ticked
wire update, which re-quantizes at the next apply). Entity state and
index can never diverge structurally — both are derived from the same
host columns, and the index mutation happens in the same event-loop
turn as the position writeback.

What a DELTA tick reads (``delta_ticks``): its host legs follow the
rows its dirty window names, not the capacity tier. The plane keeps
beside its columns what those legs would otherwise recompute every
tick — ``_key`` (the spatial key of every slot's registered cube,
written wherever ``_cube`` is), a sorted view of it (valid until a key
is written), and ``_n_moving`` (live slots with a velocity, kept at
every write of ``_vel``) — so ``dispatch_tick`` finds the dirty-cube
closure by binary search and scans the velocity column only while
somebody moves, and ``collect_tick`` hands the f64 quantiser only the
rows that were dirty at dispatch or that the device handed back
changed: every other closure row still holds the position its
registered cube was quantised from. ``apply`` then writes, and names
to the interest manager, the closure rows whose answer (recipients as
a sorted row, count, position bits) is not the one the retained
columns hold: the closure is ~16 rows a dirty cube, the rows whose
answer changed are about the rows that moved. Counters
``sim.quantised_rows``, ``sim.dispatch_scan_rows`` and
``sim.spliced_rows`` say what a tick paid.

Tick-path discipline: ``dispatch_tick``/``collect_tick`` are the
sim-tick hot functions — no per-entity Python, host syncs only at the
designated collect points (tools/check: host-sync-in-sim-tick). Frame
assembly and index churn (``apply``) are host delivery/index work,
O(fan-out) and O(churn) respectively, and run on the event loop like
the router's per-message handling.
"""

from __future__ import annotations

import itertools
import logging
import time
import uuid as uuid_mod
from collections import Counter

import numpy as np

from ..spatial import jaxconf  # must precede the jax import
import jax
import jax.numpy as jnp

from ..ops.tick import EntityState, make_tick_fn
from ..protocol import entity_wire
from ..robustness import failpoints
from ..protocol.types import Entity, Instruction, Message, Vector3
from ..spatial.hashing import spatial_key, spatial_keys
from ..spatial.quantize import cube_coords_batch
from ..utils.names import SanitizeError, sanitize_world_name
from ..utils.retrace import GUARD

logger = logging.getLogger(__name__)

#: Message.parameter marking an entity-removal batch (any other
#: parameter — usually None — upserts the carried entities)
PARAM_REMOVE = "entity.remove"
#: Message.parameter stamped on outbound neighbor frames
PARAM_FRAME = "entity.frame"

#: smallest capacity tier (pow2); arrays never shrink below it
_MIN_CAP = 256
#: parked coordinate for dead slots: quantizes to the saturated cube of
#: the dead world (wid -1), far outside any live neighborhood
_DEAD_POS = np.float32(1.0e30)
#: smallest dirty-row scatter bucket (pow2 ladder floor): below this the
#: fixed launch cost dominates and finer tiers only multiply compiles
_SCATTER_MIN_BUCKET = 64
#: smallest delta-tick sub-batch tier (pow2 ladder floor): the dirty
#: closure pads up to this before the sub-kernel launches, so steady
#: low-churn serving reuses a handful of compiled shapes
_DELTA_MIN_TIER = 64
#: minor dimension a fetched block needs to come back row-major: a
#: TPU's lane row (``canonical_tick_fn``)
_ROW_LANES = 128
#: world-name fallback envelope for wire-path registrations (the world
#: is always resolved before this is consulted)
_WIRE_MSG = Message(instruction=Instruction.LOCAL_MESSAGE)


def _next_pow2(n: int) -> int:
    return 1 << (max(n, 1) - 1).bit_length()


class WireFrame:
    """A pre-encoded outbound frame: ready wire bytes standing in for a
    Message in ``PeerMap.deliver_batch`` pairs (which reads ``.wire``
    and never re-serializes when it is set). The native per-cohort
    frame encode hands these out so the apply leg constructs no
    per-entity Message objects. Message attributes (``entities``,
    ``parameter``, …) resolve lazily by decoding the wire bytes —
    diagnostics-only; the delivery path never triggers it."""

    __slots__ = ("wire", "_msg")

    #: born here, not routed in: no router trace context — and the
    #: delivery path's ``getattr(message, "trace_ctx", None)`` must find
    #: that HERE, not fall through to a decode of the whole frame
    trace_ctx = None

    def __init__(self, wire: bytes):
        self.wire = wire
        self._msg = None

    def __getattr__(self, name):
        msg = object.__getattribute__(self, "_msg")
        if msg is None:
            from ..protocol import deserialize_message

            msg = deserialize_message(self.wire)
            object.__setattr__(self, "_msg", msg)
        return getattr(msg, name)


class _StageBuf:
    """One side of the double-buffered update-staging columns: the LWW
    coalescing surface. ``touched[slot]`` marks a staged position;
    ``has_vel[slot]`` marks a staged velocity (fields coalesce
    independently, exactly like sequential application)."""

    __slots__ = ("pos", "vel", "has_vel", "touched", "dirty")

    def __init__(self, cap: int):
        self.pos = np.zeros((cap, 3), np.float32)
        self.vel = np.zeros((cap, 3), np.float32)
        self.has_vel = np.zeros(cap, bool)
        self.touched = np.zeros(cap, bool)
        self.dirty = False  # any touched bit set since the last flip

    def grow(self, cap: int) -> None:
        old = self.touched.shape[0]
        for name in ("pos", "vel"):
            out = np.zeros((cap, 3), np.float32)
            out[:old] = getattr(self, name)
            setattr(self, name, out)
        for name in ("has_vel", "touched"):
            out = np.zeros(cap, bool)
            out[:old] = getattr(self, name)
            setattr(self, name, out)


def canonical_tick_fn(**static):
    """The plane's tick: ``ops.tick.make_tick_fn(**static)`` with every
    row's recipients SORTED, and laid out for the host. The op hands
    them nearest first (its contract); nothing on the host reads that
    order, both frame legs sort a row themselves, and a neighbour's
    step re-orders rows whose SET stood. Sorted on the device (one
    ``[tier, k]`` int32 row sort where the device idles), in full and
    delta ticks alike, the retained columns are canonical everywhere,
    in the form of the interest manager's snapshot (-1 first), and the
    delta splice's compare is of sets, in one pass.

    The sorted block comes back as ``[tier * k / 128, 128]`` (flat
    where that does not divide): the same bytes, and
    ``collect_tick``'s reshape to ``[tier, k]`` is then ROW-major. A
    TPU hands an ``[N, K]`` block with K < 128 back column-major, and
    on the chip hosts a compare of 18,600 such rows with row-major
    ones costs 1.82 ms where two row-major blocks cost 0.11."""
    tick = make_tick_fn(**static)

    def canonical_tick(state: EntityState):
        new_state, targets, counts = tick(state)
        flat = jnp.sort(targets, axis=1).reshape(-1)
        if flat.size % _ROW_LANES == 0:
            flat = flat.reshape(-1, _ROW_LANES)
        return new_state, flat, counts

    return canonical_tick


def _scatter_update(state: EntityState, idx, pos, vel, wid, pid):
    """Scatter dirty host rows into the device twin — the incremental
    H2D leg (only touched slots ship, never whole columns). ``idx`` is
    padded to its pow2 bucket with the out-of-range capacity value;
    ``mode='drop'`` discards those lanes on device."""
    return EntityState(
        position=state.position.at[idx].set(pos, mode="drop"),
        velocity=state.velocity.at[idx].set(vel, mode="drop"),
        world=state.world.at[idx].set(wid, mode="drop"),
        peer=state.peer.at[idx].set(pid, mode="drop"),
    )


class EntityPlane:
    """Device-resident entity population + its authoritative-index
    coupling for one server. Event-loop owned except where noted."""

    def __init__(
        self,
        backend,
        peer_map,
        *,
        cube_size: int,
        k: int = 8,
        dt: float = 0.05,
        bounds: float = 1000.0,
        max_entities: int = 1 << 16,
        metrics=None,
        tracer=None,
        governor=None,
        wire="auto",
        delta_ticks: str = "off",
        delta_rebuild_threshold: float = 0.5,
    ):
        self.backend = backend
        self.peer_map = peer_map
        self.cube_size = cube_size
        self.k = int(k)
        self.dt = float(dt)
        self.bounds = float(bounds)
        self.max_entities = int(max_entities)
        self.metrics = metrics
        self.tracer = tracer
        # Optional robustness.overload.OverloadGovernor: under
        # SHED_LOW+ updates of LIVE entities coalesce last-write-wins
        # per slot into the staging columns and apply once per tick —
        # lossless for position streams (the newest value per field
        # subsumes the ones it overwrote). Registrations and removals
        # always apply immediately (control plane).
        self._governor = governor
        self.coalesced = 0
        self.frames_skipped = 0

        # host SoA columns (authority; slot-indexed, pow2 capacity)
        self._cap = _MIN_CAP
        self._pos = np.full((self._cap, 3), _DEAD_POS, np.float32)
        self._vel = np.zeros((self._cap, 3), np.float32)
        self._wid = np.full(self._cap, -1, np.int32)
        self._pid = np.full(self._cap, -1, np.int32)
        #: cube currently registered in the authoritative index
        self._cube = np.zeros((self._cap, 3), np.int64)
        #: ``spatial_keys(_wid, _cube, 0)`` of every slot, written
        #: wherever ``_cube`` is: the delta closure tests THIS column
        #: instead of hashing the tier every tick (a dead slot keeps
        #: its last key; ``_live`` masks it)
        self._key = np.zeros(self._cap, np.int64)
        #: the live slots in key order, ``(keys, slots)``: while it
        #: stands the closure is two binary searches a dirty key. A
        #: write of ``_key`` or ``_live`` (alloc, release, churn) drops
        #: it; it is sorted again only by a delta dispatch that finds
        #: no such write since the LAST dispatch (keys that change
        #: every tick are tested with ``np.isin``, never sorted)
        self._key_view: tuple[np.ndarray, np.ndarray] | None = None
        self._key_written = False
        self._live = np.zeros(self._cap, bool)
        #: live slots whose velocity is not zero (by ``_is_moving``):
        #: kept at every write of ``_vel``, so a still world's delta
        #: dispatch knows without a scan that nothing integrates
        self._n_moving = 0
        #: slots mutated by wire ingest since the LAST dispatch — the
        #: post-tick position writeback must not clobber them
        self._touched = np.zeros(self._cap, bool)
        #: binary uuid per slot (frame encode + wire-path slot map)
        self._uuid_bytes = np.zeros((self._cap, 16), np.uint8)
        #: double-buffered update-staging columns: ingest writes the
        #: active side; the pre-dispatch drain flips and folds the
        #: retired side in one vectorized pass (replaces the per-uuid
        #: _pending dict of PR 10)
        self._stage = [_StageBuf(self._cap), _StageBuf(self._cap)]
        self._stage_active = 0
        #: slots whose host authority diverged from the device twin
        #: since its last upload — the incremental-H2D scatter set
        self._device_dirty = np.zeros(self._cap, bool)
        self._dev_state: EntityState | None = None
        self._dev_cap = 0

        # Delta sim ticks (ROADMAP 2): instead of re-running the full
        # integrate→sort→kNN kernel over every slot each tick, gather
        # the DIRTY-CUBE CLOSURE — all live entities in any cube a
        # dirty entity occupies now or can reach this tick — into a
        # pow2 sub-batch, run the SAME tick kernel at that (smaller)
        # tier, and splice the results over the retained last-tick
        # arrays; clean entities replay. Requires a pow2 cube size:
        # the host-side reach prediction replays the device's f32
        # integration bit-for-bit and quantizes with the golden host
        # quantizer, whose agreement with the device quantizer is
        # pinned EXACT for pow2 sizes (tests/test_quantizer_envelope).
        pow2_cube = cube_size == _next_pow2(cube_size)
        self._delta_ticks = delta_ticks in ("on", "auto") and pow2_cube
        if delta_ticks == "on" and not pow2_cube:
            logger.warning(
                "delta_ticks='on' needs a power-of-two cube size for "
                "the exact quantizer envelope (got %d) — running full "
                "recompute ticks", cube_size,
            )
        self.delta_rebuild_threshold = float(delta_rebuild_threshold)
        #: slots mutated since the last SUCCESSFUL dispatch (the delta
        #: dirty stream; _device_dirty can't serve — it clears on H2D)
        self._window_dirty = np.zeros(self._cap, bool)
        #: (wid, cx, cy, cz) cubes vacated by removals this window —
        #: the slot's wid/cube columns are wiped at release time
        self._window_dirty_cubes: list[tuple] = []
        #: retained last applied tick (the replay source)
        self._have_last = False
        self._last_cap = 0
        self._last_targets: np.ndarray | None = None
        self._last_counts: np.ndarray | None = None
        self._last_pos: np.ndarray | None = None
        #: what this plane owes the interest manager's next
        #: ``build_pairs``: the rows of the columns it reads that
        #: differ from what its LAST call read, as ``(spliced, roster)``
        #: — the closure rows every delta tick applied since found
        #: different from the retained columns and wrote, and the
        #: slots allocated or released since (``live``, uuid, world).
        #: None: the plane cannot name them (no call yet, a full tick,
        #: a shed streak that owes more than a tier) and the manager
        #: scans every row. Without a manager it stays None.
        self._owed: tuple[list, list] | None = None
        self.delta_sim_ticks = 0
        self.full_sim_ticks = 0
        self.delta_reused = 0
        self.delta_recomputed = 0
        self.delta_fallbacks = 0
        self.delta_mispredicts = 0
        self.last_delta_stats: dict = {}
        #: rows ``collect_tick`` handed the f64 quantiser, rows the
        #: delta dispatch read in passes as long as the capacity tier
        #: (the velocity scan, the closure's key test), and closure
        #: rows a delta splice found changed, wrote and named (beside
        #: ``delta_recomputed``, the closure rows it compared)
        self.quantised_rows = 0
        self.dispatch_scan_rows = 0
        self.spliced_rows = 0

        self._n = 0                     # slot high-water mark
        self._free: list[int] = []      # recycled slots below _n
        self._slot_of: dict[uuid_mod.UUID, int] = {}
        #: 16-byte uuid key → slot (the wire path's C-level bulk map)
        self._slot_of_key: dict[bytes, int] = {}
        self._uuid_of: dict[int, uuid_mod.UUID] = {}

        # interning (plane-local dense ids; the INDEX interns its own)
        self._world_ids: dict[str, int] = {}
        self._world_names: list[str] = []
        self._peer_ids: dict[uuid_mod.UUID, int] = {}
        self._peer_uuids: list[uuid_mod.UUID] = []
        #: binary uuid per dense peer id (cohort frame senders)
        self._peer_key_arr = np.zeros((64, 16), np.uint8)
        #: per-peer entity slots (eviction sweep)
        self._peer_slots: dict[int, set[int]] = {}

        # native columnar wire codec: "auto" = the shared in-tree
        # library (symbol-probed; stale .so → None and every leg
        # degrades to the object path), None/instance for tests
        self._wire = entity_wire.shared() if wire == "auto" else wire

        #: interest manager (``--interest on``): when set, apply()
        #: routes the frame leg through per-recipient delta frames
        #: instead of _build_frames. None (the default) keeps the
        #: legacy broadcast path byte for byte — the manager is never
        #: consulted, constructed, or imported on that path.
        self.interest = None

        #: (wid, cx, cy, cz, pid) → live-entity refcount backing ONE
        #: index row; transitions through 0 mutate the index
        self._sub_refs: Counter = Counter()

        # one jitted tick fn; shape (= capacity tier) keys its compile
        # cache, which the retrace GUARD audits under entities.sim_tick.
        # The neighbor resolve is the fused Pallas kernel on a TPU and
        # the XLA stencil elsewhere — read HERE from the one rule the
        # ops apply (jaxconf.on_tpu), so that the choice can be logged
        # at the first tick and read from the entity_sim gauge.
        # What the plane's wrapper adds to the op: canonical_tick_fn.
        self.pallas = jaxconf.on_tpu()
        self._tick_fn = jax.jit(canonical_tick_fn(
            cube_size=cube_size, k=self.k, dt=self.dt,
            bounds=self.bounds, pallas=self.pallas,
        ))
        GUARD.register("entities.sim_tick", self._tick_fn)
        # incremental H2D: one jitted scatter, shape-keyed on
        # (capacity tier, dirty bucket) — the ladder precompiles at boot
        self._scatter_fn = jax.jit(_scatter_update)
        GUARD.register("entities.scatter", self._scatter_fn)
        self._tick_inflight = False

        # stats (exposed via the entity_sim gauge + bench config 8)
        self.entities_registered = 0
        self.entities_removed = 0
        self.updates = 0
        self.rejected = 0
        self.dispatches = 0
        self.applied_ticks = 0
        self.dropped_ticks = 0
        self.frames = 0
        self.index_moves = 0
        self.last_integrate_ms = 0.0
        self.last_knn_ms = 0.0
        self.last_apply_ms = 0.0
        self.last_churn = 0
        # columnar-path stats (wire rows staged with zero per-entity
        # Python; flips; H2D split; native cohort-encoded frames)
        self.wire_rows = 0
        self.wire_slow_rows = 0
        self.column_flips = 0
        self.h2d_full = 0
        self.h2d_scatter = 0
        self.scatter_fallbacks = 0  # scatter errors → full upload
        self.last_h2d_rows = 0
        self.frames_native = 0
        # Frame-level reuse (ISSUE 14 satellite, the PR 13 leftover):
        # a cohort whose membership AND member positions did not
        # change since last tick replays last tick's encoded wire
        # bytes instead of re-running wql_encode_entity_frames —
        # keyed by the cohort key, guarded by exact row/position
        # byte equality, invalidated wholesale by any slot identity
        # change (registration/removal clears it: uuid/pid bytes at a
        # reused slot would otherwise alias a stale frame).
        self._frame_cache: dict[bytes, tuple] = {}
        self.frames_reused = 0

    # region: wire ingest (router arrival path)

    @property
    def entity_count(self) -> int:
        return len(self._slot_of)

    def active(self) -> bool:
        return bool(self._slot_of)

    def ingest(self, message: Message) -> int:
        """Apply one inbound entity batch THE OBJECT WAY: upsert every
        carried Entity (or remove, when ``parameter ==
        'entity.remove'``) for the sending peer. This is the semantic
        reference and the fallback for everything the columnar wire
        path (``ingest_columns``) routes around — removals, exotic
        parameters/uuid formats, per-entity worlds, a stale native
        library. Returns entities applied."""
        sender = message.sender_uuid
        removing = message.parameter == PARAM_REMOVE
        governor = self._governor
        coalesce = (
            not removing
            and governor is not None
            and governor.coalesce_entities()
        )
        applied = 0
        for ent in message.entities:  # wql: allow(per-entity-python-ingest) — the object-path semantic reference; hot traffic rides ingest_columns
            try:
                if removing:
                    applied += self._remove_entity(ent.uuid, sender)
                elif coalesce and ent.uuid in self._slot_of:
                    applied += self._stage_update(ent, message, sender)
                else:
                    applied += self._upsert(ent, message, sender)
            except SanitizeError as exc:
                logger.warning(
                    "peer %s sent entity with invalid world %r (%s)",
                    sender, ent.world_name or message.world_name, exc,
                )
        if applied and self.metrics is not None:
            self.metrics.inc("sim.updates", applied)
        self.updates += applied
        return applied

    def _stage_update(self, ent: Entity, message: Message,
                      sender: uuid_mod.UUID) -> int:
        """Coalescing admission (governor SHED_LOW+), object-path leg:
        stage the update of a LIVE entity into the columnar staging
        buffer — coalescing IS the column overwrite (last write per
        slot wins, per field); ``_drain_pending`` folds the survivors
        in one vectorized pass at the next dispatch. Ownership and
        world sanitation are enforced HERE so a hostile update can't
        hide in the staging columns. An overwrite counts as
        ``overload.coalesced`` — shed-but-lossless work (the audit
        invariant: offered == applied + coalesced + dropped)."""
        sanitize_world_name(ent.world_name or message.world_name)
        slot = self._slot_of[ent.uuid]
        owner = self._peer_uuids[self._pid[slot]]
        if owner != sender:
            logger.warning(
                "peer %s sent update for entity %s owned by %s — "
                "dropped", sender, ent.uuid, owner,
            )
            return 0
        buf = self._stage[self._stage_active]
        first = not buf.touched[slot]
        p = ent.position
        buf.pos[slot, 0] = p.x
        buf.pos[slot, 1] = p.y
        buf.pos[slot, 2] = p.z
        vel = _decode_velocity(ent.flex)
        if vel is not None:
            buf.vel[slot] = vel
            buf.has_vel[slot] = True
        buf.touched[slot] = True
        buf.dirty = True
        if first:
            return 1
        self.coalesced += 1
        if self.metrics is not None:
            self.metrics.inc("overload.coalesced")
        return 0

    def _drain_pending(self) -> None:
        """Fold the staged update columns into the host authority —
        the buffer flip that replaced PR 10's per-uuid dict walk: flip
        the double buffer (ingest keeps writing the fresh side), then
        apply the retired side's touched rows as one masked copy per
        column. The coalescing staleness bound is the same one tick
        the plane already documents."""
        buf = self._stage[self._stage_active]
        if not buf.dirty:
            return
        self._stage_active ^= 1
        rows = np.flatnonzero(buf.touched)
        self._pos[rows] = buf.pos[rows]
        hv = rows[buf.has_vel[rows]]
        if hv.size:
            vel = buf.vel[hv]
            self._n_moving += int(
                np.count_nonzero(_is_moving(vel))
            ) - int(np.count_nonzero(_is_moving(self._vel[hv])))
            self._vel[hv] = vel
        # a client update must win over the in-flight tick's writeback,
        # and its rows must ship to the device twin at this dispatch
        self._touched[rows] = True
        self._device_dirty[rows] = True
        self._window_dirty[rows] = True
        buf.touched[rows] = False
        buf.has_vel[rows] = False
        buf.dirty = False
        self.column_flips += 1

    def staged_count(self) -> int:
        """Touched rows awaiting the next flip (test/gauge probe)."""
        return int(np.count_nonzero(self._stage[self._stage_active].touched))

    def is_staged(self, eid: uuid_mod.UUID) -> bool:
        slot = self._slot_of.get(eid)
        if slot is None:
            return False
        return bool(self._stage[self._stage_active].touched[slot])

    def ingest_columns(
        self,
        senders: list,
        worlds: list,
        counts: np.ndarray,
        uuid_keys: np.ndarray,
        pos: np.ndarray,
        vel: np.ndarray,
        has_vel: np.ndarray,
    ) -> int:
        """Wire→SoA fast path: stage a whole recv batch's entity
        updates with zero per-entity Python. ``senders``/``worlds`` are
        per message; ``counts[i]`` rows of the shared columns belong to
        message i. uuid→slot mapping is one C-level bulk dict pass;
        ownership is enforced vectorized at stage time; position/
        velocity staging is a fancy-indexed column overwrite whose
        last-write-wins order is exactly arrival order. Only rows whose
        uuid is unknown (registrations — control-plane rates) take the
        per-entity object path. Returns entities applied, mirroring
        ``ingest``'s accounting."""
        n_bufs = len(senders)
        total = int(counts.sum())
        if total == 0:
            return 0
        pids = np.empty(n_bufs, np.int32)
        buf_ok = np.ones(n_bufs, bool)
        for b in range(n_bufs):
            try:
                worlds[b] = sanitize_world_name(worlds[b])
                pids[b] = self._peer_ids.get(senders[b], -1)
            except SanitizeError as exc:
                logger.warning(
                    "peer %s sent entity batch with invalid world %r "
                    "(%s)", senders[b], worlds[b], exc,
                )
                buf_ok[b] = False
                pids[b] = -1
        row_buf = np.repeat(np.arange(n_bufs), counts)
        row_ok = buf_ok[row_buf]
        exp_pid = pids[row_buf]

        # V16 (not S16): bytes_ views strip trailing NULs, void keeps
        # all 16 bytes — the keys must match uuid.bytes exactly
        keys = uuid_keys.reshape(total, 16).view("V16").ravel().tolist()
        slots = np.fromiter(
            map(self._slot_of_key.get, keys, itertools.repeat(-1)),
            np.int64, count=total,
        )
        hit = (slots >= 0) & row_ok
        safe = np.where(hit, slots, 0)
        owned = hit & (self._pid[safe] == exp_pid)
        stolen = int(hit.sum()) - int(owned.sum())
        if stolen:
            logger.warning(
                "%d entity updates for entities their senders do not "
                "own — dropped", stolen,
            )

        applied = 0
        orows = np.flatnonzero(owned)
        if orows.size:
            s = slots[orows]
            buf = self._stage[self._stage_active]
            governor = self._governor
            if governor is not None and governor.coalesce_entities():
                # dict-parity accounting: first stage per slot applies,
                # every overwrite (intra-batch duplicates included)
                # counts as coalesced — shed-but-lossless
                uniq = np.unique(s)
                fresh = int(np.count_nonzero(~buf.touched[uniq]))
                over = int(orows.size) - fresh
                if over:
                    self.coalesced += over
                    if self.metrics is not None:
                        self.metrics.inc("overload.coalesced", over)
                applied += fresh
            else:
                applied += int(orows.size)
            buf.pos[s] = pos[orows]
            hv = has_vel[orows].astype(bool)
            if hv.any():
                sv = s[hv]
                buf.vel[sv] = vel[orows][hv]
                buf.has_vel[sv] = True
            buf.touched[s] = True
            buf.dirty = True
            self.wire_rows += int(orows.size)

        # unknown uuids: registrations (or intra-batch updates of one
        # just registered) — the per-entity object path is the right
        # cost for this control-plane traffic, and re-probing the slot
        # map per row keeps intra-batch arrival order exact
        miss = row_ok & (slots < 0)
        for r in np.flatnonzero(miss).tolist():  # wql: allow(per-entity-python-ingest) — registrations only; update traffic stays columnar
            b = int(row_buf[r])
            applied += self._wire_slow_row(
                keys[r], worlds[b], pos[r], vel[r], bool(has_vel[r]),
                senders[b],
            )
            self.wire_slow_rows += 1

        if applied:
            self.updates += applied
            if self.metrics is not None:
                self.metrics.inc("sim.updates", applied)
        return applied

    def _wire_slow_row(self, key: bytes, world: str, p, v,
                       has_v: bool, sender: uuid_mod.UUID) -> int:
        """One columnar row routed through the object path (its uuid
        was unknown at batch start): registration — or, for a uuid
        registered earlier in the same batch, a normal owned update."""
        ent = Entity(
            uuid=uuid_mod.UUID(bytes=key),
            position=Vector3(float(p[0]), float(p[1]), float(p[2])),
            world_name=world,
            flex=v.tobytes() if has_v else None,
        )
        try:
            return self._upsert(ent, _WIRE_MSG, sender)
        except SanitizeError:
            return 0  # world sanitized upstream; belt and braces

    def _upsert(self, ent: Entity, message: Message,
                sender: uuid_mod.UUID) -> int:
        world = sanitize_world_name(ent.world_name or message.world_name)
        slot = self._slot_of.get(ent.uuid)
        new = slot is None
        if new:
            if len(self._slot_of) >= self.max_entities:
                self.rejected += 1
                if self.metrics is not None:
                    self.metrics.inc("sim.rejected")
                logger.warning(
                    "entity registration rejected: plane full "
                    "(%d >= max_entities %d)",
                    len(self._slot_of), self.max_entities,
                )
                return 0
            slot = self._alloc_slot(ent.uuid, sender, world)
            self.entities_registered += 1
        else:
            owner = self._peer_uuids[self._pid[slot]]
            if owner != sender:
                # an entity belongs to the peer that registered it;
                # a hijacking update is dropped, not transferred
                logger.warning(
                    "peer %s sent update for entity %s owned by %s — "
                    "dropped", sender, ent.uuid, owner,
                )
                return 0
        p = ent.position
        self._pos[slot, 0] = p.x
        self._pos[slot, 1] = p.y
        self._pos[slot, 2] = p.z
        vel = _decode_velocity(ent.flex)
        if vel is not None:
            self._n_moving += int(_is_moving(vel)) - int(
                _is_moving(self._vel[slot])
            )
            self._vel[slot] = vel
        self._touched[slot] = True
        self._device_dirty[slot] = True
        self._window_dirty[slot] = True
        if new:
            # index coupling: queryable before the first tick
            self._register_cube(slot)
        return 1

    def _alloc_slot(self, uuid: uuid_mod.UUID, sender: uuid_mod.UUID,
                    world: str) -> int:
        if self._free:
            slot = self._free.pop()
        else:
            if self._n == self._cap:
                self._grow(self._cap * 2)
            slot = self._n
            self._n += 1
        wid = self._world_ids.get(world)
        if wid is None:
            wid = self._world_ids[world] = len(self._world_names)
            self._world_names.append(world)
        pid = self._peer_ids.get(sender)
        if pid is None:
            pid = self._peer_ids[sender] = len(self._peer_uuids)
            self._peer_uuids.append(sender)
            if pid >= self._peer_key_arr.shape[0]:
                out = np.zeros(
                    (self._peer_key_arr.shape[0] * 2, 16), np.uint8
                )
                out[: self._peer_key_arr.shape[0]] = self._peer_key_arr
                self._peer_key_arr = out
            self._peer_key_arr[pid] = np.frombuffer(sender.bytes, np.uint8)
        self._slot_of[uuid] = slot
        self._slot_of_key[uuid.bytes] = slot
        self._uuid_of[slot] = uuid
        self._uuid_bytes[slot] = np.frombuffer(uuid.bytes, np.uint8)
        self._wid[slot] = wid
        self._pid[slot] = pid
        self._vel[slot] = 0.0
        self._live[slot] = True
        if self._owed is not None:
            self._owed[1].append(slot)
        # slot identity changed: cached frames keyed on row indices
        # could alias the new occupant — drop them all
        self._frame_cache.clear()
        self._peer_slots.setdefault(pid, set()).add(slot)
        # index coupling: a fresh entity is queryable IMMEDIATELY —
        # its row enters the index's delta path in this same turn.
        # The cube registers from the wire position below via the
        # same refcount transition churn uses.
        self._cube[slot] = 0  # filled by _register_cube after pos write
        return slot

    def _register_cube(self, slot: int) -> None:
        """Refcount-in the slot's CURRENT position cube (registration
        path; churn uses the vectorized transition in apply())."""
        cube = cube_coords_batch(
            self._pos[slot].astype(np.float64), self.cube_size
        )
        self._cube[slot] = cube
        self._key[slot] = spatial_key(self._wid[slot], cube)
        self._keys_changed()
        self._ref_add(
            int(self._wid[slot]), cube, int(self._pid[slot]),
        )

    def _keys_changed(self) -> None:
        """A slot's key or liveness was written: the sorted view is
        stale, and the next delta dispatch tests the column itself."""
        self._key_view = None
        self._key_written = True

    def _ref_key(self, wid: int, cube, pid: int) -> tuple:
        return (wid, int(cube[0]), int(cube[1]), int(cube[2]), pid)

    def _ref_add(self, wid: int, cube, pid: int) -> None:
        key = self._ref_key(wid, cube, pid)
        self._sub_refs[key] += 1
        if self._sub_refs[key] == 1:
            self.backend.add_subscription(
                self._world_names[wid], self._peer_uuids[pid],
                (int(cube[0]), int(cube[1]), int(cube[2])),
            )

    def _ref_drop(self, wid: int, cube, pid: int) -> None:
        key = self._ref_key(wid, cube, pid)
        self._sub_refs[key] -= 1
        if self._sub_refs[key] <= 0:
            del self._sub_refs[key]
            self.backend.remove_subscription(
                self._world_names[wid], self._peer_uuids[pid],
                (int(cube[0]), int(cube[1]), int(cube[2])),
            )

    def _remove_entity(self, uuid: uuid_mod.UUID,
                       sender: uuid_mod.UUID | None) -> int:
        slot = self._slot_of.get(uuid)
        if slot is None:
            return 0
        pid = int(self._pid[slot])
        if sender is not None and self._peer_uuids[pid] != sender:
            logger.warning(
                "peer %s sent remove for entity %s it does not own — "
                "dropped", sender, uuid,
            )
            return 0
        self._ref_drop(int(self._wid[slot]), self._cube[slot], pid)
        self._release_slot(slot, pid)
        return 1

    def _release_slot(self, slot: int, pid: int) -> None:
        if self._delta_ticks:
            # the vacated cube must dirty (its remaining residents'
            # neighborhoods change) and the slot's retained results
            # must blank — wid/cube wipe below loses both otherwise
            self._window_dirty_cubes.append((
                int(self._wid[slot]), int(self._cube[slot, 0]),
                int(self._cube[slot, 1]), int(self._cube[slot, 2]),
            ))
            self._window_dirty[slot] = False  # dead slots never compute
            if self._have_last:
                self._last_targets[slot] = -1
                self._last_counts[slot] = 0
        if self._owed is not None:
            self._owed[1].append(slot)
        uuid = self._uuid_of.pop(slot)
        del self._slot_of[uuid]
        self._slot_of_key.pop(uuid.bytes, None)
        # a staged update must not resurrect a removed entity at the
        # flip: clear the slot's staging bits on both buffer sides
        for buf in self._stage:
            buf.touched[slot] = False
            buf.has_vel[slot] = False
        slots = self._peer_slots.get(pid)
        if slots is not None:
            slots.discard(slot)
            if not slots:
                del self._peer_slots[pid]
        self._live[slot] = False
        self._keys_changed()
        self._touched[slot] = False
        self._wid[slot] = -1
        self._pid[slot] = -1
        self._pos[slot] = _DEAD_POS
        self._n_moving -= int(_is_moving(self._vel[slot]))
        self._vel[slot] = 0.0
        self._uuid_bytes[slot] = 0
        # the parked values must reach the device twin
        self._device_dirty[slot] = True
        self._free.append(slot)
        # slot identity changed (see _alloc_slot): cached frames over
        # this row are stale the moment the slot is reusable
        self._frame_cache.clear()
        self.entities_removed += 1

    def on_peer_removed(self, peer: uuid_mod.UUID) -> int:
        """Disconnect sweep: drop every entity the peer owned. The
        server purges the peer's index rows wholesale via
        ``backend.remove_peer`` BEFORE this hook runs, so only the
        plane-side bookkeeping (slots + refcounts) is released here."""
        pid = self._peer_ids.get(peer)
        if self.interest is not None:
            self.interest.forget_peer(peer)
        if pid is None:
            return 0
        removed = 0
        for slot in list(self._peer_slots.get(pid, ())):
            key = self._ref_key(
                int(self._wid[slot]), self._cube[slot], pid
            )
            self._sub_refs.pop(key, None)  # index row already purged
            self._release_slot(slot, pid)
            removed += 1
        return removed

    # region: world migration (live resharding)

    def export_world(self, world: str) -> list[dict]:
        """Snapshot every live entity of ``world`` as JSON-safe rows —
        the entity leg of a migration capsule. Ownership rides along
        (``owner`` hex): the new shard must enforce the same
        owner-only update rule the old one did."""
        wid = self._world_ids.get(world)
        if wid is None:
            return []
        rows = []
        for slot in np.flatnonzero(self._live & (self._wid == wid)):
            slot = int(slot)
            rows.append({
                "uuid": self._uuid_of[slot].hex,
                "owner": self._peer_uuids[int(self._pid[slot])].hex,
                "pos": [float(v) for v in self._pos[slot]],
                "vel": [float(v) for v in self._vel[slot]],
            })
        return rows

    def import_world(self, world: str, rows: list[dict]) -> int:
        """Replay exported entity rows into THIS plane through the
        normal registration path (``_upsert``), so index coupling,
        refcounts, and device-dirty tracking all engage exactly as a
        live registration would."""
        applied = 0
        for row in rows:
            try:
                ent = Entity(
                    uuid=uuid_mod.UUID(hex=row["uuid"]),
                    position=Vector3(*(float(v) for v in row["pos"])),
                    world_name=world,
                    flex=np.asarray(
                        row.get("vel") or (0.0, 0.0, 0.0), np.float32
                    ).tobytes(),
                )
                owner = uuid_mod.UUID(hex=row["owner"])
            except (KeyError, TypeError, ValueError):
                continue
            applied += self._upsert(ent, _WIRE_MSG, owner)
        return applied

    def remove_world(self, world: str) -> int:
        """Tombstone leg: drop every entity of ``world`` through the
        normal removal path (refcount transition included, so the
        backend index rows leave with the slots)."""
        wid = self._world_ids.get(world)
        if wid is None:
            return 0
        removed = 0
        for slot in np.flatnonzero(self._live & (self._wid == wid)):
            slot = int(slot)
            pid = int(self._pid[slot])
            self._ref_drop(wid, self._cube[slot], pid)
            self._release_slot(slot, pid)
            removed += 1
        return removed

    # endregion

    def _grow(self, cap: int) -> None:
        """Double the capacity tier (pow2): reallocate every column,
        preserving slots. The next dispatch compiles the new tier —
        visible in device.retraces as a tier first hit, exactly like
        the query engine's capacity ladder."""
        def grow2(arr, fill, dtype, width=None):
            shape = (cap,) if width is None else (cap, width)
            out = np.full(shape, fill, dtype)
            out[: self._cap] = arr
            return out

        self._pos = grow2(self._pos, _DEAD_POS, np.float32, 3)
        self._vel = grow2(self._vel, 0.0, np.float32, 3)
        self._wid = grow2(self._wid, -1, np.int32)
        self._pid = grow2(self._pid, -1, np.int32)
        self._cube = grow2(self._cube, 0, np.int64, 3)
        self._key = grow2(self._key, 0, np.int64)
        self._live = grow2(self._live, False, bool)
        self._touched = grow2(self._touched, False, bool)
        self._uuid_bytes = grow2(self._uuid_bytes, 0, np.uint8, 16)
        self._device_dirty = grow2(self._device_dirty, False, bool)
        self._window_dirty = grow2(self._window_dirty, False, bool)
        for buf in self._stage:
            buf.grow(cap)
        # shape change: the next dispatch re-ships the whole tier and
        # the retained last-tick arrays no longer fit — full recompute
        self._dev_state = None
        self._have_last = False
        self._cap = cap
        logger.info("entity plane grew to capacity tier %d", cap)

    # endregion

    # region: sim tick (ticker flush path)

    def _upload_state(self, cap: int) -> EntityState:
        """Device input for this tick: the persistent twin with only
        the DIRTY slots scattered in (incremental H2D), or a full-tier
        upload when there is no valid twin / the tier changed / the
        dirty set is dense enough that one straight re-ship wins."""
        dev = self._dev_state
        if dev is not None and self._dev_cap == cap:
            dirty = np.flatnonzero(self._device_dirty[:cap])
            if dirty.size == 0:
                self.last_h2d_rows = 0
                return dev
            if dirty.size <= cap // 2:
                try:
                    # entities.scatter: the incremental-H2D loss
                    # boundary — a scatter failure (or an armed chaos
                    # fault) degrades to one full-tier upload below,
                    # counted; the dirty bitmap is cleared only AFTER
                    # the scatter succeeds, so no row is ever lost to
                    # a failed partial transfer
                    failpoints.fire("entities.scatter")
                    bucket = max(
                        _SCATTER_MIN_BUCKET, _next_pow2(dirty.size)
                    )
                    # pad lanes carry the out-of-range index `cap`; the
                    # scatter drops them on device (mode='drop')
                    idx = np.full(bucket, cap, np.int32)
                    idx[: dirty.size] = dirty
                    rows = np.zeros((bucket, 3), np.float32)
                    rows_v = np.zeros((bucket, 3), np.float32)
                    rows_w = np.zeros(bucket, np.int32)
                    rows_p = np.zeros(bucket, np.int32)
                    rows[: dirty.size] = self._pos[dirty]
                    rows_v[: dirty.size] = self._vel[dirty]
                    rows_w[: dirty.size] = self._wid[dirty]
                    rows_p[: dirty.size] = self._pid[dirty]
                    out = self._scatter_fn(dev, idx, rows, rows_v,
                                           rows_w, rows_p)
                    self._device_dirty[:cap] = False
                    self.h2d_scatter += 1
                    self.last_h2d_rows = int(dirty.size)
                    return out
                except Exception:
                    self.scatter_fallbacks += 1
                    if self.metrics is not None:
                        self.metrics.inc("sim.scatter_fallbacks")
                    logger.exception(
                        "incremental H2D scatter failed (%d dirty "
                        "rows) — degrading to a full-tier upload",
                        int(dirty.size),
                    )
        self._device_dirty[:cap] = False
        self._dev_cap = cap
        self.h2d_full += 1
        self.last_h2d_rows = cap
        return EntityState(
            position=jnp.asarray(self._pos),
            velocity=jnp.asarray(self._vel),
            world=jnp.asarray(self._wid),
            peer=jnp.asarray(self._pid),
        )

    def dispatch_tick(self):
        """Launch one simulation tick from the host columns (event-loop
        thread; tick.sim.integrate span): fold the staged update
        columns, pick the delta or full path, launch the kernel (when
        any device work is owed), and enqueue the D2H prefetch. A
        delta tick reads the dirty window's rows and the closure they
        name, through the kept key column; the passes it still makes
        over the whole tier (the velocity scan while somebody moves,
        the key test or sort after a key was written) are counted in
        ``sim.dispatch_scan_rows``.
        Returns an opaque handle for ``collect_tick`` or None when idle
        / a previous tick is still in flight (sim ticks never stack:
        the writeback of tick N is input to tick N+1)."""
        self._drain_pending()  # staged updates fold tick-edge
        if not self._slot_of or self._tick_inflight:
            return None
        t0 = time.perf_counter()
        cap = self._cap
        handle = None
        if self._delta_ticks:
            handle = self._dispatch_tick_delta(cap, t0)
        if handle is None:
            # designated fallback: cold replay state, tier change, or
            # churn past the rebuild threshold — one full-tier tick
            # re-establishes the retained state delta ticks splice over
            handle = self._dispatch_tick_full(cap, t0)  # wql: allow(full-rebuild-on-tick)
        # window clearing happens only on a SUCCESSFUL launch: a
        # raising dispatch keeps every mark for the retry, and
        # abort_tick drops _have_last so dirt consumed by a tick that
        # never applied cannot leak a stale replay
        self._touched[:cap] = False
        self._window_dirty[:cap] = False
        self._window_dirty_cubes.clear()
        self._tick_inflight = True
        self.dispatches += 1
        self.last_integrate_ms = (time.perf_counter() - t0) * 1e3
        if self.metrics is not None:
            self.metrics.observe_ms("sim.integrate_ms", self.last_integrate_ms)
            self.metrics.inc("sim.h2d_rows", self.last_h2d_rows)
        return handle

    def _dispatch_tick_full(self, cap: int, t0: float) -> dict:
        """The pre-delta full path: ship dirty slots to the persistent
        twin, run the fused kernel over the WHOLE capacity tier."""
        state = self._upload_state(cap)
        if not self.full_sim_ticks:
            logger.info(
                "entity sim first tick: pallas=%s interpret=%s k=%d "
                "capacity=%d on %s",
                self.pallas, not jaxconf.on_tpu(), self.k, cap,
                jax.devices()[0].device_kind,
            )
        new_state, targets, counts = self._tick_fn(state)
        # device twin for the NEXT tick: integrated positions; the
        # UPLOADED (host-authoritative) velocity — the in-tick bounce
        # reflection is per-tick, exactly as the full re-upload it
        # replaced behaved (apply() writes back positions only)
        self._dev_state = EntityState(
            position=new_state.position,
            velocity=state.velocity,
            world=state.world,
            peer=state.peer,
        )
        for arr in (new_state.position, targets, counts):
            copy_async = getattr(arr, "copy_to_host_async", None)
            if copy_async is not None:
                copy_async()
        self.full_sim_ticks += 1
        return {
            "mode": "full",
            "pos": new_state.position,
            "targets": targets,
            "counts": counts,
            "cap": cap,
            "t0": t0,
        }

    def _note_delta_fallback(self, reason: str) -> None:
        self.delta_fallbacks += 1
        self.last_delta_stats = {
            "reused": 0, "recomputed": 0, "dirty_cubes": 0,
            "fallback": reason,
        }
        if self.metrics is not None:
            self.metrics.inc("delta.sim_fallbacks")

    def _predict_cubes(self, slots: np.ndarray) -> np.ndarray:
        """Post-integration cubes of ``slots``, predicted host-side by
        replaying the device's f32 integrate+reflect bit-for-bit
        (numpy f32 add/mul/compare are the same IEEE ops XLA emits)
        and quantizing with the golden host quantizer — EXACT against
        the device labels for pow2 cube sizes (the plane's delta gate;
        tests/test_quantizer_envelope pins the agreement)."""
        dt = np.float32(self.dt)
        tb = np.float32(2.0 * self.bounds)  # the kernel's weak-f32 2*b
        b = np.float32(self.bounds)
        p = self._pos[slots] + self._vel[slots] * dt
        # ONE reflection a tick, both sides judged before either is
        # applied, as the kernel does (a row beyond three bounds comes
        # back outside the other wall)
        over, under = p > b, p < -b
        p = np.where(over, tb - p, p)
        p = np.where(under, -tb - p, p)
        return cube_coords_batch(p.astype(np.float64), self.cube_size)

    def _dispatch_tick_delta(self, cap: int, t0: float) -> dict | None:
        """Delta path: build the dirty-cube closure and launch the
        tick kernel over ONLY it, at a pow2 sub-tier. Returns None to
        fall back to the full path (cold cache, tier change, or churn
        past ``delta_rebuild_threshold`` — the rebuild threshold).
        Dirty rows are the window's, plus the movers when the kept
        count says there are any; the closure comes from
        ``_closure_rows``. The handle carries what ``collect_tick``
        needs to tell which rows can have a new cube."""
        if not self._have_last or self._last_cap != cap:
            self._note_delta_fallback("cold")
            return None
        live = self._live[:cap]
        n_live = int(np.count_nonzero(live))
        dirty = self._window_dirty[:cap] & live
        if self._n_moving:
            # somebody integrates: the scan that finds them is the one
            # pass over the tier a world with movers still pays
            dirty |= live & _is_moving(self._vel[:cap])
            self._note_scan(cap)
        dirty_slots = np.flatnonzero(dirty)
        if dirty_slots.size == 0 and not self._window_dirty_cubes:
            # the world did not change: zero device work, pure replay
            self.delta_sim_ticks += 1
            self.delta_reused += n_live
            self.last_h2d_rows = 0
            self.last_delta_stats = {
                "reused": n_live, "recomputed": 0, "dirty_cubes": 0,
                "fallback": "",
            }
            return {"mode": "replay", "cap": cap, "t0": t0}
        threshold = self.delta_rebuild_threshold * max(n_live, 1)
        if dirty_slots.size > threshold:
            self._note_delta_fallback("churn")
            return None
        # dirty cubes: every cube a dirty entity occupies now or can
        # reach this tick, plus cubes vacated by removals
        wid_col = self._wid[:cap]
        parts = [self._key[dirty_slots]]
        if dirty_slots.size:
            parts.append(spatial_keys(
                wid_col[dirty_slots], self._predict_cubes(dirty_slots), 0
            ))
        if self._window_dirty_cubes:
            arr = np.asarray(self._window_dirty_cubes, np.int64)  # wql: allow(host-sync-in-sim-tick) — host tuple list, not a device array
            parts.append(spatial_keys(
                arr[:, 0].astype(np.int32), arr[:, 1:], 0
            ))
        dirty_keys = np.unique(np.concatenate(parts))
        # closure: every live entity in a dirty cube (a same-hash
        # collision only ADDS members — conservative, never wrong)
        rows, scanned = self._closure_rows(live, dirty_keys)
        self._note_scan(scanned)
        tier = max(_DELTA_MIN_TIER, _next_pow2(max(int(rows.size), 1)))
        if rows.size > threshold or tier >= cap:
            self._note_delta_fallback("closure")
            return None
        # gather the closure into the sub-tier; pad lanes are parked
        # dead rows (peer -1 → the kernel masks them out of every run)
        pos_sub = np.full((tier, 3), _DEAD_POS, np.float32)
        vel_sub = np.zeros((tier, 3), np.float32)
        wid_sub = np.full(tier, -1, np.int32)
        pid_sub = np.full(tier, -1, np.int32)
        n = int(rows.size)
        pos_sub[:n] = self._pos[rows]
        vel_sub[:n] = self._vel[rows]
        wid_sub[:n] = wid_col[rows]
        pid_sub[:n] = self._pid[rows]
        state = EntityState(
            position=jnp.asarray(pos_sub), velocity=jnp.asarray(vel_sub),
            world=jnp.asarray(wid_sub), peer=jnp.asarray(pid_sub),
        )
        new_state, targets, counts = self._tick_fn(state)
        for arr in (new_state.position, targets, counts):
            copy_async = getattr(arr, "copy_to_host_async", None)
            if copy_async is not None:
                copy_async()
        self.delta_sim_ticks += 1
        self.delta_reused += n_live - n
        self.delta_recomputed += n
        self.last_h2d_rows = n
        self.last_delta_stats = {
            "reused": n_live - n, "recomputed": n,
            "dirty_cubes": int(dirty_keys.size), "fallback": "",
        }
        return {
            "mode": "delta",
            "rows": rows,
            "dirty_keys": dirty_keys,
            # what collect_tick needs to tell the rows that can have a
            # new cube: the positions given (jnp.asarray copied them)
            # and which closure rows were dirty at dispatch
            "pos_in": pos_sub,
            "dirty_in": dirty[rows],
            "pos": new_state.position,
            "targets": targets,
            "counts": counts,
            "cap": cap,
            "tier": tier,
            "t0": t0,
        }

    def _note_scan(self, rows: int) -> None:
        """Count rows a delta dispatch read in a tier-long pass."""
        self.dispatch_scan_rows += rows
        if self.metrics is not None:
            self.metrics.inc("sim.dispatch_scan_rows", rows)

    def _closure_rows(self, live: np.ndarray,
                      dirty_keys: np.ndarray) -> tuple[np.ndarray, int]:
        """The live slots whose kept key is one of ``dirty_keys``
        (sorted, unique, not empty), ascending, and the rows read in
        tier-long passes to find them. Through the sorted view while
        it stands; a dispatch that finds it dropped sorts it again if
        no key was written since the LAST dispatch (``cap`` rows read,
        once), and otherwise tests the kept column with ``np.isin``
        (``cap`` rows read, as every tick did)."""
        cap = live.shape[0]
        written, self._key_written = self._key_written, False
        scanned = 0
        if self._key_view is None:
            if written:
                return np.flatnonzero(
                    live & np.isin(self._key[:cap], dirty_keys)
                ), cap
            slots = np.flatnonzero(live)
            keys = self._key[slots]
            order = np.argsort(keys, kind="stable")
            self._key_view = (keys[order], slots[order])
            scanned = cap
        keys, slots = self._key_view
        lo = np.searchsorted(keys, dirty_keys, "left")
        counts = np.searchsorted(keys, dirty_keys, "right") - lo
        ends = np.cumsum(counts)
        # the runs [lo, lo + counts) laid end to end
        rows = slots[
            np.arange(ends[-1]) + np.repeat(lo - (ends - counts), counts)
        ]
        rows.sort()
        return rows, scanned

    def precompile(self, max_compiles: int = 32) -> dict:
        """Boot-time shape precompilation for the sim kernels (the
        PR 8 tier-precompile discipline extended to the entity plane):
        the tick kernel at every pow2 tier up to the one
        ``max_entities`` reaches — the capacity tiers the plane grows
        through and the delta sub-batch tiers under them are the same
        shapes of the same function — plus the incremental-H2D
        scatter's dirty-bucket ladder at the boot tier and at the top
        tier, where a deployment sized by ``--entity-max`` lives.
        Largest first under the budget: the top tier is the one whose
        compile takes a minute and more on a TPU (80 s at 131,072
        rows), and left to its first tick it holds the event loop —
        /healthz included — that long. The scatter shapes of the tiers
        in between still compile at first use (sub-second each).
        Returns a stats dict in the spatial/precompile.py shape."""
        t0 = time.perf_counter()
        before = GUARD.counts()
        budget = max(1, int(max_compiles))
        compiles = skipped = 0

        def blank(tier: int) -> EntityState:
            zeros3 = jnp.zeros((tier, 3), jnp.float32)
            ids = jnp.full(tier, -1, jnp.int32)
            return EntityState(zeros3, zeros3, ids, ids)

        top = max(_next_pow2(self.max_entities), self._cap)
        tier = top
        while tier >= (_DELTA_MIN_TIER if self._delta_ticks else self._cap):
            if compiles < budget:
                jax.block_until_ready(self._tick_fn(blank(tier)))
                compiles += 1
            else:
                skipped += 1
            tier //= 2
        for cap in sorted({top, self._cap}, reverse=True):
            state = blank(cap)
            bucket = cap // 2   # a denser dirty set re-ships the tier
            while bucket >= _SCATTER_MIN_BUCKET:
                if compiles < budget:
                    state = self._scatter_fn(
                        state, np.full(bucket, cap, np.int32),
                        np.zeros((bucket, 3), np.float32),
                        np.zeros((bucket, 3), np.float32),
                        np.zeros(bucket, np.int32),
                        np.zeros(bucket, np.int32),
                    )
                    compiles += 1
                else:
                    skipped += 1
                bucket //= 2
            jax.block_until_ready(state)
        delta = GUARD.delta(before)
        stats = {
            "dispatches": compiles,
            "skipped_by_budget": skipped,
            "new_variants": sum(delta.values()),
            "families": delta,
            "wall_ms": round((time.perf_counter() - t0) * 1e3, 1),
        }
        logger.info(
            "entity tier precompilation: %d shapes walked, %d new "
            "kernel variants in %.0f ms",
            compiles, stats["new_variants"], stats["wall_ms"],
        )
        return stats

    def collect_tick(self, handle) -> dict:
        """Wait out the device and fetch results (worker thread;
        tick.sim.knn span). The three fetches below are the sim tick's
        designated device→host sync points; everything else stays
        vectorized. Also re-quantizes integrated positions to cubes
        host-side in f64 — the AUTHORITATIVE quantizer, so the index
        coupling follows the golden grid, not the device's f32 twin.
        A full tick quantises the tier. A delta tick quantises the
        closure rows that were dirty at dispatch or came back with
        other position bits than they were given (``quantised``:
        indices into ``rows``; ``cubes`` is aligned with it): a row
        that was not dirty holds the position its registered cube was
        quantised from, and the same position has the same cube.
        ``quantised_rows`` feeds the counter ``sim.quantised_rows``."""
        t0 = time.perf_counter()
        mode = handle.get("mode", "full")
        if mode == "replay":
            # nothing was dispatched: the retained tick IS the result
            return {"mode": "replay", "cap": handle["cap"], "knn_ms": 0.0}
        pos = np.asarray(handle["pos"])  # wql: allow(host-sync-in-sim-tick) — designated collect point
        targets = np.asarray(handle["targets"]).reshape(-1, self.k)  # wql: allow(host-sync-in-sim-tick) — designated collect point
        counts = np.asarray(handle["counts"])  # wql: allow(host-sync-in-sim-tick) — designated collect point
        # with the tracer's CPU clock on, the wait's two legs are read
        # apart: the fetches (device wait + D2H, the GIL released) and
        # what of the re-quantisation, pure compute, its thread was NOT
        # on the CPU for (the GIL the loop holds, or preemption). That
        # is a difference of two clocks and is not floored: where the
        # kernel samples CPU time (10 ms a tick on the chip hosts) one
        # reading means nothing and the mean of many is unbiased
        tracer = self.tracer
        cpu_clock = tracer.cpu_clock if tracer is not None else None
        if cpu_clock is not None:
            t_fetched, cpu0 = time.perf_counter(), cpu_clock()
        moved_pos = pos
        if mode == "delta":
            # only a row that was dirty at dispatch, or that the device
            # handed back with other BITS than it was given (a mover, a
            # reflection at the bounds, a -0.0 that came back 0.0), can
            # have a new cube or a new frame position: every other
            # closure row holds, bit for bit, the position its
            # registered cube was quantised from and the retained
            # column keeps (`_apply_delta` reads the position of these
            # rows alone). The pads are never read.
            n = int(handle["rows"].size)
            quantised = np.flatnonzero(
                handle["dirty_in"]
                | (pos[:n].view(np.uint32)
                   != handle["pos_in"][:n].view(np.uint32)).any(axis=1)
            )
            moved_pos = pos[quantised]
        cubes = cube_coords_batch(
            moved_pos.astype(np.float64), self.cube_size
        )
        if cpu_clock is not None:
            cpu_ms = (cpu_clock() - cpu0) / 1e6
        t1 = time.perf_counter()
        out = {
            "mode": mode,
            "pos": pos, "targets": targets, "counts": counts,
            "cubes": cubes, "cap": handle["cap"], "knn_ms": (t1 - t0) * 1e3,
            "quantised_rows": int(cubes.shape[0]),
        }
        if cpu_clock is not None:
            out["knn_fetch_ms"] = (t_fetched - t0) * 1e3
            out["knn_off_cpu_ms"] = (t1 - t_fetched) * 1e3 - cpu_ms
        if mode == "delta":
            out["rows"] = handle["rows"]
            out["dirty_keys"] = handle["dirty_keys"]
            out["quantised"] = quantised
        return out

    def abort_tick(self) -> None:
        """Drop an in-flight tick without applying it (cancelled or
        errored flush, or a resilience rebuild/failover swapping the
        backing index): host columns stay authoritative and unchanged,
        the next dispatch simply re-integrates from them. The device
        twin already holds the dropped tick's integration, so it is
        invalidated — the next dispatch re-ships the host tier. The
        delta-tick replay state drops with it: the aborted dispatch
        consumed the dirty window without ever applying, so the next
        tick must recompute the world in full."""
        self._have_last = False
        if self._tick_inflight:
            self._tick_inflight = False
            self._dev_state = None
            self.dropped_ticks += 1

    def apply(self, result: dict, trace=None,
              skip_frames: bool = False) -> list:
        """Integrate one collected tick back into the host authority
        (event-loop thread): position writeback, index churn through
        the base+delta path, neighbor-frame assembly. Returns
        ``(message, targets)`` delivery pairs for the tick's batched
        deliver. ``skip_frames`` (tick-deadline degradation) applies
        the writeback + churn but sheds the frame leg — counted, never
        silent.

        With an interest manager the plane tells it which rows to
        read (``_owed``): the closure rows this delta tick's splice
        found different from the retained columns and wrote
        (``_apply_delta``), those of earlier delta ticks shed by
        ``skip_frames`` (the splice runs on every applied delta tick,
        so a row that differs from what the manager last read was
        named by the tick that changed it), and the slots allocated or
        released since the manager's last call; a replay tick adds
        none. For the closure rows it does not name the plane vouches
        itself: it compared them, and they hold what the last call
        read. After a full tick (the first one, a tier change,
        ``abort_tick``, a mispredict, churn past the threshold) it
        names none, and the manager scans every row."""
        self._tick_inflight = False
        t0 = time.perf_counter()
        cap = result["cap"]
        mode = result.get("mode", "full")
        if mode == "replay":
            # nothing changed since the retained tick: positions,
            # cubes and the index are already exactly what a full
            # recompute would produce — only the frame leg runs
            moved_slots = np.empty(0, np.intp)
            pos = self._last_pos
            targets, counts = self._last_targets, self._last_counts
        elif mode == "delta":
            pos, targets, counts, moved_slots = self._apply_delta(result)
        else:
            pos, cubes = result["pos"], result["cubes"]
            targets, counts = result["targets"], result["counts"]

            # 1. position writeback — every live slot that the wire
            # did NOT touch since dispatch (a client update must win
            # over the concurrent integration it never saw)
            wb = self._live[:cap] & ~self._touched[:cap]
            self._pos[:cap][wb] = pos[wb]

            # 2. index churn: slots whose authoritative cube moved.
            # Only written-back slots move here — touched slots
            # re-quantize at the NEXT applied tick from their
            # client-given position.
            moved = wb & np.any(cubes != self._cube[:cap], axis=1)
            moved_slots = np.flatnonzero(moved)
            if moved_slots.size:
                self._apply_churn(moved_slots, cubes[moved_slots])
            # retain this tick as the delta replay source — as
            # WRITABLE copies: np.asarray of a device buffer is a
            # read-only zero-copy view, and delta ticks splice their
            # sub-results into these in place. ROW-major copies: a TPU
            # hands an [N, K] column back column-major (the positions
            # still; the recipients come in lane rows, canonical_tick_fn)
            # and everything from here on reads and writes rows (the
            # delta splice, the frame leg's gather of the rows a tick
            # changed — 13 ms a tick at 28K rows of a column-major
            # 131,072 x 32)
            if self._delta_ticks:
                self._last_pos = pos = np.array(pos, order="C")
                self._last_targets = targets = np.array(targets, order="C")
                self._last_counts = counts = np.array(counts)
                self._have_last = True
                self._last_cap = cap
            self._owed = None    # every row is new: nothing to vouch for
        self.last_churn = int(moved_slots.size)

        # 3. neighbor frames: one message per entity with >= 1 target,
        # fanned out to the owning peers of its k nearest co-cube
        # entities (the device already applied except-self per PEER)
        if skip_frames:
            pairs = []
            self.frames_skipped += 1
            if self.metrics is not None:
                self.metrics.inc("sim.frames_skipped")
            # a long shed streak owes more rows than a scan reads
            if self._owed is not None and (
                sum(map(len, self._owed[0])) + len(self._owed[1]) > cap
            ):
                self._owed = None
        elif self.interest is not None:
            pairs = self.interest.build_pairs(
                self, pos, targets, cap, trace, self._take_owed(),
            )
        else:
            pairs = self._build_frames(pos, targets, counts, cap)

        self.applied_ticks += 1
        self.frames += len(pairs)
        self.last_apply_ms = (time.perf_counter() - t0) * 1e3
        self.last_knn_ms = result["knn_ms"]
        quantised_rows = result.get("quantised_rows", 0)  # replay: none
        self.quantised_rows += quantised_rows
        if self.metrics is not None:
            self.metrics.inc("sim.quantised_rows", quantised_rows)
            self.metrics.observe_ms("sim.knn_ms", result["knn_ms"])
            self.metrics.observe_ms("sim.apply_ms", self.last_apply_ms)
            if "knn_fetch_ms" in result:    # collect_tick: the CPU clock
                for leg in ("knn_fetch_ms", "knn_off_cpu_ms"):
                    self.metrics.observe_ms(f"sim.{leg}", result[leg])
            if moved_slots.size:
                self.metrics.inc("sim.index_moves", int(moved_slots.size))
            if pairs:
                self.metrics.inc("sim.frames", len(pairs))
            if self._delta_ticks and self.last_delta_stats:
                self.metrics.inc(
                    "delta.sim_reused", self.last_delta_stats["reused"]
                )
                self.metrics.inc(
                    "delta.sim_recomputed",
                    self.last_delta_stats["recomputed"],
                )
        if trace is not None:
            tags = {
                "entities": len(self._slot_of),
                "frames": len(pairs),
                "index_moves": int(moved_slots.size),
                "integrate_ms": round(self.last_integrate_ms, 3),
                "knn_ms": round(result["knn_ms"], 3),
                "apply_ms": round(self.last_apply_ms, 3),
            }
            for leg in ("knn_fetch_ms", "knn_off_cpu_ms"):
                if leg in result:
                    tags[leg] = round(result[leg], 3)
            if self._delta_ticks:
                tags["delta"] = dict(self.last_delta_stats)
            trace.tag(sim=tags)
        return pairs

    def _take_owed(self):
        """Settle with the interest manager: the ``changed`` word of
        its ``build_pairs`` — ``(rows, roster)`` sorted and unique, or
        None where the plane cannot name them — and a clean slate for
        what the columns do from here on."""
        owed, self._owed = self._owed, ([], [])
        if owed is None:
            return None
        closures, roster = owed
        roster = np.unique(np.asarray(roster, np.intp))
        if len(closures) == 1 and not roster.size:
            return closures[0], roster      # a closure is sorted as made
        return np.unique(np.concatenate([roster, *closures])), roster

    def _apply_delta(self, result: dict):
        """Splice a delta sub-tick over the retained last-tick arrays,
        COMPARING before it writes: a closure row takes its freshly
        computed values only where they differ, bit for bit, from what
        the retained columns hold (recipients, which both sides keep
        sorted, so a row whose recipients only changed order is equal;
        ``counts``; the position as ``uint32`` bits: -0.0 and NaN are
        positions too); every other row, in the closure or not, keeps
        (replays) its own. The rows written are the rows named to the
        interest manager (``_owed``) and counted in
        ``sim.spliced_rows``; ``delta.sim_recomputed`` counts the
        closure they were found in.

        Only the rows ``collect_tick`` quantised are read for their
        position, written back and compared with their registered cube:
        any other closure row was not dirty at dispatch and came back
        with the bits it was given, which are the bits ``_pos`` and
        ``_last_pos`` hold (both took them from the last tick that
        computed the row, and a wire write since would have made it
        dirty), so it has nothing to write back, no new cube, and the
        device twin is no staler for it than it was.

        Returns ``(pos, targets, counts, moved_slots)`` for the shared
        apply tail — ``pos`` is the device-integrated frame position
        column, exactly what the full path hands it."""
        rows = result["rows"]
        n = int(rows.size)
        quantised = result["quantised"]
        qrows = rows[quantised]
        new_t, new_c = result["targets"][:n], result["counts"][:n]
        differ = _rows_differ(new_t, self._last_targets.take(rows, axis=0))
        differ |= new_c != self._last_counts[rows]
        differ[quantised] |= _rows_differ(
            result["pos"][quantised].view(np.uint32),
            self._last_pos[qrows].view(np.uint32),
        )
        at = np.flatnonzero(differ)
        spliced = rows[at]          # sorted: a mask of a sorted array
        self._last_targets[spliced] = new_t[at]
        self._last_counts[spliced] = new_c[at]
        self._last_pos[spliced] = result["pos"][at]
        self.spliced_rows += len(at)
        if self.metrics is not None:
            self.metrics.inc("sim.spliced_rows", len(at))
        if self._owed is not None and len(at):
            self._owed[0].append(spliced)

        # writeback + churn for the quantised rows the wire didn't
        # touch mid-flight (same mask the full path applies tier-wide);
        # rows removed mid-flight dropped out of `live` already
        wb = self._live[qrows] & ~self._touched[qrows]
        wrows = qrows[wb]
        self._pos[wrows] = result["pos"][quantised[wb]]
        qcubes = result["cubes"][wb]
        moved = np.any(qcubes != self._cube[wrows], axis=1)
        moved_slots = wrows[moved]
        if moved_slots.size:
            self._apply_churn(moved_slots, qcubes[moved])

        # defensive closure audit: every written-back row must land in
        # a cube the dispatch predicted dirty — unreachable inside the
        # pinned quantizer envelope, but a mispredict would mean some
        # clean cube replayed stale neighbors, so it forces the next
        # tick onto the full path instead of trusting the replay state
        if moved_slots.size:
            bad = int(np.count_nonzero(
                ~np.isin(self._key[moved_slots], result["dirty_keys"])
            ))
            if bad:
                self.delta_mispredicts += bad
                self._have_last = False
                logger.warning(
                    "delta tick mispredicted %d cube landings — "
                    "forcing a full recompute next tick", bad,
                )

        # the device twin never saw this sub-tick: the rows written
        # back are stale there until the next full-path scatter
        # re-ships them
        self._device_dirty[wrows] = True
        return self._last_pos, self._last_targets, self._last_counts, \
            moved_slots

    def _apply_churn(self, moved_slots: np.ndarray,
                     new_cubes: np.ndarray) -> None:
        """Move the index rows of slots whose cube changed, through the
        backend's delta path. ``new_cubes`` are the moved slots' fresh
        cubes, row-aligned with ``moved_slots``. Refcount transitions
        decide which moves actually touch the index (co-located
        entities of one peer share a row); the surviving adds/removes
        go down vectorized, grouped by world, via
        ``bulk_move_subscriptions`` when the backend has it
        (TPU/sharded) or per-row mutations otherwise."""
        old_cubes = self._cube[moved_slots].copy()
        wids = self._wid[moved_slots]
        pids = self._pid[moved_slots]
        self._cube[moved_slots] = new_cubes
        self._key[moved_slots] = spatial_keys(wids, new_cubes, 0)
        self._keys_changed()
        self.index_moves += int(moved_slots.size)

        # refcount transitions (O(churn) host work, like any index
        # mutation batch): rows crossing 0 materialize as index ops
        add_rows: list[int] = []
        rem_rows: list[int] = []
        refs = self._sub_refs
        for i in range(moved_slots.size):
            wid = int(wids[i])
            pid = int(pids[i])
            old_key = (wid, int(old_cubes[i, 0]), int(old_cubes[i, 1]),
                       int(old_cubes[i, 2]), pid)
            new_key = (wid, int(new_cubes[i, 0]), int(new_cubes[i, 1]),
                       int(new_cubes[i, 2]), pid)
            refs[old_key] -= 1
            if refs[old_key] <= 0:
                del refs[old_key]
                rem_rows.append(i)
            refs[new_key] += 1
            if refs[new_key] == 1:
                add_rows.append(i)

        bulk_move = getattr(self.backend, "bulk_move_subscriptions", None)
        for wid in np.unique(wids).tolist():
            world = self._world_names[wid]
            rem = [i for i in rem_rows if wids[i] == wid]
            add = [i for i in add_rows if wids[i] == wid]
            rem_peers = [self._peer_uuids[int(pids[i])] for i in rem]
            add_peers = [self._peer_uuids[int(pids[i])] for i in add]
            if bulk_move is not None:
                bulk_move(
                    world,
                    rem_peers, old_cubes[rem],
                    add_peers, new_cubes[add],
                )
            else:
                for peer, cube in zip(rem_peers, old_cubes[rem]):
                    self.backend.remove_subscription(
                        world, peer, tuple(int(c) for c in cube)
                    )
                for peer, cube in zip(add_peers, new_cubes[add]):
                    self.backend.add_subscription(
                        world, peer, tuple(int(c) for c in cube)
                    )
        # Make the churn visible to the device twin and run the LSM
        # compaction policy NOW: the query path calls flush() at every
        # dispatch, but an entity-sim-only server has no query
        # dispatches — without this the delta log (and its tombstones)
        # would grow without bound. No-op-cheap when nothing is dirty.
        self.backend.flush()

    def _build_frames(self, pos, targets, counts, cap: int) -> list:
        """Assemble per-entity neighbor frames: for every live entity
        with at least one resolved target, one ``entity.frame``
        LocalMessage carrying the entity's integrated position,
        addressed to the owning peers of its nearest neighbors.
        Entities sharing a (world, recipients) cohort encode in ONE
        native pass (serialize-once per cohort) and hand ready wire
        bytes to deliver_batch — zero per-entity Message objects; the
        object path below is the fallback for a stale native library.
        O(entities with neighbors) host work either way — the
        delivery-path analog of the query engine's decode."""
        live = self._live[:cap]
        valid = targets >= 0
        has_any = live & valid.any(axis=1)
        rows = np.flatnonzero(has_any)
        if rows.size == 0:
            return []
        wire = self._wire
        if wire is None or not wire.can_encode_frames:
            return self._build_frames_py(pos, targets, valid, rows)
        # cohort key = (world, sorted target lanes): rows agreeing on
        # both share one recipient list and one native encode pass
        tr = np.sort(targets[rows], axis=1)
        key = np.concatenate(
            [self._wid[rows][:, None], tr.astype(np.int32)], axis=1
        )
        cohorts, inverse = np.unique(key, axis=0, return_inverse=True)
        pairs = []
        peer_uuids = self._peer_uuids
        cache = self._frame_cache
        next_cache: dict[bytes, tuple] = {}
        reused = 0
        for c in range(cohorts.shape[0]):
            crows = rows[inverse == c]
            # frame-level reuse: the cohort key pins world + recipient
            # set; byte-identical member rows and positions pin the
            # encoded output exactly (sender keys and entity uuids are
            # per-slot constants within a roster epoch — any slot
            # alloc/release cleared the cache), so a clean cohort
            # replays last tick's wire bytes, parity byte for byte
            key_b = cohorts[c].tobytes()
            crows_b = crows.tobytes()
            sub_pos = pos[crows]
            pos_b = sub_pos.tobytes()
            cached = cache.get(key_b)
            if (
                cached is not None
                and cached[0] == crows_b
                and cached[1] == pos_b
            ):
                frames, targets_u = cached[2], cached[3]
                reused += len(frames)
            else:
                tgt = cohorts[c, 1:]
                tgt = np.unique(tgt[tgt >= 0])
                targets_u = [peer_uuids[int(p)] for p in tgt]
                world = self._world_names[int(cohorts[c, 0])]
                frames = wire.encode_frames(
                    self._peer_key_arr[self._pid[crows]],
                    self._uuid_bytes[crows],
                    sub_pos.astype(np.float64),
                    world.encode(),
                )
            next_cache[key_b] = (crows_b, pos_b, frames, targets_u)
            pairs.extend((WireFrame(f), targets_u) for f in frames)
        # cohorts absent this tick age out with the wholesale swap
        self._frame_cache = next_cache
        if reused:
            self.frames_reused += reused
            if self.metrics is not None:
                self.metrics.inc("delta.frames_reused", reused)
        self.frames_native += len(pairs)
        return pairs

    def _build_frames_py(self, pos, targets, valid, rows) -> list:
        """Object-path frame assembly (stale-native fallback): one
        Message per entity, serialized later by deliver_batch."""
        pairs = []
        peer_uuids = self._peer_uuids
        uuid_of = self._uuid_of
        world_names = self._world_names
        wid_col = self._wid
        pid_col = self._pid
        for row in rows.tolist():
            tgt_pids = np.unique(targets[row][valid[row]])
            targets_u = [peer_uuids[int(p)] for p in tgt_pids]
            position = Vector3(
                float(pos[row, 0]), float(pos[row, 1]), float(pos[row, 2])
            )
            world = world_names[int(wid_col[row])]
            msg = Message(
                instruction=Instruction.LOCAL_MESSAGE,
                parameter=PARAM_FRAME,
                sender_uuid=peer_uuids[int(pid_col[row])],
                world_name=world,
                position=position,
                entities=[Entity(
                    uuid=uuid_of[row], position=position,
                    world_name=world,
                )],
            )
            pairs.append((msg, targets_u))
        return pairs

    # endregion

    def stats(self) -> dict:
        return {
            "entities": len(self._slot_of),
            "capacity": self._cap,
            "peers": len(self._peer_slots),
            "worlds": len(self._world_names),
            "k": self.k,
            "pallas": self.pallas,
            "registered": self.entities_registered,
            "removed": self.entities_removed,
            "updates": self.updates,
            "rejected": self.rejected,
            "dispatches": self.dispatches,
            "applied_ticks": self.applied_ticks,
            "dropped_ticks": self.dropped_ticks,
            "frames": self.frames,
            "frames_skipped": self.frames_skipped,
            "frames_native": self.frames_native,
            "frames_reused": self.frames_reused,
            "coalesced": self.coalesced,
            "pending": self.staged_count(),
            "wire_rows": self.wire_rows,
            "wire_slow_rows": self.wire_slow_rows,
            "column_flips": self.column_flips,
            "h2d_full": self.h2d_full,
            "h2d_scatter": self.h2d_scatter,
            "scatter_fallbacks": self.scatter_fallbacks,
            "last_h2d_rows": self.last_h2d_rows,
            "index_moves": self.index_moves,
            "index_rows": len(self._sub_refs),
            "delta_ticks": self._delta_ticks,
            "delta_sim_ticks": self.delta_sim_ticks,
            "full_sim_ticks": self.full_sim_ticks,
            "delta_reused": self.delta_reused,
            "delta_recomputed": self.delta_recomputed,
            "delta_fallbacks": self.delta_fallbacks,
            "delta_mispredicts": self.delta_mispredicts,
            "quantised_rows": self.quantised_rows,
            "spliced_rows": self.spliced_rows,
            "dispatch_scan_rows": self.dispatch_scan_rows,
            "last_integrate_ms": round(self.last_integrate_ms, 3),
            "last_knn_ms": round(self.last_knn_ms, 3),
            "last_apply_ms": round(self.last_apply_ms, 3),
            "last_churn": self.last_churn,
        }


def _rows_differ(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Which rows of two ``[n, k]`` blocks differ anywhere. The
    row-wise ``any`` of a short axis is numpy's slow case (0.64 ms for
    18,600 x 32 where the compare itself takes 0.2), so eight flags
    are folded at a time where the width allows."""
    ne = a != b
    if ne.shape[1] % 8 == 0 and ne.flags.c_contiguous:
        return np.bitwise_or.reduce(ne.view(np.uint64), axis=1) != 0
    return ne.any(axis=1)


def _is_moving(vel):
    """Does the tick integrate this velocity (``[..., 3]``)? The one
    rule the mover count and the delta dispatch's scan share: any
    component that is not zero — a NaN counts."""
    return (np.asarray(vel) != 0.0).any(axis=-1)


def _decode_velocity(flex: bytes | None):
    """Wire velocity: ``Entity.flex`` carries 12 little-endian f32
    bytes (vx, vy, vz). Absent/short flex = no velocity change (zero
    for a fresh registration)."""
    if flex is None or len(flex) < 12:
        return None
    return np.frombuffer(flex[:12], dtype="<f4").astype(np.float32)
