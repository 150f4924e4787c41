"""Columnar wire→SoA entity ingest (ISSUE 11): native batch decode
parity with the object path, stale-library fallback, incremental-H2D
scatter parity, per-cohort native frame encoding, and the MAX_OBJS-free
columnar entity vector.

Parity discipline: a wire plane (fed raw bytes through ColumnarIngest)
and an object plane (fed decoded Messages through EntityPlane.ingest)
receive the same logical traffic; after every dispatch their host
columns — positions, velocities, ownership, liveness — must agree
lane for lane, and their neighbor frames byte for byte."""

import asyncio
import struct
import uuid

import numpy as np
import pytest

from worldql_server_tpu.engine.peers import PeerMap
from worldql_server_tpu.entities import ColumnarIngest, EntityPlane
from worldql_server_tpu.protocol import (
    Instruction,
    Message,
    deserialize_message,
    entity_wire,
    serialize_message,
)
from worldql_server_tpu.protocol.native_codec import MAX_OBJS
from worldql_server_tpu.protocol.types import Entity, Vector3
from worldql_server_tpu.spatial.cpu_backend import CpuSpatialBackend
from worldql_server_tpu.utils.retrace import GUARD


def run(coro, timeout=90):
    return asyncio.run(asyncio.wait_for(coro, timeout))


@pytest.fixture(scope="module")
def wire() -> entity_wire.EntityWire:
    ew = entity_wire.shared()
    assert ew is not None, "native entity codec failed to load"
    assert ew.can_decode and ew.can_encode_frames
    return ew


def make_plane(**kw) -> EntityPlane:
    kw.setdefault("k", 4)
    return EntityPlane(
        CpuSpatialBackend(16), PeerMap(), cube_size=16, dt=0.05,
        bounds=1000.0, **kw,
    )


def ent_msg(sender, entities, parameter=None, world="w"):
    return Message(
        instruction=Instruction.LOCAL_MESSAGE, sender_uuid=sender,
        world_name=world, parameter=parameter, entities=entities,
    )


def vel_flex(vx, vy=0.0, vz=0.0) -> bytes:
    return struct.pack("<3f", vx, vy, vz)


class Harness:
    """Twin planes: every message goes to the wire plane as BYTES
    (through ColumnarIngest, exactly the transport's call shape) and to
    the object plane as a decoded Message."""

    def __init__(self, wire_codec, governor=None):
        self.wire_plane = make_plane(governor=governor)
        self.obj_plane = make_plane(wire=None, governor=governor)
        self.ingest = ColumnarIngest(
            self.wire_plane, sender_known=lambda u: True,
            governor=governor, wire=wire_codec,
        )

    def feed(self, *messages):
        datas = [serialize_message(m) for m in messages]

        async def slow_route(data):
            self.wire_plane.ingest(deserialize_message(data))

        run(self.ingest.process_batch(list(datas), slow_route))
        for data in datas:
            self.obj_plane.ingest(deserialize_message(data))

    def tick(self):
        out = []
        for plane in (self.wire_plane, self.obj_plane):
            handle = plane.dispatch_tick()
            out.append(
                plane.apply(plane.collect_tick(handle))
                if handle is not None else []
            )
        return out

    def assert_lane_parity(self):
        w, o = self.wire_plane, self.obj_plane
        assert w._cap == o._cap
        assert np.array_equal(w._live, o._live)
        assert np.array_equal(w._pos, o._pos)
        assert np.array_equal(w._vel, o._vel)
        assert np.array_equal(w._wid, o._wid)
        assert np.array_equal(w._pid, o._pid)
        assert np.array_equal(w._cube, o._cube)
        assert w._slot_of == o._slot_of


# region: decode + staging parity


def test_wire_path_matches_object_path_lane_for_lane(wire):
    h = Harness(wire)
    owner_a, owner_b = uuid.uuid4(), uuid.uuid4()
    ents = [uuid.uuid4() for _ in range(8)]
    h.feed(
        ent_msg(owner_a, [
            Entity(uuid=ents[i], position=Vector3(i * 30.0, 1, 1),
                   world_name="w", flex=vel_flex(1.0 + i))
            for i in range(4)
        ]),
        ent_msg(owner_b, [
            # co-cube with owner_a's entity (i - 4): cross-peer frames
            Entity(uuid=ents[i], position=Vector3((i - 4) * 30.0 + 1, 2, 1),
                   world_name="w")
            for i in range(4, 8)
        ]),
    )
    assert h.wire_plane.entity_count == 8
    h.tick()
    h.assert_lane_parity()

    # steady-state updates ride the columns: no Entity objects, and the
    # second message's rows coalesce onto the first's (intra-batch LWW)
    h.feed(
        ent_msg(owner_a, [
            Entity(uuid=ents[0], position=Vector3(5.0, 5.0, 5.0),
                   world_name="w", flex=vel_flex(-3.0)),
            Entity(uuid=ents[1], position=Vector3(6.0, 5.0, 5.0),
                   world_name="w"),
        ]),
        ent_msg(owner_a, [
            Entity(uuid=ents[0], position=Vector3(7.0, 5.0, 5.0),
                   world_name="w"),
        ]),
    )
    # 2 registration batches + these 2 update batches rode the columns
    assert h.ingest.fast_messages == 4
    assert h.ingest.slow_messages == 0
    assert h.wire_plane.wire_rows == 3
    wp, op = h.tick()
    h.assert_lane_parity()
    # LWW: the later position won, the staged velocity survived
    slot = h.wire_plane._slot_of[ents[0]]
    assert h.wire_plane._vel[slot, 0] == pytest.approx(-3.0)

    # neighbor frames: byte-for-byte parity, recipients equal
    assert len(wp) == len(op) > 0
    assert sorted(f.wire for f, _ in wp) == \
        sorted(serialize_message(m) for m, _ in op)
    assert sorted(map(sorted, (t for _, t in wp))) == \
        sorted(map(sorted, (t for _, t in op)))
    assert h.wire_plane.frames_native > 0


def test_malformed_velocity_flex_parity(wire):
    """Flex under 12 bytes = no velocity change; >= 12 = first 12 as 3
    LE f32 — the wire path must agree with _decode_velocity exactly."""
    h = Harness(wire)
    owner = uuid.uuid4()
    e = uuid.uuid4()
    h.feed(ent_msg(owner, [Entity(
        uuid=e, position=Vector3(1, 1, 1), world_name="w",
        flex=vel_flex(40.0),
    )]))
    for flex in (b"", b"\x01" * 11, vel_flex(7.0) + b"trailing-junk"):
        h.feed(ent_msg(owner, [Entity(
            uuid=e, position=Vector3(2, 2, 2), world_name="w", flex=flex,
        )]))
        h.tick()
        h.assert_lane_parity()
    slot = h.wire_plane._slot_of[e]
    assert h.wire_plane._vel[slot, 0] == pytest.approx(7.0)


def test_removal_parameter_routes_through_object_path_in_order(wire):
    """A removal breaks the columnar run: the update BEFORE it stages
    first (then dies with the slot), the update AFTER re-registers."""
    h = Harness(wire)
    owner = uuid.uuid4()
    e = uuid.uuid4()
    h.feed(ent_msg(owner, [Entity(uuid=e, position=Vector3(1, 1, 1),
                                  world_name="w")]))
    h.feed(
        ent_msg(owner, [Entity(uuid=e, position=Vector3(2, 2, 2),
                               world_name="w")]),
        ent_msg(owner, [Entity(uuid=e)], parameter="entity.remove"),
        ent_msg(owner, [Entity(uuid=e, position=Vector3(9, 9, 9),
                               world_name="w")]),
    )
    assert h.ingest.slow_messages == 1  # the removal
    h.tick()
    h.assert_lane_parity()
    assert e in h.wire_plane._slot_of  # re-registered by the last update
    slot = h.wire_plane._slot_of[e]
    assert h.wire_plane._pos[slot, 0] == pytest.approx(9.0)


def test_ownership_rejected_vectorized(wire):
    h = Harness(wire)
    owner, thief = uuid.uuid4(), uuid.uuid4()
    e = uuid.uuid4()
    h.feed(ent_msg(owner, [Entity(uuid=e, position=Vector3(1, 1, 1),
                                  world_name="w")]))
    # the thief must first own SOMETHING so its pid exists — the
    # vectorized ownership check, not peer-unknown, does the rejecting
    h.feed(ent_msg(thief, [Entity(uuid=uuid.uuid4(),
                                  position=Vector3(50, 1, 1),
                                  world_name="w")]))
    h.feed(ent_msg(thief, [Entity(uuid=e, position=Vector3(66, 6, 6),
                                  world_name="w")]))
    h.tick()
    h.assert_lane_parity()
    slot = h.wire_plane._slot_of[e]
    assert h.wire_plane._pid[slot] == h.wire_plane._peer_ids[owner]
    assert h.wire_plane._pos[slot, 0] != pytest.approx(66.0)


def test_entity_world_and_uuid_escape_hatches_route_slow(wire):
    """Per-entity worlds and non-canonical uuid strings are object-path
    territory — the native decode flags the buffer, the slow route
    preserves semantics, and lanes still agree."""
    h = Harness(wire)
    owner = uuid.uuid4()
    e1, e2 = uuid.uuid4(), uuid.uuid4()
    h.feed(ent_msg(owner, [
        Entity(uuid=e1, position=Vector3(1, 1, 1), world_name="other"),
        Entity(uuid=e2, position=Vector3(2, 2, 2), world_name="w"),
    ]))
    assert h.ingest.slow_messages == 1 and h.ingest.fast_messages == 0
    h.tick()
    h.assert_lane_parity()
    assert h.wire_plane._world_names[
        h.wire_plane._wid[h.wire_plane._slot_of[e1]]
    ] == "other"


def test_stale_library_falls_back_to_object_path(wire):
    """ColumnarIngest with no native codec (stale .so) routes EVERY
    message through the slow path — same end state, object speed."""
    h = Harness(wire)
    fallback = ColumnarIngest(
        h.obj_plane, sender_known=lambda u: True, wire=None,
    )
    assert not fallback.active
    owner = uuid.uuid4()
    e = uuid.uuid4()
    msgs = [
        ent_msg(owner, [Entity(uuid=e, position=Vector3(1, 1, 1),
                               world_name="w", flex=vel_flex(2.0))]),
        ent_msg(owner, [Entity(uuid=e, position=Vector3(4, 4, 4),
                               world_name="w")]),
    ]
    datas = [serialize_message(m) for m in msgs]

    async def slow_route(data):
        h.obj_plane.ingest(deserialize_message(data))

    run(fallback.process_batch(datas, slow_route))
    assert fallback.slow_messages == 2 and fallback.fast_messages == 0

    async def wire_slow(data):
        h.wire_plane.ingest(deserialize_message(data))

    run(h.ingest.process_batch(
        [serialize_message(m) for m in msgs], wire_slow
    ))
    h.tick()
    h.assert_lane_parity()


def test_columnar_entity_vector_has_no_max_objs_cliff(wire):
    """The columnar decode reads the entities vector straight off the
    wire: a batch past WQL_MAX_OBJS stays on the fast path instead of
    silently dropping to the Python codec."""
    owner = uuid.uuid4()
    n = MAX_OBJS + 1
    msg = ent_msg(owner, [
        Entity(uuid=uuid.UUID(int=i + 1),
               position=Vector3(float(i % 97), 1, 1), world_name="w")
        for i in range(n)
    ])
    data = serialize_message(msg)  # Python codec (over the native cap)
    batch = wire.decode([data])
    assert batch.status[0] == 1
    assert batch.total == n

    plane = make_plane()
    ingest = ColumnarIngest(plane, sender_known=lambda u: True, wire=wire)

    async def never(data):
        raise AssertionError("fast-path batch routed slow")

    run(ingest.process_batch([data], never))
    assert plane.entity_count == n


# endregion

# region: incremental H2D


def test_dispatch_scatters_only_dirty_rows(wire):
    h = Harness(wire)
    owner = uuid.uuid4()
    ents = [uuid.uuid4() for _ in range(32)]
    h.feed(ent_msg(owner, [
        Entity(uuid=e, position=Vector3(i * 40.0, 1, 1), world_name="w")
        for i, e in enumerate(ents)
    ]))
    h.tick()  # first tick: full tier upload
    assert h.wire_plane.h2d_full == 1

    before = GUARD.counts().get("entities.scatter", 0)
    h.feed(ent_msg(owner, [
        Entity(uuid=ents[3], position=Vector3(500, 1, 1), world_name="w"),
        Entity(uuid=ents[7], position=Vector3(600, 1, 1), world_name="w"),
    ]))
    h.tick()
    h.assert_lane_parity()
    assert h.wire_plane.h2d_scatter == 1
    assert h.wire_plane.last_h2d_rows == 2
    assert GUARD.counts().get("entities.scatter", 0) >= before

    # quiet tick: nothing dirty, nothing shipped
    h.tick()
    h.assert_lane_parity()
    assert h.wire_plane.last_h2d_rows == 0
    assert h.wire_plane.h2d_full == 1  # never re-shipped the tier


def test_precompile_walks_the_tick_up_to_the_max_entities_tier():
    """Boot precompile reaches the --entity-max tier: growing through
    the capacity tiers, and the delta sub-batches under them, compiles
    no tick mid-serving (131,072 rows take the TPU compiler 80 s)."""
    plane = make_plane(max_entities=1000, delta_ticks="on")
    stats = plane.precompile()
    assert stats["families"]["entities.sim_tick"] == 5    # 64 … 1024
    assert stats["skipped_by_budget"] == 0
    owner = uuid.uuid4()
    rng = np.random.default_rng(3)
    before = GUARD.counts()
    for n in (300, 300, 400):    # 256 → 512 → 1024
        pos = rng.uniform(-400, 400, (n, 3))
        plane.ingest(ent_msg(owner, [
            Entity(uuid=uuid.uuid4(), position=Vector3(*map(float, p)),
                   world_name="w",
                   flex=vel_flex(1.0) if i < 3 else None)
            for i, p in enumerate(pos)
        ]))
        for _ in range(3):
            plane.apply(plane.collect_tick(plane.dispatch_tick()))
    assert plane.stats()["capacity"] == 1024
    assert plane.delta_sim_ticks > 0
    assert GUARD.delta(before).get("entities.sim_tick", 0) == 0


def test_scatter_ladder_precompiles_and_stays_quiet(wire):
    plane = make_plane()
    stats = plane.precompile()
    # the tick kernel always traces fresh (per-plane partial); the
    # scatter ladder may already be warm when earlier tests compiled
    # the same shapes (jit caches key on the shared module function)
    assert stats["new_variants"] >= 1
    owner = uuid.uuid4()
    e = uuid.uuid4()
    plane.ingest(ent_msg(owner, [Entity(
        uuid=e, position=Vector3(1, 1, 1), world_name="w",
    )]))
    before = GUARD.counts()
    for i in range(3):
        plane.ingest(ent_msg(owner, [Entity(
            uuid=e, position=Vector3(2.0 + i, 1, 1), world_name="w",
        )]))
        handle = plane.dispatch_tick()
        plane.apply(plane.collect_tick(handle))
    delta = GUARD.delta(before)
    assert delta.get("entities.scatter", 0) == 0, delta
    assert delta.get("entities.sim_tick", 0) == 0, delta
    assert plane.h2d_scatter >= 2


def test_abort_tick_invalidates_twin_and_reships(wire):
    plane = make_plane()
    owner = uuid.uuid4()
    e = uuid.uuid4()
    plane.ingest(ent_msg(owner, [Entity(
        uuid=e, position=Vector3(1, 1, 1), world_name="w",
        flex=vel_flex(10.0),
    )]))
    handle = plane.dispatch_tick()
    plane.apply(plane.collect_tick(handle))
    # dropped tick: host stays authoritative, twin invalidated
    assert plane.dispatch_tick() is not None
    plane.abort_tick()
    full_before = plane.h2d_full
    handle = plane.dispatch_tick()
    plane.apply(plane.collect_tick(handle))
    assert plane.h2d_full == full_before + 1
    slot = plane._slot_of[e]
    # three applied integrations' worth of movement never double-counts
    assert plane._pos[slot, 0] == pytest.approx(1.0 + 2 * 0.05 * 10.0)


# endregion

# region: governor interaction


def test_wire_path_coalescing_accounting_matches_dict_semantics(wire):
    from worldql_server_tpu.engine.metrics import Metrics
    from worldql_server_tpu.robustness import failpoints
    from worldql_server_tpu.robustness.overload import OverloadGovernor

    gov = OverloadGovernor(max_batch=100, metrics=Metrics())
    plane = make_plane(governor=gov, metrics=gov.metrics)
    ingest = ColumnarIngest(
        plane, sender_known=lambda u: True, governor=gov, wire=wire,
        metrics=gov.metrics,
    )
    owner = uuid.uuid4()
    e = uuid.uuid4()
    plane.ingest(ent_msg(owner, [Entity(uuid=e, position=Vector3(1, 1, 1),
                                        world_name="w")]))
    failpoints.registry.set("overload.force_state", "state:shed_low")
    try:
        gov.note_idle(0)
        assert gov.coalesce_entities()
        datas = [
            serialize_message(ent_msg(owner, [Entity(
                uuid=e, position=Vector3(10.0 + i, 2, 3), world_name="w",
            )]))
            for i in range(5)
        ]

        async def never(data):
            raise AssertionError("unexpected slow route")

        run(ingest.process_batch(datas, never))
    finally:
        failpoints.registry.clear()
    assert plane.staged_count() == 1
    assert plane.coalesced == 4
    assert gov.metrics.counters["overload.coalesced"] == 4
    # audit invariant: offered == applied/staged + coalesced (+1 reg)
    assert plane.updates + plane.coalesced == 6
    plane._drain_pending()
    slot = plane._slot_of[e]
    assert plane._pos[slot, 0] == pytest.approx(14.0)


# endregion

# region: end to end over real ZMQ


def test_e2e_zmq_columnar_path_serves_frames(wire):
    """A real server over real ZMQ: updates stream wire→SoA through
    the columnar fast path (provably fired), frames keep arriving with
    advancing positions, and the incremental H2D scatter carries the
    steady state."""
    from tests.client_util import ZmqClient, free_port
    from worldql_server_tpu.engine.config import Config
    from worldql_server_tpu.engine.server import WorldQLServer

    async def scenario():
        config = Config()
        config.store_url = "memory://"
        config.http_enabled = False
        config.ws_enabled = False
        config.zmq_server_port = free_port()
        config.zmq_server_host = "127.0.0.1"
        config.spatial_backend = "tpu"
        config.tick_interval = 0.03
        config.entity_sim = True
        config.entity_k = 4
        server = WorldQLServer(config)
        await server.start()
        try:
            assert server.entity_ingest is not None
            assert server.entity_ingest.active
            a = await ZmqClient.connect(config.zmq_server_port)
            b = await ZmqClient.connect(config.zmq_server_port)
            ea, eb = uuid.uuid4(), uuid.uuid4()
            await a.send(ent_msg(a.uuid, [Entity(
                uuid=ea, position=Vector3(1, 2, 3), world_name="w",
                flex=vel_flex(25.0),
            )]))
            await b.send(ent_msg(b.uuid, [Entity(
                uuid=eb, position=Vector3(2, 2, 3), world_name="w",
            )]))
            frame = await b.recv_until(Instruction.LOCAL_MESSAGE,
                                       timeout=20)
            assert frame.parameter == "entity.frame"
            last_x = frame.entities[0].position.x
            for _ in range(3):
                await b.send(ent_msg(b.uuid, [Entity(
                    uuid=eb, position=Vector3(2, 2, 3), world_name="w",
                )]))
                frame = await b.recv_until(Instruction.LOCAL_MESSAGE,
                                           timeout=20)
            assert frame.entities[0].position.x > last_x
            ingest = server.entity_ingest
            assert ingest.fast_messages > 0, ingest.stats()
            assert ingest.rows > 0
            plane = server.entity_plane
            assert plane.wire_rows > 0       # updates rode the columns
            assert plane.h2d_scatter > 0     # touched slots, not tiers
            assert plane.frames_native > 0   # cohort-encoded frames
            await a.close()
            await b.close()
        finally:
            await server.stop()

    run(scenario(), timeout=120)


# endregion

# region: failpoint coverage (ISSUE 12 satellite) — the PR 11 fast
# path's loss boundaries are chaos-visible: entities.decode_native
# (error ⇒ object-path fallback fires, counted) and entities.scatter
# (error ⇒ full-upload fallback), both audited in the failpoints gauge
# so no injected fault is ever invisible.


@pytest.fixture
def clean_failpoints():
    from worldql_server_tpu.robustness import failpoints

    failpoints.registry.reset()
    yield failpoints.registry
    failpoints.registry.reset()


def test_decode_native_failpoint_degrades_to_object_path(
    wire, clean_failpoints
):
    reg = clean_failpoints
    h = Harness(wire)
    owner = uuid.uuid4()
    ents = [uuid.uuid4() for _ in range(4)]
    msg = ent_msg(owner, [
        Entity(uuid=e, position=Vector3(i * 30.0, 1, 1), world_name="w")
        for i, e in enumerate(ents)
    ])

    reg.set("entities.decode_native", "error:1:x1")
    h.feed(msg)
    assert reg.fired("entities.decode_native") == 1
    # the batch still landed — through the object route, counted
    assert h.ingest.decode_fallbacks == 1
    assert h.ingest.fast_messages == 0
    assert h.ingest.slow_messages == 1
    assert h.wire_plane.entity_count == 4
    h.assert_lane_parity()

    # disarmed: the next batch rides the fast path again (columnar
    # staging folds at the tick edge — parity holds post-tick)
    h.feed(ent_msg(owner, [Entity(
        uuid=ents[0], position=Vector3(999.0, 1, 1), world_name="w",
    )]))
    assert h.ingest.fast_messages == 1
    h.tick()
    h.assert_lane_parity()


def test_scatter_failpoint_degrades_to_full_upload(
    wire, clean_failpoints
):
    reg = clean_failpoints
    plane = make_plane()
    owner = uuid.uuid4()
    ents = [uuid.uuid4() for _ in range(8)]
    plane.ingest(ent_msg(owner, [
        Entity(uuid=e, position=Vector3(i * 30.0, 1, 1), world_name="w")
        for i, e in enumerate(ents)
    ]))
    handle = plane.dispatch_tick()
    plane.apply(plane.collect_tick(handle))
    assert plane.h2d_full == 1

    # dirty two rows, then fail the scatter: the dispatch must fall
    # back to ONE full-tier upload — no row may be lost to the fault
    plane.ingest(ent_msg(owner, [
        Entity(uuid=ents[1], position=Vector3(500, 1, 1), world_name="w"),
        Entity(uuid=ents[2], position=Vector3(600, 1, 1), world_name="w"),
    ]))
    reg.set("entities.scatter", "error:1:x1")
    handle = plane.dispatch_tick()
    plane.apply(plane.collect_tick(handle))
    assert reg.fired("entities.scatter") == 1
    assert plane.scatter_fallbacks == 1
    assert plane.h2d_full == 2          # the fallback fired
    assert plane.h2d_scatter == 0
    slot = plane._slot_of[ents[1]]
    assert plane._pos[slot, 0] == pytest.approx(500.0)

    # disarmed: the next dirty rows scatter incrementally again
    plane.ingest(ent_msg(owner, [Entity(
        uuid=ents[3], position=Vector3(700, 1, 1), world_name="w",
    )]))
    handle = plane.dispatch_tick()
    plane.apply(plane.collect_tick(handle))
    assert plane.h2d_scatter == 1
    assert plane.h2d_full == 2


def test_new_failpoints_audited_in_gauge(wire, clean_failpoints):
    """Chaos audit: every fired entities.* fault shows in the
    registry's fired_counts — the same dict the server exports as the
    failpoints gauge — so the fast path is no longer fault-invisible."""
    reg = clean_failpoints
    h = Harness(wire)
    owner = uuid.uuid4()
    reg.set("entities.decode_native", "error:1:x1")
    h.feed(ent_msg(owner, [Entity(
        uuid=uuid.uuid4(), position=Vector3(1, 1, 1), world_name="w",
    )]))
    plane = h.wire_plane
    plane.ingest(ent_msg(owner, [Entity(
        uuid=uuid.uuid4(), position=Vector3(2, 2, 2), world_name="w",
    )]))
    handle = plane.dispatch_tick()
    plane.apply(plane.collect_tick(handle))
    plane.ingest(ent_msg(owner, [Entity(
        uuid=next(iter(plane._slot_of)), position=Vector3(3, 3, 3),
        world_name="w",
    )]))
    reg.set("entities.scatter", "error:1:x1")
    handle = plane.dispatch_tick()
    if handle is not None:
        plane.apply(plane.collect_tick(handle))
    counts = reg.fired_counts()
    assert counts.get("entities.decode_native") == 1
    assert counts.get("entities.scatter") == 1


# endregion

# region: ResilientBackend rebuild mid-sim-tick (ISSUE 12 satellite)


def _resilient_plane(failover_after=3):
    from worldql_server_tpu.robustness.resilient import ResilientBackend

    backend = ResilientBackend(
        CpuSpatialBackend(16), factory=lambda: CpuSpatialBackend(16),
        failover_after=failover_after,
    )
    plane = EntityPlane(
        backend, PeerMap(), cube_size=16, dt=0.05, bounds=1000.0, k=4,
    )
    # the server's wiring: rebuild/failover invalidates the twin FIRST
    backend.on_rebuild = plane.abort_tick
    return backend, plane


def test_rebuild_mid_tick_aborts_before_restore(clean_failpoints):
    """Regression: a ResilientBackend rebuild during an active entity
    tick must invalidate the device twin (dirty bitmap included) via
    abort_tick BEFORE the restore — the next dispatch re-ships the
    host authority instead of scattering onto a stale twin."""
    reg = clean_failpoints
    backend, plane = _resilient_plane()
    owner = uuid.uuid4()
    ents = [uuid.uuid4() for _ in range(4)]
    plane.ingest(ent_msg(owner, [
        Entity(uuid=e, position=Vector3(i * 30.0, 1, 1), world_name="w")
        for i, e in enumerate(ents)
    ]))
    handle = plane.dispatch_tick()
    plane.apply(plane.collect_tick(handle))
    full0 = plane.h2d_full

    # client update stages dirty rows, tick goes IN FLIGHT…
    plane.ingest(ent_msg(owner, [Entity(
        uuid=ents[1], position=Vector3(400.0, 1, 1), world_name="w",
    )]))
    assert plane.dispatch_tick() is not None
    assert plane._tick_inflight

    # …and the backend fails + rebuilds mid-tick (contained dispatch)
    reg.set("backend.dispatch", "error:1:x1")
    backend.dispatch_local_batch([])
    assert backend.rebuilds == 1
    assert not plane._tick_inflight, "rebuild must abort the tick"
    assert plane._dev_state is None, "twin must be invalidated"
    assert plane.dropped_ticks == 1

    # next dispatch re-ships the full host tier — never a stale
    # scatter — and the client's update is in it
    scatters0 = plane.h2d_scatter
    handle = plane.dispatch_tick()
    result = plane.collect_tick(handle)
    plane.apply(result)
    assert plane.h2d_full == full0 + 1
    assert plane.h2d_scatter == scatters0
    slot = plane._slot_of[ents[1]]
    assert plane._pos[slot, 0] == pytest.approx(400.0)


def test_failover_mid_tick_also_aborts(clean_failpoints):
    reg = clean_failpoints
    backend, plane = _resilient_plane(failover_after=1)
    owner = uuid.uuid4()
    plane.ingest(ent_msg(owner, [Entity(
        uuid=uuid.uuid4(), position=Vector3(1, 1, 1), world_name="w",
    )]))
    assert plane.dispatch_tick() is not None
    reg.set("backend.dispatch", "error:1:x1")
    backend.dispatch_local_batch([])
    assert backend.failed_over
    assert not plane._tick_inflight
    assert plane.dropped_ticks == 1


# endregion


# region: frame-level reuse (ISSUE 14 satellite — the PR 13 leftover)


def _tick_pairs(plane):
    handle = plane.dispatch_tick()
    assert handle is not None
    return plane.apply(plane.collect_tick(handle))


def _frame_bytes(pairs):
    return [(f.wire, tuple(t)) for f, t in pairs]


def test_clean_cohorts_replay_frame_bytes(wire):
    """An idle world's cohorts replay last tick's encoded wire bytes:
    counted in frames_reused, byte-for-byte identical to a fresh
    encode of the same state."""
    plane = make_plane()
    owner_a, owner_b = uuid.uuid4(), uuid.uuid4()
    ents = [uuid.uuid4() for _ in range(4)]
    plane.ingest(ent_msg(owner_a, [
        Entity(uuid=ents[i], position=Vector3(1.0 + i, 1, 1),
               world_name="w") for i in range(2)
    ]))
    plane.ingest(ent_msg(owner_b, [
        Entity(uuid=ents[2 + i], position=Vector3(3.0 + i, 1, 1),
               world_name="w") for i in range(2)
    ]))
    pairs1 = _tick_pairs(plane)
    assert pairs1 and plane.frames_native > 0
    assert plane.frames_reused == 0            # first tick must encode

    pairs2 = _tick_pairs(plane)                # nothing moved
    assert plane.frames_reused == len(pairs2) > 0
    assert _frame_bytes(pairs2) == _frame_bytes(pairs1)

    # parity pin: a cold cache re-encodes the SAME bytes the replay
    # handed out — reuse is a pure skip, never a drift
    plane._frame_cache = {}
    reused_before = plane.frames_reused
    pairs3 = _tick_pairs(plane)
    assert plane.frames_reused == reused_before  # cold cache: no reuse
    assert _frame_bytes(pairs3) == _frame_bytes(pairs2)


def test_frame_reuse_invalidates_on_movement_and_roster_change(wire):
    plane = make_plane()
    owner_a, owner_b = uuid.uuid4(), uuid.uuid4()
    ents = [uuid.uuid4() for _ in range(4)]
    # two MIXED-owner cohorts in far-apart cubes (same-owner-only
    # cubes produce no frames: recipients are except-self per peer)
    plane.ingest(ent_msg(owner_a, [
        Entity(uuid=ents[0], position=Vector3(1.0, 1, 1),
               world_name="w"),
        Entity(uuid=ents[1], position=Vector3(500.0, 1, 1),
               world_name="w"),
    ]))
    plane.ingest(ent_msg(owner_b, [
        Entity(uuid=ents[2], position=Vector3(1.5, 1, 1),
               world_name="w"),
        Entity(uuid=ents[3], position=Vector3(500.5, 1, 1),
               world_name="w"),
    ]))
    _tick_pairs(plane)
    _tick_pairs(plane)
    assert plane.frames_reused > 0

    # a moved entity re-encodes its cohort; frames must carry the NEW
    # position, not the cached one
    plane.ingest(ent_msg(owner_a, [
        Entity(uuid=ents[0], position=Vector3(2.5, 1, 1),
               world_name="w")
    ]))
    pairs = _tick_pairs(plane)
    moved = [
        f for f, _ in pairs
        if any(e.uuid == ents[0] for e in f.entities)
    ]
    assert moved, "moved entity still produces a frame"
    assert any(
        e.position.x == pytest.approx(2.5)
        for f in moved for e in f.entities if e.uuid == ents[0]
    ), "reused stale frame served an old position"

    # roster change clears the cache wholesale: a registration into a
    # reused slot must never alias cached bytes
    plane.ingest(ent_msg(owner_b, [Entity(
        uuid=uuid.uuid4(), position=Vector3(600.0, 1, 1),
        world_name="w",
    )]))
    assert plane._frame_cache == {}
    reused_before = plane.frames_reused
    _tick_pairs(plane)
    assert plane.frames_reused == reused_before


# endregion
