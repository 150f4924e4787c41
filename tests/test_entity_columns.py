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


def entity_server(tick_interval):
    """A real ``--entity-sim`` server on a free ZeroMQ port (not yet
    started) and its config."""
    from tests.client_util import free_port
    from worldql_server_tpu.engine.config import Config
    from worldql_server_tpu.engine.server import WorldQLServer

    config = Config()
    config.store_url = "memory://"
    config.http_enabled = False
    config.ws_enabled = False
    config.zmq_server_port = free_port()
    config.zmq_server_host = "127.0.0.1"
    config.spatial_backend = "tpu"
    config.tick_interval = tick_interval
    config.entity_sim = True
    config.entity_k = 4
    return WorldQLServer(config), config


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
    from tests.client_util import ZmqClient

    async def scenario():
        server, config = entity_server(tick_interval=0.03)
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
            # ... staged by the pump's flush-start drains, the first of
            # them on a plane with no entity yet, never a receive
            assert ingest.edge_messages == ingest.fast_messages
            plane = server.entity_plane
            assert plane.wire_rows > 0       # updates rode the columns
            assert plane.h2d_scatter > 0     # touched slots, not tiers
            assert plane.frames_native > 0   # cohort-encoded frames
            await a.close()
            await b.close()
        finally:
            await server.stop()

    run(scenario(), timeout=120)


@pytest.mark.parametrize("stopper", ["server", "transport"])
def test_e2e_zmq_only_entity_updates_wait_and_stop_stages_them(
        wire, stopper):
    """Real ZMQ, a tick interval so long that no edge comes: the
    Handshake is answered and a removal routed ON RECEIPT (behind the
    registration held before it), entity updates are held as bytes,
    and ``stop`` (the ticker's drain, or the transport's own when
    that stops first) stages what is held."""
    from tests.client_util import ZmqClient

    async def until(cond, what):
        for _ in range(300):
            if cond():
                return
            await asyncio.sleep(0.01)
        raise AssertionError(what)

    async def scenario():
        server, config = entity_server(tick_interval=60.0)
        await server.start()
        stopped = False
        try:
            ingest, plane = server.entity_ingest, server.entity_plane
            assert ingest.active and server.ticker.ingest_edge is not None
            # the echo comes back long before any edge could
            a = await asyncio.wait_for(
                ZmqClient.connect(config.zmq_server_port), 10)
            e = uuid.uuid4()
            await a.send(ent_msg(a.uuid, [Entity(
                uuid=e, position=Vector3(1, 2, 3), world_name="w")]))
            await until(lambda: len(ingest._held) == 1, "not held")
            assert plane.entity_count == 0 and ingest.fast_messages == 0
            await a.send(ent_msg(a.uuid, [Entity(uuid=e)],
                                 parameter="entity.remove"))
            await a.send(ent_msg(a.uuid, [Entity(
                uuid=e, position=Vector3(9, 2, 3), world_name="w")]))
            # the removal cut the batch: registered, then removed
            await until(lambda: ingest.fast_messages == 1
                        and len(ingest._held) == 1, "removal waited")
            assert plane.entity_count == 0
            assert plane.entities_registered == 1
            assert ingest.edge_messages == 0
            await a.close()
            if stopper == "transport":
                [transport] = [t for t in server._transports
                               if hasattr(t, "_stage_edge")]
                server._transports.remove(transport)
                await transport.stop()
            else:
                stopped = True
                await server.stop()
            assert not ingest._held
            assert plane.entities_registered == 2
            assert ingest.fast_messages == 2
            assert ingest.edge_messages == (stopper == "server")
        finally:
            if not stopped:
                await server.stop()

    run(scenario(), timeout=120)


def test_e2e_zmq_no_update_is_lost_or_reordered_between_loop_and_edge(wire):
    """Stress, time-bounded: one sender streams 1,500 updates of one
    entity (x = 1, 2, ...) with heartbeats between, against a 5 ms
    tick, so the recv loop's holds and cuts and the pump's flush-start
    drains (which read the same socket) interleave hundreds of times.
    Every pass must see the buffers in the order they were sent."""
    from tests.client_util import ZmqClient

    n = 1500

    async def scenario():
        server, config = entity_server(tick_interval=0.005)
        await server.start()
        ingest = server.entity_ingest
        seen = []
        staged = ingest.process_batch

        async def spy(datas, slow_route, ctxs=None):
            for data in datas:
                message = deserialize_message(data)
                seen.append(
                    message.entities[0].position.x if message.entities
                    else -float(message.instruction == Instruction.HEARTBEAT)
                )
            await staged(datas, slow_route, ctxs=ctxs)

        ingest.process_batch = spy
        try:
            a = await ZmqClient.connect(config.zmq_server_port)
            e = uuid.uuid4()
            beats = 0
            for i in range(1, n + 1):
                await a.send(ent_msg(a.uuid, [Entity(
                    uuid=e, position=Vector3(float(i), 2, 3),
                    world_name="w")]))
                if i % 97 == 0:
                    await a.send(Message(instruction=Instruction.HEARTBEAT))
                    beats += 1
                if i % 50 == 0:
                    await asyncio.sleep(0.001)
            for _ in range(3000):
                if ingest.fast_messages == n and not ingest._held:
                    break
                await asyncio.sleep(0.01)
            assert ingest.fast_messages == n, ingest.stats()
            xs = [x for x in seen if x > 0]
            assert xs == [float(i) for i in range(1, n + 1)]
            # each heartbeat went in where it was sent: behind 97 more
            at = [i for i, x in enumerate(seen) if x == -1.0]
            assert len(at) == beats
            assert [seen[i - 1] for i in at] == \
                [97.0 * (k + 1) for k in range(beats)]
            st = ingest.stats()
            assert 0 < st["edge_messages"] <= st["fast_messages"]
            assert st["batches"] < n // 4       # passes, not receives
            await a.close()
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


# region: the held batch (ISSUE 37) — the transport hands every buffer
# to ColumnarIngest.hold as it leaves the socket and the batch is
# staged by ONE process_batch a tick edge. Semantics must not depend
# on WHEN a buffer is staged: lanes, frames and counts equal the
# per-receive staging this replaced (one process_batch a message) and
# the object path.


class HeldHarness(Harness):
    """Harness plus a third plane fed the transport's way: ``offer``
    hands each message, one at a time, to ``hold`` (staging when it
    asks, as ``_absorb_inbound`` does) while the wire plane gets the
    parent's per-receive staging and the object plane the Message;
    ``edge`` is the flush-start drain's staging."""

    def __init__(self, wire_codec):
        super().__init__(wire_codec)
        self.held_plane = make_plane()
        self.held = ColumnarIngest(
            self.held_plane, sender_known=lambda u: True, wire=wire_codec,
        )
        self.watch = None   # entity whose staged x every slow route notes
        self.routed = []    # (instruction, parameter, staged x | None)

    def staged_x(self):
        slot = self.held_plane._slot_of.get(self.watch)
        buf = self.held_plane._stage[self.held_plane._stage_active]
        if slot is None or not buf.touched[slot]:
            return None
        return float(buf.pos[slot, 0])

    async def _held_slow(self, data):
        message = deserialize_message(data)
        self.routed.append(
            (message.instruction, message.parameter, self.staged_x())
        )
        self.held_plane.ingest(message)

    def offer(self, *messages):
        asks = []
        for message in messages:
            self.feed(message)  # a process_batch a message + the object
            asks.append(self.held.hold(serialize_message(message)))
            if asks[-1]:
                run(self.held.stage(self._held_slow))
        return asks

    def edge(self):
        run(self.held.stage(self._held_slow, edge=True))

    def tick(self):
        handle = self.held_plane.dispatch_tick()
        held = (
            self.held_plane.apply(self.held_plane.collect_tick(handle))
            if handle is not None else []
        )
        return [*super().tick(), held]

    def assert_lane_parity(self):
        super().assert_lane_parity()
        h, o = self.held_plane, self.obj_plane
        assert h._cap == o._cap
        for col in ("_live", "_pos", "_vel", "_wid", "_pid", "_cube"):
            assert np.array_equal(getattr(h, col), getattr(o, col)), col
        assert h._slot_of == o._slot_of


def test_held_batch_staged_once_equals_per_receive_staging(wire):
    h = HeldHarness(wire)
    owner_a, owner_b = uuid.uuid4(), uuid.uuid4()
    ents = [uuid.uuid4() for _ in range(8)]
    asks = h.offer(*(
        ent_msg(owner_a if i < 4 else owner_b, [Entity(
            uuid=ents[i], position=Vector3((i % 4) * 30.0 + i // 4, 1, 1),
            world_name="w", flex=vel_flex(1.0 + i),
        )])
        for i in range(8)
    ))
    assert asks == [False] * 8           # entity updates wait
    assert h.held_plane.entity_count == 0 and h.held.batches == 0
    assert h.wire_plane.entity_count == 8
    h.edge()
    assert h.held_plane.entity_count == 8 and h.held.batches == 1
    h.tick()
    h.assert_lane_parity()

    # a tick's worth, one at a time: two senders interleaved, the same
    # entity updated three times (last write wins = arrival order), a
    # velocity on the first write only
    asks = h.offer(
        ent_msg(owner_a, [
            Entity(uuid=ents[0], position=Vector3(5.0, 5.0, 5.0),
                   world_name="w", flex=vel_flex(-3.0)),
            Entity(uuid=ents[1], position=Vector3(6.0, 5.0, 5.0),
                   world_name="w"),
        ]),
        ent_msg(owner_b, [Entity(uuid=ents[4],
                                 position=Vector3(1.5, 2.0, 1.0),
                                 world_name="w")]),
        ent_msg(owner_a, [Entity(uuid=ents[0],
                                 position=Vector3(7.0, 5.0, 5.0),
                                 world_name="w")]),
        ent_msg(owner_b, [Entity(uuid=ents[0],      # not the owner's
                                 position=Vector3(66.0, 6.0, 6.0),
                                 world_name="w")]),
        ent_msg(owner_a, [Entity(uuid=ents[0],
                                 position=Vector3(8.0, 5.0, 5.0),
                                 world_name="w")]),
    )
    assert asks == [False] * 5
    assert h.held_plane.staged_count() == 0     # nothing staged early
    h.edge()
    wp, op, hp = h.tick()
    h.assert_lane_parity()
    slot = h.held_plane._slot_of[ents[0]]
    assert h.held_plane._vel[slot, 0] == pytest.approx(-3.0)

    # frames byte for byte, recipients equal, all three ways
    assert len(hp) == len(wp) == len(op) > 0
    assert sorted(f.wire for f, _ in hp) == sorted(f.wire for f, _ in wp) \
        == sorted(serialize_message(m) for m, _ in op)
    assert sorted(map(sorted, (t for _, t in hp))) == \
        sorted(map(sorted, (t for _, t in op)))

    # the same messages and rows went in, in 2 passes against 13
    per, held = h.ingest.stats(), h.held.stats()
    assert (per["batches"], held["batches"]) == (13, 2)
    for key in ("fast_messages", "slow_messages", "dropped", "rows"):
        assert held[key] == per[key], key
    assert held["edge_messages"] == held["fast_messages"] == 13
    assert per["edge_messages"] == 0
    assert h.held_plane.wire_rows == h.wire_plane.wire_rows
    assert h.held_plane.updates == h.wire_plane.updates


def _interloper(kind, owner, e):
    if kind == "removal":
        return ent_msg(owner, [Entity(uuid=e)], parameter="entity.remove")
    if kind == "local-message":     # a LocalMessage that carries none
        return Message(
            instruction=Instruction.LOCAL_MESSAGE, sender_uuid=owner,
            world_name="w", position=Vector3(1, 1, 1),
        )
    if kind == "global-entities":   # columnar too, but never waits
        return Message(
            instruction=Instruction.GLOBAL_MESSAGE, sender_uuid=owner,
            world_name="w", entities=[Entity(
                uuid=e, position=Vector3(4, 4, 4), world_name="w")],
        )
    return Message(
        instruction={"handshake": Instruction.HANDSHAKE,
                     "heartbeat": Instruction.HEARTBEAT}[kind],
        sender_uuid=owner, parameter={"handshake": "127.0.0.1:9"}.get(kind),
    )


@pytest.mark.parametrize("kind", [
    "removal", "local-message", "handshake", "heartbeat",
    "global-entities",
])
def test_a_buffer_that_cannot_wait_keeps_its_senders_arrival_order(
        wire, kind):
    """Between two updates of one sender: the first is staged BEFORE
    the interloper is routed (a removal must find it), the second
    after; only entity-update LocalMessages wait for the edge."""
    h = HeldHarness(wire)
    owner = uuid.uuid4()
    e = h.watch = uuid.uuid4()
    h.offer(ent_msg(owner, [Entity(uuid=e, position=Vector3(1, 1, 1),
                                   world_name="w")]))
    h.edge()
    h.tick()
    asks = h.offer(
        ent_msg(owner, [Entity(uuid=e, position=Vector3(2, 2, 2),
                               world_name="w")]),
        _interloper(kind, owner, e),
        ent_msg(owner, [Entity(uuid=e, position=Vector3(9, 9, 9),
                               world_name="w")]),
    )
    assert asks == [False, True, False]
    if kind == "global-entities":
        # staged in the same pass as the update before it, in order
        assert h.routed == [] and h.held.fast_messages == 3
        assert h.staged_x() == pytest.approx(4.0)
    else:
        [(instruction, parameter, staged_x)] = h.routed
        assert staged_x == pytest.approx(2.0)   # the first, not the second
        assert h.held.slow_messages == 1
    assert h.held.edge_messages == 1            # the registration's edge
    h.edge()
    assert h.held.edge_messages == 2
    h.tick()
    h.assert_lane_parity()
    slot = h.held_plane._slot_of[e]             # removal: registered anew
    assert h.held_plane._pos[slot, 0] == pytest.approx(9.0)
    assert h.held.stats()["slow_messages"] == h.ingest.stats()["slow_messages"]


@pytest.mark.parametrize("bound, n_msgs, n_ents", [
    ("messages", entity_wire.RECV_DRAIN_MAX, 1),
    ("rows", 4, 1024),      # 4 x 1,024 rows = _RUN_ROWS_MAX
])
def test_the_bound_stages_without_an_edge(wire, bound, n_msgs, n_ents):
    from worldql_server_tpu.entities.ingest import _RUN_ROWS_MAX

    assert n_msgs * n_ents >= _RUN_ROWS_MAX or n_msgs == 256
    plane = make_plane(max_entities=8192)
    ingest = ColumnarIngest(plane, sender_known=lambda u: True, wire=wire)
    owner = uuid.uuid4()

    async def never(data):
        raise AssertionError("unexpected slow route")

    asks = []
    for i in range(n_msgs):
        asks.append(ingest.hold(serialize_message(ent_msg(owner, [
            Entity(uuid=uuid.UUID(int=1 + i * n_ents + j),
                   position=Vector3(float(j % 64), float(i), 1),
                   world_name="w")
            for j in range(n_ents)
        ]))))
        if asks[-1]:
            run(ingest.stage(never))
    assert asks == [False] * (n_msgs - 1) + [True]
    assert plane.entity_count == n_msgs * n_ents
    st = ingest.stats()
    assert (st["batches"], st["fast_messages"], st["edge_messages"]) == \
        (1, n_msgs, 0)
    assert not ingest._held and ingest._held_rows == 0
    run(ingest.stage(never, edge=True))         # nothing left: no pass
    assert ingest.stats()["batches"] == 1


@pytest.mark.parametrize("way", ["held", "per-receive"])
def test_governor_counts_do_not_depend_on_when_a_batch_is_staged(wire, way):
    """Admission still runs a message, and the audit invariant
    offered == applied + coalesced + dropped holds with the same
    numbers whether five updates are staged one a call or held and
    staged once."""
    from worldql_server_tpu.engine.metrics import Metrics
    from worldql_server_tpu.robustness import failpoints
    from worldql_server_tpu.robustness.overload import OverloadGovernor

    gov = OverloadGovernor(max_batch=100, metrics=Metrics(), peer_rate=3,
                           peer_burst=4, clock=lambda: 100.0)
    plane = make_plane(governor=gov, metrics=gov.metrics)
    ingest = ColumnarIngest(
        plane, sender_known=lambda u: True, governor=gov, wire=wire,
        metrics=gov.metrics,
    )
    owner = uuid.uuid4()
    e = uuid.uuid4()
    plane.ingest(ent_msg(owner, [Entity(uuid=e, position=Vector3(1, 1, 1),
                                        world_name="w")]))
    datas = [
        serialize_message(ent_msg(owner, [Entity(
            uuid=e, position=Vector3(10.0 + i, 2, 3), world_name="w",
        )]))
        for i in range(6)
    ]

    async def never(data):
        raise AssertionError("unexpected slow route")

    failpoints.registry.set("overload.force_state", "state:shed_low")
    try:
        gov.note_idle(0)
        assert gov.coalesce_entities()
        for data in datas:
            if way == "held":
                assert not ingest.hold(data)
            else:
                run(ingest.process_batch([data], never))
        run(ingest.stage(never, edge=True))
    finally:
        failpoints.registry.clear()
    # 6 offered: the bucket (burst 4, a frozen clock) sheds 2, the
    # first staged update applies, 3 coalesce onto it
    counters = gov.metrics.counters
    assert gov.rate_limited == 2 and counters["peers.rate_limited"] == 2
    assert ingest.dropped == 2 and ingest.fast_messages == 4
    assert counters["messages.local_message"] == 6
    assert counters["messages.entity_batches"] == 4
    assert plane.staged_count() == 1
    assert plane.coalesced == 3 and counters["overload.coalesced"] == 3
    assert plane.updates == 2                   # the registration + 1
    assert 6 == (plane.updates - 1) + plane.coalesced + ingest.dropped
    plane._drain_pending()
    assert plane._pos[plane._slot_of[e], 0] == pytest.approx(13.0)


def _edge_ticker(plane, ingest, interval=0.02):
    """A pump over ``plane`` whose tick edge stages ``ingest``'s held
    batch: what ZmqTransport.start wires, minus the socket."""
    from worldql_server_tpu.engine.ticker import TickBatcher

    async def slow(data):
        plane.ingest(deserialize_message(data))

    async def edge():
        await ingest.stage(slow, edge=True)

    ticker = TickBatcher(plane.backend, PeerMap(), interval,
                         entity_plane=plane)
    ticker.ingest_edge = edge
    return ticker


@pytest.mark.parametrize("state", ["no-entities", "tick-in-flight"])
def test_an_idle_plane_still_stages_within_one_flush(wire, state):
    """The edge runs as every pump flush starts, work or none: a held
    registration reaches a plane with no entity (its flushes are idle
    and open no trace), and a held update is staged and folded while a
    sim tick is in flight (dispatch_tick launches nothing then)."""
    plane = make_plane()
    ingest = ColumnarIngest(plane, sender_known=lambda u: True, wire=wire)
    owner = uuid.uuid4()
    e = uuid.uuid4()
    if state == "tick-in-flight":
        plane.ingest(ent_msg(owner, [Entity(
            uuid=e, position=Vector3(1, 1, 1), world_name="w")]))
        assert plane.dispatch_tick() is not None    # never collected
    assert not ingest.hold(serialize_message(ent_msg(owner, [Entity(
        uuid=e, position=Vector3(7, 7, 7), world_name="w")])))

    async def scenario():
        ticker = _edge_ticker(plane, ingest)
        ticker.start()
        try:
            for _ in range(100):        # a few intervals, 2 s at most
                if ingest.edge_messages:
                    break
                await asyncio.sleep(0.02)
            if state == "tick-in-flight":
                await asyncio.sleep(0.05)   # the flush after the edge
        finally:
            if state == "tick-in-flight":
                plane.abort_tick()
            await ticker.stop()

    run(scenario())
    assert ingest.stats()["edge_messages"] == 1 and not ingest._held
    slot = plane._slot_of[e]
    assert plane._pos[slot, 0] == pytest.approx(7.0)
    if state == "tick-in-flight":
        assert plane.staged_count() == 0 and plane.column_flips == 1


def test_ticker_stop_stages_a_held_batch_before_its_drain_flush(wire):
    """stop() cancels the pump, then runs the edge and the drain
    flush: a batch held since the last flush is not lost, and a pump
    cancelled while its cut waits for a staging in progress hands the
    cut back to the head of the held batch."""
    plane = make_plane()
    ingest = ColumnarIngest(plane, sender_known=lambda u: True, wire=wire)
    owner = uuid.uuid4()
    ents = [uuid.uuid4() for _ in range(3)]

    def update(i, x):
        return serialize_message(ent_msg(owner, [Entity(
            uuid=ents[i], position=Vector3(x, 1, 1), world_name="w")]))

    async def scenario():
        ticker = _edge_ticker(plane, ingest, interval=30.0)
        ticker.start()
        await asyncio.sleep(0)
        assert not ingest.hold(update(0, 1.0))
        await ticker.stop()
        assert plane.entity_count == 1 and ingest.edge_messages == 1

        # a cut cancelled in the queue goes back, in order
        await ingest._staging.acquire()
        ingest.hold(update(1, 2.0))
        waiting = asyncio.ensure_future(ingest.stage(None, edge=True))
        await asyncio.sleep(0)
        assert not ingest._held             # cut, waiting its turn
        ingest.hold(update(2, 3.0))         # arrived behind the cut
        waiting.cancel()
        with pytest.raises(asyncio.CancelledError):
            await waiting
        ingest._staging.release()
        assert ingest._held == [update(1, 2.0), update(2, 3.0)]
        assert ingest._held_rows == 2

        async def never(data):
            raise AssertionError("unexpected slow route")

        await ingest.stage(never)
        assert plane.entity_count == 3

    run(scenario())


def test_peek_update_rows_only_holds_entity_update_local_messages():
    owner, e = uuid.uuid4(), uuid.uuid4()
    peek = entity_wire.peek_update_rows

    def entities(n):
        return [Entity(uuid=uuid.UUID(int=i + 1), position=Vector3(i, 1, 1),
                       world_name="w") for i in range(n)]

    update = serialize_message(ent_msg(owner, entities(20)))
    assert peek(update) == 20
    assert peek(serialize_message(ent_msg(owner, entities(1)))) == 1
    for kind in ("removal", "local-message", "handshake", "heartbeat",
                 "global-entities"):
        assert peek(serialize_message(_interloper(kind, owner, e))) == 0, kind
    # bytes that do not parse never raise and never wait
    for junk in (b"", b"\x00", b"\xff" * 64, update[:20], update[:40],
                 b"\x10\x00\x00\x00" + b"\xff" * 12 + b"\x00" * 8):
        assert peek(junk) == 0
    # the pure-Python encoder lays the table out the same way
    from worldql_server_tpu.protocol import codec

    assert peek(codec.py_serialize_message(ent_msg(owner, entities(3)))) == 3
    assert peek(codec.py_serialize_message(
        _interloper("removal", owner, e))) == 0


# endregion
