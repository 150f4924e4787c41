"""Entity simulation plane (ISSUE 9): wire ingest, device tick, index
coupling, and the end-to-end path — registration + position updates
over a REAL transport, through a device tick, to delivered neighbor
frames. The churn scenarios force the LSM base+delta index through at
least one compaction mid-stream; the WS variant importorskips
``websockets`` (minimal containers run the ZMQ legs only)."""

import asyncio
import random
import struct
import uuid

import numpy as np
import pytest

from tests.client_util import ZmqClient, free_port
from worldql_server_tpu.engine.config import (
    Config,
    apply_device_boot_defaults,
)
from worldql_server_tpu.engine.peers import PeerMap
from worldql_server_tpu.engine.server import WorldQLServer
from worldql_server_tpu.entities import PARAM_FRAME, PARAM_REMOVE, EntityPlane
from worldql_server_tpu.protocol import Instruction, Message
from worldql_server_tpu.protocol.types import Entity, Vector3
from worldql_server_tpu.spatial.quantize import cube_coords
from worldql_server_tpu.spatial.tpu_backend import TpuSpatialBackend
from worldql_server_tpu.utils.retrace import GUARD


def run(coro, timeout=90):
    return asyncio.run(asyncio.wait_for(coro, timeout))


def vel_flex(vx, vy=0.0, vz=0.0) -> bytes:
    """Wire velocity encoding: 12 LE f32 bytes on Entity.flex."""
    return struct.pack("<3f", vx, vy, vz)


def make_plane(k=4, cube=16, dt=0.05, **backend_kw):
    backend = TpuSpatialBackend(cube, **backend_kw)
    plane = EntityPlane(
        backend, PeerMap(), cube_size=cube, k=k, dt=dt, bounds=1000.0
    )
    return backend, plane


def ent_msg(sender, entities, parameter=None, world="w"):
    return Message(
        instruction=Instruction.LOCAL_MESSAGE, sender_uuid=sender,
        world_name=world, parameter=parameter, entities=entities,
    )


def tick(plane):
    handle = plane.dispatch_tick()
    assert handle is not None
    return plane.apply(plane.collect_tick(handle))


def make_server(**overrides) -> WorldQLServer:
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
    backend = overrides.pop("backend", None)
    for k, v in overrides.items():
        setattr(config, k, v)
    return WorldQLServer(config, backend=backend)


# region: plane unit behavior


def test_register_update_remove_and_refcounted_index_rows():
    backend, plane = make_plane()
    peer = uuid.uuid4()
    e1, e2 = uuid.uuid4(), uuid.uuid4()
    # two entities of ONE peer in the SAME cube share one index row
    plane.ingest(ent_msg(peer, [
        Entity(uuid=e1, position=Vector3(1, 1, 1), world_name="w"),
        Entity(uuid=e2, position=Vector3(2, 2, 2), world_name="w"),
    ]))
    assert plane.entity_count == 2
    assert backend.subscription_count() == 1
    assert backend.query_cube("w", Vector3(1, 1, 1)) == {peer}
    # removing one keeps the shared row; removing both drops it
    plane.ingest(ent_msg(peer, [Entity(uuid=e1)], parameter=PARAM_REMOVE))
    assert plane.entity_count == 1
    assert backend.subscription_count() == 1
    plane.ingest(ent_msg(peer, [Entity(uuid=e2)], parameter=PARAM_REMOVE))
    assert plane.entity_count == 0
    assert backend.subscription_count() == 0
    # slots recycle
    plane.ingest(ent_msg(peer, [
        Entity(uuid=uuid.uuid4(), position=Vector3(5, 5, 5), world_name="w")
    ]))
    assert plane.entity_count == 1


def test_update_keeps_velocity_and_rejects_foreign_owner():
    backend, plane = make_plane()
    owner, thief = uuid.uuid4(), uuid.uuid4()
    ent = uuid.uuid4()
    plane.ingest(ent_msg(owner, [Entity(
        uuid=ent, position=Vector3(0.5, 0.5, 0.5), world_name="w",
        flex=vel_flex(40.0),
    )]))
    slot = plane._slot_of[ent]
    assert plane._vel[slot, 0] == pytest.approx(40.0)
    # update without flex: position moves, velocity survives
    plane.ingest(ent_msg(owner, [Entity(
        uuid=ent, position=Vector3(3, 3, 3), world_name="w",
    )]))
    assert plane._vel[slot, 0] == pytest.approx(40.0)
    assert plane._pos[slot, 0] == pytest.approx(3.0)
    # a different peer cannot move or remove someone else's entity
    assert plane.ingest(ent_msg(thief, [Entity(
        uuid=ent, position=Vector3(9, 9, 9), world_name="w",
    )])) == 0
    assert plane.ingest(
        ent_msg(thief, [Entity(uuid=ent)], parameter=PARAM_REMOVE)
    ) == 0
    assert plane._pos[slot, 0] == pytest.approx(3.0)


def test_max_entities_cap_rejects_registrations():
    backend, plane = make_plane()
    plane.max_entities = 2
    peer = uuid.uuid4()
    ents = [Entity(uuid=uuid.uuid4(), position=Vector3(i, 0, 0),
                   world_name="w") for i in range(3)]
    plane.ingest(ent_msg(peer, ents))
    assert plane.entity_count == 2
    assert plane.rejected == 1


def test_tick_resolves_neighbors_and_applies_except_self_per_peer():
    backend, plane = make_plane()
    pa, pb = uuid.uuid4(), uuid.uuid4()
    ea, eb, ec = uuid.uuid4(), uuid.uuid4(), uuid.uuid4()
    # ea (peer a) and eb (peer b) co-cube; ec (peer a) co-cube too —
    # frames never target the entity's own peer
    plane.ingest(ent_msg(pa, [
        Entity(uuid=ea, position=Vector3(1, 1, 1), world_name="w"),
        Entity(uuid=ec, position=Vector3(2, 1, 1), world_name="w"),
    ]))
    plane.ingest(ent_msg(pb, [
        Entity(uuid=eb, position=Vector3(1, 2, 1), world_name="w"),
    ]))
    pairs = tick(plane)
    by_entity = {m.entities[0].uuid: set(t) for m, t in pairs}
    assert by_entity[ea] == {pb}
    assert by_entity[ec] == {pb}
    assert by_entity[eb] == {pa}
    for message, _ in pairs:
        assert message.parameter == PARAM_FRAME
        assert message.instruction == Instruction.LOCAL_MESSAGE


def test_bounded_staleness_index_follows_integrated_position():
    """The documented contract: after an applied tick, the cube
    registered in the authoritative index IS the (golden host f64)
    quantization of the entity's last integrated position — queries
    lag the device state by at most one applied tick."""
    backend, plane = make_plane(dt=0.1)
    peer = uuid.uuid4()
    ent = uuid.uuid4()
    plane.ingest(ent_msg(peer, [Entity(
        uuid=ent, position=Vector3(1, 1, 1), world_name="w",
        flex=vel_flex(50.0),
    )]))
    for _ in range(8):
        tick(plane)
        slot = plane._slot_of[ent]
        pos = plane._pos[slot]
        expected = cube_coords(
            float(pos[0]), float(pos[1]), float(pos[2]), 16
        )
        assert tuple(int(c) for c in plane._cube[slot]) == expected
        # and the index agrees: the owner is subscribed exactly there
        assert peer in backend.query_cube("w", expected)
    assert plane.index_moves > 0


def test_churn_through_delta_path_forces_compaction():
    """Sustained cube-crossing churn must flow through the index's
    base+delta path and trigger at least one LSM compaction — the
    moving-object regime ASH/1411.3212 describe (ROADMAP item 4)."""
    backend, plane = make_plane(compact_threshold=8)
    peers = [uuid.uuid4() for _ in range(4)]
    ents = [uuid.uuid4() for _ in range(24)]
    for i, ent in enumerate(ents):
        plane.ingest(ent_msg(peers[i % 4], [Entity(
            uuid=ent, position=Vector3(i * 40.0, 0.5, 0.5),
            world_name="w", flex=vel_flex(170.0),
        )]))
    compactions_seen = 0
    for _ in range(12):
        tick(plane)
        backend.wait_compaction()
        compactions_seen = max(compactions_seen, backend.compactions)
    assert compactions_seen >= 1
    assert plane.index_moves > 0
    # index integrity after the folds: every entity still queryable
    # at its current position
    for ent in ents:
        slot = plane._slot_of[ent]
        pos = plane._pos[slot]
        owner = plane._peer_uuids[int(plane._pid[slot])]
        assert owner in backend.query_cube(
            "w", Vector3(float(pos[0]), float(pos[1]), float(pos[2]))
        )


def test_peer_removal_releases_slots_and_refcounts():
    backend, plane = make_plane()
    pa, pb = uuid.uuid4(), uuid.uuid4()
    plane.ingest(ent_msg(pa, [
        Entity(uuid=uuid.uuid4(), position=Vector3(1, 1, 1),
               world_name="w") for _ in range(3)
    ]))
    plane.ingest(ent_msg(pb, [Entity(
        uuid=uuid.uuid4(), position=Vector3(2, 2, 2), world_name="w",
    )]))
    # the server purges index rows via backend.remove_peer first,
    # then releases the plane's bookkeeping (same order as
    # WorldQLServer._on_peer_remove)
    backend.remove_peer(pa)
    assert plane.on_peer_removed(pa) == 3
    assert plane.entity_count == 1
    assert backend.query_cube("w", Vector3(1, 1, 1)) == {pb}
    tick(plane)  # survivors still tick


def test_entity_churn_with_resilient_backend_keeps_mirror_consistent():
    """Regression: bulk remove/move used to fall through the
    ResilientBackend's ``__getattr__`` straight to the inner backend,
    bypassing the CPU mirror — a rebuild would then resurrect rows
    the churn had retired."""
    from worldql_server_tpu.robustness.resilient import ResilientBackend

    backend = ResilientBackend(
        TpuSpatialBackend(16), factory=lambda: TpuSpatialBackend(16)
    )
    plane = EntityPlane(
        backend, PeerMap(), cube_size=16, k=4, dt=0.1, bounds=1000.0
    )
    peer = uuid.uuid4()
    ent = uuid.uuid4()
    plane.ingest(ent_msg(peer, [Entity(
        uuid=ent, position=Vector3(1, 1, 1), world_name="w",
        flex=vel_flex(60.0),
    )]))
    for _ in range(5):
        tick(plane)
    assert plane.index_moves > 0
    slot = plane._slot_of[ent]
    pos = Vector3(*(float(c) for c in plane._pos[slot]))
    # the mirror tracked every move: exactly one row, at the current
    # cube, on BOTH sides
    assert backend.mirror.query_cube("w", pos) == {peer}
    assert backend.mirror.subscription_count() == 1
    assert backend.query_cube("w", pos) == {peer}
    # a rebuild from the mirror preserves exactly that state
    backend._rebuild()
    assert backend.query_cube("w", pos) == {peer}
    assert backend.subscription_count() == 1


def test_retrace_guard_steady_state_budget():
    """Steady ticks at one capacity tier must not grow the sim
    kernel's compile cache (entities.sim_tick family)."""
    backend, plane = make_plane()
    peer = uuid.uuid4()
    plane.ingest(ent_msg(peer, [
        Entity(uuid=uuid.uuid4(), position=Vector3(i, 1, 1),
               world_name="w", flex=vel_flex(10.0)) for i in range(8)
    ]))
    tick(plane)  # first tick compiles the tier
    since = GUARD.snapshot()
    for _ in range(6):
        tick(plane)
    delta = GUARD.delta(since)
    assert delta.get("entities.sim_tick", 0) == 0, delta


# endregion

# region: end-to-end over real transports


async def _register(client, ent, pos, vel=None, world="w"):
    await client.send(Message(
        instruction=Instruction.LOCAL_MESSAGE, world_name=world,
        entities=[Entity(
            uuid=ent, position=pos, world_name=world,
            flex=vel_flex(*vel) if vel else None,
        )],
    ))


async def _entity_sim_scenario(server):
    """Shared ZMQ scenario: register two co-cube entities from two
    peers, stream position updates, and assert neighbor frames arrive
    through the delivery path with the device path provably firing."""
    await server.start()
    try:
        a = await ZmqClient.connect(server.config.zmq_server_port)
        b = await ZmqClient.connect(server.config.zmq_server_port)
        ea, eb = uuid.uuid4(), uuid.uuid4()
        await _register(a, ea, Vector3(1, 2, 3), vel=(25.0,))
        await _register(b, eb, Vector3(2, 2, 3))

        frame_b = await b.recv_until(Instruction.LOCAL_MESSAGE, timeout=15)
        assert frame_b.parameter == PARAM_FRAME
        assert frame_b.entities[0].uuid == ea
        assert frame_b.sender_uuid == a.uuid
        frame_a = await a.recv_until(Instruction.LOCAL_MESSAGE, timeout=15)
        assert frame_a.entities[0].uuid == eb

        # stream updates: the moving entity's frames keep arriving
        # with advancing positions (device integration visible on the
        # wire), and the device path provably fired
        last_x = frame_b.entities[0].position.x
        for i in range(3):
            await _register(b, eb, Vector3(2, 2, 3))  # keep b co-cube
            frame = await b.recv_until(Instruction.LOCAL_MESSAGE, timeout=15)
            assert frame.parameter == PARAM_FRAME
        assert frame.entities[0].position.x > last_x

        plane = server.entity_plane
        assert plane.dispatches > 0
        assert plane.applied_ticks > 0
        assert plane.frames > 0
        # steady-state retrace budget: more ticks, no new variants
        since = GUARD.snapshot()
        for _ in range(3):
            await b.recv_until(Instruction.LOCAL_MESSAGE, timeout=15)
        assert GUARD.delta(since).get("entities.sim_tick", 0) == 0
        stats = server.metrics.snapshot()
        assert stats["counters"].get("sim.frames", 0) > 0
        await a.close()
        await b.close()
    finally:
        await server.stop()


def test_entity_sim_e2e_over_zmq_in_process_delivery():
    run(_entity_sim_scenario(make_server()))


def test_entity_sim_e2e_over_zmq_with_delivery_workers():
    run(_entity_sim_scenario(make_server(delivery_workers=1)))


def test_entity_sim_e2e_churn_compaction_over_zmq():
    """The acceptance churn pass: position updates streamed over the
    wire force at least one delta compaction mid-stream, and frames
    still arrive afterwards."""

    async def scenario():
        backend = TpuSpatialBackend(16, compact_threshold=8)
        server = make_server(backend=backend)
        await server.start()
        try:
            a = await ZmqClient.connect(server.config.zmq_server_port)
            b = await ZmqClient.connect(server.config.zmq_server_port)
            ents = [uuid.uuid4() for _ in range(16)]
            for i, ent in enumerate(ents):
                await _register(
                    a if i % 2 else b, ent,
                    Vector3(i * 40.0, 1, 1), vel=(200.0,),
                )
            # drive updates while the sim churns cubes every tick
            deadline = asyncio.get_running_loop().time() + 20
            while (backend.compactions < 1
                   and asyncio.get_running_loop().time() < deadline):
                for i, ent in enumerate(ents[:4]):
                    await _register(
                        a if i % 2 else b, ent,
                        Vector3(i * 40.0, 1, 1), vel=(200.0,),
                    )
                await asyncio.sleep(0.1)
            backend.wait_compaction()
            assert backend.compactions >= 1, (
                "no delta compaction fired mid-stream"
            )
            # frames still flow after the fold
            frame = await a.recv_until(Instruction.LOCAL_MESSAGE,
                                       timeout=15)
            assert frame.parameter == PARAM_FRAME
            await a.close()
            await b.close()
        finally:
            await server.stop()

    run(scenario(), timeout=120)


def test_entity_sim_e2e_over_websocket():
    pytest.importorskip("websockets")
    from tests.client_util import WsClient

    async def scenario():
        config_port = free_port()
        server = make_server()
        server.config.ws_enabled = True
        server.config.ws_port = config_port
        server.config.ws_host = "127.0.0.1"
        await server.start()
        try:
            a = await WsClient.connect(config_port)
            b = await WsClient.connect(config_port)
            ea, eb = uuid.uuid4(), uuid.uuid4()
            await _register(a, ea, Vector3(1, 2, 3), vel=(25.0,))
            await _register(b, eb, Vector3(2, 2, 3))
            frame = await b.recv_until(Instruction.LOCAL_MESSAGE,
                                       timeout=15)
            assert frame.parameter == PARAM_FRAME
            assert frame.entities[0].uuid == ea
            await a.close()
            await b.close()
        finally:
            await server.stop()

    run(scenario())


def test_peer_disconnect_sweeps_entities_e2e():
    async def scenario():
        server = make_server()
        await server.start()
        try:
            a = await ZmqClient.connect(server.config.zmq_server_port)
            b = await ZmqClient.connect(server.config.zmq_server_port)
            ea, eb = uuid.uuid4(), uuid.uuid4()
            await _register(a, ea, Vector3(1, 2, 3))
            await _register(b, eb, Vector3(2, 2, 3))
            await b.recv_until(Instruction.LOCAL_MESSAGE, timeout=15)
            assert server.entity_plane.entity_count == 2
            await server.peer_map.remove(a.uuid)
            assert server.entity_plane.entity_count == 1
            # the departed peer's entity (and index rows) are gone
            assert server.backend.query_cube("w", Vector3(1, 2, 3)) \
                == {b.uuid}
            await a.close()
            await b.close()
        finally:
            await server.stop()

    run(scenario())


# endregion

# region: default-on device boot (ROADMAP item 5, first half)


def test_device_boot_defaults_apply_when_accelerator_present():
    config = Config()
    config.store_url = "memory://"
    applied = apply_device_boot_defaults(
        config, backend_explicit=False, interval_explicit=False,
        present=True,
    )
    assert applied
    assert config.spatial_backend == "tpu"
    assert config.tick_interval == 0.05


def test_device_boot_defaults_cpu_fallback_is_byte_for_byte():
    """On a host without an accelerator the config must come back
    UNTOUCHED — field for field identical to a freshly built one."""
    config = Config()
    baseline = Config()
    applied = apply_device_boot_defaults(
        config, backend_explicit=False, interval_explicit=False,
        present=False,
    )
    assert not applied
    assert config == baseline


def test_device_boot_defaults_respect_explicit_choice(monkeypatch):
    # explicit flag wins outright
    config = Config()
    assert not apply_device_boot_defaults(
        config, backend_explicit=True, interval_explicit=False,
        present=True,
    )
    assert config.spatial_backend == "cpu"
    # explicit env var wins too
    monkeypatch.setenv("WQL_SPATIAL_BACKEND", "cpu")
    config2 = Config()
    assert not apply_device_boot_defaults(
        config2, backend_explicit=False, interval_explicit=False,
        present=True,
    )
    assert config2.spatial_backend == "cpu"
    monkeypatch.delenv("WQL_SPATIAL_BACKEND")
    # explicit interval survives the backend default
    config3 = Config()
    config3.tick_interval = 0.2
    assert apply_device_boot_defaults(
        config3, backend_explicit=False, interval_explicit=True,
        present=True,
    )
    assert config3.spatial_backend == "tpu"
    assert config3.tick_interval == 0.2


def test_accelerator_probe_honors_opt_outs(monkeypatch, tmp_path):
    from worldql_server_tpu.engine.config import accelerator_present

    fake = tmp_path / "accel0"
    fake.write_text("")
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert accelerator_present(probe_paths=(str(fake),))
    monkeypatch.setenv("WQL_DEVICE_DEFAULTS", "0")
    assert not accelerator_present(probe_paths=(str(fake),))
    monkeypatch.delenv("WQL_DEVICE_DEFAULTS")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert not accelerator_present(probe_paths=(str(fake),))
    assert not accelerator_present(probe_paths=("/nonexistent/accel",))


def test_entity_sim_config_validation():
    config = Config()
    config.store_url = "memory://"
    config.entity_sim = True
    config.spatial_backend = "cpu"
    config.tick_interval = 0
    with pytest.raises(ValueError, match="device spatial backend"):
        config.validate()
    config.spatial_backend = "tpu"
    with pytest.raises(ValueError, match="tick_interval"):
        config.validate()
    config.tick_interval = 0.05
    config.validate()
    config.entity_k = 0
    with pytest.raises(ValueError, match="entity_k"):
        config.validate()


# endregion


# region: the rows the plane owes the interest manager (ISSUE 30)


class _ColumnRecorder:
    """Stands where the interest manager stands and keeps what each
    ``build_pairs`` was handed: the five columns it reads, and the
    plane's word on which of their rows may have changed."""

    def __init__(self):
        self.seen = None
        self.hinted = self.scanned = self.named = 0

    def forget_peer(self, peer):
        pass

    def columns(self, plane, pos, targets, cap):
        live = plane._live[:cap].copy()
        return {
            "live": live,
            "keys": plane._uuid_bytes[:cap].copy(),
            "wid": plane._wid[:cap].copy(),
            "pos": np.array(pos[:cap], np.float32).view(np.uint32),
            # a dead row's recipients are nobody's: the manager masks them
            "targets": np.where(live[:, None], np.asarray(targets)[:cap], -1),
        }

    def build_pairs(self, plane, pos, targets, cap, trace=None, changed=None):
        now = self.columns(plane, pos, targets, cap)
        if changed is None:
            self.scanned += 1
        else:
            rows, roster = changed
            assert (np.diff(rows) > 0).all() and (np.diff(roster) > 0).all()
            assert np.isin(roster, rows).all()
            self.hinted += 1
            self.named += len(rows)
            was = self.seen
            assert was is not None and len(was["live"]) == cap
            spoken_for = (now["live"] | was["live"])
            identity = (
                (now["live"] != was["live"]) | (now["wid"] != was["wid"])
                | (now["keys"] != was["keys"]).any(axis=1)
            )
            differs = spoken_for & (
                identity | (now["pos"] != was["pos"]).any(axis=1)
                | (now["targets"] != was["targets"]).any(axis=1)
            )
            unnamed = np.setdiff1d(np.flatnonzero(differs), rows)
            assert unnamed.size == 0, f"rows changed and not named: {unnamed}"
            unnamed = np.setdiff1d(np.flatnonzero(identity), roster)
            assert unnamed.size == 0, f"roster changed and not named: {unnamed}"
        self.seen = now
        return []


@pytest.mark.parametrize("mid_flight", [False, True])
def test_rows_the_plane_names_cover_every_row_that_changed(mid_flight):
    """A real plane on delta ticks, 80 ticks of wire updates with
    entities removed and added, a tick whose frames were shed and an
    aborted one: after every apply, each row whose column content
    differs from what ``build_pairs`` last read is among the rows the
    plane named, each slot whose identity differs among its roster —
    or the plane named none and asked for the whole scan.
    ``mid_flight``: the wire changes land between dispatch and apply."""
    backend, plane = make_plane(k=4)
    assert backend.configure_delta_ticks("on")
    plane._delta_ticks = True
    rec = plane.interest = _ColumnRecorder()
    rng = random.Random(30 + mid_flight)
    peers = [uuid.uuid4() for _ in range(5)]
    owned: dict = {p: [] for p in peers}

    def at_random():
        return Vector3(rng.uniform(0, 96), rng.uniform(0, 96), 0.0)

    def spawn(peer):
        ent = uuid.uuid4()
        owned[peer].append(ent)
        plane.ingest(ent_msg(peer, [
            Entity(uuid=ent, position=at_random(), world_name="w")]))

    def wire(t):
        for _ in range(rng.randrange(3)):
            peer = rng.choice(peers)
            drift = rng.random()        # a few drift for a while, and stop
            plane.ingest(ent_msg(peer, [Entity(
                uuid=rng.choice(owned[peer]), position=at_random(),
                world_name="w", flex=vel_flex(rng.uniform(-2, 2))
                if drift < 0.1 else vel_flex(0.0) if drift < 0.5 else None)]))
        if t % 5 == 3:
            peer = rng.choice(peers)
            ent = owned[peer].pop(rng.randrange(len(owned[peer])))
            plane.ingest(ent_msg(peer, [Entity(uuid=ent)],
                                 parameter=PARAM_REMOVE))
        if t % 5 == 0 or t % 10 == 3:
            spawn(rng.choice(peers))     # a freed slot is taken again

    for peer in peers:
        for _ in range(40):
            spawn(peer)
    shed = 0
    for t in range(80):
        if not mid_flight:
            wire(t)
        handle = plane.dispatch_tick()
        assert handle is not None
        if mid_flight:
            wire(t)
        if t == 40:
            plane.abort_tick()
            continue
        result = plane.collect_tick(handle)
        for col in {"pos", "targets"} & result.keys():
            result[col] = np.asfortranarray(result[col])   # as a TPU does
        skip = t % 9 == 7
        shed += skip
        plane.apply(result, skip_frames=skip)
    assert shed >= 8 and plane.dropped_ticks == 1
    # the replay source is kept row-major, whatever the device handed
    assert plane._last_targets.flags.c_contiguous
    assert plane._last_pos.flags.c_contiguous
    assert plane.delta_sim_ticks >= 40 and plane.full_sim_ticks >= 2
    # the hint is there most ticks, and is a part of the swarm
    assert rec.hinted >= 50 and rec.scanned >= 2
    assert rec.hinted + rec.scanned + shed == plane.applied_ticks
    assert rec.named / rec.hinted < plane.entity_count / 2


def test_a_shed_streak_owes_at_most_a_tier_then_asks_for_the_scan():
    """Ticks shed by ``skip_frames`` owe their rows to the next
    ``build_pairs``; once they add up to more than a scan would read,
    the plane stops listing them and names none."""
    backend, plane = make_plane(k=4)
    assert backend.configure_delta_ticks("on")
    plane._delta_ticks = True
    rec = plane.interest = _ColumnRecorder()
    peer = uuid.uuid4()
    ents = [uuid.uuid4() for _ in range(40)]
    plane.ingest(ent_msg(peer, [
        Entity(uuid=e, position=Vector3(20 * i, 0, 0), world_name="w")
        for i, e in enumerate(ents)]))
    tick(plane)
    assert rec.scanned == 1 and plane._owed == ([], [])
    shed = 0
    while plane._owed is not None:
        plane.ingest(ent_msg(peer, [Entity(
            uuid=e, position=Vector3(20 * i + 1 + shed % 2, 0, 0),
            world_name="w") for i, e in enumerate(ents[:8])]))
        plane.apply(plane.collect_tick(plane.dispatch_tick()),
                    skip_frames=True)
        shed += 1
        assert shed < 400
    assert shed == plane.delta_sim_ticks >= 2
    tick(plane)
    assert rec.scanned == 2 and rec.hinted == 0
    tick(plane)
    assert rec.hinted == 1


def test_plane_without_a_manager_keeps_nothing_for_one():
    backend, plane = make_plane(k=4)
    assert backend.configure_delta_ticks("on")
    plane._delta_ticks = True
    peer = uuid.uuid4()
    ents = [uuid.uuid4() for _ in range(8)]
    plane.ingest(ent_msg(peer, [
        Entity(uuid=e, position=Vector3(20 * i, 0, 0), world_name="w")
        for i, e in enumerate(ents)]))
    for i in range(4):
        plane.ingest(ent_msg(peer, [Entity(
            uuid=ents[i], position=Vector3(20 * i + 1, 0, 0), world_name="w")]))
        plane.ingest(ent_msg(peer, [Entity(uuid=ents[-1 - i])],
                             parameter=PARAM_REMOVE))
        tick(plane)
        assert plane._owed is None
    assert plane.delta_sim_ticks >= 2


# endregion
