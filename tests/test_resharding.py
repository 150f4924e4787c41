"""Live resharding (ISSUE 19): placement epochs, capsule transfer and
crash-safe online migration.

Four layers, cheapest first:

* **PlacementMap units** — monotone epochs, override routing, spec
  serialization round-trips, last-writer-wins convergence.
* **Transfer units** — the CRC-framed chunk codec (reorder, repeat,
  corruption, resume-from-zero) and the byte-bounded transfer buffer
  (arrival-order replay, counted shed).
* **Kill-at-every-protocol-state property test** — a scripted
  in-process cluster simulator drives :class:`MigrationCoordinator`
  through the real protocol and SIGKILLs (simulated) either shard at
  every awaitable state. The invariant at every kill point: the
  protocol terminates, EXACTLY ONE shard owns the world afterwards
  (source on abort, target on completion — with the loser told to
  scrub/tombstone), and every parked frame replays in arrival order.
* **Real-socket e2e** — a 2-shard cluster over real subprocesses:
  zero record loss through a live migration (records offered before,
  during and after the move all read back), plus the SIGKILL legs
  (source before the fence, source mid-stream, destination
  mid-import, source after the flip) marked ``slow`` for the CI
  cluster step.
"""

import asyncio
import json
import os
import random
import socket
import time
import urllib.error
import urllib.request
import uuid as uuid_mod

# Children spawned by the supervisor inherit this env: without it a
# `python -m worldql_server_tpu` child may initialize the installed-
# but-hardwareless libtpu plugin and hang in device discovery.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest

from worldql_server_tpu.cluster import ClusterRuntime
from worldql_server_tpu.cluster import tracectx
from worldql_server_tpu.cluster.resharding import (
    ChunkAssembler,
    MigrationCoordinator,
    PlacementMap,
    TransferBuffer,
    encode_chunks,
    fence_payload,
    parse_fence,
)
from worldql_server_tpu.cluster.shard import ClusterShardExtension
from worldql_server_tpu.engine.config import Config
from worldql_server_tpu.protocol.types import (
    Instruction,
    Message,
    Record,
    Vector3,
)
from worldql_server_tpu.scenarios.client import ZmqPeer

POS = Vector3(5.0, 5.0, 5.0)


# region: PlacementMap units


def test_placement_is_worldmap_at_epoch_zero():
    pm = PlacementMap(4)
    base = PlacementMap(4)
    for i in range(50):
        world = f"w{i}"
        assert pm.shard_of_world(world) == base.base_shard_of_world(world)
    assert pm.epoch == 0
    assert pm.describe()["epoch"] == 0


def test_move_world_bumps_epoch_and_overrides_routing():
    pm = PlacementMap(2)
    world = "arena"
    source = pm.shard_of_world(world)
    target = 1 - source
    peer = uuid_mod.uuid4()

    epoch = pm.move_world(world, target, [peer])
    assert epoch == 1 and pm.epoch == 1
    assert pm.shard_of_world(world) == target
    assert pm.base_shard_of_world(world) == source
    assert pm.shard_of_peer(peer) == target

    # moving HOME drops the override instead of carrying a redundant
    # one forever — but the epoch still advances (the change is real)
    epoch = pm.move_world(world, source, [peer])
    assert epoch == 2
    assert pm.world_overrides == {}
    assert pm.shard_of_world(world) == source

    # clear_peer reaps without a bump: base-hash routing for a dead
    # peer is indistinguishable from the override
    pm.move_world(world, target, [peer])
    before = pm.epoch
    pm.clear_peer(peer)
    assert pm.epoch == before
    assert peer.hex not in pm.peer_overrides


def test_spec_roundtrip_and_monotone_convergence():
    pm = PlacementMap(2)
    world, peer = "lobby", uuid_mod.uuid4()
    pm.move_world(world, 1 - pm.shard_of_world(world), [peer])
    spec = json.loads(json.dumps(pm.to_spec()))  # real wire trip

    follower = PlacementMap(2)
    assert follower.apply_spec(spec)
    assert follower.epoch == pm.epoch
    assert follower.shard_of_world(world) == pm.shard_of_world(world)
    assert follower.shard_of_peer(peer) == pm.shard_of_peer(peer)

    # stale and same-epoch specs are REJECTED: applying specs in any
    # arrival order converges on the newest
    assert not follower.apply_spec(spec)
    assert not follower.apply_spec({**spec, "epoch": spec["epoch"] - 1})
    newer = dict(spec, epoch=spec["epoch"] + 5, worlds={})
    assert follower.apply_spec(newer)
    assert follower.epoch == spec["epoch"] + 5
    assert follower.world_overrides == {}

    # from_spec accepts a well-formed epoch-0 document; garbage is a
    # no-op at epoch 0
    fresh = PlacementMap.from_spec(2, {"epoch": 0, "worlds": {}, "peers": {}})
    assert fresh.epoch == 0
    assert PlacementMap.from_spec(2, {"bogus": True}).epoch == 0
    assert not PlacementMap(2).apply_spec({"epoch": "NaN-ish?"})


# endregion

# region: transfer units


def _big_doc(n=400):
    return {
        "world": "arena",
        "records": [
            {"uuid": uuid_mod.uuid4().hex, "data": "x" * 100, "i": i}
            for i in range(n)
        ],
        "sessions": [{"uuid": uuid_mod.uuid4().hex}],
    }


def test_chunk_codec_roundtrip_reorder_and_repeat():
    doc = _big_doc()
    chunks = encode_chunks(doc)
    assert len(chunks) > 1, "document must actually span chunks"

    # in-order
    asm = ChunkAssembler()
    out = None
    for chunk in chunks:
        out = asm.feed(chunk) or out
    assert out == doc and not asm.corrupt

    # shuffled + repeated chunks (resume re-streams from zero)
    asm = ChunkAssembler()
    order = chunks + chunks[: len(chunks) // 2]
    random.Random(19).shuffle(order)
    out = None
    for chunk in order:
        out = asm.feed(chunk) or out
    assert out == doc and not asm.corrupt


def test_chunk_codec_fails_loudly_on_corruption():
    chunks = encode_chunks(_big_doc())

    # flipped payload byte → per-chunk CRC catches it
    asm = ChunkAssembler()
    bad = dict(chunks[0], data="!" + chunks[0]["data"][1:])
    assert asm.feed(bad) is None and asm.corrupt
    # poisoned until reset — even good chunks are refused
    assert asm.feed(chunks[0]) is None
    asm.reset()
    assert not asm.corrupt

    # cross-wired streams (total_crc mismatch) → corrupt
    other = encode_chunks({"different": "doc", "pad": "y" * 30_000})
    asm = ChunkAssembler()
    asm.feed(chunks[0])
    asm.feed(other[1])
    assert asm.corrupt

    # shape garbage → corrupt, not an exception
    asm = ChunkAssembler()
    asm.feed({"seq": "??"})
    assert asm.corrupt


def test_transfer_buffer_bounded_counted_arrival_order():
    buf = TransferBuffer(max_bytes=100)
    assert buf.park(b"a" * 60)
    assert buf.park(b"b" * 40)
    assert not buf.park(b"c")          # over budget: shed AND counted
    assert buf.stats() == {
        "parked_frames": 2, "parked_bytes": 100, "shed": 1,
    }
    assert buf.replay() == [b"a" * 60, b"b" * 40]
    assert buf.parked_bytes == 0
    assert buf.replay() == []          # drained exactly once
    assert buf.shed == 1               # the shed count survives replay


def test_epoch_prefix_and_fence_wire_format():
    payload = b"\x01\x02frame"
    framed = tracectx.wrap_epoch(payload, 7, 9, 3)
    assert framed[:4] == tracectx.MAGIC2
    assert tracectx.unwrap_epoch(framed) == (7, 9, 3, payload)
    # v1 frames and bare bytes decode as epoch 0 — never stale
    assert tracectx.unwrap_epoch(tracectx.wrap(payload, 7, 9)) == \
        (7, 9, 0, payload)
    assert tracectx.unwrap_epoch(payload) == (0, 0, 0, payload)

    fence = fence_payload(42)
    assert parse_fence(fence) == 42
    assert parse_fence(b"not a fence") is None
    assert parse_fence(fence[:4] + b"{garbage") is None


# endregion

# region: shard-side staleness + re-route (stubbed extension)


class _StubMetrics:
    def __init__(self):
        self.counts = {}

    def inc(self, name, n=1):
        self.counts[name] = self.counts.get(name, 0) + n


class _StubShard:
    """The minimal surface ``frame_stale``/``frame_misrouted`` touch,
    borrowing the REAL methods off ClusterShardExtension."""

    frame_stale = ClusterShardExtension.frame_stale
    frame_misrouted = ClusterShardExtension.frame_misrouted

    def __init__(self, shard_id, placement):
        self.shard_id = shard_id
        self.placement = placement
        self.rerouted = 0
        self.sent = []

        class _Server:
            metrics = _StubMetrics()

        self.server = _Server()

    def _ctl_send_retry(self, packet, deadline_s=5.0):
        return packet

    def _spawn_reshard(self, packet):
        self.sent.append(packet)


def test_frame_stale_only_for_older_nonzero_epochs():
    placement = PlacementMap(2)
    placement.move_world("arena", 1 - placement.shard_of_world("arena"))
    shard = _StubShard(0, placement)
    assert placement.epoch == 1
    assert not shard.frame_stale(0)       # no placement claim
    assert not shard.frame_stale(1)       # current
    assert not shard.frame_stale(7)       # newer: router knows better
    placement.bump()
    assert shard.frame_stale(1)           # older than local map


def test_stale_frame_for_moved_world_bounces_as_reroute():
    placement = PlacementMap(2)
    world = "arena"
    source = placement.shard_of_world(world)
    placement.move_world(world, 1 - source)

    shard = _StubShard(source, placement)
    message = Message(
        instruction=Instruction.LOCAL_MESSAGE, world_name=world,
        position=POS,
    )
    message.wire = b"original wire bytes"
    assert shard.frame_misrouted(message, epoch=0)
    assert shard.rerouted == 1
    assert shard.server.metrics.counts["cluster.shard_rerouted"] == 1
    [packet] = shard.sent
    assert packet["op"] == "reroute"
    import base64

    assert base64.b64decode(packet["data"]) == b"original wire bytes"

    # the NEW owner processes the same stale-stamped frame
    owner = _StubShard(1 - source, placement)
    assert not owner.frame_misrouted(message, epoch=0)
    assert owner.sent == []

    # worlds that never moved: stale stamp, still the right owner
    still_home = Message(
        instruction=Instruction.LOCAL_MESSAGE, world_name="elsewhere9",
        position=POS,
    )
    still_home.wire = b"x"
    home = _StubShard(placement.shard_of_world("elsewhere9"), placement)
    assert not home.frame_misrouted(still_home, epoch=0)

    # peer-scoped instructions check peer placement; no sender → no
    # bounce (nothing to route by)
    hs = Message(instruction=Instruction.HANDSHAKE, sender_uuid=None)
    assert not shard.frame_misrouted(hs, epoch=0)


# endregion

# region: kill-at-every-protocol-state property test


class _SimMetrics(_StubMetrics):
    pass


class _SimCluster:
    """Scripted 2-shard cluster behind the exact router surface the
    coordinator drives: shards answer control packets after small
    async delays (the kill windows), a dead shard swallows packets,
    and a revived one replays the router-side ready hooks."""

    def __init__(self, source=0, target=1):
        self.world_map = PlacementMap(2)
        self.metrics = _SimMetrics()
        self.supervisor = self
        self.source, self.target = source, target
        # the abort-path owner assertion needs base-hash == source
        self.world = next(
            f"arena{i}" for i in range(10_000)
            if self.world_map.shard_of_world(f"arena{i}") == source
        )
        self.dead = set()
        self.replayed = []
        self.tombstones = []
        self.aborts = []
        self.coordinator = None
        self.capsule = _big_doc(300)
        self.capsule["world"] = self.world
        self._import_asm = ChunkAssembler()
        self._import_xfer = None
        self._tasks = set()

    # --- the router surface MigrationCoordinator drives ---

    def send_fence(self, shard, xfer):
        self._later(self._ack_fence(shard, xfer))
        return True

    def ctl_send(self, shard, msg):
        op = msg.get("op")
        if shard in self.dead:
            return True  # queued into a channel nobody reads
        if op == "reshard_export":
            self._later(self._export(msg))
        elif op == "reshard_import_chunk":
            self._later(self._import_chunk(msg))
        elif op == "reshard_tombstone":
            self._later(self._ack_tombstone(msg))
        elif op == "reshard_abort":
            self.aborts.append((shard, dict(msg)))
        return True

    def route_replay(self, data):
        self.replayed.append(data)

    def broadcast_placement(self):
        pass

    def queue_tombstone(self, shard, world, xfer):
        self.tombstones.append((shard, world, xfer))
        self.ctl_send(shard, {
            "op": "reshard_tombstone", "xfer": xfer, "world": world,
        })

    # --- scripted shard behavior ---

    def _later(self, coro):
        task = asyncio.get_running_loop().create_task(coro)  # wql: allow(unsupervised-task) — test harness, retained
        self._tasks.add(task)
        task.add_done_callback(self._tasks.discard)

    async def _ack_fence(self, shard, xfer):
        await asyncio.sleep(0.02)
        if shard not in self.dead:
            self.coordinator.on_fence_ack(shard, {"xfer": xfer})

    async def _export(self, msg):
        await asyncio.sleep(0.02)
        for chunk in encode_chunks(self.capsule):
            await asyncio.sleep(0.005)
            if self.source in self.dead:
                return
            self.coordinator.on_chunk(
                self.source, {"xfer": msg["xfer"], "chunk": chunk}
            )

    async def _import_chunk(self, msg):
        await asyncio.sleep(0.002)
        if self.target in self.dead:
            return
        if self._import_xfer != msg["xfer"] or msg["chunk"]["seq"] == 0:
            self._import_xfer = msg["xfer"]
            self._import_asm = ChunkAssembler()
        doc = self._import_asm.feed(msg["chunk"])
        if doc is not None:
            await asyncio.sleep(0.03)  # the durable-import window
            if self.target in self.dead:
                return
            self.coordinator.on_import_ack(self.target, {
                "xfer": msg["xfer"],
                "counts": {"records": len(doc["records"])},
            })

    async def _ack_tombstone(self, msg):
        await asyncio.sleep(0.02)
        if self.source not in self.dead:
            self.coordinator.on_tombstone_ack(
                self.source, {"xfer": msg["xfer"]}
            )

    # --- chaos ---

    def kill(self, shard):
        self.dead.add(shard)
        self.coordinator.on_shard_down(shard)

    def revive(self, shard):
        self.dead.discard(shard)
        self.coordinator.on_shard_ready(shard)
        # the real router replays queued tombstones on every ready
        for (s, world, xfer) in self.tombstones:
            if s == shard:
                self.ctl_send(s, {
                    "op": "reshard_tombstone", "xfer": xfer,
                    "world": world,
                })


async def _run_kill_case(victim, kill_state):
    sim = _SimCluster()
    world = sim.world
    coordinator = MigrationCoordinator(
        sim, world, sim.source, sim.target, xfer_id=1,
        buffer_bytes=1 << 20,
    )
    sim.coordinator = coordinator
    parked = [f"frame{i}".encode() for i in range(5)]
    for frame in parked[:3]:
        coordinator.buffer.park(frame)

    async def chaos():
        while coordinator.state != kill_state:
            if coordinator.state in ("done", "aborted"):
                return  # the protocol outran the chaos: invalid run
            await asyncio.sleep(0.001)
        if kill_state == "importing" and coordinator._import_ack.is_set():
            return
        sim.kill(victim)
        # traffic keeps arriving — park it exactly when the REAL
        # router would (should_park goes False from the flip on)
        for frame in parked[3:]:
            if coordinator.should_park(None, sim.world, None):
                coordinator.buffer.park(frame)
        await asyncio.sleep(0.05)
        sim.revive(victim)  # the supervisor restarts every corpse

    run = asyncio.ensure_future(coordinator.run())
    chaos_task = asyncio.ensure_future(chaos())
    migrated = await asyncio.wait_for(run, timeout=30)
    await chaos_task
    for task in list(sim._tasks):
        task.cancel()

    # --- the universal invariants: terminal state, exactly one owner,
    # the loser told to scrub, every parked frame replayed in order ---
    assert coordinator.state in ("done", "aborted")
    assert not coordinator.active
    owner = sim.world_map.shard_of_world(world)
    if migrated:
        assert coordinator.state == "done"
        assert owner == sim.target
        assert sim.world_map.epoch >= 1
        assert (sim.target, world, 1) not in sim.tombstones
        assert [s for (s, _, _) in sim.tombstones] == [sim.source]
    else:
        assert coordinator.state == "aborted"
        assert owner == sim.source
        assert sim.world_map.epoch == 0
        assert [s for (s, _) in sim.aborts] == [sim.target]
        assert sim.tombstones == []
    replayed_parked = [f for f in sim.replayed if f in parked]
    assert replayed_parked == [
        f for f in parked if f in sim.replayed
    ], "parked frames must replay in arrival order"
    assert len(sim.replayed) == coordinator.replayed
    assert coordinator.buffer.replay() == [], "buffer fully drained"
    return migrated, coordinator


@pytest.mark.parametrize("victim,state,expect_migrated", [
    ("source", "freeze", False),
    ("source", "streaming", False),
    ("source", "importing", False),
    ("source", "tombstoning", True),
    ("target", "freeze", True),
    ("target", "streaming", True),
    ("target", "importing", True),
])
def test_kill_at_every_protocol_state(victim, state, expect_migrated):
    """SIGKILL either shard at every awaitable protocol state: source
    death before the durable import ack aborts with ownership intact
    on the source; source death after it completes (the tombstone
    queue catches the restart); destination death NEVER aborts — the
    retained chunks re-stream from zero on its ready."""

    async def case():
        sim_victim = 0 if victim == "source" else 1
        migrated, coordinator = await _run_kill_case(sim_victim, state)
        assert migrated == expect_migrated, (
            f"kill {victim}@{state}: expected "
            f"{'migration' if expect_migrated else 'abort'}, got "
            f"state {coordinator.state} ({coordinator.error})"
        )
        if not migrated:
            assert "died before the durable import ack" in (
                coordinator.error or ""
            )
        return migrated

    asyncio.run(case())


def test_parked_frames_shed_past_budget_counted():
    """The transfer buffer's byte budget holds through the protocol:
    overflow during a migration is COUNTED shed, and the admitted
    frames still replay."""

    async def case():
        sim = _SimCluster()
        coordinator = MigrationCoordinator(
            sim, sim.world, 0, 1, xfer_id=2, buffer_bytes=32,
        )
        sim.coordinator = coordinator
        assert coordinator.buffer.park(b"a" * 30)
        assert not coordinator.buffer.park(b"b" * 30)
        migrated = await asyncio.wait_for(coordinator.run(), timeout=30)
        assert migrated
        assert sim.replayed == [b"a" * 30]
        assert coordinator.buffer.shed == 1

    asyncio.run(case())


# endregion

# region: real-socket e2e


def _port_block(n: int, attempts: int = 64) -> int:
    for _ in range(attempts):
        socks = []
        try:
            s0 = socket.socket()
            s0.bind(("127.0.0.1", 0))
            base = s0.getsockname()[1]
            socks.append(s0)
            for off in range(1, n + 1):
                s = socket.socket()
                s.bind(("127.0.0.1", base + off))
                socks.append(s)
            return base
        except OSError:
            continue
        finally:
            for s in socks:
                s.close()
    raise RuntimeError("could not find a free port block")


def _cluster_config(tmp_path, n_shards: int = 2) -> Config:
    # ONE block for both port families (the test_cluster.py idiom)
    base = _port_block(2 * n_shards + 1)
    http_base = base + n_shards + 1
    return Config(
        store_url=f"sqlite://{tmp_path}/records.db",
        http_enabled=True, http_host="127.0.0.1", http_port=http_base,
        ws_enabled=False,
        zmq_server_host="127.0.0.1", zmq_server_port=base,
        spatial_backend="cpu",
        tick_interval=0.02,
        durability="wal", wal_dir=str(tmp_path / "wal"),
        checkpoint_interval=0,   # SIGKILL must find the WAL un-truncated
        session_ttl=30.0,
        cluster_shards=n_shards,
        verbose=0,
    )


def _world_for_shard(world_map, shard: int, stem: str) -> str:
    for i in range(10_000):
        name = f"{stem}{i}"
        if world_map.shard_of_world(name) == shard:
            return name
    raise AssertionError("no world name found for shard")


async def _wait(predicate, timeout_s: float, what: str, interval=0.05):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        result = predicate()
        if result:
            return result
        await asyncio.sleep(interval)
    raise AssertionError(f"timed out waiting for {what}")


async def _connect(config, peer_uuid=None, token=None) -> ZmqPeer:
    last = None
    for _ in range(100):
        try:
            return await ZmqPeer.connect(
                config.zmq_server_port, peer_uuid=peer_uuid, token=token,
            )
        except Exception as exc:
            last = exc
            await asyncio.sleep(0.05)
    raise AssertionError(f"client could not connect: {last!r}")


async def _create_records(client, world: str, n: int, tag: str) -> set:
    want = set()
    for i in range(n):
        rec = uuid_mod.uuid4()
        await client.send(Message(
            instruction=Instruction.RECORD_CREATE, world_name=world,
            records=[Record(uuid=rec, position=POS, world_name=world,
                            data=f"{tag}{i}")],
        ))
        want.add(rec)
    return want


async def _readable(client, world: str, want: set,
                    timeout_s: float = 30) -> set:
    deadline = time.monotonic() + timeout_s
    seen: set = set()
    while time.monotonic() < deadline and not want <= seen:
        await client.send(Message(
            instruction=Instruction.RECORD_READ, world_name=world,
            position=POS,
        ))
        try:
            reply = await client.recv_until(Instruction.RECORD_REPLY, 5)
        except asyncio.TimeoutError:
            continue
        seen |= {r.uuid for r in reply.records}
    return want & seen


async def _await_migration(router, timeout_s: float = 90) -> str:
    # (longer than the coordinator's own wait for the export, 60 s: a
    # migration that gives up says why in ``describe()``, which a wait
    # of the same 60 s cut off)
    await _wait(
        lambda: router.migration is not None
        and router.migration.state in ("done", "aborted"),
        timeout_s, "migration terminal state",
    )
    return router.migration.state


async def _post_json(url: str, body: dict) -> tuple[int, dict]:
    def blocking():
        req = urllib.request.Request(
            url, data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"}, method="POST",
        )
        try:
            with urllib.request.urlopen(req, timeout=10) as resp:
                return resp.status, json.loads(resp.read() or b"{}")
        except urllib.error.HTTPError as err:
            return err.code, json.loads(err.read() or b"{}")

    # urllib is blocking; the router's HTTP server shares this loop
    return await asyncio.to_thread(blocking)


async def _live_reshard_e2e(tmp_path):
    """The happy path over real sockets: records before + during +
    after a POST /reshard-triggered migration all read back; the
    placement epoch converges to both shard processes."""
    config = _cluster_config(tmp_path)
    runtime = ClusterRuntime(config)
    await runtime.start()
    clients = []
    try:
        router = runtime.router
        world = _world_for_shard(router.world_map, 0, "arena")
        client = await _connect(config)
        clients.append(client)

        want = await _create_records(client, world, 20, "pre")
        assert await _readable(client, world, set(want)) == want

        # records keep arriving while the migration runs
        during: set = set()
        stop = asyncio.Event()

        async def mid_traffic():
            while not stop.is_set():
                during.update(
                    await _create_records(client, world, 1, "mid")
                )
                await asyncio.sleep(0.01)

        traffic = asyncio.ensure_future(mid_traffic())
        status, body = await _post_json(
            f"http://127.0.0.1:{config.http_port}/reshard",
            {"world": world, "target": 1},
        )
        assert status == 202 and body["xfer"] >= 1
        state = await _await_migration(router)
        stop.set()
        await traffic
        assert state == "done", router.migration.describe()

        # placement flipped and the epoch converged to BOTH shard
        # processes over their ~1s control-state packets
        assert router.world_map.shard_of_world(world) == 1
        assert router.world_map.epoch >= 1
        for idx in range(2):
            await _wait(
                lambda: runtime.supervisor.shard_state(idx).get(
                    "placement_epoch", -1) >= router.world_map.epoch,
                30, f"shard {idx} placement convergence",
            )

        post = await _create_records(client, world, 10, "post")
        want |= during | post
        found = await _readable(client, world, set(want))
        assert found == want, (
            f"lost {len(want - found)} of {len(want)} records across "
            f"the migration ({router.migration.describe()})"
        )
        # a refused no-op: the world is already there
        status, _ = await _post_json(
            f"http://127.0.0.1:{config.http_port}/reshard",
            {"world": world, "target": 1},
        )
        assert status == 400
    finally:
        for c in clients:
            c.close()
        await runtime.stop()


def test_live_reshard_e2e_zero_loss(tmp_path):
    asyncio.run(_live_reshard_e2e(tmp_path))


async def _kill_case_e2e(tmp_path, kill):
    """One SIGKILL leg over real subprocesses. ``kill(runtime,
    router)`` is an async hook that murders a shard at its chosen
    protocol moment and returns the expected terminal state (or None
    for either). Universal invariants: the migration terminates, all
    records survive (readable after every restart settles), and the
    world routes to exactly one owner consistent with the outcome."""
    config = _cluster_config(tmp_path)
    runtime = ClusterRuntime(config)
    await runtime.start()
    clients = []
    try:
        router = runtime.router
        world = _world_for_shard(router.world_map, 0, "arena")
        client = await _connect(config)
        clients.append(client)

        # a capsule heavy enough to hold the protocol windows open
        want = await _create_records(client, world, 400, "r")
        assert len(await _readable(client, world, set(want))) == 400

        expect = await kill(runtime, router, world)
        state = await _await_migration(router, timeout_s=120)
        if expect is not None:
            assert state == expect, router.migration.describe()

        # every corpse restarts before the books close
        for idx in range(2):
            await _wait(
                lambda: runtime.supervisor.shard_alive(idx), 90,
                f"shard {idx} alive",
            )
        owner = router.world_map.shard_of_world(world)
        assert owner == (1 if state == "done" else 0), (
            "exactly one owner, consistent with the protocol outcome"
        )

        # zero loss: reads (routed to the surviving owner) return every
        # record after the restarted shard's WAL replay
        probe = await _connect(config)
        clients.append(probe)
        found = await _readable(probe, world, set(want), timeout_s=60)
        assert found == want, (
            f"lost {len(want - found)} of {len(want)} records "
            f"(outcome={state}, owner={owner})"
        )
        # the surviving topology still takes writes for the world
        extra = await _create_records(probe, world, 5, "post")
        assert await _readable(probe, world, set(extra)) == extra
        return state
    finally:
        for c in clients:
            c.close()
        await runtime.stop()


@pytest.mark.slow
def test_reshard_sigkill_source_before_fence(tmp_path):
    """Source SIGKILLed with the migration in freeze: the fence ack
    never comes, the death notice aborts, and the source's restart
    recovers the world from its OWN WAL — ownership never moved."""

    async def kill(runtime, router, world):
        runtime.supervisor.kill_shard(0)
        xfer = router.start_reshard(world, 1, reason="chaos")
        assert xfer is not None
        return "aborted"

    state = asyncio.run(_kill_case_e2e(tmp_path, kill))
    assert state == "aborted"


@pytest.mark.slow
def test_reshard_sigkill_source_mid_stream(tmp_path):
    """Source SIGKILLed while streaming the capsule: no durable import
    ack exists, so the coordinator aborts and the restarted source
    still owns every record."""

    async def kill(runtime, router, world):
        xfer = router.start_reshard(world, 1, reason="chaos")
        assert xfer is not None
        await _wait(
            lambda: router.migration.state in ("streaming", "importing")
            and not router.migration._import_ack.is_set(),
            30, "pre-ack protocol state", interval=0.001,
        )
        if router.migration._import_ack.is_set():
            return None  # the protocol outran the chaos on this box
        runtime.supervisor.kill_shard(0)
        return None  # aborted unless the ack squeaked in first

    asyncio.run(_kill_case_e2e(tmp_path, kill))


@pytest.mark.slow
def test_reshard_sigkill_target_mid_import(tmp_path):
    """Destination SIGKILLed mid-import: never an abort — the router
    re-streams the retained capsule from zero when the restarted
    destination reports ready, and the migration completes with zero
    loss THROUGH the destination's own durability pipeline."""

    async def kill(runtime, router, world):
        xfer = router.start_reshard(world, 1, reason="chaos")
        assert xfer is not None
        await _wait(
            lambda: router.migration.state
            in ("streaming", "importing"),
            30, "transfer in flight", interval=0.001,
        )
        runtime.supervisor.kill_shard(1)
        return "done"

    state = asyncio.run(_kill_case_e2e(tmp_path, kill))
    assert state == "done"


@pytest.mark.slow
def test_reshard_sigkill_source_after_flip(tmp_path):
    """Source SIGKILLed once the migration completed: the flip is
    durable, the queued tombstone catches the source's restart, and
    reads keep answering from the new owner throughout."""

    async def kill(runtime, router, world):
        xfer = router.start_reshard(world, 1, reason="chaos")
        assert xfer is not None
        await _await_migration(router)
        assert router.migration.state == "done"
        runtime.supervisor.kill_shard(0)
        return "done"

    state = asyncio.run(_kill_case_e2e(tmp_path, kill))
    assert state == "done"


# endregion
