"""Subscription-index snapshot/restore (spatial/snapshot.py).

The reference loses all subscriptions on restart; the snapshot lets a
server checkpoint its index at shutdown and serve identical fan-out
after reboot without a re-subscribe storm.
"""

import asyncio
import uuid

import numpy as np
import pytest

from worldql_server_tpu.protocol.types import Replication, Vector3
from worldql_server_tpu.spatial.backend import LocalQuery
from worldql_server_tpu.spatial.cpu_backend import CpuSpatialBackend
from worldql_server_tpu.spatial.snapshot import (
    SnapshotError, load_snapshot, save_snapshot,
)
from worldql_server_tpu.spatial.hashing import next_pow2
from worldql_server_tpu.spatial.tpu_backend import TpuSpatialBackend


def populate(b, n=150, worlds=("alpha", "beta")):
    rng = np.random.default_rng(5)
    peers = [uuid.UUID(int=i + 1) for i in range(n)]
    pos = rng.uniform(-200, 200, (n, 3))
    for i, p in enumerate(peers):
        b.add_subscription(worlds[i % len(worlds)], p, Vector3(*pos[i]))
    # churn: some removals and a disconnect, so tombstones are live
    for i in range(0, n, 7):
        b.remove_subscription(
            worlds[i % len(worlds)], peers[i], Vector3(*pos[i])
        )
    b.remove_peer(peers[3])
    b.flush()
    return peers, pos, worlds


def assert_equivalent(a, b, peers, pos, worlds):
    assert b.subscription_count() == a.subscription_count()
    for w in worlds:
        assert b.query_world(w) == a.query_world(w)
        assert b.cube_count(w) == a.cube_count(w)
    queries = [
        LocalQuery(worlds[i % len(worlds)], Vector3(*pos[i]),
                   peers[i], Replication.EXCEPT_SELF)
        for i in range(0, len(peers), 5)
    ]
    for got, want in zip(b.match_local_batch(queries),
                         a.match_local_batch(queries)):
        assert set(got) == set(want)


@pytest.mark.parametrize("make", [
    lambda: CpuSpatialBackend(16),
    lambda: TpuSpatialBackend(16),
    lambda: TpuSpatialBackend(16, compact_threshold=16),
], ids=["cpu", "tpu", "tpu-compacted"])
def test_snapshot_roundtrip(tmp_path, make):
    src = make()
    peers, pos, worlds = populate(src)
    if hasattr(src, "wait_compaction"):
        src.wait_compaction()
    path = str(tmp_path / "index.npz")
    saved = save_snapshot(src, path)
    assert saved == src.subscription_count()

    dst = make()
    restored, restored_peers = load_snapshot(dst, path)
    assert restored == saved
    assert set(restored_peers) <= set(peers)
    assert_equivalent(src, dst, peers, pos, worlds)


@pytest.mark.parametrize("make", [
    lambda: CpuSpatialBackend(16),
    lambda: TpuSpatialBackend(16),
], ids=["cpu", "tpu"])
def test_snapshot_drop_peers_equals_evicting_them_first(tmp_path, make):
    """Shutdown drops never-reconnected peers from the EXPORT; the file
    must be what evicting them from the index and saving would give."""
    src = make()
    peers, pos, worlds = populate(src)
    ghosts = peers[::3]
    path = str(tmp_path / "dropped.npz")
    saved = save_snapshot(src, path, drop_peers=ghosts)

    for peer in ghosts:
        src.remove_peer(peer)
    assert saved == src.subscription_count()
    dst = make()
    restored, restored_peers = load_snapshot(dst, path)
    assert restored == saved
    assert not set(restored_peers) & set(ghosts)
    assert_equivalent(src, dst, peers, pos, worlds)


def _resilient_tpu():
    from worldql_server_tpu.robustness.resilient import ResilientBackend

    return ResilientBackend(TpuSpatialBackend(16))


@pytest.mark.parametrize("make", [
    lambda: CpuSpatialBackend(16),
    lambda: TpuSpatialBackend(16),
    lambda: TpuSpatialBackend(16, compact_threshold=16),
    _resilient_tpu,
], ids=["cpu", "tpu", "tpu-compacted", "resilient"])
def test_remove_peers_equals_removing_each(make):
    """The restored-index sweep sheds its ghosts in one vectorized pass
    (base and delta rows alike): same index as one remove_peer each."""
    bulk, each = make(), make()
    peers, pos, worlds = populate(bulk)
    populate(each)
    for b in (bulk, each):
        if hasattr(b, "wait_compaction"):
            b.wait_compaction()
    # peers[3] left in populate() and holds nothing; one is unknown
    ghosts = peers[::3] + [uuid.uuid4()]
    held = sum(each.remove_peer(peer) for peer in ghosts)
    assert bulk.remove_peers(ghosts) == held > 0
    bulk.flush()
    each.flush()
    assert_equivalent(each, bulk, peers, pos, worlds)
    assert bulk.remove_peers(ghosts) == 0


def test_remove_peers_sheds_a_restored_index_in_one_pass():
    """One remove_peer per ghost costs the device backend a millisecond
    each — 200,000 of them would hold the event loop for minutes."""
    import time

    n = 200_000
    rng = np.random.default_rng(11)
    peers = [uuid.UUID(int=i + 1) for i in range(n)]
    b = TpuSpatialBackend(16)
    b.bulk_add_subscriptions("w", peers, rng.integers(-40, 40, (n, 3)))
    b.flush()
    t0 = time.perf_counter()
    assert b.remove_peers(peers[64:]) == n - 64
    assert time.perf_counter() - t0 < 20.0
    b.flush()
    assert b.subscription_count() == 64
    assert b.query_world("w") == set(peers[:64])


def test_snapshot_cross_backend(tmp_path):
    """A CPU-built snapshot restores into the TPU backend and vice
    versa — the format carries semantics, not layout."""
    cpu = CpuSpatialBackend(16)
    peers, pos, worlds = populate(cpu)
    path = str(tmp_path / "x.npz")
    save_snapshot(cpu, path)
    tpu = TpuSpatialBackend(16)
    load_snapshot(tpu, path)
    assert_equivalent(cpu, tpu, peers, pos, worlds)

    path2 = str(tmp_path / "y.npz")
    save_snapshot(tpu, path2)
    cpu2 = CpuSpatialBackend(16)
    load_snapshot(cpu2, path2)
    assert_equivalent(tpu, cpu2, peers, pos, worlds)


def test_snapshot_rejects_wrong_grid(tmp_path):
    b = CpuSpatialBackend(16)
    populate(b, n=10)
    path = str(tmp_path / "g.npz")
    save_snapshot(b, path)
    other = CpuSpatialBackend(32)
    with pytest.raises(SnapshotError, match="cube_size"):
        load_snapshot(other, path)
    assert other.subscription_count() == 0  # never half-loaded


def test_server_restart_keeps_subscriptions(tmp_path):
    """e2e: subscribe over a real WebSocket, stop the server, boot a
    NEW server on the same snapshot path — fan-out works without
    re-subscribing."""
    pytest.importorskip("websockets")
    from tests.client_util import WsClient, free_port
    from worldql_server_tpu.engine.config import Config
    from worldql_server_tpu.engine.server import WorldQLServer
    from worldql_server_tpu.protocol.types import Instruction, Message

    snap = str(tmp_path / "server-index.npz")

    def make_config():
        config = Config(store_url="memory://")
        config.ws_port = free_port()
        config.http_enabled = False
        config.zmq_enabled = False
        config.spatial_backend = "tpu"
        config.index_snapshot = snap
        return config

    async def scenario():
        pos = Vector3(5.0, 5.0, 5.0)
        server = WorldQLServer(make_config())
        await server.start()
        listener = await WsClient.connect(server.config.ws_port)
        await listener.send(Message(
            instruction=Instruction.AREA_SUBSCRIBE,
            world_name="w", position=pos,
        ))
        await asyncio.sleep(0.2)
        listener_uuid = listener.uuid
        # stop with the client still connected: the checkpoint must
        # capture the SERVING state, before transport close evicts the
        # connected peers
        await server.stop()
        await listener.connection.close()

        server2 = WorldQLServer(make_config())
        await server2.start()
        try:
            # restored WITHOUT any re-subscribe
            assert server2.backend.is_subscribed_any("w", listener_uuid)
            got = server2.backend.match_local_batch([LocalQuery(
                "w", pos, uuid.uuid4(), Replication.EXCEPT_SELF,
            )])
            assert got == [[listener_uuid]]
        finally:
            await server2.stop()
        return True

    assert asyncio.run(scenario())


def test_zmq_peer_keeps_subscription_across_restart(tmp_path):
    """The headline path: a ZeroMQ peer (client-chosen UUID) reconnects
    after a server restart and receives area fan-out WITHOUT
    re-subscribing."""
    from tests.client_util import ZmqClient, free_port
    from worldql_server_tpu.engine.config import Config
    from worldql_server_tpu.engine.server import WorldQLServer
    from worldql_server_tpu.protocol.types import Instruction, Message

    snap = str(tmp_path / "zmq-index.npz")
    fixed = uuid.uuid4()
    pos = Vector3(5.0, 5.0, 5.0)

    def make_config():
        config = Config(store_url="memory://")
        config.http_enabled = False
        config.ws_enabled = False
        config.zmq_server_host = "127.0.0.1"
        config.zmq_server_port = free_port()
        config.spatial_backend = "tpu"
        config.index_snapshot = snap
        return config

    async def scenario():
        server = WorldQLServer(make_config())
        await server.start()
        z = await ZmqClient.connect(
            server.config.zmq_server_port, peer_uuid=fixed
        )
        await z.send(Message(
            instruction=Instruction.AREA_SUBSCRIBE,
            world_name="w", position=pos,
        ))
        await asyncio.sleep(0.3)
        await server.stop()  # client connected: checkpoint captures it
        await z.close()

        server2 = WorldQLServer(make_config())
        await server2.start()
        try:
            # reconnect under the SAME uuid; no AREA_SUBSCRIBE sent
            z2 = await ZmqClient.connect(
                server2.config.zmq_server_port, peer_uuid=fixed
            )
            sender = await ZmqClient.connect(server2.config.zmq_server_port)
            await sender.send(Message(
                instruction=Instruction.LOCAL_MESSAGE,
                world_name="w", position=pos, parameter="wb",
            ))
            got = await z2.recv_until(Instruction.LOCAL_MESSAGE, timeout=10)
            assert got.parameter == "wb"
            await z2.close()
            await sender.close()
        finally:
            await server2.stop()
        return True

    assert asyncio.run(scenario())


@pytest.mark.parametrize("spatial_backend", ["cpu", "tpu"])
def test_restored_peers_swept_if_they_never_reconnect(
    tmp_path, spatial_backend
):
    """Restored subscriptions must not leak across restart cycles:
    peers absent one staleness window after boot lose their rows
    (WS UUIDs are per-connection, so WS rows are always swept)."""
    from worldql_server_tpu.engine.config import Config
    from worldql_server_tpu.engine.server import WorldQLServer

    snap = str(tmp_path / "sweep.npz")
    src = CpuSpatialBackend(16)
    ghost = uuid.uuid4()
    src.add_subscription("w", ghost, Vector3(1.0, 2.0, 3.0))
    save_snapshot(src, snap)

    config = Config(store_url="memory://")
    config.http_enabled = False
    config.ws_enabled = False
    config.zmq_enabled = False
    config.spatial_backend = spatial_backend
    config.index_snapshot = snap
    config.zmq_timeout_secs = 0  # immediate sweep window for the test

    async def scenario():
        server = WorldQLServer(config)
        await server.start()
        try:
            assert server.backend.is_subscribed_any("w", ghost)
            for _ in range(50):
                await asyncio.sleep(0.02)
                if not server.backend.is_subscribed_any("w", ghost):
                    break
            assert not server.backend.is_subscribed_any("w", ghost)
            assert server.backend.subscription_count() == 0
        finally:
            await server.stop()
        return True

    assert asyncio.run(scenario())


def test_quick_restart_does_not_repersist_ghosts(tmp_path):
    """A restart SHORTER than the staleness window must still drop
    unclaimed restored rows at save time — otherwise a crash-looping
    deploy re-persists departed peers' subscriptions forever."""
    from worldql_server_tpu.engine.config import Config
    from worldql_server_tpu.engine.server import WorldQLServer

    snap = str(tmp_path / "ghost.npz")
    src = CpuSpatialBackend(16)
    ghost = uuid.uuid4()
    src.add_subscription("w", ghost, Vector3(1.0, 2.0, 3.0))
    save_snapshot(src, snap)

    config = Config(store_url="memory://")
    config.http_enabled = False
    config.ws_enabled = False
    config.zmq_enabled = False
    config.spatial_backend = "cpu"
    config.index_snapshot = snap
    config.zmq_timeout_secs = 3600  # sweep task never fires in-test

    async def scenario():
        server = WorldQLServer(config)
        await server.start()
        assert server.backend.is_subscribed_any("w", ghost)
        await server.stop()  # well inside the window
        return True

    assert asyncio.run(scenario())
    fresh = CpuSpatialBackend(16)
    restored, _ = load_snapshot(fresh, snap)
    assert restored == 0  # the ghost was not written back


def test_failed_load_never_clobbers_the_snapshot(tmp_path):
    """If the boot-time load fails, the shutdown save is disabled —
    the failing-but-intact file must survive for a fixed binary to
    restore, never be overwritten with an empty index."""
    from worldql_server_tpu.engine.config import Config
    from worldql_server_tpu.engine.server import WorldQLServer

    snap = tmp_path / "keep.npz"
    snap.write_bytes(b"not a real npz")
    original = snap.read_bytes()

    config = Config(store_url="memory://")
    config.http_enabled = False
    config.ws_enabled = False
    config.zmq_enabled = False
    config.index_snapshot = str(snap)

    async def scenario():
        server = WorldQLServer(config)
        await server.start()  # load fails, logged, serves empty
        assert server._snapshot_save_disabled
        await server.stop()
        return True

    assert asyncio.run(scenario())
    assert snap.read_bytes() == original  # untouched


def test_restore_rides_the_bulk_fold_path(tmp_path):
    """A large restore must fold straight to base with ONE deferred
    upload — no delta residue, no compaction debt (the round-3 bench
    paid ~90 s of delta sorts + drains for a 1M restore; the fold path
    measured 1.6 s build + 3.9 s flush on v5e)."""
    rng = np.random.default_rng(23)
    src = TpuSpatialBackend(cube_size=16)
    n = 30_000
    cubes = rng.integers(-60, 60, (n, 3)).astype(np.int64) * 16
    peers = [uuid.UUID(int=i + 1) for i in range(n)]
    for w in range(4):
        sel = np.flatnonzero(np.arange(n) % 4 == w)
        src.bulk_add_subscriptions(
            f"w{w}", [peers[i] for i in sel], cubes[sel]
        )
    path = str(tmp_path / "snap.npz")
    assert save_snapshot(src, path) == n

    dst = TpuSpatialBackend(cube_size=16)
    uploads = []
    real_upload = dst._upload_base

    def counting_upload(*a, **kw):
        uploads.append(len(a[0]))
        return real_upload(*a, **kw)

    dst._upload_base = counting_upload
    restored, _ = load_snapshot(dst, path)
    assert restored == n
    stats = dst.device_stats()
    assert stats["delta_rows"] == 0, (
        f"restore left {stats['delta_rows']} rows in the delta log"
    )
    # the whole restore shipped ONE deferred base upload (at the
    # load_snapshot-internal flush), regardless of per-world call count
    assert uploads == [next_pow2(n)]
    assert dst._base_bundle is not None
    assert stats["compaction_in_flight"] is False
    assert dst.subscription_count() == n
    got = dst.query_cube("w0", tuple(cubes[0]))
    assert peers[0] in got
