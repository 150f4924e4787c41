"""End-to-end delivery-plane tests (ISSUE 6): real sockets, real
worker processes.

The ZMQ flows run everywhere (pyzmq is a hard dependency); the WS
handoff flows skip in containers without ``websockets`` — CI runs
both. Acceptance criteria exercised here:

* zero lost frames through the sharded plane (every expected delivery
  arrives at a live client);
* kill-a-worker chaos: SIGKILL one sender worker mid-load → its peers
  evict with reason ``worker_lost`` (``peers.evicted_worker_lost``),
  the surviving shard keeps delivering, the tick pipeline never
  stalls (flight-recorder ``tick.deliver`` stays bounded), and the
  supervisor restarts-with-backoff / degrades on budget exhaustion;
* ``--delivery-workers 0`` builds none of the machinery (the
  in-process path object graph is unchanged);
* clean shutdown: workers exit 0, shm rings unlink.
"""

import asyncio
import glob
import os
import signal
import uuid as uuid_mod

import pytest

from tests.client_util import ZmqClient, free_port
from worldql_server_tpu.engine.config import Config
from worldql_server_tpu.engine.server import WorldQLServer
from worldql_server_tpu.protocol import Instruction, Message, Vector3

POS = Vector3(5.0, 5.0, 5.0)


def make_server(**overrides) -> WorldQLServer:
    config = Config()
    config.store_url = "memory://"
    config.http_enabled = False
    config.ws_enabled = False
    config.zmq_server_port = free_port()
    config.zmq_server_host = "127.0.0.1"
    config.delivery_workers = 2
    config.tick_interval = 0.02
    config.supervisor_backoff = 0.05
    for k, v in overrides.items():
        setattr(config, k, v)
    return WorldQLServer(config)


def run(coro):
    return asyncio.run(asyncio.wait_for(coro, 90))


async def connect_subscribed(port, n):
    clients = [await ZmqClient.connect(port) for _ in range(n)]
    for c in clients:
        await c.send(Message(
            instruction=Instruction.AREA_SUBSCRIBE,
            world_name="w", position=POS,
        ))
    await asyncio.sleep(0.25)  # subscriptions + adoption settle
    return clients


async def close_all(clients):
    for c in clients:
        await c.close()


def test_zero_delivery_workers_builds_no_plane():
    """The default path constructs NONE of the plane machinery — the
    PeerMap routes through the unchanged in-process pump."""
    server = make_server(delivery_workers=0)
    assert server.delivery_plane is None
    assert server.peer_map._plane is None
    snapshot = server.metrics.snapshot()
    assert "delivery" not in snapshot["gauges"]


def test_fanout_through_workers_zero_lost_frames():
    """N peers × M broadcasts through 2 sender workers: every expected
    delivery arrives (deliveries == deliveries_expected), both workers
    carried traffic, and /metrics exposes per-worker counters."""
    async def scenario():
        server = make_server()
        await server.start()
        try:
            n, rounds = 6, 20
            clients = await connect_subscribed(
                server.config.zmq_server_port, n
            )
            # every peer must be worker-owned
            for c in clients:
                assert server.peer_map.get(c.uuid).shard is not None
            for r in range(rounds):
                for c in clients:
                    await c.send(Message(
                        instruction=Instruction.LOCAL_MESSAGE,
                        world_name="w", position=POS,
                        parameter=f"m{r}",
                    ))
                await asyncio.sleep(0.01)
            expected_each = (n - 1) * rounds
            for c in clients:
                got = 0
                while got < expected_each:
                    msg = await c.recv_until(
                        Instruction.LOCAL_MESSAGE, timeout=10
                    )
                    assert msg.parameter.startswith("m")
                    got += 1
                assert got == expected_each
            # worker accounting reached the parent registry
            await asyncio.sleep(0.4)  # one stats interval
            snap = server.metrics.snapshot()
            w0 = snap["gauges"]["delivery.worker.0"]
            w1 = snap["gauges"]["delivery.worker.1"]
            assert w0["deliveries"] > 0 and w1["deliveries"] > 0
            assert snap["counters"]["delivery.deliveries"] > 0
            assert snap["counters"].get("delivery.ring_full_drops", 0) == 0
            assert snap["gauges"]["delivery"]["peers"] == n
            # the per-worker gauges flatten into scrape-valid series
            from tests.prom_parser import validate_exposition

            text = server.metrics.render_prometheus()
            validate_exposition(text)
            assert any(
                line.startswith("wql_delivery_worker_0_deliveries")
                for line in text.splitlines()
            )
            await close_all(clients)
        finally:
            await server.stop()

    run(scenario())


def test_router_reply_routes_through_worker():
    """Direct per-peer sends (router replies — here the ZMQ heartbeat
    echo path is exercised via PeerConnect unicast on insert) also ride
    the worker shard: adopt() rebinds ALL of the peer's write paths,
    not just the tick fan-out."""
    async def scenario():
        server = make_server()
        await server.start()
        try:
            c1 = (await connect_subscribed(
                server.config.zmq_server_port, 1
            ))[0]
            # second client's insert broadcasts PeerConnect to c1 —
            # delivered by c1's owning worker
            c2 = await ZmqClient.connect(server.config.zmq_server_port)
            msg = await c1.recv_until(Instruction.PEER_CONNECT, timeout=10)
            assert msg.parameter == str(c2.uuid)
            await close_all([c1, c2])
        finally:
            await server.stop()

    run(scenario())


def test_kill_worker_evicts_shard_and_keeps_delivering():
    """ISSUE acceptance: SIGKILL one sender worker mid-load → its peers
    evicted with reason worker_lost, remaining shard keeps delivering,
    the tick pipeline never stalls (bounded tick.deliver in the flight
    recorder), and the supervisor restarts the worker."""
    async def scenario():
        server = make_server(trace=True)
        await server.start()
        try:
            clients = await connect_subscribed(
                server.config.zmq_server_port, 6
            )
            plane = server.delivery_plane
            shard0 = plane._shards[0]
            victims = set(shard0.peers)
            assert victims and len(victims) < len(clients)
            os.kill(shard0.proc.pid, signal.SIGKILL)
            # keep load flowing through the tick path during the death
            survivors = [c for c in clients if c.uuid not in victims]
            for r in range(10):
                await survivors[0].send(Message(
                    instruction=Instruction.LOCAL_MESSAGE,
                    world_name="w", position=POS, parameter=f"s{r}",
                ))
                await asyncio.sleep(0.02)
            # surviving shard kept delivering
            for c in survivors[1:]:
                await c.recv_until(Instruction.LOCAL_MESSAGE, timeout=10)
            # authoritative eviction with the mandated reason
            deadline = asyncio.get_event_loop().time() + 10
            while asyncio.get_event_loop().time() < deadline:
                snap = server.metrics.snapshot()
                if snap["counters"].get(
                    "peers.evicted_worker_lost", 0
                ) >= len(victims):
                    break
                await asyncio.sleep(0.05)
            assert snap["counters"]["peers.evicted_worker_lost"] == len(
                victims
            )
            for uuid in victims:
                assert server.peer_map.get(uuid) is None
            # the tick path did not stall: every recorded tick.deliver span
            # stayed far below the eviction window
            ticks = server.recorder.snapshot()
            assert ticks, "flight recorder captured no ticks"
            for t in ticks:
                for span in t["spans"]:
                    if span["name"] == "tick.deliver":
                        assert span["dur_ms"] < 2000.0
            # restart-with-backoff: the shard comes back and adopts
            deadline = asyncio.get_event_loop().time() + 15
            while asyncio.get_event_loop().time() < deadline:
                if plane.alive_workers() == 2:
                    break
                await asyncio.sleep(0.05)
            assert plane.alive_workers() == 2
            assert plane.stats()["restarts"] >= 1
            fresh = await ZmqClient.connect(server.config.zmq_server_port)
            await fresh.send(Message(
                instruction=Instruction.AREA_SUBSCRIBE,
                world_name="w", position=POS,
            ))
            await asyncio.sleep(0.25)
            assert server.peer_map.get(fresh.uuid).shard is not None
            await survivors[0].send(Message(
                instruction=Instruction.LOCAL_MESSAGE,
                world_name="w", position=POS, parameter="post-restart",
            ))
            got = await fresh.recv_until(
                Instruction.LOCAL_MESSAGE, timeout=10
            )
            assert got.parameter == "post-restart"
            await close_all(clients + [fresh])
        finally:
            await server.stop()

    run(scenario())


def test_budget_exhaustion_degrades_to_in_process_pump():
    """A worker whose restart budget is exhausted retires its shard;
    with every shard retired the plane is degraded but the SERVER is
    not: new peers fall back to the parent-owned path and still get
    their frames."""
    async def scenario():
        server = make_server(delivery_workers=1, supervisor_budget=0)
        await server.start()
        try:
            c_old = (await connect_subscribed(
                server.config.zmq_server_port, 1
            ))[0]
            plane = server.delivery_plane
            os.kill(plane._shards[0].proc.pid, signal.SIGKILL)
            deadline = asyncio.get_event_loop().time() + 10
            while asyncio.get_event_loop().time() < deadline:
                if plane._shards[0].retired:
                    break
                await asyncio.sleep(0.05)
            assert plane._shards[0].retired
            assert plane.degraded()
            assert server.delivery_status()["degraded"]
            # the old peer was evicted; fresh peers adopt NOWHERE and
            # ride the parent-owned path — delivery continues
            c1, c2 = await connect_subscribed(
                server.config.zmq_server_port, 2
            )
            assert server.peer_map.get(c1.uuid).shard is None
            await c1.send(Message(
                instruction=Instruction.LOCAL_MESSAGE,
                world_name="w", position=POS, parameter="degraded",
            ))
            got = await c2.recv_until(Instruction.LOCAL_MESSAGE, timeout=10)
            assert got.parameter == "degraded"
            await close_all([c_old, c1, c2])
        finally:
            await server.stop()

    run(scenario())


def test_clean_shutdown_reaps_workers_and_rings():
    """server.stop() drains and joins every worker (exit code 0, not a
    kill) and unlinks the shm ring segments."""
    async def scenario():
        server = make_server()
        await server.start()
        plane = server.delivery_plane
        procs = [s.proc for s in plane._shards]
        ring_names = [s.ring.name for s in plane._shards]
        clients = await connect_subscribed(
            server.config.zmq_server_port, 2
        )
        await clients[0].send(Message(
            instruction=Instruction.LOCAL_MESSAGE,
            world_name="w", position=POS, parameter="bye",
        ))
        await clients[1].recv_until(Instruction.LOCAL_MESSAGE, timeout=10)
        await close_all(clients)
        await server.stop()
        for p in procs:
            assert p.exitcode == 0, p.exitcode
        for name in ring_names:
            assert not glob.glob(f"/dev/shm/*{name}*"), name

    run(scenario())


def test_staleness_sweep_evicts_worker_owned_peer():
    """Heartbeat staleness stays parent-authoritative for worker-owned
    peers: the sweep removes the peer, the shard releases its slot."""
    async def scenario():
        server = make_server()
        await server.start()
        try:
            clients = await connect_subscribed(
                server.config.zmq_server_port, 2
            )
            plane = server.delivery_plane
            assert sum(len(s.peers) for s in plane._shards) == 2
            # silence one peer past the (test-shortened) window
            target = clients[0]
            peer = server.peer_map.get(target.uuid)
            peer.last_heartbeat -= 10_000
            removed = await server._sweep_stale_once()
            assert removed == 1
            assert server.peer_map.get(target.uuid) is None
            assert sum(len(s.peers) for s in plane._shards) == 1
            await close_all(clients)
        finally:
            await server.stop()

    run(scenario())


def test_failed_sink_reported_by_worker_evicts_peer():
    """The worker→parent fail report path, deterministically: a peer
    whose connect-back endpoint the worker cannot open is reported
    (``{"op": "fail"}``) and the PARENT evicts it through the normal
    removal path with ``peers.evicted_send_failed`` — outgoing.rs:66-76
    semantics across the process boundary. (The slow-consumer variant
    of the same plumbing is exercised by the WS overflow test below;
    loopback ZMQ PUSH queues up to a deep SNDHWM before failing, which
    no bounded test budget can saturate.)"""
    from worldql_server_tpu.engine.peers import Peer

    async def scenario():
        server = make_server()
        await server.start()
        try:
            clients = await connect_subscribed(
                server.config.zmq_server_port, 2
            )

            async def noop_send(data):
                pass

            ghost = Peer(
                uuid=uuid_mod.uuid4(), addr="ghost",
                send_raw=noop_send, kind="zeromq",
            )
            plane = server.delivery_plane
            # an endpoint zmq cannot even parse/resolve: the worker's
            # sink construction raises and must REPORT, not die
            assert plane.adopt(ghost, endpoint="bogus://not-an-endpoint")
            deadline = asyncio.get_event_loop().time() + 15
            while asyncio.get_event_loop().time() < deadline:
                snap = server.metrics.snapshot()
                if snap["counters"].get("peers.evicted_send_failed", 0):
                    break
                await asyncio.sleep(0.05)
            assert snap["counters"]["peers.evicted_send_failed"] >= 1
            assert plane.alive_workers() == 2  # shard survived
            # the shard released the slot
            assert all(
                ghost.uuid not in s.peers for s in plane._shards
            )
            # and real traffic still flows
            await clients[0].send(Message(
                instruction=Instruction.LOCAL_MESSAGE,
                world_name="w", position=POS, parameter="still-alive",
            ))
            got = await clients[1].recv_until(
                Instruction.LOCAL_MESSAGE, timeout=10
            )
            assert got.parameter == "still-alive"
            await close_all(clients)
        finally:
            await server.stop()

    run(scenario())


# region: WS handoff flows (skip without the websockets library)


def test_ws_handoff_delivers_through_worker():
    websockets = pytest.importorskip("websockets")  # noqa: F841
    from tests.client_util import WsClient

    async def scenario():
        server = make_server(ws_enabled=True)
        server.config.ws_port = free_port()
        server.config.ws_host = "127.0.0.1"
        await server.start()
        try:
            c1 = await WsClient.connect(server.config.ws_port)
            c2 = await WsClient.connect(server.config.ws_port)
            for c in (c1, c2):
                # connect() returns once the handshake echo is SENT; the
                # server inserts the peer after it has read that echo
                deadline = asyncio.get_event_loop().time() + 10
                while server.peer_map.get(c.uuid) is None:
                    assert asyncio.get_event_loop().time() < deadline
                    await asyncio.sleep(0.005)
                assert server.peer_map.get(c.uuid).shard is not None
                await c.send(Message(
                    instruction=Instruction.AREA_SUBSCRIBE,
                    world_name="w", position=POS,
                ))
            await asyncio.sleep(0.25)
            for r in range(10):
                await c1.send(Message(
                    instruction=Instruction.LOCAL_MESSAGE,
                    world_name="w", position=POS, parameter=f"ws{r}",
                ))
            for r in range(10):
                got = await c2.recv_until(
                    Instruction.LOCAL_MESSAGE, timeout=10
                )
                assert got.parameter == f"ws{r}"  # ordered, lossless
            await c1.close()
            await c2.close()
        finally:
            await server.stop()

    run(scenario())


def test_ws_and_zmq_mixed_fanout_through_workers():
    """The CI smoke mix: WS and ZMQ peers in one cube, every delivery
    arriving exactly once through whichever worker owns the socket."""
    websockets = pytest.importorskip("websockets")  # noqa: F841
    from tests.client_util import WsClient

    async def scenario():
        server = make_server(ws_enabled=True)
        server.config.ws_port = free_port()
        server.config.ws_host = "127.0.0.1"
        await server.start()
        try:
            ws = [await WsClient.connect(server.config.ws_port)
                  for _ in range(2)]
            zq = await connect_subscribed(
                server.config.zmq_server_port, 2
            )
            for c in ws:
                await c.send(Message(
                    instruction=Instruction.AREA_SUBSCRIBE,
                    world_name="w", position=POS,
                ))
            await asyncio.sleep(0.25)
            rounds = 10
            for r in range(rounds):
                await ws[0].send(Message(
                    instruction=Instruction.LOCAL_MESSAGE,
                    world_name="w", position=POS, parameter=f"mix{r}",
                ))
            for c in [ws[1], *zq]:
                for _ in range(rounds):
                    got = await c.recv_until(
                        Instruction.LOCAL_MESSAGE, timeout=10
                    )
                    assert got.parameter.startswith("mix")
            snap = server.metrics.snapshot()
            assert snap["counters"].get("delivery.ring_full_drops", 0) == 0
            for c in ws:
                await c.close()
            await close_all(zq)
        finally:
            await server.stop()

    run(scenario())


def test_ws_worker_evicts_slow_consumer():
    """The worker-side PENDING_HARD_LIMIT mirrors the parent's
    _WRITE_HARD_LIMIT eviction: a WS client that stops reading is
    reported by its worker and evicted by the parent."""
    websockets = pytest.importorskip("websockets")  # noqa: F841
    from tests.client_util import WsClient

    async def scenario():
        server = make_server(
            ws_enabled=True, delivery_ring_bytes=16 * 1024 * 1024
        )
        server.config.ws_port = free_port()
        server.config.ws_host = "127.0.0.1"
        await server.start()
        try:
            slow = await WsClient.connect(server.config.ws_port)
            fast = await WsClient.connect(server.config.ws_port)
            for c in (slow, fast):
                await c.send(Message(
                    instruction=Instruction.AREA_SUBSCRIBE,
                    world_name="w", position=POS,
                ))
            await asyncio.sleep(0.25)
            # stop the slow client's reads at the TCP level so the
            # worker's backlog grows past the hard limit
            slow.connection.transport.pause_reading()
            payload = "y" * 65536
            deadline = asyncio.get_event_loop().time() + 30
            while asyncio.get_event_loop().time() < deadline:
                if server.peer_map.get(slow.uuid) is None:
                    break
                for _ in range(40):
                    await fast.send(Message(
                        instruction=Instruction.LOCAL_MESSAGE,
                        world_name="w", position=POS, parameter=payload,
                    ))
                await asyncio.sleep(0.05)
            assert server.peer_map.get(slow.uuid) is None
            snap = server.metrics.snapshot()
            assert (
                snap["counters"].get("peers.evicted_overflow", 0)
                + snap["counters"].get("peers.evicted_send_failed", 0)
            ) >= 1
            await fast.close()
        finally:
            await server.stop()

    run(scenario())


# endregion
