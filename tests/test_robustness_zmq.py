"""ZMQ-transport robustness over real sockets: poison-message
containment in the recv loop, staleness-sweeper fault isolation, and
end-to-end stale-peer eviction (silent peer → sweep → connect-back
PUSH socket closed → metrics carry the eviction reason).

Lives apart from test_transports.py because that module importorskips
``websockets`` wholesale; everything here needs only pyzmq.
"""

import asyncio
import uuid

from tests.client_util import ZmqClient, free_port
from worldql_server_tpu.engine.config import Config
from worldql_server_tpu.engine.server import WorldQLServer
from worldql_server_tpu.protocol import Instruction, Message, Vector3


def make_server(**overrides) -> WorldQLServer:
    config = Config()
    config.store_url = "memory://"
    config.http_enabled = False
    config.ws_enabled = False
    config.zmq_server_port = free_port()
    config.zmq_server_host = "127.0.0.1"
    for k, v in overrides.items():
        setattr(config, k, v)
    return WorldQLServer(config)


def run(coro):
    return asyncio.run(asyncio.wait_for(coro, 30))


async def wait_for(predicate, timeout=3.0, interval=0.01):
    for _ in range(int(timeout / interval)):
        if predicate():
            return True
        await asyncio.sleep(interval)
    return predicate()


def test_recv_loop_survives_poison_message():
    """Regression (ISSUE 4 satellite): an exception escaping
    router.handle_message used to kill _recv_loop permanently — the
    transport stayed 'up' but deaf. Now the poison message is dropped,
    counted in zmq.recv_errors, and the NEXT message still routes."""

    async def scenario():
        server = make_server()
        await server.start()
        try:
            client = await ZmqClient.connect(server.config.zmq_server_port)

            real_handle = server.router.handle_message
            poisoned = {"n": 0}

            async def poison_once(message):
                if poisoned["n"] == 0:
                    poisoned["n"] += 1
                    raise RuntimeError("poison payload hit a router bug")
                await real_handle(message)

            server.router.handle_message = poison_once

            # the poison message: swallowed, counted, loop survives
            await client.send(Message(
                instruction=Instruction.GLOBAL_MESSAGE, world_name="w",
            ))
            assert await wait_for(
                lambda: server.metrics.counters["zmq.recv_errors"] == 1
            )

            # next message still routes: heartbeat echoes back
            await client.send(Message(instruction=Instruction.HEARTBEAT))
            echo = await client.recv_until(Instruction.HEARTBEAT)
            assert echo is not None
            assert poisoned["n"] == 1

            await client.close()
        finally:
            await server.stop()
        return True

    assert run(scenario())


def test_recv_backlog_gives_the_event_loop_a_turn():
    """A recv on a socket with a backlog never suspends: a burst used
    to be routed to its last message before the ticker or /healthz ran
    again (100K entity registrations held a v5e host's loop for 17 s).
    The recv path now gives way every 10 ms of uninterrupted work."""
    import time

    async def scenario():
        server = make_server()
        await server.start()
        try:
            client = await ZmqClient.connect(server.config.zmq_server_port)
            n, handled, seen = 300, 0, set()

            async def slow_handle(message):    # 1 ms of work a message
                nonlocal handled
                t0 = time.perf_counter()
                while time.perf_counter() - t0 < 0.001:
                    pass
                handled += 1

            server.router.handle_message = slow_handle

            async def bystander():    # any other task of the loop
                while True:
                    seen.add(handled)
                    await asyncio.sleep(0)

            watch = asyncio.create_task(bystander())
            for _ in range(n):
                await client.send(Message(instruction=Instruction.HEARTBEAT))
            assert await wait_for(lambda: handled == n, timeout=20.0)
            watch.cancel()
            # starved, the bystander sees the count before and after
            assert len(seen - {0, n}) >= 5, sorted(seen)
            await client.close()
        finally:
            await server.stop()
        return True

    assert run(scenario())


def test_sweeper_continues_past_raising_removal_hook():
    """Regression (ISSUE 4 satellite): one peer whose removal hook
    raises used to abort the whole sweep (and kill the sweeper task).
    The second stale peer must still be evicted, and the error
    counted."""

    async def scenario():
        server = make_server()
        await server.start()
        try:
            c1 = await ZmqClient.connect(
                server.config.zmq_server_port, peer_uuid=uuid.UUID(int=1)
            )
            c2 = await ZmqClient.connect(
                server.config.zmq_server_port, peer_uuid=uuid.UUID(int=2)
            )
            assert await wait_for(lambda: server.peer_map.size() == 2)

            real_remove = server.backend.remove_peer

            def hook_raises_for_c1(peer):
                if peer == c1.uuid:
                    raise RuntimeError("index purge failed")
                return real_remove(peer)

            server.backend.remove_peer = hook_raises_for_c1

            # age both peers past the staleness window
            for peer in server.peer_map._map.values():
                peer.last_heartbeat -= server.config.zmq_timeout_secs + 1

            removed = await server._sweep_stale_once()

            # c1's hook raised AFTER the map pop; c2's eviction ran
            assert removed == 1  # only c2 completed cleanly
            assert server.peer_map.size() == 0
            assert server.metrics.counters["sweeper.remove_errors"] == 1
            assert server.metrics.counters["peers.evicted_stale"] == 1

            await c1.close()
            await c2.close()
        finally:
            await server.stop()
        return True

    assert run(scenario())


def test_stale_peer_eviction_end_to_end_over_zmq():
    """Silent peer over the real wire: the sweep evicts it, the
    connect-back PUSH socket is closed via on_peer_removed, the
    surviving peer hears PeerDisconnect, and metrics carry the
    eviction reason."""

    async def scenario():
        server = make_server()
        await server.start()
        try:
            silent = await ZmqClient.connect(server.config.zmq_server_port)
            alive = await ZmqClient.connect(server.config.zmq_server_port)
            assert await wait_for(lambda: server.peer_map.size() == 2)

            [zmq_transport] = server._transports
            assert silent.uuid in zmq_transport._push_sockets
            push = zmq_transport._push_sockets[silent.uuid]

            # only the silent peer goes stale
            server.peer_map.get(silent.uuid).last_heartbeat -= (
                server.config.zmq_timeout_secs + 1
            )
            # the live one keeps heartbeating
            await alive.send(Message(instruction=Instruction.HEARTBEAT))
            await alive.recv_until(Instruction.HEARTBEAT)

            assert await server._sweep_stale_once() == 1

            assert server.peer_map.get(silent.uuid) is None
            assert server.peer_map.get(alive.uuid) is not None
            # connect-back socket torn down via on_peer_removed
            assert silent.uuid not in zmq_transport._push_sockets
            assert push.closed
            # the survivor hears about the disconnect
            note = await alive.recv_until(Instruction.PEER_DISCONNECT)
            assert note.parameter == str(silent.uuid)
            # eviction reason is visible in metrics
            assert server.metrics.counters["peers.evicted_stale"] == 1
            assert "peers.evicted_send_failed" not in \
                server.metrics.counters

            await silent.close()
            await alive.close()
        finally:
            await server.stop()
        return True

    assert run(scenario())


def test_subscription_survives_for_live_peer_after_sweep():
    """The sweep must only purge the STALE peer's spatial rows — the
    live peer's subscription keeps routing LocalMessages after the
    eviction."""

    async def scenario():
        server = make_server()
        await server.start()
        try:
            silent = await ZmqClient.connect(server.config.zmq_server_port)
            alive = await ZmqClient.connect(server.config.zmq_server_port)
            assert await wait_for(lambda: server.peer_map.size() == 2)

            pos = Vector3(5, 5, 5)
            for c in (silent, alive):
                await c.send(Message(
                    instruction=Instruction.AREA_SUBSCRIBE,
                    world_name="world", position=pos,
                ))
            assert await wait_for(
                lambda: server.backend.subscription_count() == 2
            )

            server.peer_map.get(silent.uuid).last_heartbeat -= (
                server.config.zmq_timeout_secs + 1
            )
            await server._sweep_stale_once()
            assert server.backend.subscription_count() == 1

            sender = await ZmqClient.connect(server.config.zmq_server_port)
            await sender.send(Message(
                instruction=Instruction.LOCAL_MESSAGE, world_name="world",
                position=pos, parameter="still-routing",
            ))
            got = await alive.recv_until(Instruction.LOCAL_MESSAGE)
            assert got.parameter == "still-routing"

            for c in (silent, alive, sender):
                await c.close()
        finally:
            await server.stop()
        return True

    assert run(scenario())
