"""End-to-end transport tests: real sockets, real wire protocol.

Each test boots a full WorldQLServer on ephemeral ports and drives it
with the clients from client_util — the same flows an external plugin
ecosystem would exercise (the reference left this layer untested;
SURVEY §4 requires we exceed it).
"""

import asyncio
import contextlib
import uuid

import aiohttp
import pytest
import zmq
import zmq.asyncio

pytest.importorskip("websockets")  # WS transport is half this module

from tests.client_util import (
    WsClient, ZmqClient, free_port, stops_on_a_thread, zmq_context,
)
from tests.test_robustness_zmq import wait_for
from worldql_server_tpu.engine.config import Config
from worldql_server_tpu.engine.peers import FramedPayload
from worldql_server_tpu.engine.server import WorldQLServer
from worldql_server_tpu.protocol import (
    Instruction,
    Message,
    Replication,
    Vector3,
    serialize_message,
)
from worldql_server_tpu.protocol.types import NIL_UUID


def make_server(**overrides) -> WorldQLServer:
    config = Config()
    config.store_url = "memory://"
    config.http_port = free_port()
    config.ws_port = free_port()
    config.zmq_server_port = free_port()
    config.http_host = config.ws_host = config.zmq_server_host = "127.0.0.1"
    for k, v in overrides.items():
        setattr(config, k, v)
    return WorldQLServer(config)


def run(coro):
    return asyncio.run(asyncio.wait_for(coro, 30))


def test_ws_handshake_and_local_message():
    async def scenario():
        server = make_server(zmq_enabled=False, http_enabled=False)
        await server.start()
        try:
            c1 = await WsClient.connect(server.config.ws_port)
            c2 = await WsClient.connect(server.config.ws_port)
            assert c1.uuid != c2.uuid

            # c1 sees c2's PeerConnect broadcast (peer_map.rs:106-113).
            connect = await c1.recv_until(Instruction.PEER_CONNECT)
            assert connect.parameter == str(c2.uuid)

            pos = Vector3(5, 5, 5)
            for c in (c1, c2):
                await c.send(
                    Message(
                        instruction=Instruction.AREA_SUBSCRIBE,
                        world_name="world",
                        position=pos,
                    )
                )
            await asyncio.sleep(0.05)

            await c1.send(
                Message(
                    instruction=Instruction.LOCAL_MESSAGE,
                    world_name="world",
                    position=pos,
                    parameter="hi",
                )
            )
            got = await c2.recv_until(Instruction.LOCAL_MESSAGE)
            assert got.parameter == "hi"
            assert got.sender_uuid == c1.uuid

            await c1.close()
            await c2.close()
        finally:
            await server.stop()
        return True

    assert run(scenario())


def test_ws_hard_limit_evicts_saturated_peer():
    """A peer whose transport write buffer exceeds the hard limit is
    EVICTED on the next fast-path write (failed-send semantics,
    outgoing.rs:66-76): removed from the PeerMap, socket aborted.
    Driven deterministically by dropping the limit below zero so the
    first delivery attempt registers as saturation — loopback kernel
    buffers otherwise absorb tens of MB before the condition is real."""
    import worldql_server_tpu.transports.websocket as ws_mod

    async def scenario():
        server = make_server(zmq_enabled=False, http_enabled=False)
        await server.start()
        old_limit = ws_mod._WRITE_HARD_LIMIT
        try:
            victim = await WsClient.connect(server.config.ws_port)
            sender = await WsClient.connect(server.config.ws_port)
            # connect() returns after SENDING the handshake echo; the
            # server-side insert lands on a later loop turn
            for _ in range(100):
                if server.peer_map.size() == 2:
                    break
                await asyncio.sleep(0.01)
            assert server.peer_map.size() == 2
            pos = Vector3(5, 5, 5)
            for c in (victim, sender):
                await c.send(Message(
                    instruction=Instruction.AREA_SUBSCRIBE,
                    world_name="world", position=pos,
                ))
            await asyncio.sleep(0.05)
            ws_mod._WRITE_HARD_LIMIT = -1  # every write = saturated
            await sender.send(Message(
                instruction=Instruction.LOCAL_MESSAGE,
                world_name="world", position=pos, parameter="boom",
            ))
            for _ in range(100):
                await asyncio.sleep(0.02)
                if victim.uuid not in server.peer_map:
                    break
            assert victim.uuid not in server.peer_map, \
                "saturated peer must be evicted"
            # and its socket was aborted, not left half-open
            await asyncio.wait_for(victim.connection.wait_closed(), timeout=5)
        finally:
            ws_mod._WRITE_HARD_LIMIT = old_limit
            await server.stop()
        return True

    assert run(scenario())


def test_ws_wrong_sender_uuid_disconnects():
    async def scenario():
        server = make_server(zmq_enabled=False, http_enabled=False)
        await server.start()
        try:
            c = await WsClient.connect(server.config.ws_port)
            bad = Message(
                instruction=Instruction.GLOBAL_MESSAGE,
                sender_uuid=uuid.uuid4(),  # spoofed
                world_name="@global",
            )
            await c.send_raw(serialize_message(bad))
            # Server must close the connection (websocket.rs:163-170).
            with pytest.raises(Exception):
                while True:
                    await c.recv(timeout=2)
        finally:
            await server.stop()
        return True

    assert run(scenario())


def test_ws_duplicate_handshake_disconnects():
    async def scenario():
        server = make_server(zmq_enabled=False, http_enabled=False)
        await server.start()
        try:
            c = await WsClient.connect(server.config.ws_port)
            await c.send(Message(instruction=Instruction.HANDSHAKE))
            with pytest.raises(Exception):
                while True:
                    await c.recv(timeout=2)
        finally:
            await server.stop()
        return True

    assert run(scenario())


def test_ws_heartbeat_echo():
    async def scenario():
        server = make_server(zmq_enabled=False, http_enabled=False)
        await server.start()
        try:
            c = await WsClient.connect(server.config.ws_port)
            await c.send(Message(instruction=Instruction.HEARTBEAT))
            echo = await c.recv_until(Instruction.HEARTBEAT)
            assert echo.sender_uuid == NIL_UUID  # heartbeat.rs:36-42
            await c.close()
        finally:
            await server.stop()
        return True

    assert run(scenario())


def test_http_global_message_auth_and_delivery():
    async def scenario():
        server = make_server(zmq_enabled=False, http_auth_token="secret")
        await server.start()
        try:
            c = await WsClient.connect(server.config.ws_port)
            await c.send(
                Message(
                    instruction=Instruction.AREA_SUBSCRIBE,
                    world_name="world",
                    position=Vector3(0, 0, 0),
                )
            )
            await asyncio.sleep(0.05)

            url = f"http://127.0.0.1:{server.config.http_port}/global_message"
            async with aiohttp.ClientSession() as session:
                # No token → 401 (http_rest.rs:89-90)
                async with session.post(url, json={"world_name": "world"}) as r:
                    assert r.status == 401
                # Wrong token → 401 (http_rest.rs:93-97)
                async with session.post(
                    url,
                    json={"world_name": "world"},
                    headers={"Authorization": "Bearer nope"},
                ) as r:
                    assert r.status == 401
                # Bad body → 400
                async with session.post(
                    url,
                    data=b"not json",
                    headers={"Authorization": "Bearer secret"},
                ) as r:
                    assert r.status == 400
                # Valid → 204, delivered to world subscriber with nil
                # sender (http_rest.rs:46-60,104)
                async with session.post(
                    url,
                    json={"world_name": "world", "parameter": "from-http"},
                    headers={"Authorization": "Bearer secret"},
                ) as r:
                    assert r.status == 204

            got = await c.recv_until(Instruction.GLOBAL_MESSAGE)
            assert got.parameter == "from-http"
            assert got.sender_uuid == NIL_UUID
            await c.close()
        finally:
            await server.stop()
        return True

    assert run(scenario())


def test_zmq_handshake_and_fanout():
    async def scenario():
        server = make_server(http_enabled=False, ws_enabled=False)
        await server.start()
        try:
            z1 = await ZmqClient.connect(server.config.zmq_server_port)
            z2 = await ZmqClient.connect(server.config.zmq_server_port)

            pos = Vector3(5, 5, 5)
            for z in (z1, z2):
                await z.send(
                    Message(
                        instruction=Instruction.AREA_SUBSCRIBE,
                        world_name="world",
                        position=pos,
                    )
                )
            await asyncio.sleep(0.1)

            await z1.send(
                Message(
                    instruction=Instruction.LOCAL_MESSAGE,
                    world_name="world",
                    position=pos,
                    parameter="zmq-hello",
                    replication=Replication.INCLUDING_SELF,
                )
            )
            got1 = await z1.recv_until(Instruction.LOCAL_MESSAGE)
            got2 = await z2.recv_until(Instruction.LOCAL_MESSAGE)
            assert got1.parameter == got2.parameter == "zmq-hello"

            await z1.close()
            await z2.close()
        finally:
            await server.stop()
        return True

    assert run(scenario())


def test_zmq_unknown_sender_dropped():
    async def scenario():
        server = make_server(http_enabled=False, ws_enabled=False)
        await server.start()
        try:
            z1 = await ZmqClient.connect(server.config.zmq_server_port)
            await z1.send(
                Message(
                    instruction=Instruction.AREA_SUBSCRIBE,
                    world_name="world",
                    position=Vector3(0, 0, 0),
                )
            )
            await asyncio.sleep(0.05)

            # A message from an unregistered uuid must be ignored
            # (incoming.rs:64-69): z2 sends without handshaking. (Its
            # socket stays open over the wait: closed at once with
            # linger 0, it may never have sent.)
            with zmq_context() as ctx:
                push = ctx.socket(zmq.PUSH)
                push.connect(
                    f"tcp://127.0.0.1:{server.config.zmq_server_port}")
                push.send(
                    serialize_message(
                        Message(
                            instruction=Instruction.GLOBAL_MESSAGE,
                            sender_uuid=uuid.uuid4(),
                            world_name="@global",
                            parameter="ghost",
                        )
                    )
                )
                with pytest.raises(asyncio.TimeoutError):
                    await z1.recv_until(
                        Instruction.GLOBAL_MESSAGE, timeout=0.5)
            await z1.close()
        finally:
            await server.stop()
        return True

    assert run(scenario())


def test_cross_transport_ws_to_zmq():
    async def scenario():
        server = make_server(http_enabled=False)
        await server.start()
        try:
            w = await WsClient.connect(server.config.ws_port)
            z = await ZmqClient.connect(server.config.zmq_server_port)

            pos = Vector3(-20, 3, 7)
            for send in (w.send, z.send):
                await send(
                    Message(
                        instruction=Instruction.AREA_SUBSCRIBE,
                        world_name="mixed",
                        position=pos,
                    )
                )
            await asyncio.sleep(0.1)

            await w.send(
                Message(
                    instruction=Instruction.LOCAL_MESSAGE,
                    world_name="mixed",
                    position=pos,
                    parameter="across",
                )
            )
            got = await z.recv_until(Instruction.LOCAL_MESSAGE)
            assert got.parameter == "across"
            assert got.sender_uuid == w.uuid

            await w.close()
            await z.close()
        finally:
            await server.stop()
        return True

    assert run(scenario())


def test_oversized_zmq_frame_cannot_exhaust_memory():
    """A hostile ZMQ peer streaming a frame above max_message_size is
    cut off by libzmq (MAXMSGSIZE); the PULL socket and every other
    peer keep working."""
    async def scenario():
        server = make_server(
            http_enabled=False, ws_enabled=False,
            max_message_size=64 * 1024,
        )
        await server.start()
        try:
            z1 = await ZmqClient.connect(server.config.zmq_server_port)
            pos = Vector3(5, 5, 5)
            await z1.send(Message(
                instruction=Instruction.AREA_SUBSCRIBE,
                world_name="world", position=pos,
            ))
            await asyncio.sleep(0.1)

            # raw oversized frame straight at the PULL socket
            with zmq_context(zmq.asyncio.Context) as ctx:
                hostile = ctx.socket(zmq.PUSH)
                hostile.connect(
                    f"tcp://127.0.0.1:{server.config.zmq_server_port}"
                )
                await hostile.send(b"\xff" * (1024 * 1024))
                await asyncio.sleep(0.2)

            # the server still serves the well-behaved peer
            await z1.send(Message(
                instruction=Instruction.LOCAL_MESSAGE,
                world_name="world", position=pos,
                parameter="still-alive",
                replication=Replication.INCLUDING_SELF,
            ))
            got = await z1.recv_until(Instruction.LOCAL_MESSAGE, timeout=5)
            assert got.parameter == "still-alive"
            await z1.close()
        finally:
            await server.stop()
        return True

    assert run(scenario())


def test_oversized_ws_frame_closes_only_that_connection():
    """A WS client sending a frame above max_message_size loses its
    connection (library-enforced cap); the server and other clients
    keep working."""
    async def scenario():
        server = make_server(
            http_enabled=False, zmq_enabled=False,
            max_message_size=64 * 1024,
        )
        await server.start()
        try:
            good = await WsClient.connect(server.config.ws_port)
            bad = await WsClient.connect(server.config.ws_port)
            pos = Vector3(5, 5, 5)
            await good.send(Message(
                instruction=Instruction.AREA_SUBSCRIBE,
                world_name="world", position=pos,
            ))
            await asyncio.sleep(0.1)

            await bad.send_raw(b"\xff" * (1024 * 1024))
            # the offender's connection actually CLOSES (a timeout here
            # would mean the cap silently regressed)
            await asyncio.wait_for(bad.connection.wait_closed(), timeout=5)
            # everyone else is unaffected
            await good.send(Message(
                instruction=Instruction.LOCAL_MESSAGE,
                world_name="world", position=pos,
                parameter="ok",
                replication=Replication.INCLUDING_SELF,
            ))
            got = await good.recv_until(Instruction.LOCAL_MESSAGE, timeout=5)
            assert got.parameter == "ok"
            await good.close()
        finally:
            await server.stop()
        return True

    assert run(scenario())


# region: the ZeroMQ peer's synchronous write path (ISSUE 25)


def test_a_client_nobody_closed_is_ended_after_its_test_not_by_the_collector():
    """What ``tests/conftest.py`` does after every test: a connected
    ``ZmqClient`` stays strongly held until it is closed, so the
    collector never finalizes its context ahead of its sockets (that
    ``term()`` waits for ever), and ``close_leftovers`` ends it."""
    async def scenario():
        server = make_server(http_enabled=False, ws_enabled=False)
        await server.start()
        try:
            closed = await ZmqClient.connect(server.config.zmq_server_port)
            await closed.close()
            return await ZmqClient.connect(server.config.zmq_server_port)
        finally:
            await server.stop()

    forgotten = run(scenario())
    assert ZmqClient._open == {forgotten} and not forgotten.push.closed
    ZmqClient.close_leftovers()
    assert not ZmqClient._open
    assert forgotten.push.closed and forgotten.pull.closed


def test_zmq_stop_closes_a_socket_that_no_list_names():
    """A socket of the transport's context that ``_push_sockets`` does
    not hold (what a handshake cancelled before its last line leaves
    behind) is closed with the rest: ``stop()`` returns, and a server
    comes down on SIGTERM. A bare ``term()`` waited for it for ever."""
    async def scenario(stopping):
        server = make_server(http_enabled=False, ws_enabled=False)
        await server.start()
        [transport] = server._transports
        orphan = transport.ctx.socket(zmq.PUSH)
        orphan.connect(f"tcp://127.0.0.1:{free_port()}")
        stopping.set()
        await server.stop()
        return transport.ctx.closed, orphan.closed

    assert stops_on_a_thread(scenario) == (True, True)


def test_zmq_stop_ends_a_handshake_that_still_awaits_its_echo():
    """The receive task is cancelled inside ``_handshake``, between the
    peer's PUSH socket being made and being filed: ``stop()`` closes
    that socket too."""
    async def scenario(stopping):
        server = make_server(
            http_enabled=False, ws_enabled=False, session_ttl=60.0)
        await server.start()
        [transport] = server._transports
        # the echo's PUSH gets no pipe before its peer listens, and
        # nobody ever does: the send waits
        transport.ctx.setsockopt(zmq.IMMEDIATE, 1)
        ident = uuid.uuid4()
        with zmq_context(zmq.asyncio.Context) as ctx:
            push = ctx.socket(zmq.PUSH)
            push.connect(f"tcp://127.0.0.1:{server.config.zmq_server_port}")
            await push.send(serialize_message(Message(
                instruction=Instruction.HANDSHAKE, sender_uuid=ident,
                parameter=f"127.0.0.1:{free_port()}")))
            # (the token is minted on the handshake's way to that send)
            assert await wait_for(
                lambda: server.sessions.get(ident) is not None)
            await asyncio.sleep(0.1)
            assert ident not in server.peer_map
            assert not transport._push_sockets
            stopping.set()
            await server.stop()
        return transport.ctx.closed

    assert stops_on_a_thread(scenario) is True


@contextlib.asynccontextmanager
async def zmq_served(**overrides):
    """A ZeroMQ-only server and a ``connect()`` whose clients close with
    it, pass or fail (a client context left open hangs the exit)."""
    server = make_server(http_enabled=False, ws_enabled=False, **overrides)
    clients = []

    async def connect() -> ZmqClient:
        clients.append(
            await ZmqClient.connect(server.config.zmq_server_port))
        return clients[-1]

    await server.start()
    try:
        yield server, connect
    finally:
        for client in clients:
            await client.close()
        await server.stop()


def frames(n: int, start: int = 0) -> list[Message]:
    return [
        Message(instruction=Instruction.LOCAL_MESSAGE, world_name="w",
                position=Vector3(5, 5, 5), parameter=f"m{i}")
        for i in range(start, start + n)
    ]


async def recv_parameters(client: ZmqClient, n: int) -> list[str]:
    return [
        (await client.recv_until(Instruction.LOCAL_MESSAGE)).parameter
        for _ in range(n)
    ]


async def nothing_more(client: ZmqClient) -> bool:
    try:
        await client.recv_until(Instruction.LOCAL_MESSAGE, timeout=0.3)
    except asyncio.TimeoutError:
        return True
    return False


def delivery_counters(server) -> tuple[int, int]:
    counters = server.metrics.counters
    return (counters["delivery.sync_frames"],
            counters["delivery.awaited_frames"])


class PlainSocketSpy:
    """Stands where a ZeroMQ peer's plain (shadow) socket does: counts
    the sends that reach it and refuses the attempts it is told to
    with ``zmq.Again``, as a socket at its high-water mark would."""

    def __init__(self, real):
        self.real = real
        self.attempts = 0
        self.refuse: set[int] = set()

    def send(self, data, flags=0):
        attempt = self.attempts
        self.attempts += 1
        if attempt in self.refuse:
            raise zmq.Again()
        return self.real.send(data, flags)


@pytest.fixture
def plain_sockets(monkeypatch):
    """Every plain socket the transport makes over a peer's PUSH
    socket, as spies, in the order of the handshakes. (pyzmq's own
    asyncio sockets shadow an ADDRESS, and are left alone.) A spy
    stands in the per-peer closure's way, not in the native pass's
    (which writes to the libzmq handle: tests/test_zmq_send_pass.py),
    so a transport made under this fixture is served by the closure."""
    from worldql_server_tpu.transports import zmq_pass

    monkeypatch.setattr(zmq_pass, "shared", lambda: None)
    made: list[PlainSocketSpy] = []
    shadow = zmq.Socket.shadow

    def spied(target):
        real = shadow(target)
        if not isinstance(target, zmq.Socket):
            return real
        made.append(PlainSocketSpy(real))
        return made[-1]

    monkeypatch.setattr(zmq.Socket, "shadow", staticmethod(spied))
    return made


def test_zmq_listener_queues_a_whole_deployment_dialling_at_once():
    """One PULL listener for every peer: its accept queue holds as many
    as one context can serve (libzmq's default of 100 made the rest of
    a reconnect storm wait out TCP's SYN retries, up to 63 s)."""
    async def scenario():
        async with zmq_served() as (server, connect):
            [transport] = server._transports
            assert transport._pull.getsockopt(zmq.BACKLOG) >= 1023
            await connect()
            assert server.peer_map.size() == 1

    run(scenario())


@pytest.mark.parametrize("n", [1, 50])
def test_zmq_flush_writes_straight_into_the_socket(n):
    """Over the real wire: the LocalMessages of ONE tick reach a ZeroMQ
    peer once each and in order through the synchronous path: no
    awaited frame, no ``deliver.drain`` in the tick's trace."""
    async def scenario():
        async with zmq_served(trace=True, tick_interval=30.0) as (
                server, connect):
            traces = []
            record = server.tracer.on_trace

            def keep(trace):
                traces.append(trace)
                record(trace)

            server.tracer.on_trace = keep
            sender, hearer = await connect(), await connect()
            for z in (sender, hearer):
                await z.send(Message(
                    instruction=Instruction.AREA_SUBSCRIBE,
                    world_name="w", position=Vector3(5, 5, 5)))
            for message in frames(n):
                await sender.send(message)
            # the pump sleeps 30 s: this flush is the one tick
            assert await wait_for(lambda: len(server.ticker._queue) == n)
            sync0, awaited0 = delivery_counters(server)
            await server.ticker.flush()
            assert await recv_parameters(hearer, n) == [
                f"m{i}" for i in range(n)]
            assert await nothing_more(hearer)
            sync1, awaited1 = delivery_counters(server)
            assert (sync1 - sync0, awaited1 - awaited0) == (n, 0)
            [tick] = [t for t in traces if t.name == "tick"
                      and any(s.name == "deliver.write" for s in t.spans)]
            spans = {s.name: s for s in tick.spans}
            assert spans["deliver.write"].tags["sync_frames"] == n
            assert spans["deliver.write"].tags["slow_peers"] == 0
            assert "deliver.drain" not in spans

    run(scenario())


@pytest.mark.parametrize("k", [0, 1, 13, 19])
def test_zmq_high_water_mark_hands_the_rest_to_the_awaited_path(
        k, plain_sockets):
    """``zmq.Again`` at frame k of 20: frames 0..k-1 went to the socket
    and are not sent again, k.. go through the awaited drain, and while
    that drain is open a second flush neither overtakes it nor takes
    the synchronous path."""
    async def scenario():
        async with zmq_served() as (server, connect):
            z = await connect()
            [spy] = plain_sockets
            peer = server.peer_map.get(z.uuid)
            # hold the awaited path shut, as a peer that does not read
            gate = asyncio.Event()
            send_raw = peer._send_raw

            async def gated(data):
                await gate.wait()
                await send_raw(data)

            peer._send_raw = gated
            spy.refuse = {spy.attempts + k}
            sync0, awaited0 = delivery_counters(server)
            first = asyncio.ensure_future(server.peer_map.deliver_batch(
                [(m, [z.uuid]) for m in frames(20)]))
            assert await wait_for(lambda: peer._drain is not None)
            attempts = spy.attempts
            second = asyncio.ensure_future(server.peer_map.deliver_batch(
                [(m, [z.uuid]) for m in frames(5, start=20)]))
            await asyncio.sleep(0.05)
            assert not first.done() and not second.done()
            # a broadcast's single-frame path refuses too
            assert not peer.try_write(FramedPayload(b"x"))
            assert spy.attempts == attempts     # nothing tried the socket
            gate.set()
            assert await first == 20 and await second == 5
            assert peer._drain is None
            assert await recv_parameters(z, 25) == [
                f"m{i}" for i in range(25)]
            assert await nothing_more(z)
            sync1, awaited1 = delivery_counters(server)
            assert (sync1 - sync0, awaited1 - awaited0) == (k, 25 - k)
            # with nothing owed the synchronous path takes over again
            await server.peer_map.deliver_batch(
                [(m, [z.uuid]) for m in frames(3, start=25)])
            assert delivery_counters(server) == (sync1 + 3, awaited1)
            assert await recv_parameters(z, 3) == ["m25", "m26", "m27"]

    run(scenario())


def test_zmq_full_socket_waits_in_the_awaited_path_and_loses_nothing():
    """A real socket at its real high-water mark: a peer that does not
    read takes what libzmq and the kernel buffer, the flush waits for
    the rest as it always did, a heartbeat is still answered behind
    it, and once the peer reads every frame arrives once, in order."""
    async def scenario():
        async with zmq_served() as (server, connect):
            z = await connect()
            n, blob = 6000, "x" * 8192          # ~48 MB owed
            messages = [
                Message(instruction=Instruction.LOCAL_MESSAGE,
                        world_name="w", position=Vector3(5, 5, 5),
                        parameter=f"{i}:{blob}")
                for i in range(n)
            ]
            sync0, awaited0 = delivery_counters(server)
            flush = asyncio.ensure_future(server.peer_map.deliver_batch(
                [(m, [z.uuid]) for m in messages]))
            peer = server.peer_map.get(z.uuid)
            assert await wait_for(lambda: peer._drain is not None)
            await asyncio.sleep(0.2)
            assert not flush.done()             # it waits, it drops nothing
            # a reply owed meanwhile queues behind the drain's frames
            await z.send(Message(instruction=Instruction.HEARTBEAT))
            got = []
            while len(got) < n + 1:
                message = await z.recv(timeout=10)
                got.append(message.instruction.name if message.instruction
                           != Instruction.LOCAL_MESSAGE
                           else int(message.parameter.split(":", 1)[0]))
            assert await flush == n
            assert [g for g in got if g != "HEARTBEAT"] == list(range(n))
            assert got.count("HEARTBEAT") == 1
            sync1, awaited1 = delivery_counters(server)
            assert sync1 - sync0 > 0 and awaited1 - awaited0 > 0
            assert (sync1 - sync0) + (awaited1 - awaited0) == n
            # the socket drained: the synchronous path is back
            await server.peer_map.deliver_batch(
                [(m, [z.uuid]) for m in frames(3)])
            assert delivery_counters(server) == (sync1 + 3, awaited1)
            assert await recv_parameters(z, 3) == ["m0", "m1", "m2"]

    run(scenario())


@pytest.mark.parametrize("n", [1, 6])
def test_zmq_failed_synchronous_send_evicts_that_peer_alone(n):
    """``transport.send=error:1:x1`` fires on the synchronous path: the
    peer it hits is evicted as a failed awaited send evicts (counter,
    ``remove_if``, loss hook, bytes taken back), the other peer of the
    same flush gets every frame."""
    from worldql_server_tpu.robustness import failpoints

    async def scenario():
        async with zmq_served() as (server, connect):
            victim, other = await connect(), await connect()
            losses = []
            server.peer_map.on_frame_loss = losses.append
            messages = frames(n)
            size = len(serialize_message(messages[0]))
            bytes0 = server.peer_map.bytes_delivered
            sync0, awaited0 = delivery_counters(server)
            fired0 = failpoints.registry.fired("transport.send")
            failpoints.registry.set("transport.send", "error:1:x1")
            try:
                sent = await server.peer_map.deliver_batch(
                    [(m, [victim.uuid, other.uuid]) for m in messages])
            finally:
                failpoints.registry.clear()
            assert failpoints.registry.fired("transport.send") == fired0 + 1
            assert sent == 2 * n
            counters = server.metrics.counters
            assert counters["peers.evicted_send_failed"] == 1
            assert counters["broadcast.send_errors"] == n
            assert losses == [victim.uuid]
            assert server.peer_map.bytes_delivered - bytes0 == n * size
            # the victim's frames failed in the awaited path, counted
            assert delivery_counters(server) == (sync0 + n, awaited0 + n)
            assert await recv_parameters(other, n) == [
                f"m{i}" for i in range(n)]
            assert await wait_for(
                lambda: server.peer_map.get(victim.uuid) is None)
            assert server.peer_map.get(other.uuid) is not None
            [transport] = server._transports
            assert victim.uuid not in transport._push_sockets
            assert await nothing_more(victim)

    run(scenario())


@pytest.mark.parametrize("case", ["plane-adopted", "closed", "socket-gone"])
def test_zmq_synchronous_path_is_not_for(case, plain_sockets):
    """A peer the delivery plane adopted writes to its worker's ring,
    a closed peer and one whose socket is gone fall back to the awaited
    path: none of them sends on the parent's plain socket."""
    async def scenario():
        adopted = case == "plane-adopted"
        async with zmq_served(delivery_workers=int(adopted)) as (
                server, connect):
            z = await connect()
            [spy] = plain_sockets
            peer = server.peer_map.get(z.uuid)
            [transport] = server._transports
            if adopted:
                assert peer.shard is not None
                assert z.uuid not in transport._push_sockets
                await asyncio.sleep(0.25)   # the worker connects its PUSH
            elif case == "closed":
                peer.closed = True
            else:
                transport._drop_socket(z.uuid)
            sent = await server.peer_map.deliver_batch(
                [(m, [z.uuid]) for m in frames(4)])
            assert sent == 4 and spy.attempts == 0
            if adopted:
                assert await recv_parameters(z, 4) == [
                    "m0", "m1", "m2", "m3"]
            else:
                assert delivery_counters(server) == (0, 4)
                assert server.metrics.counters["broadcast.send_errors"] == 4
                assert await nothing_more(z)

    run(scenario())


# endregion
