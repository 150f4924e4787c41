"""Minimal WorldQL clients for tests and manual driving.

Speak the real wire protocol over real sockets — the same path an
external game plugin would use.
"""

from __future__ import annotations

import asyncio
import contextlib
import faulthandler
import socket
import threading
import uuid as uuid_mod

import zmq
import zmq.asyncio

try:
    from websockets.asyncio.client import connect as ws_connect
except ModuleNotFoundError:  # minimal containers: WS-dependent tests
    ws_connect = None        # importorskip("websockets") and skip

from worldql_server_tpu.protocol import (
    Instruction,
    Message,
    deserialize_message,
    serialize_message,
)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@contextlib.contextmanager
def dead_port():
    """A loopback port nobody listens at, and nobody else can take
    while the block lasts: bound, never listening, so a dial is
    refused. (A port read from ``free_port()`` is anybody's again, the
    same test's next ``bind_to_random_port`` included.)"""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        yield s.getsockname()[1]


@contextlib.contextmanager
def zmq_context(kind=zmq.Context):
    """The one way a test makes and ends a zmq context (``kind``:
    ``zmq.asyncio.Context`` for awaitable sockets). Its sockets are
    born with ``LINGER 0``, and it ends by ``destroy(linger=0)``, which
    closes every socket made from it, the ones a failed test never got
    to close too: a bare ``term()`` waits for those for ever."""
    ctx = kind()
    ctx.setsockopt(zmq.LINGER, 0)
    try:
        yield ctx
    finally:
        ctx.destroy(linger=0)


def stops_on_a_thread(scenario, grace: float = 5.0):
    """``scenario(stopping)`` on a loop of a thread of its own; it sets
    ``stopping`` right before the stop under test, which then has
    ``grace`` seconds. (A stop that waits in ``zmq_ctx_term`` blocks
    its loop's thread, so no ``wait_for`` on that loop can end it.)
    Returns what the scenario returned."""
    stopping, out = threading.Event(), []
    thread = threading.Thread(
        target=lambda: out.append(asyncio.run(scenario(stopping))),
        daemon=True)
    thread.start()
    assert stopping.wait(30), "never got as far as the stop"
    thread.join(grace)
    if thread.is_alive():
        faulthandler.dump_traceback(all_threads=True)   # where it waits
        raise AssertionError(f"stop() still waits after {grace} s")
    [result] = out
    return result


class WsClient:
    """WebSocket client: server assigns our UUID (websocket.rs:51-87).
    With sessions enabled the assigning handshake carries a resume
    token as ``flex`` (kept on ``self.token``)."""

    def __init__(self, connection, uuid: uuid_mod.UUID,
                 token: str | None = None):
        self.connection = connection
        self.uuid = uuid
        self.token = token

    @classmethod
    async def connect(cls, port: int, host: str = "127.0.0.1") -> "WsClient":
        if ws_connect is None:
            raise RuntimeError("websockets is not installed")
        connection = await ws_connect(f"ws://{host}:{port}")
        handshake = deserialize_message(await connection.recv())
        assert handshake.instruction == Instruction.HANDSHAKE
        assigned = uuid_mod.UUID(handshake.parameter)
        token = (
            bytes(handshake.flex).decode("ascii")
            if handshake.flex else None
        )
        client = cls(connection, assigned, token)
        await client.send(Message(instruction=Instruction.HANDSHAKE))
        return client

    @classmethod
    async def resume(
        cls, port: int, token: str, uuid: uuid_mod.UUID,
        host: str = "127.0.0.1",
    ) -> "WsClient":
        """Reconnect presenting a session token: the echo carries it
        as ``flex`` and the server rebinds this connection to the
        parked peer ``uuid`` — subsequent frames sign as it."""
        if ws_connect is None:
            raise RuntimeError("websockets is not installed")
        connection = await ws_connect(f"ws://{host}:{port}")
        handshake = deserialize_message(await connection.recv())
        assert handshake.instruction == Instruction.HANDSHAKE
        assigned = uuid_mod.UUID(handshake.parameter)
        client = cls(connection, assigned, token)
        await client.send(Message(
            instruction=Instruction.HANDSHAKE, flex=token.encode(),
        ))
        client.uuid = uuid
        return client

    async def drop(self) -> None:
        """Hard drop: kill the TCP socket without a close frame — the
        network-blip shape session continuity exists for."""
        transport = getattr(self.connection, "transport", None)
        if transport is not None:
            transport.abort()
        else:  # older websockets: best effort
            await self.connection.close()

    async def send(self, message: Message) -> None:
        message.sender_uuid = self.uuid
        await self.connection.send(serialize_message(message))

    async def send_raw(self, data) -> None:
        await self.connection.send(data)

    async def recv(self, timeout: float = 2.0) -> Message:
        frame = await asyncio.wait_for(self.connection.recv(), timeout)
        return deserialize_message(frame)

    async def recv_until(
        self, instruction: Instruction, timeout: float = 2.0
    ) -> Message:
        while True:
            message = await self.recv(timeout)
            if message.instruction == instruction:
                return message

    async def close(self) -> None:
        await self.connection.close()


class ZmqClient:
    """ZeroMQ client: we pick our UUID and hand the server a
    connect-back address (incoming.rs:52-72, outgoing.rs:81-130).
    With sessions enabled the handshake echo's parameter carries a
    resume token (kept on ``self.token``); a refused handshake echoes
    ``retry-after:<ms>`` instead (``self.retry_after_ms``)."""

    #: Connected and not closed yet. The strong reference is the point:
    #: a client a (failed) test never closed must not be left to the
    #: collector. Its sockets sit in cycles of their own futures, the
    #: collector clears weak references before it runs finalizers, so
    #: pyzmq's ``Context.__del__`` finds its set of sockets empty,
    #: closes nothing and calls ``term()``, which then waits for ever
    #: for sockets whose finalizers queue behind it: tier-1's 21-minute
    #: hang (PERF.md, PR 43). ``tests/conftest.py`` ends what is left
    #: here after every test.
    _open: set = set()

    def __init__(self, push, pull, uuid: uuid_mod.UUID):
        self.push = push  # client → server PULL
        self.pull = pull  # server PUSH → client
        self.uuid = uuid
        self.token: str | None = None
        self.retry_after_ms: int | None = None
        self._end = None  # ends the context (set once connected)

    @classmethod
    async def connect(
        cls, server_port: int, host: str = "127.0.0.1",
        peer_uuid: uuid_mod.UUID | None = None,
        token: str | None = None,
    ) -> "ZmqClient":
        """Handshake (optionally presenting ``token`` to resume a
        parked session under ``peer_uuid``). A handshake that fails
        ends the context on its way out."""
        with contextlib.ExitStack() as stack:
            ctx = stack.enter_context(zmq_context(zmq.asyncio.Context))
            pull = ctx.socket(zmq.PULL)
            client_port = pull.bind_to_random_port(f"tcp://{host}")
            push = ctx.socket(zmq.PUSH)
            push.connect(f"tcp://{host}:{server_port}")

            client = cls(push, pull, peer_uuid or uuid_mod.uuid4())
            await client.send(
                Message(
                    instruction=Instruction.HANDSHAKE,
                    parameter=f"{host}:{client_port}",
                    flex=token.encode() if token is not None else None,
                )
            )
            echo = await client.recv()
            assert echo.instruction == Instruction.HANDSHAKE
            if echo.parameter is not None:
                if echo.parameter.startswith("retry-after:"):
                    client.retry_after_ms = int(
                        echo.parameter.split(":", 1)[1]
                    )
                else:
                    client.token = echo.parameter
            client._end = stack.pop_all().close
        cls._open.add(client)
        return client

    @classmethod
    async def resume(
        cls, server_port: int, token: str, peer_uuid: uuid_mod.UUID,
        host: str = "127.0.0.1",
    ) -> "ZmqClient":
        return await cls.connect(
            server_port, host, peer_uuid=peer_uuid, token=token,
        )

    async def send(self, message: Message) -> None:
        message.sender_uuid = self.uuid
        await self.push.send(serialize_message(message))

    async def send_raw(self, data: bytes) -> None:
        """Send pre-serialized (possibly router-framed) bytes as-is —
        lets a test impersonate the cluster router's forward leg."""
        await self.push.send(data)

    async def recv(self, timeout: float = 2.0) -> Message:
        data = await asyncio.wait_for(self.pull.recv(), timeout)
        return deserialize_message(data)

    async def recv_until(
        self, instruction: Instruction, timeout: float = 2.0
    ) -> Message:
        while True:
            message = await self.recv(timeout)
            if message.instruction == instruction:
                return message

    async def close(self) -> None:
        self._open.discard(self)
        self._end()

    @classmethod
    def close_leftovers(cls) -> None:
        while cls._open:
            cls._open.pop()._end()
