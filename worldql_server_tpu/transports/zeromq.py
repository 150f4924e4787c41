"""ZeroMQ transport.

Rebuild of the reference's asymmetric socket pattern
(worldql_server/src/transport/zeromq/): the server binds one PULL
socket for all inbound traffic (incoming.rs:19-24); each client runs
its own PULL and the server connects a dedicated PUSH socket *back* to
an address the client supplies as the Handshake ``parameter``
(outgoing.rs:95-118).

Handshake flow: a message from an unknown sender UUID is dropped unless
it is a Handshake carrying an address parameter; the server then
connects a PUSH socket to ``tcp://<parameter>``, echoes a bare
Handshake (nil sender, no parameter — outgoing.rs:108-118), and
registers the peer. Known senders' Handshakes are swallowed
(incoming.rs:56-61); UUID clashes drop the handshake
(outgoing.rs:88-94). ZMQ peers are heartbeat-tracked: the engine's
staleness sweeper evicts them (outgoing.rs:28-47,132-150), and a failed
send evicts immediately (outgoing.rs:66-76).

Session continuity (``--session-ttl``, robustness/sessions.py): the
handshake echo's ``parameter`` carries a minted session token; a
reconnecting client presents it as ``flex`` on its Handshake and the
server rebinds the new connect-back to the parked state — valid even
while the stale old binding is still registered (the server has not
yet noticed the drop). Handshakes are also a governor admission class
(``--overload on``): a refused handshake gets a one-shot jittered
``retry-after:<ms>`` Handshake on its connect-back address (budgeted —
the refusal path must not become a reflector) and no registration
work happens at all.
"""

from __future__ import annotations

import asyncio
import logging
import time
import uuid as uuid_mod

import zmq
import zmq.asyncio

from ..engine.peers import PassEnd, Peer
from ..observability.spans import NOOP_SPAN
from ..protocol.entity_wire import RECV_DRAIN_MAX
from ..protocol import (
    DeserializeError,
    Instruction,
    Message,
    deserialize_message,
    serialize_message,
)
from ..robustness import failpoints
from . import zmq_pass

logger = logging.getLogger(__name__)

#: the PULL listener's accept queue: as many as one zmq context holds
#: sockets (ZMQ_MAX_SOCKETS, 1,023), so a whole deployment can dial at
#: once (the kernel caps it at net.core.somaxconn)
_LISTEN_BACKLOG = 1024

#: longest the recv loop works through a backlog before it lets the
#: rest of the event loop (ticker, HTTP, senders) run
_RECV_YIELD_SECS = 0.01


def _valid_socket_addr(parameter: str) -> bool:
    """The reference parses the parameter as a SocketAddr
    (outgoing.rs:97-103): ``ip:port`` (IPv4 or bracketed IPv6)."""
    import ipaddress

    host, sep, port = parameter.rpartition(":")
    if not sep or not host:
        return False
    if host.startswith("[") and host.endswith("]"):
        host = host[1:-1]
    try:
        ipaddress.ip_address(host)
    except ValueError:
        return False
    return port.isdigit() and 0 < int(port) < 65536


class _CountedPull:
    """The PULL socket of a TRACED server: the receive measured where
    it happens, outside every span (the ``zmq_recv`` gauge). A message
    found waiting costs the loop the ``recv_multipart`` CALL alone
    (pyzmq's Python, a ``getsockopt(EVENTS)``, the non-blocking
    receive): ``take_ns``, ``perf_counter_ns`` around every call, those
    of a drain that found nothing too. One that arrives on an idle
    socket costs a suspended await besides: zmq's fd handler, a task
    wake-up, two trips through the selector. ``suspends`` counts the
    calls whose future was not done on return; ``messages`` those that
    took a multipart or suspended for one (a suspended receive ends
    with a message or with the loop, so at a scrape at most one is
    still to arrive). ``suspends`` / ``messages`` is the socket's
    queue as the loop sees it: near 1 a wake cycle a message, near 0 a
    backlog drained. Built by ``start`` only with tracing on: an
    untraced server's ``_pull`` is the socket itself."""

    __slots__ = ("_sock", "_recv", "messages", "suspends", "take_ns")

    def __init__(self, sock):
        self._sock = sock
        self._recv = sock.recv_multipart
        self.messages = self.suspends = self.take_ns = 0

    def recv_multipart(self, flags=0):
        t0 = time.perf_counter_ns()
        recv = self._recv(flags)
        self.take_ns += time.perf_counter_ns() - t0
        if not recv.done():
            self.suspends += 1
            self.messages += 1
        elif not recv.cancelled() and recv.exception() is None:
            self.messages += 1      # (else zmq.Again: nothing was there)
        return recv

    def stats(self) -> dict:
        return {"messages": self.messages, "suspends": self.suspends,
                "take_ns": self.take_ns}

    def __getattr__(self, name):    # close, setsockopt: the socket's
        return getattr(self._sock, name)


class ZmqTransport:
    def __init__(self, server):
        self.server = server
        self.ctx = zmq.asyncio.Context()
        # (a _CountedPull around it when the server is traced)
        self._pull: zmq.asyncio.Socket | None = None
        self._push_sockets: dict[uuid_mod.UUID, zmq.asyncio.Socket] = {}
        self._recv_task: asyncio.Task | None = None
        self._recv_handle = None  # SupervisedTask under a supervisor
        # the columnar recv loop's awaited receive, until the loop or
        # _stage_edge has taken its message
        self._awaited: asyncio.Future | None = None
        # Failed-send evictions run as tasks; the loop only weak-refs
        # running tasks, so retain them or a GC pass could drop an
        # eviction mid-flight and leak the dead peer from the map.
        self._evictions: set[asyncio.Task] = set()
        self._yielded = time.monotonic()  # see _give_way
        # The flush's native writer (None: the library lacks the
        # symbol, and every flush is written peer by peer).
        self._send_pass = zmq_pass.shared()

    async def start(self) -> None:
        config = self.server.config
        self._pull = self.ctx.socket(zmq.PULL)
        # Bound inbound frames BEFORE bind: without MAXMSGSIZE a single
        # hostile peer can stream an arbitrarily large message into
        # server memory (libzmq buffers the whole frame). Oversized
        # senders are disconnected by libzmq; the PULL socket and every
        # other peer keep working.
        self._pull.setsockopt(zmq.MAXMSGSIZE, config.max_message_size)
        # Every peer dials this ONE listener, and after a restart they
        # all dial at once. libzmq's default backlog of 100 drops the
        # SYNs beyond it, and TCP retries those 1, 3, 7, 15, 31, 63 s
        # later: 896 peers took 68 s to connect, the last of them past
        # their 120 s patience (PERF.md, PR 25).
        self._pull.setsockopt(zmq.BACKLOG, _LISTEN_BACKLOG)
        self._pull.bind(f"tcp://{config.zmq_server_host}:{config.zmq_server_port}")
        tracer = getattr(self.server, "tracer", None)
        if tracer is not None and tracer.enabled:
            self._pull = _CountedPull(self._pull)
            self.server.metrics.gauge("zmq_recv", self._pull.stats)
        logger.info(
            "ZeroMQ PULL server listening on %s:%s",
            config.zmq_server_host,
            config.zmq_server_port,
        )
        logger.info(
            "ZeroMQ flush writer: %s",
            "one native pass a flush (wql_send_pass, %d thread%s)" % (
                self._send_pass.threads,
                "" if self._send_pass.threads == 1 else "s")
            if self._send_pass is not None
            else "peer by peer (no native send pass loaded)",
        )
        supervisor = getattr(self.server, "supervisor", None)
        if supervisor is not None:
            # CRITICAL: a permanently dead recv loop is a silently deaf
            # transport — restart within budget, then escalate
            self._recv_handle = supervisor.spawn(
                "zmq-recv", self._recv_loop, critical=True
            )
        else:
            self._recv_task = asyncio.create_task(self._recv_loop(), name="zmq-pull")  # wql: allow(unsupervised-task)
        fast = getattr(self.server, "entity_ingest", None)
        ticker = getattr(self.server, "ticker", None)
        if fast is not None and fast.active and ticker is not None:
            # the held batch's tick edge: see _stage_edge
            ticker.ingest_edge = self._stage_edge

    async def stop(self) -> None:
        if self._recv_handle is not None:
            await self._recv_handle.stop()
            self._recv_handle = None
        if self._recv_task is not None:
            self._recv_task.cancel()
            try:
                await self._recv_task
            except (asyncio.CancelledError, Exception):
                pass
            self._recv_task = None
        fast = getattr(self.server, "entity_ingest", None)
        if fast is not None:
            # what the recv loop held and no flush staged (the ticker
            # stops first): routed while the peers' sockets are open
            await fast.stage(self._route_data)
        # Every socket of the context, linger 0: the peers' PUSH, the
        # PULL, and whatever no list names (a handshake cancelled
        # between ``ctx.socket()`` and ``_push_sockets``, a refusal
        # hint in flight). A bare ``term()`` waits for those for ever,
        # on the thread whose collector might have closed them.
        # ``destroy`` closes from this thread, and no other touches
        # these sockets: they are the loop's, and the send pass's
        # helper threads live inside one ``wql_send_pass`` call that
        # this thread made and has returned from.
        self._push_sockets.clear()
        self._pull = None
        self.ctx.destroy(linger=0)

    async def _recv_loop(self) -> None:
        """PULL loop (incoming.rs:26-75): multipart frames are
        concatenated, deserialized-or-dropped, then routed.

        Columnar drain (--entity-sim + native codec): everything the
        socket already holds — bounded by ``RECV_DRAIN_MAX`` — is
        handed to ``ColumnarIngest.hold``, which keeps entity-update
        LocalMessages as bytes. The held batch is staged (batch-decoded
        straight into the plane's SoA columns, the rest routed through
        ``_route_data`` in arrival order) ONCE a tick edge, by
        ``_stage_edge`` at the start of every pump flush, and from here
        only when ``hold`` asks: at ``RECV_DRAIN_MAX`` messages or
        ``_RUN_ROWS_MAX`` rows held, and for every buffer that is not
        an entity update, which is thereby routed on receipt behind
        what was held before it. ``stop`` stages what is left. Without
        the fast path the loop is the per-message path it always was.

        Per-message crash containment: ANY exception escaping the
        processing of one message (a router bug a hostile payload
        tickles, a handshake connect error) drops THAT message —
        logged and counted in ``zmq.recv_errors`` — and the loop keeps
        receiving. Before this, one poison message permanently deafened
        the transport while the process kept running. Faults in the
        receive machinery itself (socket teardown, the `zmq.recv`
        failpoint) still escape and are the supervisor's job."""
        assert self._pull is not None
        limit = self.server.config.max_message_size
        while True:
            # outside the containment: kills the LOOP, exercising the
            # supervisor's restart/escalate policy in the chaos suite
            failpoints.fire("zmq.recv")
            await self._give_way()
            fast = getattr(self.server, "entity_ingest", None)
            if fast is None or not fast.active:
                parts = await self._pull.recv_multipart()
                try:
                    await self._process_inbound(parts, limit)
                except Exception:
                    self.server.metrics.inc("zmq.recv_errors")
                    logger.exception(
                        "error processing inbound zmq message — dropped"
                    )
                continue
            self._awaited = recv = self._pull.recv_multipart()
            parts = await recv
            if self._awaited is not recv:
                continue  # _stage_edge took it, ahead of what it drained
            self._awaited = None
            await self._drain(fast, parts)

    async def _drain(self, fast, parts: list[bytes] | None = None,
                     edge: bool = False) -> None:
        """Hand ``parts`` and then what the socket holds, at most
        ``RECV_DRAIN_MAX`` messages, to the ingest; stage when it
        asks. The recv loop's and the flush-start drain's (``edge``)
        one way in."""
        limit = self.server.config.max_message_size
        # Clustered shards receive router-framed bytes (the WQTX
        # trace prefix, cluster/tracectx.py). Strip it BEFORE the
        # native entity classifier — a prefixed buffer fails
        # classification and the whole batch degrades to the
        # object path (PR 15's KNOWN GAP, closed here) — and
        # carry the ctx alongside so slow-routed messages still
        # thread trace_ctx onto their Message.
        cluster = getattr(self.server, "cluster", None)
        unwrapped = 0
        for _ in range(RECV_DRAIN_MAX):
            if parts is None:
                try:
                    parts = await self._pull.recv_multipart(zmq.NOBLOCK)
                except zmq.Again:
                    break
            data = self._flatten(parts, limit)
            parts = None
            if data is not None:
                unwrapped += await self._absorb_inbound(
                    fast, cluster, data, edge
                )
        if unwrapped:
            # the fast-path-through-router proof: router-framed
            # messages reaching the columnar batch pre-unwrapped
            self.server.metrics.inc("zmq.ctx_unwrapped", unwrapped)

    async def _stage_edge(self) -> None:
        """The tick edge of the columnar way in (the pump calls it as
        each of its flushes starts, ``TickBatcher.ingest_edge``): take
        what the socket still holds and stage the held batch in ONE
        pass, so an update received or waiting in libzmq when a flush
        starts is in that flush's fold. Under ``zmq.stage``: the loop's
        account charges the pass to ``ingest`` though the pump's task
        runs it."""
        if self._pull is None:
            return  # stopped: stop() staged what was held
        fast = self.server.entity_ingest    # start() hooked it to one
        tracer = getattr(self.server, "tracer", None)
        with tracer.span("zmq.stage") if tracer is not None else NOOP_SPAN:
            # a message the recv loop's awaited receive already took
            # out of the socket, while its task has not run yet, came
            # before everything still in there
            recv, parts = self._awaited, None
            if (recv is not None and recv.done() and not recv.cancelled()
                    and recv.exception() is None):
                self._awaited, parts = None, recv.result()
            await self._drain(fast, parts, edge=True)
            # contains per message internally; never raises
            await fast.stage(self._route_data, edge=True)

    async def _absorb_inbound(self, fast, cluster, data: bytes,
                              edge: bool) -> int:
        """Hand one inbound frame to the ingest. Live resharding
        (cluster/resharding) adds two diverts ahead of the hold:
        freeze FENCE frames ack over control instead of decoding, and
        STALE-EPOCH frames (stamped under an older placement than this
        shard holds) take the full decode + ownership check — a stale
        entity frame must never reach the SoA columns directly, it may
        belong to a world this shard just lost. Everything else is
        held with its trace ctx in lockstep. Returns 1 when a live
        trace ctx was stripped."""
        ctx = None
        if cluster is not None:
            trace_id, t_ctx, epoch, data = cluster.unwrap(data)
            if data[:4] == cluster.FENCE_MAGIC:
                cluster.on_fence(data)
                return 0
            ctx = (trace_id, t_ctx)
            if cluster.frame_stale(epoch):
                await self._route_data(data, ctx=ctx, epoch=epoch)
                return 0
        if fast.hold(data, ctx):
            await fast.stage(self._route_data, edge)
        return 1 if ctx is not None and ctx[0] else 0

    def _flatten(self, parts: list[bytes], limit: int) -> bytes | None:
        """Bound + join one multipart message (None = dropped).
        MAXMSGSIZE bounds each PART; bound the flattened total before
        the join materializes it a second time. (libzmq assembles
        multipart atomically before delivery, so its own buffering of
        many under-cap parts cannot be bounded by any socket option —
        see Config.max_message_size.)"""
        if sum(len(p) for p in parts) > limit:
            logger.warning(
                "dropping oversized multipart zmq message (%d parts)",
                len(parts),
            )
            return None
        return b"".join(parts)

    async def _process_inbound(self, parts: list[bytes], limit: int) -> None:
        """One inbound multipart message: bound, decode, route."""
        data = self._flatten(parts, limit)
        if data is not None:
            await self._route_data(data)

    async def _give_way(self) -> None:
        """Let the rest of the event loop run once the recv path has
        held it for ``_RECV_YIELD_SECS``. A recv on a socket with a
        backlog completes without suspending, and a drained batch is
        routed message after message, so a burst — 100,000 entity
        registrations took a v5e host 17 s — would keep the ticker and
        /healthz off the loop until its last message."""
        if time.monotonic() - self._yielded > _RECV_YIELD_SECS:
            await asyncio.sleep(0)
            self._yielded = time.monotonic()

    async def _route_data(self, data: bytes,
                          ctx: tuple[int, int] | None = None,
                          epoch: int = 0) -> None:
        await self._give_way()
        tracer = getattr(self.server, "tracer", None)
        if tracer is not None and tracer.enabled:
            # recv→decode→route under one span tree: the decode and the
            # router's handle span nest inside "zmq.recv", so a slow
            # inbound message shows WHERE it spent its wall time
            with tracer.span("zmq.recv", bytes=len(data)) as rspan:
                await self._decode_route(data, tracer, rspan, ctx=ctx,
                                         epoch=epoch)
        else:
            await self._decode_route(data, None, ctx=ctx, epoch=epoch)

    async def _decode_route(self, data: bytes, tracer, rspan=None,
                            ctx: tuple[int, int] | None = None,
                            epoch: int = 0) -> None:
        # Cluster shards receive every message through the router,
        # which frames a trace context on (cluster/tracectx.py):
        # strip it BEFORE the codec (fan-out re-broadcasts the
        # unwrapped bytes) and thread it onto the Message so delivery
        # closes the router-ingress clock at socket-write-complete.
        # The columnar recv loop unwraps pre-batch (the native
        # classifier needs bare wire bytes) and passes the ctx in;
        # the per-message path unwraps here. Non-cluster servers pay
        # one attribute test.
        if ctx is not None:
            trace_id, t_ctx = ctx
        else:
            cluster = getattr(self.server, "cluster", None)
            trace_id = t_ctx = 0
            if cluster is not None:
                trace_id, t_ctx, epoch, data = cluster.unwrap(data)
                if data[:4] == cluster.FENCE_MAGIC:
                    # freeze fence on the per-message path (no columnar
                    # fast path armed): ack over control, never decode
                    cluster.on_fence(data)
                    return
        try:
            failpoints.fire("codec.decode")
            if tracer is not None:
                with tracer.span("codec.decode"):
                    message = deserialize_message(data)
            else:
                message = deserialize_message(data)
        except DeserializeError:
            logger.debug("dropping invalid zmq message: deserialize error")
            return
        if epoch:
            # live resharding: a frame stamped under an older placement
            # epoch, for a world/peer this shard no longer owns, bounces
            # back to the router as a re-route hint instead of mutating
            # state the placement already moved away
            cluster = getattr(self.server, "cluster", None)
            if (
                cluster is not None
                and cluster.frame_stale(epoch)
                and cluster.frame_misrouted(message, epoch)
            ):
                return
        if trace_id:
            message.trace_ctx = (trace_id, t_ctx)
            if rspan is not None:
                # the cross-process chain key: this span tree carries
                # the same trace id the router's forward span and the
                # remote shard's stitched ring spans carry
                rspan.tag(trace_id=format(trace_id, "016x"))

        if message.sender_uuid in self.server.peer_map:
            if message.instruction != Instruction.HANDSHAKE:
                await self.server.router.handle_message(message)
                return
            # known-sender handshakes are swallowed (incoming.rs:56-61)
            # UNLESS a valid session token rides along: the client is
            # resuming over a stale binding the server has not yet
            # noticed dropping — rebind instead of ignoring
            sessions = getattr(self.server, "sessions", None)
            if sessions is None or sessions.peek(
                message.flex, message.sender_uuid
            ) is None:
                return
            await self._handle_handshake(message)
            return

        if (
            message.instruction != Instruction.HANDSHAKE
            or message.parameter is None
        ):
            return  # unknown sender, not a handshake → ignore

        await self._handle_handshake(message)

    async def _handle_handshake(self, message: Message) -> None:
        """Connect-back PUSH + handshake echo + registration or
        session resume (outgoing.rs:81-130). Admission runs BEFORE any
        connect-back/socket work — a shed handshake costs one decode."""
        sessions = getattr(self.server, "sessions", None)
        session = None
        if sessions is not None:
            session = sessions.peek(message.flex, message.sender_uuid)
        if message.sender_uuid in self.server.peer_map and session is None:
            return  # clashing UUID → drop

        parameter = message.parameter
        if parameter is None or not _valid_socket_addr(parameter):
            return  # invalid socket address → drop
        endpoint = f"tcp://{parameter}"

        # Storm-safe admission (ISSUE 12): new connects shed before
        # resumes; REJECT still admits resumes up to the governor's
        # token bucket. Refusals get a budgeted jittered retry-after
        # hint on the address the client just supplied.
        governor = getattr(self.server, "governor", None)
        if governor is not None:
            admitted, retry_ms = governor.admit_handshake(
                resume=session is not None
            )
            if not admitted:
                await self._send_refusal(endpoint, retry_ms, governor)
                return

        logger.debug("zeromq peer address: %s", endpoint)
        peer_uuid = message.sender_uuid

        token = None
        if sessions is not None:
            if session is not None:
                token = session.token
            else:
                if sessions.get(peer_uuid) is not None:
                    # tokenless handshake for a UUID with held state:
                    # that state belongs to the TOKEN holder — tear it
                    # down first; this is a brand-new peer
                    self.server._teardown_peer_state(peer_uuid)
                token = sessions.mint(peer_uuid, "zeromq").token

        push = self.ctx.socket(zmq.PUSH)
        push.setsockopt(zmq.LINGER, 0)
        push.connect(endpoint)

        # Handshake echo: nil sender (outgoing.rs:108-118); with
        # sessions enabled the parameter carries the resume token
        # (``--session-ttl 0`` keeps the bare no-parameter echo).
        await push.send(
            serialize_message(
                Message(instruction=Instruction.HANDSHAKE, parameter=token)
            )
        )

        def evict() -> None:
            # Failed send ⇒ evict peer (outgoing.rs:66-76) — but
            # only while THIS binding is still current: a stale
            # binding's dying send must not evict a resumed one.
            self.server.metrics.inc("peers.evicted_send_failed")
            self._drop_socket(peer_uuid)
            task = asyncio.get_running_loop().create_task(  # wql: allow(unsupervised-task)
                self.server.peer_map.remove_if(peer_uuid, peer)
            )
            self._evictions.add(task)
            task.add_done_callback(self._evictions.discard)

        awaited = 0  # send_raw calls that have not returned yet

        async def send_raw(data: bytes) -> None:
            nonlocal awaited
            sock = self._push_sockets.get(peer_uuid)
            if sock is None:
                raise ConnectionError("push socket gone")
            awaited += 1
            try:
                failpoints.fire("transport.send")
                await sock.send(data)
            except Exception:
                evict()
                raise
            finally:
                awaited -= 1

        # The flush's way out: a plain socket over the same libzmq
        # socket, so a send is one call and no Future. Never closed
        # (closing a shadow closes what it shadows), never used once
        # ``push`` has left ``_push_sockets``, and never while an
        # awaited send is in flight: it would overtake that send, and
        # take the socket's one edge-triggered wake-up from it.
        send_now = zmq.Socket.shadow(push).send
        handle = push.underlying

        def writable() -> bool:
            """The one guard of both sync writers: no awaited send in
            flight, and this binding is still the current one."""
            return not awaited and self._push_sockets.get(peer_uuid) is push

        def try_write_many(framed_list) -> int:
            """Sync path: each frame its own message, non-blocking,
            in order. Returns how many the socket took; the caller
            owes the rest to ``send_raw``: after ``zmq.Again`` (the
            high-water mark) they wait there as they always did,
            after an eviction they fail there and are counted."""
            if not writable():
                return 0
            taken = 0
            try:
                for framed in framed_list:
                    failpoints.fire("transport.send")
                    send_now(framed.payload, zmq.DONTWAIT)
                    taken += 1
            except zmq.Again:
                pass
            except Exception:
                evict()
            return taken

        def pass_handle() -> int:
            """The same libzmq socket for the flush's native pass,
            which does per peer what the closure above does; 0 sends
            the peer to the closure: it is not writable, or the
            ``transport.send`` failpoint is armed and only the closure
            can fire it frame by frame."""
            if writable() and not failpoints.armed("transport.send"):
                return handle
            return 0

        def try_write(framed) -> bool:
            return try_write_many((framed,)) == 1

        old = None
        if session is not None:
            # Resume: silently drop the stale old binding (connect-back
            # socket, delivery shard slot) — parked state untouched —
            # so the fresh binding below can take its place, possibly
            # on a different shard.
            old = self.server.prepare_rebind(peer_uuid)

        peer = Peer(
            uuid=peer_uuid,
            addr=parameter,
            send_raw=send_raw,
            kind="zeromq",
            tracks_heartbeat=True,
            try_write=try_write,
            try_write_many=try_write_many,
            pass_end=None if self._send_pass is None else PassEnd(
                self._send_pass, pass_handle, evict),
        )
        plane = getattr(self.server, "delivery_plane", None)
        adopted = plane is not None and plane.adopt(peer, endpoint=endpoint)
        if adopted:
            # the owning sender worker connects its OWN PUSH to the
            # peer's PULL; the parent's echo socket closes once the
            # handshake echo flushes (bounded linger) — from here on
            # every frame for this peer rides the worker's shard
            push.close(linger=2000)
        else:
            # single-process mode (or degraded plane): the parent owns
            # the socket, reference semantics unchanged
            self._push_sockets[peer_uuid] = push
        if session is not None:
            sessions.resume(session)
            if old is not None:
                # resume over a still-registered stale binding: the
                # swap is survivor-invisible (no Disconnect/Connect)
                self.server.peer_map.rebind(peer)
            else:
                # parked resume: PeerDisconnect was broadcast at park
                # time, so the rebind announces like a connect
                await self.server.peer_map.insert(peer)
            logger.info(
                "[%s] zeromq session resumed for %s", parameter, peer_uuid
            )
        else:
            await self.server.peer_map.insert(peer)

    async def _send_refusal(self, endpoint: str, retry_ms: int,
                            governor) -> None:
        """One-shot refusal hint: a Handshake whose parameter is
        ``retry-after:<ms>`` pushed to the refused client's own
        connect-back address, within the governor's hint budget —
        beyond it the refusal is silent (cheapest possible shed)."""
        self.server.metrics.inc("zmq.handshakes_refused")
        if not governor.take_refusal_hint():
            return
        push = self.ctx.socket(zmq.PUSH)
        push.setsockopt(zmq.LINGER, 200)
        try:
            push.connect(endpoint)
            await push.send(serialize_message(Message(
                instruction=Instruction.HANDSHAKE,
                parameter=f"retry-after:{retry_ms}",
            )))
            self.server.metrics.inc("zmq.refusal_hints")
        except Exception:
            logger.debug("refusal hint to %s failed", endpoint)
        finally:
            push.close(linger=200)

    def _drop_socket(self, peer_uuid: uuid_mod.UUID) -> None:
        sock = self._push_sockets.pop(peer_uuid, None)
        if sock is not None:
            sock.close(linger=0)

    def on_peer_removed(self, peer_uuid: uuid_mod.UUID) -> None:
        """PeerMap removal hook: close the connect-back PUSH socket."""
        self._drop_socket(peer_uuid)
