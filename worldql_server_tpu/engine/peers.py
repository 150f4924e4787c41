"""Peer registry and broadcast hub.

Python rebuild of the reference's Peer/PeerMap
(worldql_server/src/transport/peer.rs, peer_map.rs). One asyncio event
loop replaces the Rust ``Arc<RwLock<PeerMap>>``: map mutations are
atomic between awaits, and broadcasts serialize the message once then
fan out concurrently (peer_map.rs:22-40).

Transports supply an async ``send_raw(bytes)`` and may mark themselves
heartbeat-tracked (ZeroMQ-style, staleness-swept) or not
(WebSocket-style, liveness == stream health; peer.rs:59-69).
"""

from __future__ import annotations

import asyncio
import errno
import logging
import time
import uuid as uuid_mod
from typing import Awaitable, Callable, Iterable, NamedTuple, Sequence

from ..observability.spans import Tracer
from ..protocol import Instruction, Message, serialize_message

logger = logging.getLogger(__name__)

SendRaw = Callable[[bytes], Awaitable[None]]
OnRemove = Callable[[uuid_mod.UUID], None]


class PeerSendError(Exception):
    pass


class FramedPayload:
    """One serialized Message shared across every recipient of a
    broadcast. ``payload`` is the wire bytes; ``cache`` holds
    transport-framed variants (e.g. the complete WebSocket frame) so a
    message delivered to N same-transport peers frames ONCE, not N
    times — server→client WS frames are unmasked and therefore
    byte-identical for every recipient."""

    __slots__ = ("payload", "cache", "ctx")

    def __init__(self, payload: bytes):
        self.payload = payload
        self.cache: dict[str, bytes] = {}
        # Cluster trace context (trace_id, t_router_ingress_ns) copied
        # from Message.trace_ctx at framing time, so a shard's ring
        # proxy can thread it onto the inter-shard bus and the REMOTE
        # shard closes the same router-ingress clock at its own socket
        # write. None everywhere outside a cluster shard.
        self.ctx: tuple | None = None


#: synchronous fast-path writer a transport may attach to its peers:
#: returns True when the frame was handed to the transport's buffer
#: without awaiting (the hot path for per-tick fan-out), False to fall
#: back to the awaited ``send_raw`` (saturated buffer, closing, or the
#: transport has no sync path)
TryWrite = Callable[[FramedPayload], bool]

#: batch variant: hand a peer's whole per-tick frame list to the
#: transport without awaiting. A transport whose write is one piece
#: (WebSocket's ``writelines``) answers True or False for the whole
#: list; one that writes a frame at a time (ZeroMQ: a message each)
#: answers HOW MANY frames it took, from the front — the rest, and
#: only the rest, is then owed through ``send_raw``, in order
TryWriteMany = Callable[[list[FramedPayload]], "bool | int"]


class PassEnd(NamedTuple):
    """What a transport attaches to a peer whose per-tick frames can
    leave in ONE native pass with every other such peer's
    (``transports/zmq_pass.py``: no interpreter between two sends,
    and where a wake of libzmq's I/O thread is dear the peers' wakes
    are paid side by side on a few threads). A peer without one, and
    every write that is not a tick's fan-out, takes
    ``try_write_many``."""

    #: the pass, shared by all peers of the transport:
    #: ``write(payloads, handles, frames) -> (total, taken, err)``;
    #: ``handles[p]`` takes ``payloads[i] for i in frames[p]``; for each
    #: peer how many frames its socket took from the front and the
    #: errno that stopped it (0 none, ``EAGAIN`` the high-water mark)
    write: Callable[[list[bytes], list[int], list[list[int]]],
                    tuple[int, Sequence[int], Sequence[int]]]
    #: this peer's socket handle, or 0 when it must not be written in
    #: the pass right now (the closure's own guard, asked here too)
    handle: Callable[[], int]
    #: a send failed with anything but ``EAGAIN``: what the closure's
    #: ``except Exception`` does (ZeroMQ: evict)
    failed: Callable[[], None]


class Peer:
    """Uniform outbound handle over any transport (peer.rs:33-88)."""

    __slots__ = ("uuid", "addr", "kind", "_send_raw", "_try_write",
                 "_try_write_many", "_pass_end", "tracks_heartbeat",
                 "last_heartbeat", "closed", "shard", "slot", "_drain")

    def __init__(
        self,
        uuid: uuid_mod.UUID,
        addr: str,
        send_raw: SendRaw,
        kind: str = "unknown",
        tracks_heartbeat: bool = False,
        try_write: TryWrite | None = None,
        try_write_many: TryWriteMany | None = None,
        pass_end: PassEnd | None = None,
    ):
        self.uuid = uuid
        self.addr = addr
        self.kind = kind
        self._send_raw = send_raw
        self._try_write = try_write
        self._try_write_many = try_write_many
        self._pass_end = pass_end
        self.tracks_heartbeat = tracks_heartbeat
        self.last_heartbeat = time.monotonic()
        self.closed = False
        # Delivery-plane ownership (delivery/plane.py adopt): the
        # sender-worker shard and per-shard socket slot this peer's
        # frames route to. None = parent-owned (single-process mode,
        # or degraded fallback) — the write paths above are then the
        # transport's own.
        self.shard: int | None = None
        self.slot: int | None = None
        # Tail of the awaited drains deliver_batch owes this peer (a
        # future its last one resolves), None when nothing is owed.
        # While it is set the sync paths refuse, and a new drain
        # starts when this one ends: a later frame never overtakes.
        self._drain: asyncio.Future | None = None

    def update_last_heartbeat(self) -> None:
        self.last_heartbeat = time.monotonic()

    def is_stale(self, now: float, max_age_secs: float) -> bool:
        """Heartbeat-tracked peers go stale; stream peers never do
        (peer.rs:59-69)."""
        if not self.tracks_heartbeat:
            return False
        return (now - self.last_heartbeat) > max_age_secs

    async def send(self, message: Message) -> None:
        await self.send_raw(serialize_message(message))

    async def send_raw(self, data: bytes) -> None:
        if self.closed:
            raise PeerSendError(f"peer {self.uuid} is closed")
        try:
            await self._send_raw(data)
        except Exception as exc:
            raise PeerSendError(str(exc)) from exc

    def _sync_refused(self) -> bool:
        """Whether the peer may NOT be written synchronously: it is
        closed, or an awaited drain still owes it frames that a sync
        write would overtake. Every sync writer asks here."""
        return self.closed or self._drain is not None

    def try_write(self, framed: FramedPayload) -> bool:
        """Synchronous fast-path delivery; False = use ``send_raw``."""
        if self._try_write is None or self._sync_refused():
            return False
        return self._try_write(framed)

    def pass_handle(self) -> int:
        """The socket handle a flush's native pass writes this peer's
        frames to; 0 = not in the pass (no such end, refused as any
        sync write, or the transport says not now)."""
        if self._pass_end is None or self._sync_refused():
            return 0
        return self._pass_end.handle()

    def try_write_many(self, framed_list: list[FramedPayload]) -> int:
        """Hand a whole per-tick frame list to the transport without
        awaiting. Returns how many frames it took, from the front:
        ``framed_list[taken:]`` is owed through ``send_raw``."""
        if self._sync_refused():
            return 0
        if self._try_write_many is not None:
            taken = self._try_write_many(framed_list)
            return len(framed_list) if taken is True else int(taken)
        if self._try_write is not None and len(framed_list) == 1:
            return int(self._try_write(framed_list[0]))
        return 0

    def __repr__(self) -> str:
        return f"Peer({self.kind}, {self.uuid}, {self.addr})"


class PeerMap:
    """UUID → Peer registry + broadcast primitives (peer_map.rs:16-176).

    ``on_remove`` mirrors the reference's remove channel
    (peer_map.rs:139): the engine hooks it to purge the spatial index
    when a peer disconnects.
    """

    def __init__(self, on_remove: OnRemove | None = None, metrics=None,
                 plane=None, sessions=None, tracer=None):
        self._map: dict[uuid_mod.UUID, Peer] = {}
        self._on_remove = on_remove
        self.metrics = metrics
        # Span tracing of a batched delivery's three legs
        # (deliver.outbox / deliver.write / deliver.drain, nested under
        # the ticker's tick.deliver). Absent or disabled: the shared
        # no-op span, one branch a leg a flush.
        self._tracer = tracer if tracer is not None else Tracer()
        # Optional delivery plane (delivery/plane.py): when present,
        # deliver_batch groups worker-owned targets per shard and
        # writes each frame ONCE per shard ring; parent-owned peers
        # (and the whole map when plane is None — the default) take
        # the byte-for-byte in-process path below.
        self._plane = plane
        # Optional robustness.sessions.SessionStore (--session-ttl):
        # frames addressed to a PARKED peer (dropped transport, state
        # held for resume) are counted there — accounting, never
        # buffering. None (the default) costs one attribute test on
        # the map-miss path only.
        self._sessions = sessions
        # Optional loss hook (--interest on): called with a peer UUID
        # whenever a frame addressed to it could not be delivered on
        # THIS path — map miss (parked/unknown) or slow-path send
        # error. The server wires it to InterestManager.mark_resync so
        # no local loss can leak a delta past a gap; the worker plane
        # reports its own losses through on_peer_lost/on_frame_drop.
        self.on_frame_loss: Callable[[uuid_mod.UUID], None] | None = None
        #: cumulative wire bytes handed to transports by deliver_batch
        #: (both paths; failed slow-path sends subtracted) — the
        #: ticker diffs this into the delivery.bytes_per_tick gauge
        #: and the bench into bytes/recipient/s
        self.bytes_delivered = 0

    # region: lookups

    def __contains__(self, uuid: uuid_mod.UUID) -> bool:
        return uuid in self._map

    def get(self, uuid: uuid_mod.UUID) -> Peer | None:
        return self._map.get(uuid)

    def size(self) -> int:
        return len(self._map)

    def peer_ids(self) -> list[uuid_mod.UUID]:
        return list(self._map.keys())

    def stale_peers(self, max_age_secs: float) -> list[uuid_mod.UUID]:
        now = time.monotonic()
        return [
            p.uuid for p in self._map.values() if p.is_stale(now, max_age_secs)
        ]

    # endregion

    # region: modifiers

    async def insert(self, peer: Peer) -> Peer | None:
        """Register a peer and announce PeerConnect to everyone else
        (peer_map.rs:100-116)."""
        logger.info("[%s] %s peer connected", peer.addr, peer.kind)
        existing = self._map.get(peer.uuid)
        self._map[peer.uuid] = peer

        await self.broadcast_except(
            Message(
                instruction=Instruction.PEER_CONNECT,
                parameter=str(peer.uuid),
            ),
            peer.uuid,
        )
        return existing

    async def remove(self, uuid: uuid_mod.UUID) -> Peer | None:
        """Drop a peer, announce PeerDisconnect to all remaining peers,
        and fire the removal hook (peer_map.rs:121-141)."""
        peer = self._map.pop(uuid, None)
        if peer is not None:
            peer.closed = True
            logger.info("[%s] %s peer disconnected", peer.addr, peer.kind)
            await self.broadcast_all(
                Message(
                    instruction=Instruction.PEER_DISCONNECT,
                    parameter=str(uuid),
                )
            )
        if self._on_remove is not None:
            self._on_remove(uuid)
        return peer

    def detach(self, uuid: uuid_mod.UUID) -> Peer | None:
        """Silently pop a peer's TRANSPORT binding: no PeerDisconnect
        broadcast, no removal hook — the logical state (index rows,
        entity slots, session) stays untouched. The session-resume
        rebind uses this to swap a stale binding for a fresh one with
        zero survivor-visible churn."""
        peer = self._map.pop(uuid, None)
        if peer is not None:
            peer.closed = True
        return peer

    def rebind(self, peer: Peer) -> None:
        """Install a fresh transport binding for a peer the survivors
        still consider connected (resume-over-stale-binding): silent
        counterpart of :meth:`insert`."""
        peer.closed = False
        self._map[peer.uuid] = peer

    async def remove_if(self, uuid: uuid_mod.UUID, peer: Peer) -> bool:
        """Remove only when ``peer`` is still the CURRENT binding: a
        connection's teardown path must never evict the fresh binding
        a resume installed after it."""
        if self._map.get(uuid) is not peer:
            return False
        await self.remove(uuid)
        return True

    # endregion

    # region: broadcasts — serialize once, frame once per transport,
    # write synchronously where the transport allows, await the rest

    async def _broadcast(self, message: Message, peers: Iterable[Peer]) -> None:
        framed = FramedPayload(serialize_message(message))
        ctx = getattr(message, "trace_ctx", None)
        if ctx is not None:
            framed.ctx = ctx
        n, errors = 0, 0
        slow: list[Peer] = []
        for p in peers:
            n += 1
            if not p.try_write(framed):
                slow.append(p)
        if slow:
            results = await asyncio.gather(
                *(p.send_raw(framed.payload) for p in slow),
                return_exceptions=True,
            )
            for result in results:
                if isinstance(result, Exception):
                    errors += 1
                    logger.debug("broadcast error: %s", result)
        if self.metrics is not None:
            self.metrics.inc("broadcast.sends", n - errors)
            if errors:
                self.metrics.inc("broadcast.send_errors", errors)

    async def deliver_batch(
        self,
        pairs: Iterable[tuple[Message, Iterable[uuid_mod.UUID]]],
        t_ingress_ns: int = 0,
    ) -> int:
        """Deliver a tick's worth of resolved fan-outs.

        Three levels of batching against the reference's per-message
        lock + join_all (peer_map.rs:22-40):
        * serialize once per message — and when the message still
          carries its inbound wire bytes (``Message.wire``: LocalMessage
          fan-out re-broadcasts the sender's bytes verbatim), skip
          re-serialization entirely;
        * frame once per transport kind (FramedPayload cache);
        * ONE sync write per peer per tick, no task, no await — a
          WebSocket peer's frames coalesce into a single transport
          write (``try_write_many``, writev-style); the ZeroMQ peers'
          go to their sockets one non-blocking message each, all of
          them in ONE native pass (``PassEnd``), or peer by peer
          through ``try_write_many`` where the pass does not serve.
        What a sync path does not take (a saturated or closing
        transport, a ZeroMQ socket at its high-water mark: the frames
        from the first refused one on) falls back to awaited sends in
        one gather at the end, in order, and until that drain ends the
        peer's sync paths refuse, so nothing overtakes it. Counters
        ``delivery.sync_frames`` / ``delivery.awaited_frames`` say
        which way the frames went, ``delivery.pass_frames`` how many
        of the sync ones the native pass took. ``t_ingress_ns`` is the batch's
        frame-clock stamp
        (``time.monotonic_ns`` at ticker flush start, 0 = unclocked):
        both paths close it at delivery completion into the
        ``frame.e2e_ms`` histogram — the honest dispatch→socket-write
        fan-out latency. Returns the number of sends attempted."""
        if self._plane is not None:
            return await self._deliver_batch_planed(pairs, t_ingress_ns)
        return await self._deliver_batch_local(pairs, t_ingress_ns)

    async def _deliver_batch_planed(
        self,
        pairs: Iterable[tuple[Message, Iterable[uuid_mod.UUID]]],
        t_ingress_ns: int = 0,
    ) -> int:
        """Sharded delivery (delivery plane enabled): each message's
        wire bytes are written ONCE into every owning shard's ring with
        the full slot list — no per-peer framing, no per-frame pickling
        — and the worker processes fan out from there. Targets not
        adopted by a worker (degraded shards, exotic transports) drain
        through the unchanged in-process path afterwards, preserving
        per-peer arrival order within this batch."""
        from array import array

        plane = self._plane
        worker_sends = n_msgs = 0
        local_pairs: list[tuple[Message, list[uuid_mod.UUID]]] = []
        with plane.tracer.span("delivery.fanout") as span:
            for message, uuids in pairs:
                n_msgs += 1
                data = message.wire
                if data is None:
                    data = serialize_message(message)
                groups: dict[int, tuple[bytes, array]] = {}
                local_targets: list[uuid_mod.UUID] = []
                for u in uuids:
                    p = self._map.get(u)
                    if p is None:
                        if self._sessions is not None:
                            self._sessions.note_undelivered(u)
                        if self.on_frame_loss is not None:
                            self.on_frame_loss(u)
                        continue
                    if p.shard is not None:
                        group = groups.get(p.shard)
                        if group is None:
                            groups[p.shard] = (data, array("I", (p.slot,)))
                        else:
                            group[1].append(p.slot)
                    else:
                        local_targets.append(u)
                if groups:
                    worker_sends += await plane.deliver(
                        groups, t_ingress_ns
                    )
                    self.bytes_delivered += len(data) * sum(
                        len(g[1]) for g in groups.values()
                    )
                if local_targets:
                    local_pairs.append((message, local_targets))
            span.tag(messages=n_msgs, worker_sends=worker_sends)
        n = worker_sends
        if local_pairs:
            # counts its own broadcast.sends for these pairs
            n += await self._deliver_batch_local(local_pairs, t_ingress_ns)
        if self.metrics is not None and worker_sends:
            self.metrics.inc("broadcast.sends", worker_sends)
        return n

    async def _deliver_batch_local(
        self,
        pairs: Iterable[tuple[Message, Iterable[uuid_mod.UUID]]],
        t_ingress_ns: int = 0,
    ) -> int:
        t_start_ns = time.monotonic_ns()
        tracer = self._tracer
        # the batch's messages, numbered; a peer's list holds the
        # numbers of its frames in batch order, so the native pass's
        # table is built from plain ints, not from a frame object each
        batch: list[FramedPayload] = []
        outbox: dict[Peer, list[int]] = {}
        n = 0
        with tracer.span("deliver.outbox") as span:
            bytes_before = self.bytes_delivered
            for message, uuids in pairs:
                data = message.wire
                framed = FramedPayload(
                    serialize_message(message) if data is None else data
                )
                ctx = getattr(message, "trace_ctx", None)
                if ctx is not None:
                    framed.ctx = ctx
                i = len(batch)
                batch.append(framed)
                size = len(framed.payload)
                for u in uuids:
                    p = self._map.get(u)
                    if p is None:
                        if self._sessions is not None:
                            self._sessions.note_undelivered(u)
                        if self.on_frame_loss is not None:
                            self.on_frame_loss(u)
                        continue
                    n += 1
                    self.bytes_delivered += size
                    frames = outbox.get(p)
                    if frames is None:
                        outbox[p] = [i]
                    else:
                        frames.append(i)
            span.tag(frames=n, peers=len(outbox),
                     bytes=self.bytes_delivered - bytes_before)
        n_msgs = len(batch)
        # peers owed an awaited drain: (peer, the frames its sync path
        # left, the drain to wait for, the future this one resolves)
        slow: list[tuple[Peer, list[FramedPayload],
                         asyncio.Future | None, asyncio.Future]] = []
        awaited = passed = 0

        def owe(p: Peer, frames: list[int], taken: int) -> None:
            nonlocal awaited
            awaited += len(frames) - taken
            # joins the peer's chain of drains HERE, not at the drain
            # task's first step: from this line on the peer's sync
            # paths refuse
            prev, p._drain = p._drain, asyncio.Future()
            slow.append((p, [batch[i] for i in frames[taken:]],
                         prev, p._drain))

        with tracer.span("deliver.write") as span:
            # the peers whose transport offers a handle leave in one
            # native pass AFTER this loop; everyone else (WebSocket,
            # a peer with an awaited send in flight, a stale binding,
            # an armed failpoint, no symbol) through the closure here
            write = None
            pass_peers: list[Peer] = []
            handles: list[int] = []
            tables: list[list[int]] = []
            for p, frames in outbox.items():
                handle = p.pass_handle()
                if handle and (write is None or write is p._pass_end.write):
                    write = p._pass_end.write
                    pass_peers.append(p)
                    handles.append(handle)
                    tables.append(frames)
                    continue
                taken = p.try_write_many([batch[i] for i in frames])
                if taken < len(frames):
                    owe(p, frames, taken)
            if write is not None:
                passed, took, errs = write(
                    [framed.payload for framed in batch], handles, tables
                )
                if passed < sum(map(len, tables)):
                    # some socket stopped early: its high-water mark
                    # (the rest waits in the drain, as after zmq.Again)
                    # or an error (the rest fails there, counted)
                    for p, frames, taken, err in zip(
                        pass_peers, tables, took, errs
                    ):
                        if taken < len(frames):
                            if err != errno.EAGAIN:
                                p._pass_end.failed()
                            owe(p, frames, taken)
            span.tag(peers=len(outbox), slow_peers=len(slow),
                     sync_frames=n - awaited, pass_peers=len(pass_peers))
        errors = 0
        if slow:
            # SEQUENTIAL per peer: concurrent send() calls on one
            # websockets connection raise ConcurrencyError (and would
            # reorder frames anyway); distinct peers still overlap
            async def drain_peer(p: Peer, fl: list[FramedPayload],
                                 prev: asyncio.Future | None,
                                 done: asyncio.Future) -> int:
                failed = 0
                try:
                    if prev is not None:
                        await prev
                    for f in fl:
                        try:
                            await p.send_raw(f.payload)
                        except Exception as exc:
                            failed += 1
                            self.bytes_delivered -= len(f.payload)
                            logger.debug("batch delivery error: %s", exc)
                finally:
                    if not done.done():
                        done.set_result(None)
                    if p._drain is done:
                        p._drain = None
                if failed and self.on_frame_loss is not None:
                    # the peer missed >= 1 frame of this batch: the
                    # next interest frame must be a full resync
                    self.on_frame_loss(p.uuid)
                return failed
            # the slow-path gather: what a sync path did not take (a
            # peer with none, a saturated or closing transport, a
            # ZeroMQ socket at its high-water mark, a peer whose
            # earlier drain is still running)
            with tracer.span(
                "deliver.drain", slow_peers=len(slow), frames=awaited,
            ):
                for failed in await asyncio.gather(
                    *(drain_peer(*owed) for owed in slow)
                ):
                    errors += failed
        if self.metrics is not None:
            self.metrics.inc("broadcast.sends", n - errors)
            if errors:
                self.metrics.inc("broadcast.send_errors", errors)
            # how often the sync paths engage: frames a transport took
            # without an await against frames owed through send_raw
            self.metrics.inc("delivery.sync_frames", n - awaited)
            self.metrics.inc("delivery.awaited_frames", awaited)
            # of the sync frames, those the native pass took
            self.metrics.inc("delivery.pass_frames", passed)
            # e2e stamps, closed at batch completion (the slow-path
            # drain included — fast-path frames already sat in their
            # transport buffers by then, so this is the conservative
            # close). One batched histogram write per series, not one
            # per frame — the lock must not ride the 16K-frame loop.
            # delivery.e2e_ms mirrors the worker-side ring-write→
            # write-complete stamp so the two pump variants compare.
            now_ns = time.monotonic_ns()
            if n_msgs:
                self.metrics.observe_ms_n(
                    "delivery.e2e_ms", (now_ns - t_start_ns) / 1e6, n_msgs
                )
                if t_ingress_ns:
                    self.metrics.observe_ms_n(
                        "frame.e2e_ms", (now_ns - t_ingress_ns) / 1e6,
                        n_msgs,
                    )
        return n

    async def broadcast_all(self, message: Message) -> None:
        await self._broadcast(message, self._map.values())

    async def broadcast_to(
        self, message: Message, uuids: Iterable[uuid_mod.UUID]
    ) -> None:
        peers = [self._map[u] for u in set(uuids) if u in self._map]
        await self._broadcast(message, peers)

    async def broadcast_except(
        self, message: Message, except_uuid: uuid_mod.UUID
    ) -> None:
        peers = [p for p in self._map.values() if p.uuid != except_uuid]
        await self._broadcast(message, peers)

    # endregion
