"""Tick-batched LocalMessage routing (engine/ticker.py)."""

import asyncio
import statistics
import threading
import time
import uuid

import numpy as np
import pytest

from worldql_server_tpu.engine.config import Config
from worldql_server_tpu.engine.metrics import Metrics
from worldql_server_tpu.engine.peers import Peer, PeerMap
from worldql_server_tpu.engine.router import Router
from worldql_server_tpu.engine.staging import QueryStaging
from worldql_server_tpu.engine.ticker import TickBatcher
from worldql_server_tpu.observability.spans import Tracer
from worldql_server_tpu.parallel import ShardedTpuSpatialBackend, make_fanout_mesh
from worldql_server_tpu.protocol import deserialize_message
from worldql_server_tpu.protocol.types import Instruction, Message, Vector3
from worldql_server_tpu.robustness import failpoints
from worldql_server_tpu.robustness.overload import OverloadGovernor
from worldql_server_tpu.spatial.backend import to_cube
from worldql_server_tpu.spatial.cpu_backend import CpuSpatialBackend
from worldql_server_tpu.spatial.tpu_backend import TpuSpatialBackend
from worldql_server_tpu.storage.memory_store import MemoryRecordStore


def run(coro):
    return asyncio.run(coro)


class Harness:
    def __init__(self, backend_cls, interval=0.03, max_batch=16_384,
                 staged=False, **kw):
        config = Config()
        self.backend = backend_cls(config.sub_region_size)
        self.store = MemoryRecordStore(config)
        self.peer_map = PeerMap(on_remove=self.backend.remove_peer)
        if staged and self.backend.supports_staged_dispatch():
            # as engine/server.py binds it: by the backend's capability
            kw["staging"] = QueryStaging(self.backend)
        self.ticker = TickBatcher(
            self.backend, self.peer_map, interval, max_batch=max_batch, **kw
        )
        self.router = Router(
            self.peer_map, self.backend, self.store, ticker=self.ticker
        )
        self.inboxes: dict[uuid.UUID, list[Message]] = {}

    async def add_peer(self) -> uuid.UUID:
        peer_uuid = uuid.uuid4()
        inbox: list[Message] = []
        self.inboxes[peer_uuid] = inbox

        async def send_raw(data: bytes) -> None:
            inbox.append(deserialize_message(data))

        await self.peer_map.insert(Peer(peer_uuid, "loopback", send_raw, "test"))
        return peer_uuid

    def locals_for(self, peer_uuid):
        return [
            m for m in self.inboxes[peer_uuid]
            if m.instruction == Instruction.LOCAL_MESSAGE
        ]

    async def subscribe(self, peer, pos):
        await self.router.handle_message(Message(
            instruction=Instruction.AREA_SUBSCRIBE, sender_uuid=peer,
            world_name="world", position=pos,
        ))

    async def local(self, sender, pos, parameter=None):
        await self.router.handle_message(Message(
            instruction=Instruction.LOCAL_MESSAGE, sender_uuid=sender,
            world_name="world", position=pos, parameter=parameter,
        ))

    async def pair(self, pos):
        """Two peers subscribed at ``pos``: (sender, receiver)."""
        a = await self.add_peer()
        b = await self.add_peer()
        await self.subscribe(a, pos)
        await self.subscribe(b, pos)
        return a, b

    async def wait_for(self, cond, timeout=20.0):
        deadline = time.monotonic() + timeout
        while not cond():
            assert time.monotonic() < deadline
            await asyncio.sleep(0.005)


class GatedCollect:
    """Wrap a backend's collect so the test controls when a tick's
    device wait 'completes' (it runs on a worker thread)."""

    def __init__(self, backend):
        self.real = backend.collect_local_batch
        self.gates: list = []          # threading.Events, FIFO per collect
        self.started: list = []
        backend.collect_local_batch = self._collect

    def gate(self):
        ev = threading.Event()
        self.gates.append(ev)
        return ev

    def _collect(self, handle):
        gate = self.gates.pop(0) if self.gates else None
        self.started.append(handle)
        if gate is not None:
            gate.wait(30)
        return self.real(handle)


@pytest.mark.parametrize("backend_cls", [CpuSpatialBackend, TpuSpatialBackend])
def test_messages_deliver_on_tick_not_immediately(backend_cls):
    async def scenario():
        h = Harness(backend_cls)
        h.ticker.start()
        a = await h.add_peer()
        b = await h.add_peer()
        pos = Vector3(5, 5, 5)
        await h.subscribe(a, pos)
        await h.subscribe(b, pos)

        await h.local(a, pos, "m1")
        await h.local(a, pos, "m2")
        assert h.locals_for(b) == []  # queued, not resolved yet

        # > interval; generous ceiling for first-use jit compile
        for _ in range(600):
            await asyncio.sleep(0.05)
            if len(h.locals_for(b)) >= 2:
                break
        got = h.locals_for(b)
        assert [m.parameter for m in got] == ["m1", "m2"]  # arrival order
        assert h.locals_for(a) == []  # EXCEPT_SELF
        assert h.ticker.ticks >= 1
        assert h.ticker.messages == 2
        await h.ticker.stop()

    run(scenario())


def test_size_cap_flushes_early():
    async def scenario():
        h = Harness(TpuSpatialBackend, interval=60.0, max_batch=3)
        a = await h.add_peer()
        b = await h.add_peer()
        pos = Vector3(5, 5, 5)
        await h.subscribe(a, pos)
        await h.subscribe(b, pos)

        for i in range(3):  # hits max_batch → immediate flush, no timer
            await h.local(a, pos, f"m{i}")
        assert [m.parameter for m in h.locals_for(b)] == ["m0", "m1", "m2"]

    run(scenario())


def test_stop_drains_queue():
    async def scenario():
        h = Harness(TpuSpatialBackend, interval=60.0)
        h.ticker.start()
        a = await h.add_peer()
        b = await h.add_peer()
        pos = Vector3(5, 5, 5)
        await h.subscribe(a, pos)
        await h.subscribe(b, pos)
        await h.local(a, pos, "pending")
        assert h.locals_for(b) == []
        await h.ticker.stop()  # cancel timer, drain queue
        assert [m.parameter for m in h.locals_for(b)] == ["pending"]

    run(scenario())


def test_mutations_between_ticks_apply_before_flush():
    async def scenario():
        h = Harness(TpuSpatialBackend, interval=60.0)
        a = await h.add_peer()
        b = await h.add_peer()
        pos = Vector3(5, 5, 5)
        await h.subscribe(a, pos)
        await h.local(a, pos, "m")  # b not subscribed yet
        await h.subscribe(b, pos)   # subscribe lands before the flush
        await h.ticker.flush()
        assert [m.parameter for m in h.locals_for(b)] == ["m"]

    run(scenario())


# region: an index that changes under the tick (ROADMAP M8)
#
# The rule the served path keeps: AreaSubscribe / AreaUnsubscribe write
# the index at receipt, a LocalMessage waits for its flush, so a
# message is OWED to the subscribers its cube has when its flush
# starts, not when it arrived. A reference replays index writes and
# flushes in the server's order; CpuSpatialBackend behind the same
# router and ticker is that reference here.


def cube_pos(k: int) -> Vector3:
    return Vector3(16.0 * k + 5.0, 5.0, 5.0)


class Churn:
    """One harness replaying a script: peers by index, every message
    labelled by its order, each flush's deliveries taken down as
    {label: [recipient, ...]} and, a peer, in the order they came."""

    def __init__(self, harness: Harness):
        self.h = harness
        self.peers: list[uuid.UUID] = []
        self.sent = 0
        self.seen: list[int] = []
        self.flushes: list[tuple[dict, list]] = []

    async def join(self, n: int) -> None:
        for _ in range(n):
            self.peers.append(await self.h.add_peer())
            self.seen.append(0)

    async def sub(self, i: int, k: int) -> None:
        await self.h.subscribe(self.peers[i], cube_pos(k))

    async def unsub(self, i: int, k: int) -> None:
        await self.h.router.handle_message(Message(
            instruction=Instruction.AREA_UNSUBSCRIBE,
            sender_uuid=self.peers[i], world_name="world",
            position=cube_pos(k),
        ))

    async def msg(self, i: int, k: int) -> None:
        await self.h.local(self.peers[i], cube_pos(k), f"m{self.sent}")
        self.sent += 1

    async def drop(self, i: int) -> None:
        await self.h.peer_map.remove(self.peers[i])

    def bulk_move(self, i: int, old: range, new: range) -> None:
        """Every cube of ``old`` to its partner in ``new`` in one call
        where the backend has one, else a subscription at a time, as
        entities/plane.py falls back."""
        backend, who = self.h.backend, self.peers[i]
        bulk = getattr(backend, "bulk_move_subscriptions", None)
        if bulk is None:
            for k in old:
                assert backend.remove_subscription("world", who, cube_pos(k))
            for k in new:
                assert backend.add_subscription("world", who, cube_pos(k))
            return
        cubes = [
            np.array([to_cube(cube_pos(k), backend.cube_size) for k in ks])
            for ks in (old, new)
        ]
        assert bulk(
            "world", [who] * len(old), cubes[0], [who] * len(new), cubes[1]
        ) == (len(old), len(new))

    def wait_compaction(self) -> None:
        wait = getattr(self.h.backend, "wait_compaction", None)
        if wait is not None:
            wait()

    async def flush(self) -> None:
        await self.h.ticker.flush()
        by_message: dict[str, list[int]] = {}
        by_peer = []
        for i, peer in enumerate(self.peers):
            got = [m.parameter for m in self.h.locals_for(peer)]
            fresh, self.seen[i] = got[self.seen[i]:], len(got)
            by_peer.append(fresh)
            for label in fresh:
                by_message.setdefault(label, []).append(i)
        self.flushes.append((by_message, by_peer))


async def churn_subscribe_after(c: Churn):
    await c.join(5)
    await c.sub(0, 0)
    await c.sub(1, 0)
    await c.msg(0, 0)
    await c.sub(2, 0)           # after the message, before its flush
    await c.flush()
    await c.msg(0, 0)
    await c.msg(1, 1)           # nobody there yet
    await c.sub(3, 0)
    await c.sub(4, 1)
    await c.flush()
    await c.msg(1, 0)
    await c.msg(0, 1)
    await c.flush()


async def churn_unsubscribe_after(c: Churn):
    await c.join(4)
    for i in range(4):
        await c.sub(i, 0)
    await c.msg(0, 0)
    await c.unsub(1, 0)         # after the message, before its flush
    await c.flush()
    await c.msg(0, 0)
    await c.unsub(2, 0)
    await c.flush()
    await c.msg(0, 0)
    await c.flush()
    await c.msg(0, 0)
    await c.unsub(3, 0)         # the cube's last listener
    await c.flush()


async def churn_move_between_messages(c: Churn):
    await c.join(4)
    for i in range(3):
        await c.sub(i, 0)
    await c.sub(3, 1)
    for _ in range(3):
        for src, dst in ((0, 1), (1, 0)):
            await c.msg(0, src)
            await c.unsub(1, src)   # peer 1 moves between two messages
            await c.sub(1, dst)     # of one tick
            await c.msg(0, src)
            await c.msg(3, dst)
            await c.msg(2, dst)
        await c.flush()
    await c.unsub(1, 0)
    await c.sub(1, 1)
    await c.msg(0, 0)
    await c.msg(3, 1)
    await c.flush()


async def churn_same_signature(c: Churn):
    """The same (sender, cube) every flush: a device backend may replay
    it from its reuse cache only while the cube stands unchanged."""
    await c.join(5)
    for i in range(3):
        await c.sub(i, 0)
    await c.sub(4, 1)

    async def tick():
        await c.msg(0, 0)
        await c.msg(0, 1)       # a clean cube beside the changing one
        await c.flush()

    await tick()
    await tick()                # unchanged: a replay is right
    await c.sub(3, 0)
    await tick()                # one more listener
    await c.unsub(1, 0)
    await tick()                # one fewer
    await c.unsub(2, 0)
    await c.sub(2, 0)           # left and came back: the same set
    await tick()
    await tick()


async def churn_bulk_move(c: Churn):
    await c.join(3)
    for k in range(64):
        await c.sub(1, k)
    await c.sub(2, 0)
    await c.sub(2, 100)

    async def tick():
        for k in (0, 63, 100, 163):
            await c.msg(0, k)

    await tick()
    await c.flush()
    await tick()
    c.bulk_move(1, range(64), range(100, 164))    # messages queued
    await tick()
    await c.flush()
    await tick()
    await c.flush()
    c.bulk_move(1, range(100, 132), range(32))    # half of it back
    await tick()
    await c.msg(0, 31)
    await c.msg(0, 131)
    await c.msg(0, 132)
    await c.flush()


async def churn_remove_peer(c: Churn):
    await c.join(4)
    for i in range(3):
        await c.sub(i, 0)
    await c.sub(1, 1)
    await c.msg(0, 0)
    await c.msg(2, 1)
    await c.drop(1)             # a recipient, its messages queued
    await c.flush()
    await c.msg(0, 0)
    await c.msg(2, 1)
    await c.flush()
    await c.sub(3, 0)
    await c.sub(3, 1)
    await c.msg(0, 0)
    await c.msg(2, 1)
    await c.flush()


async def churn_compaction(c: Churn):
    """More index writes between two flushes than the delta log may
    hold (the device backends are built with a threshold of 32): the
    flush that takes the messages starts a base+delta fold, writes land
    while it runs, a later flush swaps it in."""
    await c.join(10)
    for i in range(10):
        await c.sub(i, 0)

    async def tick():
        for k in (0, 3, 7):
            await c.msg(0, k)
            await c.msg(9, k)

    await tick()
    await c.flush()
    for i in range(10):
        for k in range(1, 8):
            await c.sub(i, k)
    for i in range(5, 10):
        await c.unsub(i, 0)
    await tick()
    await c.flush()             # 70 rows in the log: the fold starts
    await c.unsub(2, 3)         # a write beside the fold in flight
    await c.sub(5, 0)
    await tick()
    await c.flush()
    c.wait_compaction()
    await c.unsub(3, 7)
    await tick()
    await c.flush()             # the folded base is swapped in
    await tick()
    await c.flush()


CHURN_SCRIPTS = {
    "subscribe-after-message": churn_subscribe_after,
    "unsubscribe-after-message": churn_unsubscribe_after,
    "move-between-messages": churn_move_between_messages,
    "same-signature-cube-changed": churn_same_signature,
    "bulk-move-64-cubes": churn_bulk_move,
    "remove-queued-recipient": churn_remove_peer,
    "churn-forces-compaction": churn_compaction,
}


def served_backend(name: str, compact_threshold=None):
    """What Harness builds its backend from: a device backend armed
    as engine/server.py's build_backend arms it."""
    if name == "cpu":
        return CpuSpatialBackend

    def make(cube_size):
        if name == "sharded":
            backend = ShardedTpuSpatialBackend(
                cube_size, make_fanout_mesh(1, 4),
                compact_threshold=compact_threshold,
            )
        else:
            backend = TpuSpatialBackend(
                cube_size, compact_threshold=compact_threshold
            )
        assert backend.configure_delta_ticks(Config().delta_ticks)
        return backend

    return make


@pytest.mark.parametrize("pattern", list(CHURN_SCRIPTS))
@pytest.mark.parametrize("backend", [
    "cpu", "tpu", "tpu-list-path", "sharded",
])
def test_index_changes_under_the_tick_match_the_cpu_reference(
    backend, pattern
):
    """Subscriptions that change while messages wait: every flush of
    the script delivers, message by message and in every peer's order,
    what a CPU index written and flushed in the same order delivers.
    The device backends dispatch staged columns, as the server binds
    them; ``tpu-list-path`` takes the object-list dispatch a desynced
    staging window falls back to."""
    script = CHURN_SCRIPTS[pattern]
    threshold = 32 if pattern == "churn-forces-compaction" else None
    staged = backend != "tpu-list-path"

    async def replay(backend_cls):
        churn = Churn(Harness(backend_cls, interval=60.0, staged=staged))
        await script(churn)
        return churn

    async def scenario():
        want = await replay(CpuSpatialBackend)
        got = await replay(served_backend(backend, threshold))
        assert len(got.flushes) == len(want.flushes) >= 3
        for n, (g, w) in enumerate(zip(got.flushes, want.flushes)):
            assert g == w, f"flush {n} of {pattern} on {backend}"
        assert sum(len(f[0]) for f in want.flushes) > 0
        if backend == "cpu":
            return
        device = got.h.backend
        assert (device.staged_dispatches > 0) == staged
        if pattern == "same-signature-cube-changed" and staged:
            assert device.delta_reused > 0, "the reuse cache never replayed"
        if pattern == "churn-forces-compaction":
            assert device.compactions >= 1, "no base+delta fold ran"

    run(scenario())


# endregion


def test_cancel_mid_flush_does_not_redeliver():
    """A stop() landing mid-flush must not double-send (ADVICE r1).
    With batched delivery the window is two-sided: a cancel BEFORE the
    device collect re-queues the whole batch for the drain flush; a
    cancel once delivery has started counts the batch as delivered
    (fast-path frames are already in transport buffers)."""

    async def scenario():
        h = Harness(CpuSpatialBackend, interval=60.0)
        a = await h.add_peer()
        b = await h.add_peer()
        pos = Vector3(5, 5, 5)
        await h.subscribe(a, pos)
        await h.subscribe(b, pos)
        for i in range(4):
            await h.local(a, pos, f"m{i}")

        # Case 1: cancel INSIDE the device collect (before delivery):
        # everything re-queues, nothing was sent.
        real_dispatch = h.backend.dispatch_local_batch

        def dispatch_cancels(queries):
            raise asyncio.CancelledError

        h.backend.dispatch_local_batch = dispatch_cancels
        with pytest.raises(asyncio.CancelledError):
            await h.ticker.flush()
        h.backend.dispatch_local_batch = real_dispatch
        assert h.locals_for(b) == []

        # Case 2: cancel INSIDE the delivery: the batch counts as
        # delivered — the drain flush must not double-send.
        real_deliver = h.peer_map.deliver_batch

        async def deliver_then_cancel(pairs, t_ingress_ns=0):
            await real_deliver(pairs, t_ingress_ns)
            raise asyncio.CancelledError

        h.peer_map.deliver_batch = deliver_then_cancel
        with pytest.raises(asyncio.CancelledError):
            await h.ticker.flush()
        h.peer_map.deliver_batch = real_deliver

        await h.ticker.flush()  # drain: nothing left to deliver twice
        assert [m.parameter for m in h.locals_for(b)] == [
            "m0", "m1", "m2", "m3"
        ]

    run(scenario())


def test_sender_disconnect_before_flush_is_safe():
    async def scenario():
        h = Harness(TpuSpatialBackend, interval=60.0)
        a = await h.add_peer()
        b = await h.add_peer()
        pos = Vector3(5, 5, 5)
        await h.subscribe(a, pos)
        await h.subscribe(b, pos)
        await h.local(a, pos, "m")
        await h.peer_map.remove(b)  # target vanishes pre-flush
        await h.ticker.flush()      # must not raise
        assert h.locals_for(a) == []

    run(scenario())


def test_second_cancel_still_completes_inflight_delivery():
    """ADVICE r5 (engine/ticker.py:130): the protective wait used
    ``suppress(Exception)``, which does not cover CancelledError — a
    SECOND cancellation during the protective await abandoned the wait
    (and a bare ``await deliver_task`` would have cancelled the
    delivery itself). The shield-and-re-await loop must ride out
    repeated cancellations until the in-flight delivery lands."""

    async def scenario():
        h = Harness(CpuSpatialBackend, interval=60.0)
        a = await h.add_peer()
        b = await h.add_peer()
        pos = Vector3(5, 5, 5)
        await h.subscribe(a, pos)
        await h.subscribe(b, pos)
        await h.local(a, pos, "m0")

        started = asyncio.Event()
        release = asyncio.Event()
        real_deliver = h.peer_map.deliver_batch
        delivered: list[int] = []

        async def slow_deliver(pairs, t_ingress_ns=0):
            started.set()
            await release.wait()
            await real_deliver(pairs, t_ingress_ns)
            delivered.append(len(pairs))

        h.peer_map.deliver_batch = slow_deliver
        flush_task = asyncio.create_task(h.ticker.flush())
        await started.wait()

        flush_task.cancel()       # 1st: enters the protective wait
        for _ in range(3):
            await asyncio.sleep(0)
        flush_task.cancel()       # 2nd: lands inside the protective wait
        for _ in range(3):
            await asyncio.sleep(0)
        assert not flush_task.done()  # still guarding the delivery
        release.set()

        with pytest.raises(asyncio.CancelledError):
            await flush_task
        # the in-flight delivery completed exactly once, frames intact
        assert delivered == [1]
        assert [m.parameter for m in h.locals_for(b)] == ["m0"]

    run(scenario())


# region: one flush (ISSUE 29): every queued message goes dispatch →
# collect → deliver inside ``flush()`` under the ``_flushing`` lock,
# whoever asked for the flush


POS = Vector3(5, 5, 5)


@pytest.mark.parametrize("first", ["pump", "size"])
def test_flush_arriving_during_a_collect_is_delivered_after_it(first):
    """The ``_flushing`` lock's promise: a flush that arrives while
    another is in its (gated) collect takes no step until that one has
    delivered, so every peer sees tick N whole before tick N+1 — with
    the timer's flush in the collect and the ``max_batch`` one arriving,
    and the other way round."""

    async def scenario():
        h = Harness(CpuSpatialBackend, interval=0.02, max_batch=2)
        a, b = await h.pair(POS)
        gated = GatedCollect(h.backend)
        g0 = gated.gate()

        async def send(*params):
            for p in params:
                await h.local(a, POS, p)

        if first == "pump":
            h.ticker.start()
            await send("t0-m0")            # the timer's flush takes it
            await h.wait_for(lambda: gated.started)
            # the second fills the queue: enqueue awaits a flush inline
            late = asyncio.create_task(send("t1-m0", "t1-m1"))
            want = ["t0-m0", "t1-m0", "t1-m1"]
        else:
            late = asyncio.create_task(send("t0-m0", "t0-m1"))
            await h.wait_for(lambda: gated.started)
            h.ticker.start()
            await send("t1-m0")            # the timer's flush is due
            want = ["t0-m0", "t0-m1", "t1-m0"]

        # several intervals: an unserialized second flush would have
        # collected (ungated) and delivered by now
        await asyncio.sleep(0.15)
        assert len(gated.started) == 1
        assert h.locals_for(b) == []

        g0.set()
        await late
        # ticks: a flush is counted after its delivery has landed
        await h.wait_for(lambda: h.ticker.ticks == 2)
        assert [m.parameter for m in h.locals_for(b)] == want
        await h.ticker.stop()
        assert len(h.locals_for(b)) == 3

    run(scenario())


def test_stop_during_collect_requeues_and_delivers_exactly_once():
    """stop() lands while the pump's flush waits on the device: the
    batch it took is re-queued AHEAD of what arrived since, and the
    drain flush delivers both once, in arrival order."""

    async def scenario():
        h = Harness(CpuSpatialBackend, interval=0.02)
        a, b = await h.pair(POS)
        gated = GatedCollect(h.backend)
        g0 = gated.gate()
        h.ticker.start()
        await h.local(a, POS, "taken")
        await h.wait_for(lambda: gated.started)
        await h.local(a, POS, "queued")
        await h.ticker.stop()
        g0.set()  # the abandoned collect's worker thread
        assert [m.parameter for m in h.locals_for(b)] == ["taken", "queued"]
        assert len(gated.started) == 2  # the cancelled collect + the drain's

    run(scenario())


class IdlePlane:
    """An active entity plane that owes no frames: every flush ticks
    it, and it counts what the flush asked of it."""

    interest = None

    def __init__(self):
        self.applied = self.aborted = 0

    def active(self):
        return True

    def dispatch_tick(self):
        return object()

    def collect_tick(self, handle):
        return handle

    def apply(self, result, trace, skip_frames=False):
        self.applied += 1
        return []

    def abort_tick(self):
        self.aborted += 1


@pytest.mark.parametrize("with_plane", [False, True],
                         ids=["no-plane", "entity-plane"])
@pytest.mark.parametrize("stage", ["dispatch_local_batch",
                                   "collect_local_batch"])
def test_failed_stage_drops_its_batch_only(stage, with_plane):
    """A dispatch or collect that raises inside the pump's flush drops
    THAT batch: the pump lives, the next tick delivers, and the sim
    tick launched beside the failed batch is released exactly once."""

    async def scenario():
        plane = IdlePlane() if with_plane else None
        h = Harness(CpuSpatialBackend, interval=0.02, entity_plane=plane)
        a, b = await h.pair(POS)
        real = getattr(h.backend, stage)
        calls = []

        def flaky(arg):
            calls.append(arg)
            if len(calls) == 1:
                raise RuntimeError("device fell over")
            return real(arg)

        setattr(h.backend, stage, flaky)
        h.ticker.start()
        await h.local(a, POS, "dropped")
        await h.wait_for(lambda: calls)
        await h.local(a, POS, "survives")
        # messages: only a delivered batch counts, once it has landed
        await h.wait_for(lambda: h.ticker.messages)
        assert [m.parameter for m in h.locals_for(b)] == ["survives"]
        assert h.ticker.messages == 1
        if plane is not None:
            # read before stop(): a cancel landing inside a later sim
            # tick aborts that one too
            assert plane.aborted == 1
            assert plane.applied >= 1
        await h.ticker.stop()
        assert len(h.locals_for(b)) == 1

    run(scenario())


def test_flush_observes_every_series_and_span_the_benchmark_reads():
    traces = []
    tracer = Tracer(enabled=True)
    tracer.on_trace = traces.append

    async def scenario():
        h = Harness(CpuSpatialBackend, interval=60.0, metrics=Metrics(),
                    tracer=tracer)
        a, _ = await h.pair(POS)
        await h.local(a, POS, "m")
        await h.ticker.flush()
        return h.ticker.metrics.snapshot()

    snap = run(scenario())
    for series in ("tick.queue_wait_ms", "tick.dispatch_ms",
                   "tick.collect_ms", "tick.deliver_ms", "tick.flush_ms"):
        assert snap["latency"][series]["count"] == 1, series
    assert snap["counters"]["tick.flushes"] == 1
    assert snap["counters"]["tick.messages"] == 1
    [trace] = traces
    assert [s.name for s in trace.spans if s.parent is None] == [
        "tick.dispatch", "tick.collect", "tick.build_pairs", "tick.deliver",
    ]


# endregion


# region: the pump's deadline (ISSUE 28): a flush is due one interval
# after the last one STARTED. Times are walls on a shared CPU, so every
# bound leaves room: what is asserted is which of two designs ran (50
# against 70 ms, 80 against 130), not a timer's precision.


class SlowBackend:
    """Resolves nothing; ``collect`` holds its worker thread for the
    next of ``collect_s`` (the last one repeats). ``starts`` is the
    loop's clock at every dispatch: a flush with work starts there."""

    def __init__(self, *collect_s):
        self.collect_s = list(collect_s)
        self.starts = []
        self.log = []

    def dispatch_local_batch(self, queries):
        self.starts.append(asyncio.get_running_loop().time())
        self.log.append(("dispatch", len(self.starts)))
        return queries

    def collect_local_batch(self, handle):
        n = len(self.starts) - 1
        time.sleep(self.collect_s[min(n, len(self.collect_s) - 1)])
        return [[] for _ in handle]


class NoPeers:
    bytes_delivered = 0

    async def deliver_batch(self, pairs, t_ingress_ns=0):
        pass


class PumpHarness:
    def __init__(self, *collect_s, interval=0.05, metrics=None, **kw):
        self.backend = SlowBackend(*collect_s)
        self.metrics = metrics if metrics is not None else Metrics()
        self.ticker = TickBatcher(
            self.backend, NoPeers(), interval, metrics=self.metrics, **kw
        )
        self._feeder = None

    async def put(self, n=1):
        for _ in range(n):
            await self.ticker.enqueue(
                Message(instruction=Instruction.LOCAL_MESSAGE), object()
            )

    def feed(self, every_s=0.005):
        """Traffic all the while, so that every flush has work."""
        async def feeder():
            while True:
                await self.put()
                await asyncio.sleep(every_s)
        self._feeder = asyncio.ensure_future(feeder())

    async def run_until(self, flushes, timeout=20.0):
        self.ticker.start()
        deadline = time.monotonic() + timeout
        while len(self.backend.starts) < flushes:
            assert time.monotonic() < deadline, self.backend.starts
            await asyncio.sleep(0.005)
        if self._feeder is not None:
            self._feeder.cancel()
        await self.ticker.stop()

    def periods_ms(self):
        s = self.backend.starts
        return [(b - a) * 1e3 for a, b in zip(s, s[1:])]

    def counted(self):
        snap = self.metrics.snapshot()
        hist = snap["latency"].get("tick.period_ms", {"count": 0})
        return hist, snap["counters"].get("tick.late_flushes", 0)


def test_pump_short_flushes_start_one_interval_apart():
    """A 20 ms flush at a 50 ms interval: flushes start 50 ms apart,
    not 70 (the flush + a whole interval of sleep after it)."""
    async def scenario():
        h = PumpHarness(0.02)
        h.feed()
        await h.run_until(10)
        periods = h.periods_ms()[:9]
        assert 49.0 <= statistics.median(periods) < 62.0, periods
        hist, late = h.counted()
        # the pump's first flush has no earlier start to count from;
        # stop()'s drain flush is not the pump's
        pump_flushes = h.metrics.counters["tick.flushes"] - 1
        assert hist["count"] in (pump_flushes - 1, pump_flushes), hist
        assert hist["mean_ms"] == pytest.approx(
            statistics.mean(h.periods_ms()[:hist["count"]]), abs=5.0)
        assert late <= 2  # nothing overran (a hiccup of the box may)

    run(scenario())


class TurnsAfterAccount(Metrics):
    """Makes a callback ready in the flush's LAST step (the account,
    after its last await), which in turn makes a second one ready."""

    def __init__(self, log):
        super().__init__()
        self.log = log

    def inc(self, name, by=1):
        super().inc(name, by)
        if name == "tick.flushes":
            k = self.counters[name]
            loop = asyncio.get_running_loop()

            def turn():
                self.log.append(("turn", k))
                loop.call_soon(self.log.append, ("turn after", k))

            loop.call_soon(turn)


def test_pump_long_flush_is_followed_at_once_after_one_turn():
    """An 80 ms flush at a 50 ms interval: the next starts at once
    (80 ms apart, not 130), after exactly ONE turn of the loop: what
    was ready when the flush ended runs first, what THAT made ready
    runs after the next flush's first step."""
    async def scenario():
        log = []
        h = PumpHarness(0.08, metrics=TurnsAfterAccount(log))
        h.backend.log = log
        h.feed()
        await h.run_until(6)
        periods = h.periods_ms()[:5]
        assert 79.0 <= statistics.median(periods) < 115.0, periods
        for k in range(1, 5):
            at = [log.index(e) for e in (
                ("turn", k), ("dispatch", k + 1), ("turn after", k))]
            assert at == sorted(at), (k, log)
        hist, late = h.counted()
        # every pump flush but the first began past its due time
        assert late == hist["count"] >= 5

    run(scenario())


def test_pump_does_not_catch_up_after_one_long_flush():
    """One 160 ms flush (three intervals' worth), then quick ones: the
    flush after it starts at once, and the ones after THAT a whole
    interval apart. Lateness is never repaid with a burst."""
    async def scenario():
        h = PumpHarness(0.16, 0.001)
        h.feed()
        await h.run_until(6)
        periods = h.periods_ms()[:5]
        assert 159.0 <= periods[0] < 200.0, periods
        assert all(49.0 <= p < 100.0 for p in periods[1:]), periods
        _, late = h.counted()
        assert late == 1

    run(scenario())


def test_pump_size_triggered_flush_restarts_the_clock():
    """A full queue flushes at once (the governed enqueue signals the
    pump), and the NEXT flush is due one interval after that start,
    neither at the old deadline nor an interval after it."""
    async def scenario():
        h = PumpHarness(
            0.001, interval=0.3, max_batch=4,
            governor=OverloadGovernor(max_batch=4, min_batch=4),
        )
        loop = asyncio.get_running_loop()
        h.ticker.start()
        t0 = loop.time()
        await asyncio.sleep(0.1)
        await h.put(4)              # the cap: flush now
        await asyncio.sleep(0.05)
        assert len(h.backend.starts) == 1
        assert (h.backend.starts[0] - t0) * 1e3 < 250.0  # not the timer's 300
        await h.put()               # rides the timer
        await h.run_until(2)
        [period] = h.periods_ms()
        # the old deadline was 200 ms after the first start
        assert 299.0 <= period < 400.0, period
        hist, late = h.counted()
        assert (hist["count"], late) == (1, 0)
        assert hist["mean_ms"] == pytest.approx(period, abs=1.0)

    run(scenario())


def test_pump_period_counts_idle_starts_and_only_flushes_with_work():
    """Messages 100 ms apart at a 25 ms interval: most flushes are
    idle and count nowhere; each flush WITH work observes the pump's
    period (idle flushes are starts too), not the gap between
    messages."""
    async def scenario():
        h = PumpHarness(0.001, interval=0.025)
        h.feed(every_s=0.1)
        await h.run_until(5)
        assert all(p > 60.0 for p in h.periods_ms()[:4]), h.periods_ms()
        hist, late = h.counted()
        assert 3 <= hist["count"] <= h.metrics.counters["tick.flushes"]
        assert 24.0 <= hist["mean_ms"] < 40.0, hist
        assert late <= 1

    run(scenario())


def test_pump_failpoint_kills_the_pump_outside_the_containment():
    """`ticker.pump` fires between the wait and the flush, where no
    handler contains it: the pump task itself dies (the supervisor's
    case, tests/test_chaos.py), and a pump started again counts a new
    clock from its own start: its first flush observes no period."""
    async def scenario():
        h = PumpHarness(0.001, interval=0.02)
        failpoints.registry.reset()
        try:
            h.ticker.start()
            await asyncio.sleep(0.05)
            failpoints.registry.set("ticker.pump", "error:1:x1")
            pump = h.ticker._task
            for _ in range(400):
                if pump.done():
                    break
                await asyncio.sleep(0.005)
            assert isinstance(pump.exception(), failpoints.FailpointError)
        finally:
            failpoints.registry.reset()
        await asyncio.sleep(0.1)    # far past any deadline of the old pump
        await h.put()
        h.ticker._task = None
        await h.run_until(1)
        hist, late = h.counted()
        assert (hist["count"], late) == (0, 0)

    run(scenario())


# endregion
