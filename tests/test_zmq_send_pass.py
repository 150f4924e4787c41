"""The flush's native ZeroMQ writer (native/sendpass.cpp,
transports/zmq_pass.py, ISSUE 33) over real loopback PUSH/PULL
sockets: alone against the per-peer Python loop it replaces (the
reference), and inside a served flush (``PeerMap.deliver_batch``)."""

import asyncio
import errno
import json
import logging
import pathlib
import random
import subprocess
import uuid

import pytest
import zmq
import zmq.asyncio

from tests.client_util import ZmqClient, dead_port, free_port, zmq_context
from tests.test_robustness_zmq import wait_for
from tests.test_transports import (
    frames, nothing_more, recv_parameters, run, zmq_served,
)
from benchmark import layers
from worldql_server_tpu.engine.peers import FramedPayload
from worldql_server_tpu.protocol import (
    Instruction, Message, deserialize_message, serialize_message,
)
from worldql_server_tpu.robustness import failpoints
from worldql_server_tpu.transports import zmq_pass

HWM = 3     # what the full peer of a table takes


@pytest.fixture(params=[1, 4], ids=["one-thread", "4-threads"])
def threads(request, monkeypatch):
    """The pass on the caller's thread alone, and cut into four shares
    (what a host with dear system calls gets): every case that sends
    through it runs both ways."""
    native = zmq_pass.shared()
    if native is not None:
        monkeypatch.setattr(native, "threads", request.param)
    return request.param


# region: the pass alone, against the loop it replaces


def closure_pass(payloads, sockets, table):
    """What ``try_write_many`` does a peer, as the pass reports it."""
    total, taken, errs = 0, [], []
    for sock, owed in zip(sockets, table):
        n, err = 0, 0
        try:
            for i in owed:
                sock.send(payloads[i], zmq.DONTWAIT)
                n += 1
        except zmq.ZMQError as exc:
            err = exc.errno
        total += n
        taken.append(n)
        errs.append(err)
    return total, taken, errs


class Rig:
    """``n`` PUSH sockets dialled to ``n`` PULL sockets of the same
    context (a ``zmq_context()``, which closes them) over loopback.
    Peer ``full`` dials ``dead``, a port nobody listens at, with
    ``SNDHWM`` 3, so it takes exactly 3 frames; peer ``broken`` offers
    a PULL socket, which refuses every send."""

    def __init__(self, ctx, n, full=None, broken=None, dead=None):
        self.pulls, self.senders = [], []
        for p in range(n):
            pull = ctx.socket(zmq.PULL)
            port = pull.bind_to_random_port("tcp://127.0.0.1")
            self.pulls.append(pull)
            if p == broken:
                self.senders.append(pull)
                continue
            push = ctx.socket(zmq.PUSH)
            if p == full:
                push.setsockopt(zmq.SNDHWM, HWM)
                port = dead
            push.connect(f"tcp://127.0.0.1:{port}")
            self.senders.append(push)
        self.silent = {full, broken}

    def received(self, table):
        """What each PULL socket got, in order (the silent peers
        aside), once every frame the table owes it has arrived."""
        out = []
        for p, pull in enumerate(self.pulls):
            got = []
            if p not in self.silent:
                for _ in table[p]:
                    assert pull.poll(5000), f"peer {p} is short"
                    got.append(pull.recv())
                assert not pull.poll(50)
            out.append(got)
        return out


def table_of(seed, n_peers, n_msgs):
    """A flush's table: every peer owes a random subset of the batch
    (possibly none, possibly all), in batch order."""
    rng = random.Random(seed)
    payloads = [bytes([65 + i % 26]) * rng.randint(1, 300)
                for i in range(n_msgs)]
    table = [sorted(rng.sample(range(n_msgs), rng.randint(0, n_msgs)))
             for _ in range(n_peers)]
    return payloads, table


@pytest.fixture
def send_pass(threads):
    native = zmq_pass.load()
    assert native is not None, "make -C native built no wql_send_pass"
    native.threads = threads
    return native


@pytest.mark.parametrize("seed,n_peers,n_msgs,full,broken", [
    (1, 1, 1, None, None),
    (2, 8, 5, None, None),
    (3, 40, 13, None, None),
    (4, 6, 9, 2, None),        # one peer at its high-water mark
    (5, 6, 9, None, 4),        # one socket that errors
    (6, 12, 20, 0, 11),        # both, first and last of the pass
    (7, 40, 9, 0, 39),         # the same over four whole shares
    (8, 33, 7, 16, 17),        # ... and side by side at a share's edge
])
def test_pass_equals_the_closure_on_the_same_table(
        send_pass, seed, n_peers, n_msgs, full, broken):
    """Every frame once and in batch order a peer; a full socket takes
    a prefix and stops with EAGAIN, a broken one takes nothing and
    says why, and neither costs another peer a frame: the per-peer
    Python loop is the reference, answer for answer."""
    payloads, table = table_of(seed, n_peers, n_msgs)
    if full is not None:
        table[full] = list(range(n_msgs))       # more than it can take
    if broken is not None:
        table[broken] = table[broken] or [0]
    with zmq_context() as ctx, dead_port() as dead:
        native, closure = (
            Rig(ctx, n_peers, full, broken, dead) for _ in range(2))
        total, taken, errs = send_pass(
            payloads, [s.underlying for s in native.senders], table)
        want = closure_pass(payloads, closure.senders, table)
        assert (total, list(taken), list(errs)) == want
        assert total == sum(taken)
        for p, owed in enumerate(table):
            if p == full:
                assert (taken[p], errs[p]) == (HWM, errno.EAGAIN)
            elif p == broken:
                assert taken[p] == 0 and errs[p] not in (0, errno.EAGAIN)
            else:
                assert (taken[p], errs[p]) == (len(owed), 0)
        expected = [[] if p in (full, broken)
                    else [payloads[i] for i in owed]
                    for p, owed in enumerate(table)]
        assert native.received(table) == expected
        assert closure.received(table) == expected


@pytest.mark.parametrize("wrap", [bytes, bytearray, memoryview])
def test_pass_takes_any_buffer_a_socket_would(send_pass, wrap):
    """The closure's ``send`` takes any buffer; so does the pass (a
    payload that is not ``bytes`` is copied once, zero bytes and all)."""
    payloads = [b"a\x00b", b"", b"c" * 300]
    with zmq_context() as ctx:
        rig = Rig(ctx, 2)
        total, taken, errs = send_pass(
            [wrap(p) for p in payloads],
            [s.underlying for s in rig.senders], [[0, 1, 2], [2]])
        assert (total, list(taken), list(errs)) == (4, [3, 1], [0, 0])
        assert rig.received([[0, 1, 2], [2]]) == [payloads, [payloads[2]]]


def test_pass_of_no_peers_sends_nothing(send_pass):
    assert tuple(map(list, send_pass([b"x"], [], [])[1:])) == ([], [])
    assert send_pass([b"x"], [], [])[0] == 0


# endregion

# region: the pass inside a served flush


def counters(server):
    c = server.metrics.counters
    return (c["delivery.sync_frames"], c["delivery.awaited_frames"],
            c["delivery.pass_frames"])


def moved(server, before):
    return tuple(b - a for a, b in zip(before, counters(server)))


async def dial_dead_port(server, hwm):
    """A peer whose connect-back address nobody listens at yet, its
    PUSH socket made with ``SNDHWM`` ``hwm`` (the handshake echo takes
    one of them). Returns its uuid and the port to bind later."""
    [transport] = server._transports
    port, ident = free_port(), uuid.uuid4()
    transport.ctx.setsockopt(zmq.SNDHWM, hwm)
    try:
        with zmq_context(zmq.asyncio.Context) as ctx:
            push = ctx.socket(zmq.PUSH)
            push.connect(
                f"tcp://127.0.0.1:{server.config.zmq_server_port}")
            await push.send(serialize_message(Message(
                instruction=Instruction.HANDSHAKE, sender_uuid=ident,
                parameter=f"127.0.0.1:{port}")))
            assert await wait_for(lambda: ident in server.peer_map)
    finally:
        transport.ctx.setsockopt(zmq.SNDHWM, 1000)
    return ident, port


async def drain_port(port, n):
    """Bind the dead port at last and read ``n`` messages."""
    with zmq_context(zmq.asyncio.Context) as ctx:
        pull = ctx.socket(zmq.PULL)
        pull.bind(f"tcp://127.0.0.1:{port}")
        return [deserialize_message(
            await asyncio.wait_for(pull.recv(), 10)) for _ in range(n)]


@pytest.mark.usefixtures("threads")
@pytest.mark.parametrize("n", [1, 50])
def test_served_flush_leaves_in_one_pass(n):
    """Three peers, overlapping frame lists: every frame once and in
    batch order a peer, all of them through the pass, and the
    ``deliver.write`` span says how many peers it took."""
    async def scenario():
        async with zmq_served(trace=True) as (server, connect):
            traces = []
            server.tracer.on_trace = traces.append
            a, b, c = [await connect() for _ in range(3)]
            before = counters(server)
            pairs = [(m, [a.uuid, c.uuid] if i % 2 else [a.uuid, b.uuid])
                     for i, m in enumerate(frames(n))]
            assert await server.peer_map.deliver_batch(pairs) == 2 * n
            assert await recv_parameters(a, n) == [
                f"m{i}" for i in range(n)]
            assert await recv_parameters(b, (n + 1) // 2) == [
                f"m{i}" for i in range(0, n, 2)]
            assert await recv_parameters(c, n // 2) == [
                f"m{i}" for i in range(1, n, 2)]
            for z in (a, b, c):
                assert await nothing_more(z)
            assert moved(server, before) == (2 * n, 0, 2 * n)
            [write] = [s for t in traces for s in t.spans
                       if s.name == "deliver.write"]
            assert write.tags["pass_peers"] == (2 if n == 1 else 3)
            assert write.tags["sync_frames"] == 2 * n
            assert write.tags["slow_peers"] == 0

    run(scenario())


@pytest.mark.usefixtures("threads")
@pytest.mark.parametrize("hwm", [2, 9])
def test_full_peer_takes_a_prefix_and_the_drain_the_rest(hwm):
    """A real socket at a real (small) high-water mark, nobody
    reading: the pass gives it what it takes, the rest waits in the
    awaited drain in order, a later flush does not overtake it, and
    the other peers of the same pass are whole."""
    async def scenario():
        async with zmq_served() as (server, connect):
            a, b = await connect(), await connect()
            full, port = await dial_dead_port(server, hwm)
            peer = server.peer_map.get(full)
            room = hwm - 1                      # the echo holds one
            before = counters(server)
            first = asyncio.ensure_future(server.peer_map.deliver_batch(
                [(m, [a.uuid, full, b.uuid]) for m in frames(20)]))
            assert await wait_for(lambda: peer._drain is not None)
            for z in (a, b):
                assert await recv_parameters(z, 20) == [
                    f"m{i}" for i in range(20)]
            assert moved(server, before) == (0, 0, 0)   # still open
            # a later flush: the full peer's frames queue behind the
            # drain, the others leave in the pass; a reply refuses too
            assert peer.pass_handle() == 0
            assert not peer.try_write(FramedPayload(b"x"))
            second = asyncio.ensure_future(server.peer_map.deliver_batch(
                [(m, [full, a.uuid]) for m in frames(5, start=20)]))
            assert await recv_parameters(a, 5) == [
                f"m{i}" for i in range(20, 25)]
            await asyncio.sleep(0.05)
            assert not first.done() and not second.done()
            got = await drain_port(port, 26)
            assert got[0].instruction == Instruction.HANDSHAKE
            assert [m.parameter for m in got[1:]] == [
                f"m{i}" for i in range(25)]
            assert await first == 60 and await second == 10
            assert peer._drain is None
            assert moved(server, before) == (
                40 + room + 5, (20 - room) + 5, 40 + room + 5)
            # with nothing owed the pass takes the peer again
            before = counters(server)
            await server.peer_map.deliver_batch(
                [(m, [full]) for m in frames(2, start=25)])
            assert moved(server, before) == (2, 0, 2)

    run(scenario())


@pytest.mark.usefixtures("threads")
def test_errored_socket_evicts_that_peer_alone():
    """A send that fails with anything but EAGAIN: that peer is
    evicted as a failed awaited send evicts (counter, PeerDisconnect),
    what it was still owed fails in the drain and is counted, and the
    other peers of the pass get every frame."""
    async def scenario():
        async with zmq_served() as (server, connect):
            a, bad, b = [await connect() for _ in range(3)]
            peer = server.peer_map.get(bad.uuid)
            [transport] = server._transports
            refuses = transport.ctx.socket(zmq.PULL)    # ENOTSUP
            peer._pass_end = peer._pass_end._replace(
                handle=lambda: refuses.underlying)
            before = counters(server)
            errors0 = server.metrics.counters["broadcast.send_errors"]
            try:
                assert await server.peer_map.deliver_batch(
                    [(m, [a.uuid, bad.uuid, b.uuid])
                     for m in frames(4)]) == 12
            finally:
                refuses.close(linger=0)
            assert moved(server, before) == (8, 4, 8)
            assert server.metrics.counters["broadcast.send_errors"] \
                == errors0 + 4
            assert server.metrics.counters["peers.evicted_send_failed"] == 1
            assert await wait_for(
                lambda: bad.uuid not in server.peer_map)
            for z in (a, b):
                assert await recv_parameters(z, 4) == [
                    "m0", "m1", "m2", "m3"]
                gone = await z.recv_until(Instruction.PEER_DISCONNECT)
                assert gone.parameter == str(bad.uuid)
            assert server.peer_map.size() == 2

    run(scenario())


@pytest.mark.usefixtures("threads")
def test_awaited_send_in_flight_keeps_the_peer_out_of_the_pass():
    """A reply still waiting in ``send_raw`` (the socket is full): the
    flush's frames must not overtake it, so the peer is not in the
    pass and not in the closure's sync path either."""
    async def scenario():
        async with zmq_served() as (server, connect):
            a = await connect()
            full, port = await dial_dead_port(server, 1)    # the echo
            peer = server.peer_map.get(full)
            reply = asyncio.ensure_future(peer.send_raw(
                serialize_message(frames(1, start=99)[0])))
            await asyncio.sleep(0.05)
            assert not reply.done() and peer._drain is None
            assert peer.pass_handle() == 0
            before = counters(server)
            flush = asyncio.ensure_future(server.peer_map.deliver_batch(
                [(m, [full, a.uuid]) for m in frames(3)]))
            assert await recv_parameters(a, 3) == ["m0", "m1", "m2"]
            got = await drain_port(port, 5)
            assert [m.parameter for m in got[1:]] == [
                "m99", "m0", "m1", "m2"]
            assert await flush == 6
            await reply
            assert moved(server, before) == (3, 3, 3)

    run(scenario())


@pytest.mark.usefixtures("threads")
def test_stale_binding_is_left_out_of_the_pass():
    """A session resumed over a binding the server had not yet seen
    drop: the old binding offers no handle (closed, and no longer the
    socket of record), the new one is written in the pass."""
    async def scenario():
        async with zmq_served(session_ttl=30.0) as (server, connect):
            z = await connect()
            old = server.peer_map.get(z.uuid)
            assert old.pass_handle() != 0
            again = await ZmqClient.resume(
                server.config.zmq_server_port, z.token, z.uuid)
            try:
                new = server.peer_map.get(z.uuid)
                assert new is not old and old.closed
                assert old.pass_handle() == 0
                old.closed = False      # the transport's own guard
                assert old.pass_handle() == 0
                assert old.try_write_many([FramedPayload(b"x")]) == 0
                before = counters(server)
                await server.peer_map.deliver_batch(
                    [(m, [z.uuid]) for m in frames(3)])
                assert moved(server, before) == (3, 0, 3)
                assert await recv_parameters(again, 3) == [
                    "m0", "m1", "m2"]
                assert await nothing_more(z)
            finally:
                await again.close()

    run(scenario())


@pytest.mark.usefixtures("threads")
@pytest.mark.parametrize("n", [1, 6])
def test_armed_failpoint_takes_the_flush_to_the_closure(n):
    """``transport.send`` armed: the pass cannot fire it frame by
    frame, so the whole flush is written peer by peer and the fault is
    injected and counted as before; disarmed, the pass serves again."""
    async def scenario():
        async with zmq_served() as (server, connect):
            a, b = await connect(), await connect()
            before = counters(server)
            fired0 = failpoints.registry.fired("transport.send")
            failpoints.registry.set("transport.send", "error:1:x1")
            try:
                await server.peer_map.deliver_batch(
                    [(m, [a.uuid, b.uuid]) for m in frames(n)])
                assert failpoints.registry.fired(
                    "transport.send") == fired0 + 1
                # a's first frame fired: a is evicted, b is whole
                assert moved(server, before) == (n, n, 0)
                assert await recv_parameters(b, n) == [
                    f"m{i}" for i in range(n)]
                assert await wait_for(
                    lambda: a.uuid not in server.peer_map)
            finally:
                failpoints.registry.clear("transport.send")
            before = counters(server)
            await server.peer_map.deliver_batch(
                [(m, [b.uuid]) for m in frames(n)])
            assert moved(server, before) == (n, 0, n)

    run(scenario())


def test_library_without_the_symbol_leaves_the_closure_serving(
        tmp_path, monkeypatch, caplog):
    """A stale library (no ``wql_send_pass``): the loader says so and
    returns nothing, the boot log names the writer that serves, and
    the flush goes peer by peer, every frame synchronous."""
    stale = tmp_path / "libstale.so"
    subprocess.run(["g++", "-shared", "-fPIC", "-x", "c++", "/dev/null",
                    "-o", str(stale)], check=True)
    monkeypatch.setenv("WQL_NATIVE_CODEC", str(stale))
    monkeypatch.setattr(zmq_pass, "_shared", None)
    monkeypatch.setattr(zmq_pass, "_shared_loaded", False)

    async def scenario():
        with caplog.at_level(logging.INFO, logger="worldql_server_tpu"):
            async with zmq_served() as (server, connect):
                z = await connect()
                assert server.peer_map.get(z.uuid)._pass_end is None
                before = counters(server)
                await server.peer_map.deliver_batch(
                    [(m, [z.uuid]) for m in frames(4)])
                assert moved(server, before) == (4, 0, 0)
                assert await recv_parameters(z, 4) == [
                    "m0", "m1", "m2", "m3"]

    run(scenario())
    assert "no send pass (stale build)" in caplog.text
    assert "ZeroMQ flush writer: peer by peer" in caplog.text


def test_boot_log_names_the_native_writer(threads, caplog):
    async def scenario():
        with caplog.at_level(logging.INFO, logger="worldql_server_tpu"):
            async with zmq_served() as (server, connect):
                z = await connect()
                assert server.peer_map.get(z.uuid)._pass_end is not None

    run(scenario())
    assert ("ZeroMQ flush writer: one native pass a flush (wql_send_pass, "
            + ("1 thread)" if threads == 1 else "4 threads)")) in caplog.text


# endregion

# region: the per-layer metric that says the pass engages

ROOT = pathlib.Path(__file__).resolve().parent.parent
CELLS = ["crowd-1m.hot-cube", "crowd-1m.pair-flood",
         "entity-100k-even.random-walk", "worlds-64x10k.hot-cube"]


def pass_share(passed):
    """``deliver_pass_share`` as the benchmark reads it from a recorded
    pair of scrapes in which ``passed`` (a share of the window's sync
    frames, or None: a program without the counter) went through the
    pass."""
    rec = json.loads((ROOT / "benchmark" / "tests"
                      / "recorded_scrapes_sync.json").read_text())
    ctx = {"before": rec["before"], "after": rec["after"],
           "ticks": rec["ticks"], "window_unix": tuple(rec["window_unix"])}
    sync = [ctx[k]["counters"]["delivery.sync_frames"]
            for k in ("before", "after")]
    assert sync[1] > sync[0]
    if passed is not None:
        ctx["before"]["counters"]["delivery.pass_frames"] = 7
        ctx["after"]["counters"]["delivery.pass_frames"] = \
            7 + passed * (sync[1] - sync[0])
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    [entry] = [m for m in bench["per_layer"]
               if m["name"] == "deliver_pass_share"]
    # later PRs append their cells to the list and their metrics behind it
    assert entry["workloads"][:len(CELLS)] == CELLS
    return [layers.read_all({"per_layer": [entry]}, cell, ctx)
            for cell in CELLS]


@pytest.mark.parametrize("passed, reads", [
    (1.0, {"deliver_pass_share": {"value": 100.0, "unit": "%"}}),
    (0.5, {"deliver_pass_share": {"value": 50.0, "unit": "%"}}),
    (0.0, {"deliver_pass_share": {"value": 0.0, "unit": "%"}}),
    (None, {}),     # the parent commit: nothing read, nothing raised
])
def test_pass_share_is_read_from_the_counters(passed, reads):
    assert pass_share(passed) == [reads] * len(CELLS)


# endregion
