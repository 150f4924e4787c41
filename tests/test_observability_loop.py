"""The event loop's account (ISSUE 24): cumulative span totals, on-loop
time by innermost open span, the busy/wall identity, the queue-wait
clock, spans inside a profiler capture, and the /metrics surfaces.
And beneath the spans (ISSUE 38): the CPU clock of a tick's spans, the
clocked rule, the loop's own CPU time, and the receive's counters.

The tests that assert times drive FAKE clocks (``FakeClocks``): the
account, the spans and the profiler hook take their clocks as
arguments, so nothing here waits for the wall clock of a loaded host.
"""

import asyncio
import json
import sys
import threading
import time
import urllib.request
import uuid
from pathlib import Path

import pytest

from worldql_server_tpu.engine.config import Config
from worldql_server_tpu.engine.metrics import Metrics
from worldql_server_tpu.engine.peers import Peer, PeerMap
from worldql_server_tpu.engine.server import WorldQLServer
from worldql_server_tpu.engine.ticker import TickBatcher
from worldql_server_tpu.observability import (
    NOOP_SPAN, FlightRecorder, ProfilerHook, Tracer,
)
from worldql_server_tpu.observability.loop_time import (
    LAYERS, LoopAccount, layer_of,
)
from worldql_server_tpu.protocol import deserialize_message
from worldql_server_tpu.protocol.types import (
    Instruction, Message, Replication, Vector3,
)
from worldql_server_tpu.spatial.backend import LocalQuery
from worldql_server_tpu.spatial.cpu_backend import CpuSpatialBackend

import zmq
import zmq.asyncio

from worldql_server_tpu.transports.zeromq import _CountedPull

from client_util import free_port, zmq_context
from prom_parser import validate_exposition


def run(coro):
    return asyncio.run(coro)


class FakeClocks:
    """The clocks of an account and of its tracer, moved by the test
    alone. ``spin(ms)`` is ms of work ON the CPU: the wall clock and
    the calling thread's CPU clock both move. ``wait(ms)`` is ms off
    it (the GIL, the kernel): the wall clock alone. ``idle(ms)`` makes
    the loop's next ``select`` sleep that long. With ``tick_ms`` every
    READING of the wall clock moves it too (a bracket is as long as
    the readings inside it are many)."""

    def __init__(self, tick_ms: float = 0.0):
        self.ns = 10**9             # never 0: the account's "no step"
        self.cpu: dict[int, int] = {}
        self.cpu_reads = 0
        self.tick_ns = int(tick_ms * 1e6)
        self._idle_ns = 0

    def wall_ns(self) -> int:
        self.ns += self.tick_ns
        return self.ns

    def wall_s(self) -> float:
        return self.wall_ns() / 1e9

    def cpu_ns(self) -> int:
        self.cpu_reads += 1
        return self.cpu.get(threading.get_ident(), 0)

    def spin(self, ms: float) -> None:
        me = threading.get_ident()
        self.cpu[me] = self.cpu.get(me, 0) + int(ms * 1e6)
        self.ns += int(ms * 1e6)

    def wait(self, ms: float) -> None:
        self.ns += int(ms * 1e6)

    def idle(self, ms: float) -> None:
        self._idle_ns += int(ms * 1e6)

    def sleeping_select(self, loop) -> None:
        """Before the account's ``install`` (which times whatever
        ``select`` it finds): the selector sleeps what ``idle`` asked."""
        selector = loop._selector

        def select(timeout=None, _select=selector.select):
            self.ns, self._idle_ns = self.ns + self._idle_ns, 0
            return _select(timeout)

        selector.select = select


def accounted_tracer(clocks: FakeClocks | None = None):
    """A tracer whose spans feed a LoopAccount on the running loop; on
    ``clocks`` when given, else on the real ones."""
    if clocks is None:
        tracer = Tracer(enabled=True, cpu_clock=time.thread_time_ns)
        tracer.loop = LoopAccount().install()
        return tracer
    tracer = Tracer(enabled=True, clock=clocks.wall_s,
                    cpu_clock=clocks.cpu_ns)
    clocks.sleeping_select(asyncio.get_running_loop())
    tracer.loop = LoopAccount(clock=clocks.wall_ns,
                              cpu_clock=clocks.cpu_ns).install()
    return tracer


# region: cumulative totals


def test_totals_lose_nothing_when_the_loose_ring_overflows():
    recorder = FlightRecorder(depth=4)          # loose ring: 16
    tracer = Tracer(enabled=True)
    wall = []

    def sink(trace):
        wall.extend(s.dur_ms for s in trace.spans if s.name == "zmq.recv")
        recorder.record(trace)

    tracer.on_trace = sink
    n = 10 * recorder.depth * 4
    for _ in range(n):
        with tracer.span("zmq.recv", bytes=64):
            with tracer.span("codec.decode"):
                pass
    assert len(recorder.loose_snapshot()) == recorder.depth * 4
    totals = tracer.span_totals()
    assert totals["zmq.recv"]["count"] == n
    assert totals["codec.decode"]["count"] == n
    # the total is the sum of every span's own duration, not a sample
    assert totals["zmq.recv"]["wall_ms"] == pytest.approx(sum(wall), abs=0.01)
    # decode nests in recv: its wall is inside recv's, never added to it
    assert totals["codec.decode"]["wall_ms"] <= totals["zmq.recv"]["wall_ms"]


def test_totals_count_spans_closed_on_worker_threads():
    tracer = Tracer(enabled=True)
    trace = tracer.begin("tick")

    async def scenario():
        def on_worker():
            with trace.span("tick.worker"):
                time.sleep(0.005)
        await asyncio.gather(*(asyncio.to_thread(on_worker)
                               for _ in range(8)))

    run(scenario())
    total = tracer.span_totals()["tick.worker"]
    assert total["count"] == 8 and total["wall_ms"] >= 8 * 5 * 0.9


# endregion

# region: on-loop time


def test_loop_time_goes_to_the_innermost_open_span_across_create_task():
    async def scenario():
        clocks = FakeClocks()
        tracer = accounted_tracer(clocks)

        async def child():
            clocks.spin(5)              # no span of its own: the span
            with tracer.span("b"):      # open in the context it was
                clocks.spin(5)          # made in pays, then "b"

        async def parent():
            with tracer.span("a"):
                clocks.spin(5)
                await asyncio.create_task(child())

        await asyncio.create_task(parent(), name="parent")
        tracer.loop.uninstall()
        return tracer.span_totals()

    spans = run(scenario())
    assert spans["a"]["loop_ms"] == pytest.approx(10, abs=3)
    assert spans["b"]["loop_ms"] == pytest.approx(5, abs=2)
    # "a" was open while "b" ran: that is in its wall, not its loop time
    assert spans["a"]["wall_ms"] >= 14
    assert spans["a"]["max_step_ms"] >= 4
    # nothing of it was left to the tasks themselves
    assert spans.get("task:parent", {"loop_ms": 0})["loop_ms"] < 1


def test_work_on_a_worker_thread_is_not_loop_time():
    async def scenario():
        clocks = FakeClocks()
        tracer = accounted_tracer(clocks)
        # the worker's 20 ms begin once the step that started it is
        # over (a plain callback opens the gate: no step is running)
        gate = threading.Event()

        def on_worker():
            # (a wait, not a spin: python work on another thread
            # takes the GIL from the loop's steps, and that wait IS in
            # their time; what must not be is the worker's own)
            with tracer.span("d"):      # _CURRENT rode to_thread: nests
                gate.wait(10)
                clocks.wait(20)

        async def collect():
            with tracer.span("c"):
                asyncio.get_running_loop().call_soon(gate.set)
                await asyncio.to_thread(on_worker)

        await asyncio.create_task(collect())
        tracer.loop.uninstall()
        return tracer.span_totals()

    spans = run(scenario())
    assert spans["d"]["wall_ms"] >= 19 and "loop_ms" not in spans["d"]
    assert spans["c"]["wall_ms"] >= 19
    assert spans["c"]["loop_ms"] < 5


def test_a_cancelled_tasks_last_step_is_charged_and_the_clock_stops():
    async def scenario():
        clocks = FakeClocks()
        tracer = accounted_tracer(clocks)

        async def doomed():
            with tracer.span("e"):
                clocks.spin(3)
                try:
                    await asyncio.sleep(30)
                finally:
                    clocks.spin(3)      # runs inside the throw() step

        task = asyncio.create_task(doomed())
        await asyncio.sleep(0.01)
        task.cancel()
        with pytest.raises(asyncio.CancelledError):
            await task
        account = tracer.loop
        # between steps no stretch is open and nobody is charged
        idle = (account._mark, account._owner)
        account.uninstall()
        return tracer.span_totals(), idle

    spans, idle = run(scenario())
    assert spans["e"]["count"] == 1
    assert spans["e"]["loop_ms"] == pytest.approx(6, abs=2)
    assert idle == (0, None)


def test_a_span_around_an_await_gets_its_wall_but_only_its_own_loop_time():
    """Finding 2 of ISSUE 24: ``tick.deliver`` wraps an await; the recv
    loop's work in the meantime is in its wall time and must not be in
    its loop time. And the identity: layers + unattributed = busy <=
    wall."""
    async def scenario():
        clocks = FakeClocks()
        tracer = accounted_tracer(clocks)
        before = tracer.loop.snapshot()
        received = asyncio.Event()

        async def deliver():
            with tracer.span("tick.deliver"):
                clocks.idle(10)         # the loop's next select sleeps
                await received.wait()

        async def recv():
            for _ in range(25):
                with tracer.span("zmq.recv"):
                    clocks.spin(2)
                await asyncio.sleep(0)
            received.set()

        await asyncio.gather(asyncio.create_task(deliver()),
                             asyncio.create_task(recv()))
        after = tracer.loop.snapshot()
        tracer.loop.uninstall()
        return tracer.span_totals(), before, after

    spans, before, after = run(scenario())
    assert spans["tick.deliver"]["wall_ms"] >= 55
    assert spans["tick.deliver"]["loop_ms"] < 3
    assert spans["zmq.recv"]["loop_ms"] == pytest.approx(50, abs=6)
    d = {k: after[k] - before[k] for k in after}
    assert d["ingest"] == pytest.approx(50, abs=6)      # zmq.recv's layer
    assert d["deliver"] < 3
    claimed = sum(d[layer] for layer in LAYERS)
    assert claimed + d["unattributed"] == pytest.approx(d["busy_ms"],
                                                        abs=0.05)
    assert d["unattributed"] >= 0
    assert d["busy_ms"] <= d["wall_ms"] + 0.05
    assert d["busy_ms"] >= 50 and d["wall_ms"] >= 55
    assert d["rest"] == pytest.approx(
        d["busy_ms"] - d["ingest"] - d["dispatch"] - d["deliver"], abs=0.05)


def test_every_layer_of_the_table_is_a_known_one():
    for name, want in [
        ("zmq.recv", "ingest"), ("codec.decode", "ingest"),
        ("router.handle", "ingest"), ("task:sup:zmq-recv", "ingest"),
        ("tick.dispatch", "dispatch"), ("task:sup:tick-batcher", "dispatch"),
        ("tick.collect", "collect"), ("tick.build_pairs", "deliver"),
        ("deliver.drain", "deliver"), ("tick.sim.apply", "sim"),
        ("task:PeerMap._deliver_batch_local.<locals>.drain_peer", "deliver"),
        ("task:sup:loop-monitor", "admin"), ("wal.fsync", "other"),
    ]:
        assert layer_of(name) == want and want in LAYERS


def test_unnamed_tasks_are_called_after_their_coroutine():
    async def scenario():
        tracer = accounted_tracer()

        async def drain_peer():
            pass

        await asyncio.gather(*(drain_peer() for _ in range(50)))
        tracer.loop.uninstall()
        return tracer.loop.by_name()

    held = run(scenario())
    [name] = [n for n in held if "drain_peer" in n]
    assert name.startswith("task:") and not any(
        n.startswith("task:Task-") for n in held)
    assert held[name]["steps"] == 50


# endregion

# region: the CPU clock beneath a tick's spans (ISSUE 38)


def test_a_span_inside_one_step_is_clocked_and_its_parts_add_up():
    async def scenario():
        clocks = FakeClocks()
        tracer = accounted_tracer(clocks)
        traces = []
        tracer.on_trace = traces.append
        trace = tracer.begin("tick")

        async def flush():
            with trace.span("tick.dispatch"):
                clocks.spin(3)          # its own Python
                clocks.wait(2)          # a worker thread holds the GIL
            with trace.span("tick.deliver"):
                # a span of the tracer's nests in the tick's trace, and
                # is clocked with it
                with tracer.span("deliver.write"):
                    clocks.spin(1)
                    clocks.wait(7)
            with trace.span("tick.dispatch"):
                clocks.spin(1)
            trace.finish()

        await asyncio.create_task(flush())
        tracer.loop.uninstall()
        return tracer.span_totals(), traces

    spans, [tick] = run(scenario())
    assert spans["tick.dispatch"] == {
        "count": 2, "wall_ms": 6.0, "clocked": 2, "clocked_ms": 6.0,
        "cpu_ms": 4.0, "off_cpu_ms": 2.0, "loop_ms": 6.0, "steps": 2,
        "max_step_ms": 5.0,
    }
    write = spans["deliver.write"]
    assert (write["clocked"], write["cpu_ms"], write["off_cpu_ms"]) == (
        1, 1.0, 7.0)
    for row in spans.values():
        if "clocked" in row:
            assert row["cpu_ms"] + row["off_cpu_ms"] == row["clocked_ms"]
            assert row["clocked_ms"] <= row["wall_ms"]
    # the tick's dump carries each clocked span's own reading
    dumped = {(s["name"], s["dur_ms"]): s.get("cpu_ms")
              for s in tick.as_dict()["spans"]}
    assert dumped[("tick.dispatch", 5.0)] == 3.0
    assert dumped[("deliver.write", 8.0)] == 1.0


def test_a_span_around_an_await_adds_to_its_wall_and_to_none_of_the_three():
    async def scenario():
        clocks = FakeClocks()
        tracer = accounted_tracer(clocks)
        trace = tracer.begin("tick")

        async def other_task():
            clocks.spin(20)             # on the same thread's CPU clock

        async def flush():
            with trace.span("tick.deliver"):
                clocks.spin(1)
                await asyncio.create_task(other_task())
                clocks.spin(1)
            # one name, one instance of each kind: only the second
            # is clocked
            with trace.span("tick.sim.knn"):
                await asyncio.sleep(0)
            with trace.span("tick.sim.knn"):
                clocks.spin(2)
                clocks.wait(1)

        await asyncio.create_task(flush())
        tracer.loop.uninstall()
        return tracer.span_totals(), trace

    spans, trace = run(scenario())
    assert spans["tick.deliver"]["wall_ms"] == 22.0
    assert not {"clocked", "clocked_ms", "cpu_ms",
                "off_cpu_ms"} & set(spans["tick.deliver"])
    assert all(s.cpu_ms is None for s in trace.spans
               if s.name == "tick.deliver")
    knn = spans["tick.sim.knn"]
    assert (knn["count"], knn["clocked"]) == (2, 1)
    assert (knn["clocked_ms"], knn["cpu_ms"], knn["off_cpu_ms"]) == (
        3.0, 2.0, 1.0)
    assert knn["clocked_ms"] <= knn["wall_ms"]


def test_a_span_on_a_worker_thread_is_clocked_on_that_threads_clock():
    async def scenario():
        clocks = FakeClocks()
        tracer = accounted_tracer(clocks)
        trace = tracer.begin("tick")

        def on_worker():
            with trace.span("tick.worker"):
                clocks.spin(4)
                clocks.wait(6)          # the loop holds the GIL

        async def collect():
            with trace.span("tick.collect"):
                clocks.spin(50)         # the loop's thread, not the worker's
                await asyncio.to_thread(on_worker)

        await asyncio.create_task(collect())
        tracer.loop.uninstall()
        return tracer.span_totals()

    spans = run(scenario())
    worker = spans["tick.worker"]
    assert (worker["clocked"], worker["clocked_ms"], worker["cpu_ms"],
            worker["off_cpu_ms"]) == (1, 10.0, 4.0, 6.0)
    assert "loop_ms" not in worker and "clocked" not in spans["tick.collect"]


def test_a_loose_per_message_span_reads_no_cpu_clock():
    async def scenario():
        clocks = FakeClocks()
        tracer = accounted_tracer(clocks)

        async def recv():
            for _ in range(100):
                with tracer.span("zmq.recv", bytes=64):
                    with tracer.span("codec.decode"):
                        clocks.spin(0.1)
                    with tracer.span("router.handle"):
                        clocks.spin(0.1)

        reads = clocks.cpu_reads        # (install read it once)
        await asyncio.create_task(recv())
        after_messages = clocks.cpu_reads - reads
        trace = tracer.begin("tick")
        with trace.span("tick.dispatch"):   # (outside a step: unclocked)
            pass
        tracer.loop.uninstall()
        return tracer.span_totals(), after_messages

    spans, cpu_reads = run(scenario())
    assert cpu_reads == 0
    assert spans["zmq.recv"]["count"] == spans["router.handle"]["count"] == 100
    assert not any("clocked" in row for row in spans.values())
    # and with the clock off, an explicit trace's spans read none either
    tracer = Tracer(enabled=True)
    with tracer.begin("tick").span("tick.dispatch") as span:
        pass
    assert span.cpu_ms is None


def test_the_loop_gauge_says_how_much_of_busy_was_on_the_cpu():
    async def scenario():
        clocks = FakeClocks()
        tracer = accounted_tracer(clocks)
        before = tracer.loop.snapshot()

        async def work():
            clocks.spin(10)
            clocks.wait(5)              # busy, and not running
            clocks.idle(20)             # then asleep in the selector
            await asyncio.sleep(0)

        await asyncio.create_task(work())
        after = tracer.loop.snapshot()
        tracer.loop.uninstall()
        return {k: round(after[k] - before[k], 3) for k in after}

    d = run(scenario())
    assert (d["wall_ms"], d["busy_ms"], d["cpu_ms"], d["off_cpu_ms"]) == (
        35.0, 15.0, 10.0, 5.0)
    assert d["cpu_ms"] <= d["busy_ms"] <= d["wall_ms"]


def test_a_spinning_thread_reads_cpu_and_a_sleeping_one_does_not():
    """The one test on the real clocks, generous: beside each other, a
    thread that burns 20 ms of CPU and one that sleeps 50 ms."""
    async def scenario():
        tracer = accounted_tracer()
        trace = tracer.begin("tick")

        def spinner():
            with trace.span("tick.spinner"):
                c0 = time.thread_time()
                while time.thread_time() - c0 < 0.02:
                    pass

        def sleeper():
            with trace.span("tick.sleeper"):
                time.sleep(0.05)

        await asyncio.gather(asyncio.to_thread(spinner),
                             asyncio.to_thread(sleeper))
        tracer.loop.uninstall()
        return tracer.span_totals()

    spans = run(scenario())
    spinner, sleeper = spans["tick.spinner"], spans["tick.sleeper"]
    assert spinner["clocked"] == sleeper["clocked"] == 1
    assert spinner["cpu_ms"] >= 19
    assert sleeper["cpu_ms"] < 10 and sleeper["off_cpu_ms"] >= 40
    for row in (spinner, sleeper):
        assert row["cpu_ms"] + row["off_cpu_ms"] == pytest.approx(
            row["clocked_ms"], abs=0.0011)


# endregion

# region: the receive, counted where it happens (ISSUE 38)


def test_suspends_counts_only_the_receives_that_found_the_socket_empty():
    async def scenario():
        with zmq_context(zmq.asyncio.Context) as ctx:
            pull, push = ctx.socket(zmq.PULL), ctx.socket(zmq.PUSH)
            pull.bind("inproc://counted")
            push.connect("inproc://counted")
            counted = _CountedPull(pull)
            # three queued before the loop asks: found waiting
            for i in range(3):
                await push.send_multipart([b"early", bytes([i])])
            await asyncio.sleep(0.05)
            got = [await counted.recv_multipart() for _ in range(3)]
            early = counted.stats()
            # a drain that finds nothing is a call, not a message
            with pytest.raises(zmq.Again):
                await counted.recv_multipart(zmq.NOBLOCK)
            drained = counted.stats()
            # two asked for before they were sent: each await suspends
            for i in range(2):
                recv = counted.recv_multipart()
                assert not recv.done()
                await push.send_multipart([b"late", bytes([i])])
                got.append(await recv)
            return got, early, drained, counted.stats()

    got, early, drained, late = run(scenario())
    assert [parts[0] for parts in got] == [b"early"] * 3 + [b"late"] * 2
    assert (early["messages"], early["suspends"]) == (3, 0)
    assert (drained["messages"], drained["suspends"]) == (3, 0)
    assert (late["messages"], late["suspends"]) == (5, 2)
    assert 0 < early["take_ns"] < drained["take_ns"] < late["take_ns"]


@pytest.mark.parametrize("trace", [False, True])
def test_the_recv_loop_counts_with_tracing_on_and_touches_nothing_off(trace):
    async def scenario():
        port = free_port()
        server = WorldQLServer(Config(
            store_url="memory://", ws_enabled=False, http_enabled=False,
            zmq_server_host="127.0.0.1", zmq_server_port=port,
            trace=trace))
        await server.start()
        try:
            with zmq_context(zmq.asyncio.Context) as ctx:
                push = ctx.socket(zmq.PUSH)
                [transport] = server._transports
                push.connect(f"tcp://127.0.0.1:{port}")
                for _ in range(20):     # (an unknown sender's: dropped)
                    await push.send(b"not a message")
                    await asyncio.sleep(0.002)
                for _ in range(200):
                    if server.metrics.snapshot()["gauges"].get(
                            "zmq_recv", {"messages": 20})["messages"] >= 20:
                        break
                    await asyncio.sleep(0.01)
                await asyncio.sleep(0.05)
                return (type(transport._pull),
                        server.metrics.snapshot()["gauges"].get("zmq_recv"))
        finally:
            await server.stop()

    pull_type, gauge = run(scenario())
    if not trace:
        # the socket itself: no wrapper, no counter, no gauge
        assert pull_type is zmq.asyncio.Socket and gauge is None
        return
    assert pull_type is _CountedPull
    # the 20 sent, and the receive that waits for the 21st
    assert gauge["messages"] == 21
    assert 1 <= gauge["suspends"] <= gauge["messages"]
    assert gauge["take_ns"] > 0


# endregion

# region: the off path


def server_config(**kw):
    return Config(store_url="memory://", ws_enabled=False,
                  zmq_enabled=False, tick_interval=0.02, **kw)


def test_tracing_off_installs_no_factory_and_wraps_nothing():
    async def scenario():
        loop = asyncio.get_running_loop()
        server = WorldQLServer(server_config(http_enabled=False))
        await server.start()
        try:
            assert loop.get_task_factory() is None
            assert server.tracer.loop is None
            assert server.tracer.span("zmq.recv") is NOOP_SPAN
            assert "select" not in vars(loop._selector)
            probe = asyncio.create_task(asyncio.sleep(0))
            assert type(probe.get_coro()).__name__ == "coroutine"
            await probe
        finally:
            await server.stop()

    run(scenario())


def test_tracing_on_accounts_the_loop_and_stop_restores_it():
    async def scenario():
        loop = asyncio.get_running_loop()
        server = WorldQLServer(server_config(http_enabled=False, trace=True))
        await server.start()
        try:
            assert loop.get_task_factory() is not None
            assert "select" in vars(loop._selector)
            assert server.metrics.gauge_value("loop_time")["busy_ms"] >= 0
        finally:
            await server.stop()
        assert loop.get_task_factory() is None
        assert "select" not in vars(loop._selector)

    run(scenario())


# endregion

# region: queue wait


def test_queue_wait_reads_the_sleep_between_enqueue_and_flush():
    async def scenario():
        backend = CpuSpatialBackend(16)
        metrics = Metrics()
        tracer = Tracer(enabled=True)
        traces = []
        tracer.on_trace = traces.append
        peer_map = PeerMap(on_remove=backend.remove_peer, tracer=tracer)
        ticker = TickBatcher(backend, peer_map, 60.0, metrics=metrics,
                             tracer=tracer)
        a, b = uuid.uuid4(), uuid.uuid4()
        pos = Vector3(1, 1, 1)
        for p in (a, b):
            async def send_raw(data):
                pass
            await peer_map.insert(Peer(p, "loopback", send_raw, "test"))
            backend.add_subscription("world", p, pos)
        for _ in range(4):
            await ticker.enqueue(
                Message(instruction=Instruction.LOCAL_MESSAGE, sender_uuid=a,
                        world_name="world", position=pos),
                LocalQuery("world", pos, a, Replication.EXCEPT_SELF))
        await asyncio.sleep(0.04)
        await ticker.flush()
        return metrics.snapshot()["latency"], traces

    latency, traces = run(scenario())
    wait = latency["tick.queue_wait_ms"]
    assert wait["count"] == 4                   # one observation a message
    assert 38 <= wait["mean_ms"] <= 80
    [tick] = [t for t in traces if t.name == "tick"]
    assert 38 <= tick.tags["queue_wait_mean_ms"] <= 80
    assert tick.tags["queue_wait_max_ms"] >= tick.tags["queue_wait_mean_ms"]
    # the delivery's legs nest under tick.deliver, in the tick's trace
    spans = {s.name: s for s in tick.spans}
    assert {"tick.build_pairs", "deliver.outbox", "deliver.write",
            "deliver.drain"} <= set(spans)
    assert spans["deliver.drain"].parent == spans["tick.deliver"].id
    assert spans["deliver.outbox"].tags["frames"] == 4
    assert spans["deliver.write"].tags["slow_peers"] == 1
    # ring dumps carry the trace's start on CLOCK_MONOTONIC too
    assert abs(tick.as_dict()["start_mono_ns"] - time.monotonic_ns()) < 60e9


# endregion

# region: the program's spans inside a profiler capture


def test_spans_annotate_the_profile_only_while_a_capture_is_active(tmp_path):
    pytest.importorskip("jax")
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    from benchmark.trace_reduce import python_line, read_xplane

    # a wall clock that moves 1 ms a READING: the stop's bracket is as
    # long as the readings inside it are many, on any host
    tracer = Tracer(enabled=True, clock=FakeClocks(tick_ms=1.0).wall_s)
    hook = ProfilerHook(tracer=tracer)
    assert tracer.annotate is None
    with tracer.span("before.capture"):
        pass
    hook.start(str(tmp_path / "prof"))              # python tracer: off
    try:
        assert tracer.annotate is not None
        trace = tracer.begin("tick")
        with trace.span("tick.deliver"):
            with tracer.span("deliver.drain"):
                time.sleep(0.002)
        trace.finish()
    finally:
        hook.stop()
    assert tracer.annotate is None
    with tracer.span("after.capture"):
        pass
    # without the python tracer, stopping is quick (it took 1.2 s on
    # the chip host with it: PERF.md). On the hook's own clock: the
    # bracket holds the stop's span (enter, exit, its loose trace's
    # finish) and nothing else that reads a clock (how long a stop takes
    # on a chip host is that host's ``profile.stop_loop_stall_ms``, not
    # a unit test's to time)
    assert hook.status()["last_stop_ms"] < 1000
    stop = tracer.span_totals()["profile.stop"]
    assert stop["count"] == 1 and stop["wall_ms"] <= hook.last_stop_ms
    assert (stop["wall_ms"], hook.last_stop_ms) == (1.0, pytest.approx(4.0))
    [pb] = list((tmp_path / "prof").rglob("*.xplane.pb"))
    names = {name for _, _, name in python_line(read_xplane(pb))}
    assert {"tick.deliver", "deliver.drain"} <= names
    assert not {"before.capture", "after.capture"} & names
    # no python frames: the line holds the program's spans alone
    assert not any(".py" in name for name in names)


# endregion

# region: the surfaces


def test_metrics_carries_the_new_gauges_in_both_forms():
    async def scenario():
        http_port = free_port()
        server = WorldQLServer(server_config(http_port=http_port, trace=True))
        await server.start()
        try:
            inbox = []

            async def send_raw(data):
                inbox.append(deserialize_message(data))

            a, b = uuid.uuid4(), uuid.uuid4()
            pos = Vector3(1, 1, 1)
            for peer in (a, b):
                await server.peer_map.insert(
                    Peer(peer, "loopback", send_raw, "test"))
                await server.router.handle_message(Message(
                    instruction=Instruction.AREA_SUBSCRIBE,
                    sender_uuid=peer, world_name="world", position=pos))

            async def feed():
                for _ in range(5):
                    await server.router.handle_message(Message(
                        instruction=Instruction.LOCAL_MESSAGE, sender_uuid=a,
                        world_name="world", position=pos, parameter="x"))
                    await asyncio.sleep(0.03)

            await asyncio.create_task(feed(), name="feed")
            assert len(inbox) >= 4

            def get(path, accept=None):
                req = urllib.request.Request(
                    f"http://127.0.0.1:{http_port}{path}",
                    headers={"Accept": accept} if accept else {})
                with urllib.request.urlopen(req) as resp:
                    return resp.read().decode()

            snap = json.loads(
                await asyncio.to_thread(get, "/metrics", "application/json"))
            text = await asyncio.to_thread(get, "/metrics")
            ticks = json.loads(await asyncio.to_thread(get, "/debug/ticks"))
            return snap, text, ticks
        finally:
            await server.stop()

    snap, text, ticks = run(scenario())
    spans, loop_time = snap["gauges"]["spans"], snap["gauges"]["loop_time"]
    assert spans["router.handle"]["count"] == 7
    assert spans["tick.deliver"]["count"] >= 4
    assert {"count", "wall_ms", "loop_ms", "steps", "max_step_ms"} <= set(
        spans["router.handle"])
    assert set(LAYERS) | {"busy_ms", "wall_ms", "unattributed", "rest",
                          "cpu_ms", "off_cpu_ms"} == set(loop_time)
    assert loop_time["ingest"] > 0 and loop_time["deliver"] > 0
    assert loop_time["busy_ms"] <= loop_time["wall_ms"]
    assert 0 < loop_time["cpu_ms"] <= loop_time["wall_ms"]
    # the tick's spans read the CPU clock; the per-message ones do not
    for name, row in spans.items():
        if "clocked" in row:
            assert row["cpu_ms"] + row["off_cpu_ms"] == pytest.approx(
                row["clocked_ms"], abs=0.0011)
            assert row["clocked_ms"] <= row["wall_ms"] + 0.001
    assert spans["deliver.write"]["clocked"] == spans["deliver.write"]["count"]
    assert "clocked" not in spans["tick.deliver"]       # wraps an await
    assert "clocked" not in spans["router.handle"]      # a loose trace's
    assert snap["latency"]["tick.queue_wait_ms"]["count"] == 5
    assert "start_mono_ns" in ticks["ticks"][0]
    assert "device_stats_at_dispatch" not in ticks["ticks"][0]["tags"]
    # Prometheus form: the table is one labelled series a column
    types, samples = validate_exposition(text)
    assert types["wql_spans_wall_ms"] == "gauge"
    assert types["wql_loop_time_busy_ms"] == "gauge"
    assert types["wql_loop_time_off_cpu_ms"] == "gauge"
    assert types["wql_spans_off_cpu_ms"] == "gauge"
    assert types["wql_tick_queue_wait_seconds"] == "histogram"
    rows = {labels["name"]: v for name, labels, v in samples
            if name == "wql_spans_count"}
    assert rows["router.handle"] == 7


def test_profile_hook_takes_the_python_tracer_field(tmp_path):
    pytest.importorskip("jax")

    async def scenario():
        http_port = free_port()
        server = WorldQLServer(server_config(http_port=http_port, trace=True))
        await server.start()
        seen = []
        real_start = server.profiler.start
        server.profiler.start = lambda log_dir, python_tracer=False: (
            seen.append(python_tracer), real_start(log_dir, python_tracer))
        try:
            def post(payload):
                req = urllib.request.Request(
                    f"http://127.0.0.1:{http_port}/debug/profile",
                    data=json.dumps(payload).encode(),
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req) as resp:
                    return json.loads(resp.read())

            for i, extra in enumerate(({}, {"python_tracer": True})):
                body = await asyncio.to_thread(post, {
                    "action": "start", "dir": str(tmp_path / f"p{i}"),
                    **extra})
                assert body["active_dir"]
                assert server.tracer.annotate is not None
                body = await asyncio.to_thread(post, {"action": "stop"})
                assert body["captures"] == i + 1 and "last_stop_ms" in body
                assert server.tracer.annotate is None
            stall = server.metrics.snapshot()["latency"][
                "profile.stop_loop_stall_ms"]
        finally:
            await server.stop()
        return seen, stall

    seen, stall = run(scenario())
    assert seen == [False, True]
    # stop_trace ran on a worker thread, with the loop's stall observed
    assert stall["count"] == 2 and stall["max_ms"] < 1000


# endregion
