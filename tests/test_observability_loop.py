"""The event loop's account (ISSUE 24): cumulative span totals, on-loop
time by innermost open span, the busy/wall identity, the queue-wait
clock, spans inside a profiler capture, and the /metrics surfaces.
"""

import asyncio
import json
import sys
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

from client_util import free_port
from prom_parser import validate_exposition


def run(coro):
    return asyncio.run(coro)


def spin(ms: float) -> None:
    """Hold the thread (and, on the loop's thread, the loop)."""
    end = time.perf_counter() + ms / 1e3
    while time.perf_counter() < end:
        pass


def accounted_tracer():
    """A tracer whose spans feed a LoopAccount on the running loop."""
    tracer = Tracer(enabled=True)
    tracer.loop = LoopAccount().install()
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
        tracer = accounted_tracer()

        async def child():
            spin(5)                     # no span of its own: the span
            with tracer.span("b"):      # open in the context it was
                spin(5)                 # made in pays, then "b"

        async def parent():
            with tracer.span("a"):
                spin(5)
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
        tracer = accounted_tracer()

        def on_worker():
            # (a sleep, not a spin: python work on another thread
            # takes the GIL from the loop's steps, and that wait IS in
            # their time; what must not be is the worker's own)
            with tracer.span("d"):      # _CURRENT rode to_thread: nests
                time.sleep(0.02)

        async def collect():
            with tracer.span("c"):
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
        tracer = accounted_tracer()

        async def doomed():
            with tracer.span("e"):
                spin(3)
                try:
                    await asyncio.sleep(30)
                finally:
                    spin(3)             # runs inside the throw() step

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
        tracer = accounted_tracer()
        before = tracer.loop.snapshot()

        async def deliver():
            with tracer.span("tick.deliver"):
                await asyncio.sleep(0.06)

        async def recv():
            for _ in range(25):
                with tracer.span("zmq.recv"):
                    spin(2)
                await asyncio.sleep(0)

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
            spin(1)

        await asyncio.gather(*(drain_peer() for _ in range(50)))
        tracer.loop.uninstall()
        return tracer.loop.by_name()

    held = run(scenario())
    [name] = [n for n in held if "drain_peer" in n]
    assert name.startswith("task:") and not any(
        n.startswith("task:Task-") for n in held)
    assert held[name]["steps"] == 50


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

    tracer = Tracer(enabled=True)
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
    # the chip host with it: PERF.md)
    assert hook.status()["last_stop_ms"] < 1000
    stop = tracer.span_totals()["profile.stop"]
    assert stop["count"] == 1 and stop["wall_ms"] <= hook.last_stop_ms
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
    assert set(LAYERS) | {"busy_ms", "wall_ms", "unattributed",
                          "rest"} == set(loop_time)
    assert loop_time["ingest"] > 0 and loop_time["deliver"] > 0
    assert loop_time["busy_ms"] <= loop_time["wall_ms"]
    assert snap["latency"]["tick.queue_wait_ms"]["count"] == 5
    assert "start_mono_ns" in ticks["ticks"][0]
    assert "device_stats_at_dispatch" not in ticks["ticks"][0]["tags"]
    # Prometheus form: the table is one labelled series a column
    types, samples = validate_exposition(text)
    assert types["wql_spans_wall_ms"] == "gauge"
    assert types["wql_loop_time_busy_ms"] == "gauge"
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
