"""Tick flight recorder (ISSUE 5): spans, slow-tick dumps, loop health,
Chrome-trace export, and the boot-and-scrape smoke over the real server.
"""

import asyncio
import json
import time
import urllib.request
import uuid

import pytest

from worldql_server_tpu.engine.config import Config
from worldql_server_tpu.engine.peers import Peer, PeerMap
from worldql_server_tpu.engine.server import WorldQLServer
from worldql_server_tpu.engine.ticker import TickBatcher
from worldql_server_tpu.observability import (
    FlightRecorder, LoopMonitor, Tracer, chrome_trace,
)
from worldql_server_tpu.observability.spans import NULL_TRACE
from worldql_server_tpu.protocol import deserialize_message
from worldql_server_tpu.protocol.types import Instruction, Message, Vector3
from worldql_server_tpu.robustness import failpoints
from worldql_server_tpu.robustness.resilient import ResilientBackend
from worldql_server_tpu.spatial.cpu_backend import CpuSpatialBackend

from client_util import free_port
from prom_parser import validate_exposition


def run(coro):
    return asyncio.run(coro)


@pytest.fixture(autouse=True)
def _clean_failpoints():
    failpoints.registry.reset()
    yield
    failpoints.registry.reset()


# region: span API unit behavior


def test_disabled_tracer_returns_shared_null_objects():
    tr = Tracer(enabled=False)
    assert tr.begin("tick") is NULL_TRACE
    span = tr.span("anything")
    with span:
        pass  # no trace recorded, no sink, no allocation per call
    assert tr.begin("tick") is tr.begin("other")


def test_spans_nest_and_parent_link_across_contexts():
    tr = Tracer(enabled=True)
    out = []
    tr.on_trace = out.append
    trace = tr.begin("tick", tick=7)
    with trace.span("tick.dispatch"):
        pass
    with trace.span("tick.collect"):
        with tr.span("fetch"):   # context-var parented child
            pass
    trace.finish()
    [t] = out
    spans = {s.name: s for s in t.spans}
    assert spans["tick.dispatch"].parent is None
    assert spans["tick.collect"].parent is None
    assert spans["fetch"].parent == spans["tick.collect"].id
    # top-level stage accounting never double-counts nested children
    assert "fetch" not in t.stage_ms()
    assert t.tags["tick"] == 7


def test_loose_span_becomes_own_trace():
    tr = Tracer(enabled=True)
    out = []
    tr.on_trace = out.append
    with tr.span("router.handle", type="HEARTBEAT"):
        pass
    [t] = out
    assert t.name == "router.handle"
    assert t.tags["type"] == "HEARTBEAT"
    assert len(t.spans) == 1


def test_span_records_from_worker_thread():
    # the collect stage runs via asyncio.to_thread; contextvars copy
    # into it, and Trace.add must be lock-safe from that thread
    tr = Tracer(enabled=True)
    trace = tr.begin("tick")

    async def scenario():
        def on_worker():
            with trace.span("tick.worker"):
                time.sleep(0.001)
        await asyncio.to_thread(on_worker)

    run(scenario())
    trace.finish()
    [s] = trace.spans
    assert s.name == "tick.worker"
    assert s.thread != "MainThread"


def test_trace_finish_is_idempotent_and_emits_once():
    tr = Tracer(enabled=True)
    out = []
    tr.on_trace = out.append
    trace = tr.begin("tick")
    trace.finish()
    trace.finish()
    assert len(out) == 1


# endregion

# region: flight recorder


def _mk_trace(dur_s=0.0, name="tick", **tags):
    tr = Tracer(enabled=True)
    trace = tr.begin(name, **tags)
    with trace.span(f"{name}.stage"):
        if dur_s:
            time.sleep(dur_s)
    trace.finish()
    return trace


def test_ring_buffer_keeps_last_n_ticks():
    rec = FlightRecorder(depth=3)
    for i in range(7):
        rec.record(_mk_trace(tick=i))
    snap = rec.snapshot()
    assert len(snap) == 3
    assert [t["tags"]["tick"] for t in snap] == [4, 5, 6]
    assert rec.stats()["ticks_seen"] == 7


def test_loose_traces_ride_their_own_ring():
    rec = FlightRecorder(depth=2)
    rec.record(_mk_trace(name="router.handle"))
    rec.record(_mk_trace(name="tick"))
    assert len(rec.snapshot()) == 1
    assert len(rec.loose_snapshot()) == 1


def test_slow_tick_auto_dump(tmp_path):
    rec = FlightRecorder(
        depth=4, slow_tick_ms=5.0, dump_dir=str(tmp_path),
        context=lambda: {"loop_lag_ms": 1.25},
    )
    rec.record(_mk_trace(dur_s=0.0))       # fast: no dump
    assert rec.slow_ticks == 0
    rec.record(_mk_trace(dur_s=0.02))      # 20 ms > 5 ms: dumps
    assert rec.slow_ticks == 1
    lines = open(rec.dump_path).read().splitlines()
    assert len(lines) == 1
    record = json.loads(lines[0])
    assert record["trace"]["name"] == "tick"
    assert record["loop_health"] == {"loop_lag_ms": 1.25}
    assert record["trace"]["spans"][0]["name"] == "tick.stage"


def test_slow_tick_threshold_zero_dumps_every_tick(tmp_path):
    rec = FlightRecorder(depth=4, slow_tick_ms=0, dump_dir=str(tmp_path))
    rec.record(_mk_trace())
    rec.record(_mk_trace())
    assert rec.slow_ticks == 2
    assert len(open(rec.dump_path).read().splitlines()) == 2


# endregion

# region: chrome-trace export


def test_chrome_trace_event_schema():
    rec = FlightRecorder(depth=4)
    rec.record(_mk_trace(dur_s=0.002, tick=1))
    doc = chrome_trace(rec.snapshot())
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert xs, "no complete events exported"
    for e in doc["traceEvents"]:
        for key in ("name", "ph", "ts", "pid", "tid"):
            assert key in e
    for e in xs:
        assert e["dur"] >= 0
        assert e["ts"] > 1e15  # epoch microseconds, not relative
    names = {e["args"]["name"] for e in doc["traceEvents"]
             if e["ph"] == "M"}
    assert names  # thread_name metadata present


# endregion

# region: loop monitor


def test_loop_monitor_observes_lag_and_gc():
    from worldql_server_tpu.engine.metrics import Metrics

    metrics = Metrics()
    mon = LoopMonitor(metrics=metrics, interval=0.01)

    async def scenario():
        mon.install()
        try:
            task = asyncio.create_task(mon.run())
            # block the loop long enough for the probe to wake late
            await asyncio.sleep(0)
            time.sleep(0.05)
            await asyncio.sleep(0.03)
            import gc

            gc.collect()
            task.cancel()
            try:
                await task
            except asyncio.CancelledError:
                pass
        finally:
            mon.uninstall()

    run(scenario())
    assert metrics.histograms["loop.lag_ms"].total >= 1
    assert mon.max_lag_ms >= 20.0   # the 50 ms block showed up as lag
    assert mon.gc_passes >= 1
    assert metrics.histograms["gc.pause_ms"].total >= 1
    snap = mon.snapshot()
    assert snap["loop_lag_max_ms"] == round(mon.max_lag_ms, 3)
    assert "gc_counts" in snap


def test_gc_hook_takes_no_lock_and_the_probe_observes_its_pauses():
    """A collection pass starts at any bytecode of the loop's thread,
    inside ``Metrics.observe_ms`` too (it allocates): a hook that
    observed under Metrics' lock waited for its own caller, and the
    loop never ran again (ISSUE 37: traced runs hung near a profiler
    capture's stop). The hook only notes; the probe observes."""
    from worldql_server_tpu.engine.metrics import Metrics

    class Taken:
        def __enter__(self):
            raise AssertionError("the GC hook took Metrics' lock")

        def __exit__(self, *exc):
            return False

    metrics = Metrics()
    mon = LoopMonitor(metrics=metrics, interval=0.01)
    lock, metrics._lock = metrics._lock, Taken()
    mon._gc_callback("start", {})
    mon._gc_callback("stop", {})
    mon._gc_callback("stop", {})    # a stop without its start: ignored
    metrics._lock = lock
    assert mon.gc_passes == 1 and "gc.pause_ms" not in metrics.histograms

    async def scenario():
        task = asyncio.create_task(mon.run())
        await asyncio.sleep(0.05)
        task.cancel()
        try:
            await task
        except asyncio.CancelledError:
            pass

    run(scenario())
    assert metrics.histograms["gc.pause_ms"].total == 1
    mon._gc_callback("start", {})
    mon._gc_callback("stop", {})
    mon.install()
    mon.uninstall()                 # ... and so does uninstall
    assert metrics.histograms["gc.pause_ms"].total >= 2


# endregion

# region: acceptance — forced slow tick attributes its wall time


class _TickHarness:
    """TickBatcher over a ResilientBackend(CPU) with two subscribed
    peers — the smallest real path that exercises dispatch → collect
    (through the backend.collect failpoint site) → deliver."""

    def __init__(self, tracer, interval=60.0):
        self.backend = ResilientBackend(CpuSpatialBackend(16))
        self.peer_map = PeerMap(on_remove=self.backend.remove_peer)
        self.ticker = TickBatcher(
            self.backend, self.peer_map, interval, tracer=tracer
        )
        self.inboxes = {}

    async def add_subscribed_peer(self, pos):
        peer_uuid = uuid.uuid4()
        inbox = []
        self.inboxes[peer_uuid] = inbox

        async def send_raw(data):
            inbox.append(deserialize_message(data))

        await self.peer_map.insert(
            Peer(peer_uuid, "loopback", send_raw, "test")
        )
        self.backend.add_subscription("world", peer_uuid, pos)
        return peer_uuid

    async def queue_local(self, sender, pos):
        from worldql_server_tpu.spatial.backend import LocalQuery
        from worldql_server_tpu.protocol.types import Replication

        msg = Message(
            instruction=Instruction.LOCAL_MESSAGE, sender_uuid=sender,
            world_name="world", position=pos,
            replication=Replication.EXCEPT_SELF,
        )
        await self.ticker.enqueue(
            msg, LocalQuery("world", pos, sender, Replication.EXCEPT_SELF)
        )


def test_forced_slow_tick_dump_attributes_90pct_to_stages(tmp_path):
    """ISSUE 5 acceptance: a slow tick forced via the
    ``backend.collect=delay:…`` failpoint auto-dumps a span tree whose
    named stages attribute >= 90% of the tick's wall time."""
    tracer = Tracer(enabled=True)
    rec = FlightRecorder(
        depth=8, slow_tick_ms=20.0, dump_dir=str(tmp_path),
        context=lambda: {"probe": True},
    )
    tracer.on_trace = rec.record
    failpoints.registry.configure("backend.collect=delay:60ms")

    async def scenario():
        h = _TickHarness(tracer)
        pos = Vector3(5, 5, 5)
        a = await h.add_subscribed_peer(pos)
        await h.add_subscribed_peer(pos)
        await h.queue_local(a, pos)
        await h.ticker.flush()
        return h

    h = run(scenario())
    assert rec.slow_ticks == 1, "the delayed tick must have auto-dumped"
    [record] = [json.loads(s) for s in open(rec.dump_path)]
    trace = record["trace"]
    assert trace["name"] == "tick"
    wall = trace["dur_ms"]
    assert wall >= 60.0
    stages = {}
    for span in trace["spans"]:
        if span["parent"] is None:
            stages[span["name"]] = (
                stages.get(span["name"], 0.0) + span["dur_ms"]
            )
    assert {"tick.dispatch", "tick.collect", "tick.deliver"} <= set(stages)
    attributed = sum(stages.values())
    assert attributed >= 0.9 * wall, (
        f"span tree attributes only {attributed:.1f} of {wall:.1f} ms: "
        f"{stages}"
    )
    assert stages["tick.collect"] >= 0.8 * wall  # the delay lives there
    assert record["loop_health"] == {"probe": True}
    # the delivery actually happened (spans must never eat the tick);
    # count LOCAL_MESSAGEs only — peer insertion broadcast PeerConnects
    delivered = sum(
        1 for inbox in h.inboxes.values() for m in inbox
        if m.instruction == Instruction.LOCAL_MESSAGE
    )
    assert delivered == 1


def test_tracing_disabled_records_nothing():
    async def scenario():
        h = _TickHarness(tracer=None)
        pos = Vector3(5, 5, 5)
        a = await h.add_subscribed_peer(pos)
        await h.add_subscribed_peer(pos)
        await h.queue_local(a, pos)
        await h.ticker.flush()
        return h

    h = run(scenario())
    assert sum(
        1 for inbox in h.inboxes.values() for m in inbox
        if m.instruction == Instruction.LOCAL_MESSAGE
    ) == 1


# endregion

# region: boot-and-scrape smoke (the CI step's substance)


def test_boot_scrape_debug_ticks_and_dump(tmp_path):
    """Boot the real server on CPU with a slow-tick threshold of 0,
    drive ticks, then assert: /metrics parses under the strict
    scraper grammar, /debug/ticks returns schema-valid Chrome trace
    JSON, /healthz carries the slow-tick count, and the dump file
    exists."""

    async def scenario():
        http_port = free_port()
        config = Config(
            store_url="memory://", http_port=http_port,
            ws_enabled=False, zmq_enabled=False,
            tick_interval=0.02, slow_tick_ms=0.0,
            slow_tick_dir=str(tmp_path / "dumps"),
            flight_recorder_depth=16,
        )
        assert config.trace_enabled  # implied by slow_tick_ms
        server = WorldQLServer(config)
        await server.start()
        try:
            inbox = []

            async def send_raw(data):
                inbox.append(deserialize_message(data))

            a, b = uuid.uuid4(), uuid.uuid4()
            for peer in (a, b):
                await server.peer_map.insert(
                    Peer(peer, "loopback", send_raw, "test")
                )
            pos = Vector3(1, 1, 1)
            for peer in (a, b):
                await server.router.handle_message(Message(
                    instruction=Instruction.AREA_SUBSCRIBE,
                    sender_uuid=peer, world_name="world", position=pos,
                ))
            for _ in range(3):
                await server.router.handle_message(Message(
                    instruction=Instruction.LOCAL_MESSAGE, sender_uuid=a,
                    world_name="world", position=pos, parameter="x",
                ))
                deadline = time.perf_counter() + 10
                seen = len(inbox)
                while len(inbox) == seen:
                    assert time.perf_counter() < deadline
                    await asyncio.sleep(0.01)

            def get(path):
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{http_port}{path}"
                ) as resp:
                    return resp.read().decode()

            # 1. /metrics parses under a strict scraper grammar
            text = await asyncio.to_thread(get, "/metrics")
            types, _ = validate_exposition(text)
            assert types["wql_tick_flush_seconds"] == "histogram"
            assert "wql_tick_slow_dumps_total" in types

            # 2. /debug/ticks: structured + Chrome trace formats
            body = json.loads(await asyncio.to_thread(get, "/debug/ticks"))
            assert body["recorder"]["slow_ticks"] >= 3
            assert len(body["ticks"]) >= 3
            chrome = json.loads(
                await asyncio.to_thread(get, "/debug/ticks?format=chrome")
            )
            events = chrome["traceEvents"]
            assert events
            for e in events:
                for key in ("name", "ph", "ts", "pid", "tid"):
                    assert key in e
                if e["ph"] == "X":
                    assert "dur" in e
            assert {e["name"] for e in events if e["ph"] == "X"} >= {
                "tick.dispatch", "tick.collect", "tick.deliver",
            }
            # the router's loose per-message spans export too
            assert any(
                e["ph"] == "X" and e["name"] == "router.handle"
                for e in events
            )

            # 3. /healthz carries the slow-tick count
            health = json.loads(await asyncio.to_thread(get, "/healthz"))
            assert health["flight_recorder"]["slow_ticks"] >= 3

            # 4. the auto-dump file exists and is line-json
            dump = tmp_path / "dumps" / "slow-ticks.jsonl"
            assert dump.exists()
            for line in dump.read_text().splitlines():
                assert json.loads(line)["trace"]["name"] == "tick"
        finally:
            await server.stop()

    run(scenario())


def test_debug_ticks_absent_when_tracing_off():
    async def scenario():
        http_port = free_port()
        server = WorldQLServer(Config(
            store_url="memory://", http_port=http_port,
            ws_enabled=False, zmq_enabled=False,
        ))
        await server.start()
        try:
            def status(path):
                try:
                    with urllib.request.urlopen(
                        f"http://127.0.0.1:{http_port}{path}"
                    ) as resp:
                        return resp.status
                except urllib.error.HTTPError as exc:
                    return exc.code

            assert await asyncio.to_thread(status, "/debug/ticks") == 404

            def healthz():
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{http_port}/healthz"
                ) as resp:
                    return json.loads(resp.read())

            # /healthz keeps the reference-shaped minimal body
            assert await asyncio.to_thread(healthz) == {"status": "ok"}
        finally:
            await server.stop()

    run(scenario())


def test_profiler_hook_endpoint(tmp_path):
    async def scenario():
        http_port = free_port()
        server = WorldQLServer(Config(
            store_url="memory://", http_port=http_port,
            ws_enabled=False, zmq_enabled=False, trace=True,
        ))
        await server.start()
        try:
            def post(payload):
                req = urllib.request.Request(
                    f"http://127.0.0.1:{http_port}/debug/profile",
                    data=json.dumps(payload).encode(),
                    headers={"Content-Type": "application/json"},
                )
                try:
                    with urllib.request.urlopen(req) as resp:
                        return resp.status, json.loads(resp.read())
                except urllib.error.HTTPError as exc:
                    return exc.code, json.loads(exc.read() or b"{}")

            code, _ = await asyncio.to_thread(post, {"action": "bogus"})
            assert code == 400
            code, _ = await asyncio.to_thread(post, {"action": "stop"})
            assert code == 409  # nothing in flight
            code, body = await asyncio.to_thread(post, {
                "action": "start", "dir": str(tmp_path / "prof"),
            })
            assert code == 200 and body["active_dir"]
            code, _ = await asyncio.to_thread(
                post, {"action": "start", "dir": "elsewhere"}
            )
            assert code == 409  # one capture at a time
            code, body = await asyncio.to_thread(post, {"action": "stop"})
            assert code == 200
            assert body["captures"] == 1 and body["active_dir"] is None
        finally:
            await server.stop()

    run(scenario())


# endregion
