"""Metrics registry + /metrics endpoint."""

import asyncio
import json
import urllib.request
import uuid

import pytest

from worldql_server_tpu.engine.config import Config
from worldql_server_tpu.engine.metrics import Histogram, Metrics
from worldql_server_tpu.engine.server import WorldQLServer
from worldql_server_tpu.protocol.types import Instruction, Message, Vector3

from client_util import WsClient, free_port


def run(coro):
    return asyncio.run(coro)


def test_histogram_quantiles():
    h = Histogram()
    for v in (0.1, 0.3, 0.9, 4.0, 90.0):
        h.observe_ms(v)
    snap = h.snapshot()
    assert snap["count"] == 5
    assert abs(snap["mean_ms"] - (0.1 + 0.3 + 0.9 + 4.0 + 90.0) / 5) < 1e-9
    assert snap["p50_ms"] <= 2.5  # bucket upper bound containing 0.9
    assert snap["p99_ms"] >= 90.0


def test_histogram_multi_second_range_stays_finite():
    # BENCH_r05's 207 s outlier regime: the ladder must resolve
    # multi-second latencies into real buckets, not collapse to +inf
    h = Histogram()
    h.observe_ms(10_000.0)
    assert h.quantile(0.5) == 10_000.0


def test_histogram_overflow_reports_max_observed_not_inf():
    h = Histogram()
    h.observe_ms(500_000.0)   # above the 250 s top bucket
    h.observe_ms(750_000.0)
    snap = h.snapshot()
    assert h.quantile(0.5) == 750_000.0      # finite upper estimate
    assert snap["p99_ms"] == 750_000.0
    assert snap["max_ms"] == 750_000.0
    assert snap["p50_ms"] != float("inf")


def test_histogram_max_tracks_in_range_values_too():
    h = Histogram()
    for v in (1.0, 42.0, 3.0):
        h.observe_ms(v)
    assert h.snapshot()["max_ms"] == 42.0
    # ranks inside the ladder still report bucket upper bounds
    assert h.quantile(0.99) == 50.0


def test_observe_ms_thread_safe_under_contention():
    # PR 3 observes tick.collect_ms from the collect worker thread
    # while the loop observes other series: lazy Histogram creation
    # plus bucket list read-modify-writes must not lose updates.
    import threading

    m = Metrics()
    n, workers = 20_000, 4

    def hammer():
        for i in range(n):
            m.observe_ms("contended_ms", 1.0 if i % 2 else 5_000.0)

    threads = [threading.Thread(target=hammer) for _ in range(workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    h = m.histograms["contended_ms"]
    assert h.total == n * workers
    assert sum(h.counts) == h.total
    assert h.max_ms == 5_000.0


def test_render_prometheus_passes_strict_scraper_grammar():
    from prom_parser import validate_exposition

    m = Metrics()
    m.inc("messages.local_message", 3)
    m.inc("zmq.recv_errors")
    for v in (0.1, 4.0, 90.0, 3_000.0, 999_999.0):  # incl. overflow
        m.observe_ms("tick.flush_ms", v)
    m.observe_ms("durability.apply_ms", 1.25)
    m.gauge("peers", lambda: 2)
    m.gauge("tick", lambda: {"last_batch": 1, "pipeline": 2,
                             "label": "text-skipped"})
    m.set_gauge("tick.compaction_bucket", 4096)

    text = m.render_prometheus()
    types, samples = validate_exposition(text)

    assert types["wql_messages_local_message_total"] == "counter"
    assert types["wql_tick_flush_seconds"] == "histogram"
    assert types["wql_peers"] == "gauge"
    by_name = {}
    for name, labels, value in samples:
        by_name.setdefault(name, []).append((labels, value))
    # le bounds are in SECONDS: the ms ladder's 2.5 ms bucket is 0.0025
    les = {lab["le"] for lab, _ in by_name["wql_tick_flush_seconds_bucket"]}
    assert "0.0025" in les and "250" in les and "+Inf" in les
    # flattened dict gauge leaves, non-numeric leaf skipped
    assert ("wql_tick_last_batch", [({}, 1.0)]) in by_name.items()
    assert "wql_tick_label" not in by_name
    [(_, count)] = by_name["wql_tick_flush_seconds_count"]
    assert count == 5


def test_render_prometheus_renders_a_table_gauge_one_series_a_column():
    """A gauge whose every value is a dict (``spans``: name -> {count,
    wall_ms, ...}) is a table: one ``name``-labelled series a column,
    so a column has ONE ``# TYPE`` line whatever the number of rows;
    a mixed dict keeps the one-level flattening."""
    from prom_parser import validate_exposition

    m = Metrics()
    m.gauge("spans", lambda: {
        "zmq.recv": {"count": 5400, "wall_ms": 512.25, "loop_ms": 300.5},
        "task:PeerMap._deliver_batch_local.<locals>.drain_peer":
            {"count": 0, "wall_ms": 0.0, "loop_ms": 41.0},
        "codec.decode": {"count": 5400, "wall_ms": 80.0, "note": "text"},
    })
    m.gauge("loop_time", lambda: {"busy_ms": 812.5, "ingest": 300.5})
    m.gauge("supervisor", lambda: {"tasks_unhealthy": 0,
                                   "tasks": {"zmq-recv": {"crashes": 0}}})
    types, samples = validate_exposition(m.render_prometheus())
    assert types["wql_spans_wall_ms"] == types["wql_spans_count"] == "gauge"
    assert "wql_spans_note" not in types
    wall = {labels["name"]: v for name, labels, v in samples
            if name == "wql_spans_wall_ms"}
    assert wall == {
        "zmq.recv": 512.25, "codec.decode": 80.0,
        "task:PeerMap._deliver_batch_local.<locals>.drain_peer": 0.0,
    }
    loop = [labels["name"] for name, labels, _ in samples
            if name == "wql_spans_loop_ms"]
    assert sorted(loop) == sorted(set(wall) - {"codec.decode"})
    flat = {name: v for name, labels, v in samples if not labels}
    assert flat["wql_loop_time_busy_ms"] == 812.5
    assert flat["wql_supervisor_tasks_unhealthy"] == 0
    assert not any(name.startswith("wql_supervisor_tasks_")
                   and name != "wql_supervisor_tasks_unhealthy"
                   for name in types)


def test_counters_and_gauges():
    m = Metrics()
    m.inc("a")
    m.inc("a", 2)
    m.gauge("g", lambda: 7)
    m.gauge("bad", lambda: 1 / 0)
    snap = m.snapshot()
    assert snap["counters"]["a"] == 3
    assert snap["gauges"]["g"] == 7
    assert str(snap["gauges"]["bad"]).startswith("error")


def test_server_metrics_endpoint():
    pytest.importorskip("websockets")  # this e2e flow drives a WS client

    async def scenario():
        ws_port, http_port = free_port(), free_port()
        server = WorldQLServer(Config(
            ws_port=ws_port, http_port=http_port, zmq_enabled=False,
            store_url="memory://", tick_interval=0.02,
        ))
        await server.start()
        try:
            a = await WsClient.connect(ws_port)
            b = await WsClient.connect(ws_port)
            pos = Vector3(1, 1, 1)
            for c in (a, b):
                await c.send(Message(
                    instruction=Instruction.AREA_SUBSCRIBE, sender_uuid=c.uuid,
                    world_name="world", position=pos,
                ))
            await a.send(Message(
                instruction=Instruction.LOCAL_MESSAGE, sender_uuid=a.uuid,
                world_name="world", position=pos, parameter="x",
            ))
            await b.recv_until(Instruction.LOCAL_MESSAGE, timeout=30)

            def fetch():
                req = urllib.request.Request(
                    f"http://127.0.0.1:{http_port}/metrics",
                    headers={"Accept": "application/json"},
                )
                with urllib.request.urlopen(req) as resp:
                    return json.loads(resp.read())

            snap = await asyncio.to_thread(fetch)
            assert snap["counters"]["messages.area_subscribe"] == 2
            assert snap["counters"]["messages.local_message"] == 1
            assert snap["counters"]["tick.messages"] == 1
            assert snap["gauges"]["peers"] == 2
            assert snap["gauges"]["subscriptions"] == 2
            assert snap["latency"]["tick.flush_ms"]["count"] >= 1
            assert snap["gauges"]["tick"]["last_batch"] == 1

            def fetch_prometheus():
                # a scraper's plain GET (no JSON Accept) must get the
                # text exposition format
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{http_port}/metrics"
                ) as resp:
                    assert resp.headers.get_content_type() == "text/plain"
                    return resp.read().decode()

            text = await asyncio.to_thread(fetch_prometheus)
            assert "# TYPE wql_messages_local_message_total counter" in text
            assert "wql_messages_local_message_total 1" in text
            assert "wql_peers 2" in text
            assert 'wql_tick_flush_seconds_bucket{le="+Inf"}' in text
            assert "# TYPE wql_uptime_seconds gauge" in text

            def health():
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{http_port}/healthz"
                ) as resp:
                    return json.loads(resp.read())

            # the ticker is supervised, so the body carries the
            # supervisor's block beside the status: what an operator's
            # probe relies on is the status and that no task is down
            body = await asyncio.to_thread(health)
            assert body["status"] == "ok"
            assert body["tasks_unhealthy"] == 0
            assert body["supervisor"]["tasks"]["tick-batcher"]["critical"]
            # tracing is off here: the queue-wait clock still runs (the
            # one series this path pays for), the loop is not accounted
            assert snap["latency"]["tick.queue_wait_ms"]["count"] == 1
            assert "loop_time" not in snap["gauges"]
            assert "spans" not in snap["gauges"]
            await a.close()
            await b.close()
        finally:
            await server.stop()

    run(scenario())


def test_metrics_endpoint_requires_auth_token():
    pytest.importorskip("websockets")  # server boots the WS transport

    async def scenario():
        ws_port, http_port = free_port(), free_port()
        server = WorldQLServer(Config(
            ws_port=ws_port, http_port=http_port, zmq_enabled=False,
            store_url="memory://", http_auth_token="sekrit",
        ))
        await server.start()
        try:
            def fetch(headers):
                req = urllib.request.Request(
                    f"http://127.0.0.1:{http_port}/metrics", headers=headers
                )
                try:
                    with urllib.request.urlopen(req) as resp:
                        return resp.status
                except urllib.error.HTTPError as exc:
                    return exc.code

            assert await asyncio.to_thread(fetch, {}) == 401
            assert await asyncio.to_thread(
                fetch, {"Authorization": "Bearer sekrit"}
            ) == 200
        finally:
            await server.stop()

    run(scenario())
