"""Device telemetry (ISSUE 7): retrace visibility, per-tick timing
split, live-buffer gauge, and server wiring.

The acceptance pin: a FORCED retrace (capacity-tier first hit) is
visible as both a /metrics counter increment and a named loose span in
the flight recorder.
"""

import uuid as uuid_mod

import pytest

jax = pytest.importorskip("jax")

from worldql_server_tpu.engine.config import Config          # noqa: E402
from worldql_server_tpu.engine.metrics import Metrics        # noqa: E402
from worldql_server_tpu.engine.server import WorldQLServer   # noqa: E402
from worldql_server_tpu.observability import (               # noqa: E402
    DeviceTelemetry, FlightRecorder, Tracer,
)
from worldql_server_tpu.observability.device import (        # noqa: E402
    live_device_bytes,
)
from worldql_server_tpu.protocol.types import (              # noqa: E402
    Replication, Vector3,
)
from worldql_server_tpu.spatial.backend import LocalQuery    # noqa: E402
from worldql_server_tpu.spatial.tpu_backend import (         # noqa: E402
    TpuSpatialBackend,
)

POS = Vector3(5.0, 5.0, 5.0)

#: a capacity tier no other test dispatches at — the first hit MUST
#: compile fresh kernel variants even inside a shared pytest process
FRESH_TIER = 1 << 17
#: enough filler rows to push the delta device buffer past its 1024
#: floor: every other suite's small backends sit ON the floor, so the
#: 2048-cap segment shape (and every kernel keyed on it) is unique to
#: this file — the forced-retrace pin must stay a FIRST hit no matter
#: which tests warmed the shared jit caches earlier in the process
_FILLER_ROWS = 1200


def make_backend() -> TpuSpatialBackend:
    import numpy as np

    backend = TpuSpatialBackend(16)
    a, b = uuid_mod.uuid4(), uuid_mod.uuid4()
    backend.add_subscription("w", a, POS)
    backend.add_subscription("w", b, POS)
    filler = [uuid_mod.uuid4() for _ in range(_FILLER_ROWS)]
    cubes = np.stack([
        np.arange(_FILLER_ROWS, dtype=np.int64) + 100,
        np.full(_FILLER_ROWS, 7, np.int64),
        np.full(_FILLER_ROWS, 7, np.int64),
    ], axis=1)
    backend.bulk_add_subscriptions("w", filler, cubes)
    backend._sender = a
    return backend


def dispatch_collect(backend):
    query = LocalQuery("w", POS, backend._sender, Replication.EXCEPT_SELF)
    return backend.collect_local_batch(
        backend.dispatch_local_batch([query])
    )


def make_telemetry(backend):
    metrics = Metrics()
    tracer = Tracer(enabled=True)
    recorder = FlightRecorder(depth=8)
    tracer.on_trace = recorder.record
    tel = DeviceTelemetry(
        metrics=metrics, tracer=tracer, backend=backend
    ).install()
    return tel, metrics, recorder


def test_forced_retrace_is_counted_and_leaves_a_loose_span():
    """ISSUE acceptance: a capacity-tier first hit increments
    device.retraces in /metrics AND records a named device.retrace
    loose span (kernel family, capacity tier, compile ms) in the
    flight recorder — and a steady-state repeat emits NOTHING."""
    backend = make_backend()
    tel, metrics, recorder = make_telemetry(backend)
    try:
        backend._delivery_cap = FRESH_TIER
        [targets] = dispatch_collect(backend)
        assert targets  # the fan-out itself still resolved
        delta = tel.poll_retraces()
        assert delta, "tier first hit must grow a kernel family"
        snap = metrics.snapshot()
        assert snap["counters"]["device.retraces"] >= 1
        assert snap["counters"].get("device.compiles", 0) >= 1
        loose = recorder.loose_snapshot()
        spans = [t for t in loose if t["name"] == "device.retrace"]
        assert spans, "no device.retrace loose span recorded"
        tagged = spans[-1]["tags"]
        assert tagged["family"].startswith(("tpu_backend.", "sharded."))
        assert tagged["new_variants"] >= 1
        assert tagged["t_cap"] == FRESH_TIER
        assert tagged["query_cap"] >= 1      # the compiled variant's
        assert "compile_ms" in tagged
        # steady state: same tier again — no retrace, no new span
        before = len(recorder.loose_snapshot())
        dispatch_collect(backend)
        assert tel.poll_retraces() == {}
        assert metrics.snapshot()["counters"]["device.retraces"] == \
            snap["counters"]["device.retraces"]
        assert len([
            t for t in recorder.loose_snapshot()
            if t["name"] == "device.retrace"
        ]) == len([
            t for t in loose if t["name"] == "device.retrace"
        ])
        assert len(recorder.loose_snapshot()) == before
    finally:
        tel.uninstall()


def test_per_tick_device_timing_split_reaches_trace_and_metrics():
    backend = make_backend()
    tel, metrics, recorder = make_telemetry(backend)
    try:
        dispatch_collect(backend)
        timing = backend.last_device_timing
        for leg in ("encode_ms", "h2d_ms", "compute_ms", "d2h_ms"):
            assert leg in timing, timing
            assert timing[leg] >= 0.0 or leg == "h2d_ms"
        assert "d2h_enqueue_ms" in timing
        assert timing["path"] in ("csr", "dense", "overflow")
        # the tick hook tags the trace and feeds the histograms
        tracer = Tracer(enabled=True)
        trace = tracer.begin("tick", tick=1)
        tel.on_tick(trace)
        trace.finish()
        assert "device_timing" in trace.tags
        assert set(trace.tags["device_timing"]) >= {
            "encode_ms", "compute_ms", "d2h_ms",
        }
        lat = metrics.snapshot()["latency"]
        for leg in ("encode_ms", "h2d_ms", "compute_ms", "d2h_ms"):
            assert lat[f"device.{leg}"]["count"] >= 1
    finally:
        tel.uninstall()


def test_timing_pairs_across_pipelined_dispatches():
    """Two dispatches in flight (tick pipeline): each collect merges
    its OWN dispatch's timing — the dict rides the handle, so pairing
    is structural at any depth. query_cap tags make the pairing
    observable (1 query → tier 8; 9 queries → tier 16)."""
    backend = make_backend()
    q = LocalQuery("w", POS, backend._sender, Replication.EXCEPT_SELF)
    h1 = backend.dispatch_local_batch([q])
    h2 = backend.dispatch_local_batch([q] * 9)
    # out-of-order collect: attribution must still be per-handle
    backend.collect_local_batch(h2)
    assert backend.last_device_timing["query_cap"] == 16
    backend.collect_local_batch(h1)
    assert backend.last_device_timing["query_cap"] == 8
    assert "compute_ms" in backend.last_device_timing
    assert backend.last_device_timing["staged"] is False


def test_timing_stays_paired_when_a_collect_errors_and_drops_its_tick():
    """ISSUE 8 satellite regression: under pipeline depth > 1, a
    collect that errors (its tick dropped) must NOT desync the
    dispatch-timing pairing — the old FIFO deque silently attributed
    tick N's encode/h2d split to tick N+1 after an error fired before
    the pop (e.g. a backend.collect failpoint in ResilientBackend)."""
    from worldql_server_tpu.robustness import failpoints
    from worldql_server_tpu.robustness.resilient import ResilientBackend

    inner = TpuSpatialBackend(16)
    backend = ResilientBackend(inner, failover_after=100)
    a, b = uuid_mod.uuid4(), uuid_mod.uuid4()
    # mutations through the wrapper so the mirror can degrade-resolve
    backend.add_subscription("w", a, POS)
    backend.add_subscription("w", b, POS)
    q = LocalQuery("w", POS, a, Replication.EXCEPT_SELF)
    h1 = backend.dispatch_local_batch([q])
    h2 = backend.dispatch_local_batch([q] * 9)
    failpoints.registry.configure("backend.collect=error:1:x1")
    try:
        # h1's collect dies at the failpoint BEFORE the inner collect —
        # its timing must die with its handle, not leak to h2
        out1 = backend.collect_local_batch(h1)
        assert out1 == [[b]]  # mirror-degraded result, still correct
        backend.collect_local_batch(h2)
        assert inner.last_device_timing["query_cap"] == 16, \
            "collect error desynced dispatch-timing attribution"
    finally:
        failpoints.registry.clear()


def test_live_buffer_gauge_and_stats():
    backend = make_backend()
    tel, metrics, recorder = make_telemetry(backend)
    try:
        dispatch_collect(backend)
        # the index's device twin is resident → live bytes are nonzero
        assert live_device_bytes() > 0
        stats = tel.stats()
        assert stats["buffer_bytes"] > 0
        assert stats["compiles"] >= 0 and stats["retraces"] >= 0
    finally:
        tel.uninstall()


def test_server_wires_device_telemetry_only_for_device_backends():
    config = Config(
        store_url="memory://", http_enabled=False, ws_enabled=False,
        zmq_enabled=False, tick_interval=0.05,
    )
    cpu_server = WorldQLServer(config)
    assert cpu_server.device_telemetry is None  # CPU backend: no device

    dev_server = WorldQLServer(config, backend=make_backend())
    try:
        assert dev_server.device_telemetry is not None
        assert dev_server.ticker._device_telemetry is \
            dev_server.device_telemetry
        snap = dev_server.metrics.snapshot()
        assert "device" in snap["gauges"]
        assert "buffer_bytes" in snap["gauges"]["device"]
    finally:
        dev_server.device_telemetry.uninstall()

    off = Config(
        store_url="memory://", http_enabled=False, ws_enabled=False,
        zmq_enabled=False, device_telemetry=False,
    )
    assert WorldQLServer(off, backend=make_backend()) \
        .device_telemetry is None


def test_collect_decode_leg_rides_the_timing_and_its_histogram():
    """ISSUE 24: the decode after the fetch (ids walked into UUID
    lists) is a leg of its own beside compute_ms / d2h_ms, on every
    collect path, and on_tick feeds it to ``device.decode_ms``."""
    backend = make_backend()
    tel, metrics, _ = make_telemetry(backend)
    try:
        [targets] = dispatch_collect(backend)
        assert targets
        timing = backend.last_device_timing
        assert timing["path"] in ("csr", "dense", "overflow")
        assert timing["decode_ms"] > 0.0
        tracer = Tracer(enabled=True)
        trace = tracer.begin("tick", tick=1)
        tel.on_tick(trace)
        trace.finish()
        assert trace.tags["device_timing"]["decode_ms"] > 0.0
        lat = metrics.snapshot()["latency"]
        assert lat["device.decode_ms"]["count"] == 1
        # the dense ceiling path brackets its decode too
        backend._delivery_cap = 1 << 30
        dispatch_collect(backend)
        assert backend.last_device_timing["path"] == "dense"
        assert backend.last_device_timing["decode_ms"] > 0.0
    finally:
        tel.uninstall()


def test_compile_time_is_a_counter_a_windows_delta_can_be_taken_of():
    """ISSUE 24: the ms compiles held their caller accumulate in the
    ``device.compile_ms_total`` counter: whole ms, within 1 ms of the
    gauge's float however many sub-ms compiles there were."""
    import jax.numpy as jnp

    tel, metrics, _ = make_telemetry(make_backend())
    try:
        # a function no other test compiled: the listener's own path
        jax.jit(lambda x: x * 3.25 + 24.0)(jnp.ones(24)).block_until_ready()
        snap = metrics.snapshot()
        assert snap["counters"]["device.compiles"] >= 1
        assert "device.compile_ms_total" in snap["counters"]
        for _ in range(7):
            tel._on_compile(0.0004)             # 0.4 ms each
        tel._on_compile(1.2)
        snap = metrics.snapshot()
        counted = snap["counters"]["device.compile_ms_total"]
        assert counted >= 1202
        assert 0 <= tel.stats()["compile_ms_total"] - counted < 1.0
        assert snap["latency"]["device.compile_ms"]["count"] == \
            snap["counters"]["device.compiles"]
    finally:
        tel.uninstall()
