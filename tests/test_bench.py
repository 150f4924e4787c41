"""bench.py harness smoke tests: run tiny shapes, check the JSON
contract (driver protocol: one json object per line on stdout)."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent

ENV = {
    "PATH": "/usr/bin:/bin:/usr/local/bin",
    "JAX_PLATFORM_NAME": "cpu",
    # JAX_PLATFORMS (plural) is load-bearing: with libtpu installed but
    # no TPU attached, backend enumeration in the child initializes the
    # TPU plugin anyway and sleeps forever in its device-discovery
    # retry loop — the subprocess then idles out the full 600 s timeout.
    # Restricting the platform set keeps the child CPU-only.
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
}


def run_bench(*argv: str) -> tuple[list[dict], str]:
    import pytest

    out = subprocess.run(
        [sys.executable, str(ROOT / "bench.py"), *argv],
        capture_output=True, text=True, timeout=600, cwd=ROOT, env=ENV,
    )
    if out.returncode != 0 and "No module named 'websockets'" in out.stderr:
        pytest.skip("bench config needs websockets (not installed here)")
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [l for l in out.stdout.strip().splitlines() if l.strip()]
    records = [json.loads(l) for l in lines]
    for rec in records:
        # configs 1-8 time something; 9-15 report rates and ratios,
        # which must be positive too, and failure counts — the one
        # unit where 0 is the good value
        if rec["config"] <= 8:
            assert rec["unit"] == "ms"
            assert "vs_baseline" in rec
        else:
            assert rec["unit"] in ("per_s", "count", "x")
        if rec["unit"] != "count":
            assert rec["value"] > 0
    return records, out.stderr


def test_bench_default_contract():
    """Default invocation: ONE line, the config-5 headline metric —
    the ENGINE-side tick (host↔device link excluded) — still carrying
    the north-star e2e
    p50/p99 latency keys (VERDICT r2 #3, r4 next #2)."""
    records, stderr = run_bench(
        "--subs", "4000", "--queries", "256", "--ticks", "6",
        "--cpu-ticks", "2", "--delivery-clients", "256",
    )
    assert len(records) == 1, records
    rec = records[0]
    assert rec["metric"] == "local_fanout_engine_tick_ms"
    # the sharded-plane delivery variant rode along (ISSUE 6): same
    # workload through worker processes, zero lost frames
    workers = rec["server_delivery"]["workers"]
    assert workers["n_workers"] >= 2
    assert workers["lost_frames"] == 0
    assert workers["deliveries_per_s"] > 0
    assert workers["per_worker_deliveries_per_s"] > 0
    assert workers["workers_for_1m_per_s"] >= 1
    assert sum(w.get("deliveries", 0) for w in workers["per_worker"]) > 0
    assert rec["engine_p99_ms"] >= rec["value"] > 0
    assert rec["sustained_e2e_tick_ms"] > 0
    assert rec["p99_ms_depth1"] > 0
    assert rec["p99_ms_depth2"] > 0
    assert rec["p50_ms_depth1"] <= rec["p99_ms_depth1"]
    assert rec["target_p99_ms"] == 5.0
    # the correctness oracle must have actually run
    assert "parity check" in stderr


def test_bench_config1_ws_echo():
    """Config 1: the real server + WS clients echo loop."""
    records, _ = run_bench("--config", "1", "--quick")
    assert len(records) == 1
    rec = records[0]
    assert rec["metric"] == "ws_echo_delivery_p99_ms"
    assert rec["deliveries_per_s"] > 0
    assert rec["clients"] == 64


def test_bench_config2_random_walk():
    """Config 2: bulk resubscribe churn through compaction warmup —
    the riskiest index path the harness drives."""
    records, stderr = run_bench("--config", "2", "--quick")
    assert len(records) == 1
    rec = records[0]
    assert rec["metric"] == "random_walk_tick_ms"
    assert rec["clients"] == 1000
    assert rec["resubs_per_tick"] > 0
    assert rec["iter_p50_ms"] <= rec["iter_p99_ms"]
    assert rec["measurement"] == "pipelined-depth2-v3"
    assert "warmup" in stderr


def test_bench_config3_knn():
    records, _ = run_bench("--config", "3", "--quick")
    rec = records[0]
    assert rec["metric"] == "knn_tick_ms"
    assert rec["entities"] == 8192
    assert rec["entity_queries_per_s"] > 0


def test_bench_config4_sharded():
    records, _ = run_bench("--config", "4", "--quick")
    rec = records[0]
    assert rec["metric"] == "sharded_worlds_tick_ms"
    assert rec["worlds"] == 8
    assert rec["mesh"] == {"batch": 1, "space": 1}


def test_bench_config6_record_op_durability():
    """Config 6: RecordCreate handler latency per durability mode —
    the BENCH-trajectory fields that track handler p99 with
    durability on (ISSUE 2)."""
    records, stderr = run_bench("--config", "6", "--quick")
    assert len(records) == 1
    rec = records[0]
    assert rec["metric"] == "record_op_handler_p99_ms"
    for mode in ("off", "wal", "sync"):
        assert rec[f"{mode}_p99_ms"] > 0
        assert rec[f"{mode}_p50_ms"] <= rec[f"{mode}_p99_ms"]
    assert rec["value"] == rec["wal_p99_ms"]
    assert rec["ops"] == 300
    assert "durability=wal" in stderr


@pytest.mark.slow   # CI's bench-smoke step runs this path directly
def test_bench_smoke_forces_compacted_collect():
    """--smoke (the CI regression gate for ISSUE 3): config-5 on tiny
    CPU shapes with the on-device result compaction forced on and the
    WS delivery pump skipped. The run itself asserts the compacted
    collect path fired; the JSON carries the fetch counters and the
    pipeline-fill tick recorded outside the percentiles."""
    records, stderr = run_bench("--config", "5", "--smoke")
    assert len(records) == 1
    rec = records[0]
    assert rec["metric"] == "local_fanout_engine_tick_ms"
    assert rec["compact_fetches"] > 0
    assert rec["server_delivery"] is None
    assert rec["first_tick_ms_depth2"] > 0
    assert "smoke:" in stderr and "parity check" in stderr


def test_bench_all_emits_one_line_per_config():
    """--all: fourteen configs, fourteen JSON lines, in config order
    (config 7 re-execs with a forced device topology and runs
    standalone)."""
    records, _ = run_bench(
        "--all", "--quick", "--subs", "4000", "--queries", "256",
        "--ticks", "6", "--cpu-ticks", "2",
    )
    assert [rec["config"] for rec in records] == [
        1, 2, 3, 4, 5, 6, 8, 9, 10, 11, 12, 13, 14, 15,
    ]
    assert len({rec["metric"] for rec in records}) == 14


def test_bench_config8_entity_sim():
    """Config 8 (ISSUE 9 + 11): entity-sim workload — columnar
    wire→SoA→device ingest, device kNN tick with incremental H2D, e2e
    frame latency over real ZMQ. --smoke additionally asserts the
    device path AND the native columnar decode fired (both legs),
    churn forced a compaction, and frames were delivered."""
    records, stderr = run_bench("--config", "8", "--smoke")
    assert len(records) == 1
    rec = records[0]
    assert rec["metric"] == "entity_sim_knn_ms"
    block = rec["entity_sim"]
    assert block["updates_per_s"] > 0
    assert block["updates_per_s_sustained"] > 0
    assert block["wire_native"] is True
    assert block["wire_rows"] > 0
    assert block["h2d_scatter"] > 0
    assert block["e2e_wire_rows"] > 0
    assert block["knn_ms"] > 0
    assert block["e2e_p99_ms"] > 0
    assert block["e2e_frames"] > 0
    assert block["frames_native"] > 0
    assert block["compactions"] >= 1
    assert block["sim_retraces_quiet"] == 0
    assert "entity_sim:" in stderr


@pytest.mark.slow   # three real-ZMQ load windows + drains: ~30 s
def test_bench_config9_overload():
    """Config 9 (ISSUE 10): the overload-storm admission workload —
    saturation / 2x / 10x offered-load windows over real ZMQ with the
    governor on. --smoke additionally asserts the saturation storm
    escalated the governor and shed (accounted exactly), the record
    stream landed, and the governor recovered to OK. CI runs the same
    smoke directly in the bench step; this pins the harness shape."""
    records, stderr = run_bench("--config", "9", "--smoke")
    assert len(records) == 1
    rec = records[0]
    assert rec["metric"] == "overload_admitted_at_10x_per_s"
    block = rec["overload"]
    assert block["sustainable_per_s"] > 0
    for name in ("saturation", "2x", "10x"):
        phase = block["phases"][name]
        assert phase["audit_exact"] is True
        assert phase["offered_per_s"] > 0
    sat = block["phases"]["saturation"]
    assert sat["shed_at_ingest"] + sat["drop_oldest"] > 0
    assert sat["governor_peak_level"] >= 1
    assert block["recovered_to_ok_within_ticks"] is not None
    assert "overload:" in stderr


@pytest.mark.slow   # two jax boots + per-mesh compiles: minutes on CPU
def test_bench_config7_sharded_overhead():
    """Config 7 (ISSUE 6 satellite / ROADMAP item 3): the sharded
    backend's 1→N-device scaling curve. In this CPU container the
    bench re-execs itself with 8 virtual host devices; quick mode
    times the 1- and 2-shard meshes against single-device."""
    records, stderr = run_bench(
        "--config", "7", "--quick", "--subs", "4000", "--queries",
        "256", "--ticks", "4",
    )
    assert len(records) == 1
    rec = records[0]
    assert rec["metric"] == "sharded_overhead_tick_ms"
    block = rec["sharded_overhead"]
    assert block["single_device_tick_ms"] > 0
    devices = [p["devices"] for p in block["curve"]]
    assert devices == [1, 2]
    for point in block["curve"]:
        assert point["tick_ms"] > 0 and point["vs_single"] > 0
    assert block["shard_map_pmax_overhead_x"] == block["curve"][0][
        "vs_single"
    ]
    assert "sharded_overhead" in stderr
