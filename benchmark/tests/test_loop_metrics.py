"""The per-layer metrics PR 24 added are data: each `layer_metrics/*.json`
is found by name, reads a value from a recorded pair of scrapes (a CPU
rehearsal: `recorded_scrapes.json`), is listed for both cells, and reads
NOTHING, without raising, from a program that has no such series (the
parent commit, which the driver runs with these files laid over it)."""

import json

import pytest

from benchmark import layers
from benchmark.harness import load_json
from benchmark.tests.util import ROOT

NEW = [
    "loop_busy_ms_per_s", "loop_ingest_ms_per_tick",
    "loop_dispatch_ms_per_tick", "loop_deliver_ms_per_tick",
    "loop_other_ms_per_tick", "ingest_wall_ms_per_tick", "queue_wait_ms",
    "deliver_drain_ms", "deliver_outbox_ms", "collect_wait_ms",
    "collect_d2h_ms", "collect_decode_ms", "compile_stall_ms",
]
CELLS = ["crowd-1m.hot-cube", "crowd-1m.pair-flood"]


def recorded() -> dict:
    rec = json.loads(
        (ROOT / "benchmark" / "tests" / "recorded_scrapes.json").read_text())
    return {"before": rec["before"], "after": rec["after"],
            "ticks": rec["ticks"], "window_unix": tuple(rec["window_unix"])}


def bench_entry(name: str) -> dict:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    [entry] = [m for m in bench["per_layer"] if m["name"] == name]
    return entry


@pytest.mark.parametrize("name", NEW)
def test_metric_is_found_by_name_and_reads_the_recorded_scrapes(name):
    entry = bench_entry(name)
    assert entry["workloads"] == CELLS and entry["moves"] == "deliver_p50_ms"
    spec = load_json("layer_metrics", name)
    assert spec["layer"] == entry["layer"] and spec["unit"] == entry["unit"]
    for cell in CELLS:
        out = layers.read_all({"per_layer": [entry]}, cell, recorded())
        assert set(out) == {name}
        assert out[name]["unit"] == entry["unit"]
        assert out[name]["value"] >= 0.0


@pytest.mark.parametrize("name", NEW)
def test_metric_reads_nothing_from_a_program_without_the_series(name):
    ctx = recorded()
    for scrape in (ctx["before"], ctx["after"]):
        scrape["gauges"] = {}
        scrape["counters"] = {"tick.flushes": scrape["counters"]["tick.flushes"]}
        scrape["latency"] = {"tick.flush_ms": scrape["latency"]["tick.flush_ms"]}
    for tick in ctx["ticks"]:
        tick["spans"] = [s for s in tick["spans"]
                         if not s["name"].startswith("deliver.")]
    assert layers.read_all({"per_layer": [bench_entry(name)]}, CELLS[0],
                           ctx) == {}


def test_the_loop_layers_add_up_to_the_busy_time():
    """What the acceptance check reads on the chip: ingest + dispatch +
    deliver + other, a tick, times ticks a second = busy ms a second."""
    ctx = recorded()
    bench = {"per_layer": [bench_entry(n) for n in NEW]}
    m = {k: v["value"] for k, v in
         layers.read_all(bench, CELLS[1], ctx).items()}
    flushes = (ctx["after"]["counters"]["tick.flushes"]
               - ctx["before"]["counters"]["tick.flushes"])
    window_s = ctx["window_unix"][1] - ctx["window_unix"][0]
    a_tick = (m["loop_ingest_ms_per_tick"] + m["loop_dispatch_ms_per_tick"]
              + m["loop_deliver_ms_per_tick"] + m["loop_other_ms_per_tick"])
    assert a_tick * flushes / window_s == pytest.approx(
        m["loop_busy_ms_per_s"], rel=1e-4)
    # the unsampled total counts every message once (decode nests in recv)
    spans = [ctx[s]["gauges"]["spans"]["zmq.recv"]["count"]
             for s in ("before", "after")]
    msgs = [sum(v for k, v in ctx[s]["counters"].items()
                if k.startswith("messages.")) for s in ("before", "after")]
    assert spans[1] - spans[0] == msgs[1] - msgs[0] > 0
