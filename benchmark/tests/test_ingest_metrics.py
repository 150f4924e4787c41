"""The two per-layer metrics PR 37 added are data: each is found by
name, is listed for the entity cell under the layer `transports +
codec`, and reads a value from a recorded pair of scrapes of a traced
run (CPU rehearsals: `recorded_scrapes_ingest.json`). From the PARENT
commit's scrapes `ingest_batches_per_tick` reads the per-receive
staging it replaced (the gauge's `batches` was there) and
`ingest_edge_share` reads NOTHING, without raising (no
`edge_messages`); neither reads anything from a server that builds no
`entity_ingest` gauge (the crowd cells)."""

import json

import pytest

from benchmark import layers
from benchmark.harness import load_json
from benchmark.tests.util import ROOT

CELL = "entity-100k-even.random-walk"
NAMES = ["ingest_batches_per_tick", "ingest_edge_share"]


def recorded(side: str = "change") -> dict:
    rec = json.loads((ROOT / "benchmark" / "tests"
                      / "recorded_scrapes_ingest.json").read_text())[side]
    return {"before": rec["before"], "after": rec["after"],
            "ticks": [], "window_unix": (0.0, 1.0)}


def bench_entry(name: str) -> dict:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    [entry] = [m for m in bench["per_layer"] if m["name"] == name]
    return entry


def read(name: str, ctx: dict, cell: str = CELL) -> dict:
    return layers.read_all({"per_layer": [bench_entry(name)]}, cell, ctx)


@pytest.mark.parametrize("name, unit, better", zip(
    NAMES, ["count", "%"], ["lower", "higher"]))
def test_metric_is_found_by_name_and_listed_for_the_entity_cell(
        name, unit, better):
    entry = bench_entry(name)
    # a later PR may append its cells: this one comes first
    assert entry["workloads"][0] == CELL
    assert entry["moves"] == "deliver_p50_ms"
    assert (entry["layer"], entry["unit"], entry["better"],
            entry["source"]) == ("transports + codec", unit, better,
                                 "program_counter")
    spec = load_json("layer_metrics", name)
    shared = ("name", "layer", "unit", "moves", "better")
    assert {k: spec[k] for k in shared} == {k: entry[k] for k in shared}
    assert set(read(name, recorded())) == {name}
    # the crowd cells run without --entity-sim: not theirs to report
    assert read(name, recorded(), "crowd-1m.hot-cube") == {}


def test_batches_a_tick_is_the_stagings_a_flush():
    ctx = recorded()
    a, b = (ctx[side] for side in ("before", "after"))
    flushes = b["counters"]["tick.flushes"] - a["counters"]["tick.flushes"]
    assert flushes > 50
    got = read(NAMES[0], ctx)[NAMES[0]]
    batches = (b["gauges"]["entity_ingest"]["batches"]
               - a["gauges"]["entity_ingest"]["batches"])
    assert got["unit"] == "count"
    assert got["value"] == pytest.approx(batches / flushes)
    # one staging a tick edge (a tick without a message makes none)
    assert 0.5 < got["value"] <= 1.0
    # the parent staged a receive: the rehearsal's 8 messages a tick
    parent = read(NAMES[0], recorded("parent"))[NAMES[0]]["value"]
    assert 7.0 < parent <= 8.0


def test_edge_share_is_the_edges_part_of_the_fast_messages():
    ctx = recorded()
    assert read(NAMES[1], ctx)[NAMES[1]] == {"value": 100.0, "unit": "%"}
    fast = (ctx["after"]["gauges"]["entity_ingest"]["fast_messages"]
            - ctx["before"]["gauges"]["entity_ingest"]["fast_messages"])
    # a quarter of them staged at a bound instead
    ctx["after"]["gauges"]["entity_ingest"]["edge_messages"] -= fast // 4
    assert read(NAMES[1], ctx)[NAMES[1]]["value"] == pytest.approx(75.0)
    # a window without a message reads 0, not a division by zero
    ctx["after"] = ctx["before"]
    assert read(NAMES[1], ctx)[NAMES[1]]["value"] == 0.0


def test_edge_share_reads_nothing_from_the_parent_commit():
    assert read(NAMES[1], recorded("parent")) == {}


@pytest.mark.parametrize("name", NAMES)
def test_metric_reads_nothing_from_a_server_without_the_gauge(name):
    ctx = recorded()
    for scrape in (ctx["before"], ctx["after"]):
        del scrape["gauges"]["entity_ingest"]
    assert read(name, ctx) == {}
