"""The two per-layer metrics PR 30 added are data: each is found by
name, is listed for the entity cell under the layer `entity plane`,
reads a value from a recorded pair of scrapes of a traced run (a CPU
rehearsal: `recorded_scrapes_hint.json`), and reads NOTHING, without
raising, from a program that has no such counters (the parent commit's
interest diff counts no `interest.rows_scanned`, `interest.hinted_ticks`
or `interest.scanned_ticks`)."""

import json

import pytest

from benchmark import layers
from benchmark.harness import load_json
from benchmark.tests.util import ROOT

CELL = "entity-100k-even.random-walk"
NAMES = ["interest_rows_scanned_per_tick", "interest_hinted_tick_share"]
COUNTERS = ["interest.rows_scanned", "interest.hinted_ticks",
            "interest.scanned_ticks"]


def recorded() -> dict:
    rec = json.loads((ROOT / "benchmark" / "tests"
                      / "recorded_scrapes_hint.json").read_text())
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
            entry["source"]) == ("entity plane", unit, better,
                                 "program_counter")
    spec = load_json("layer_metrics", name)
    shared = ("name", "layer", "unit", "moves", "better")
    assert {k: spec[k] for k in shared} == {k: entry[k] for k in shared}
    assert set(read(name, recorded())) == {name}
    # the crowd cells build no interest manager: not theirs to report
    assert read(name, recorded(), "crowd-1m.hot-cube") == {}


def test_rows_scanned_is_the_windows_count_a_flush():
    ctx = recorded()
    a, b = (ctx[side]["counters"] for side in ("before", "after"))
    flushes = b["tick.flushes"] - a["tick.flushes"]
    assert flushes > 50
    got = read(NAMES[0], ctx)[NAMES[0]]
    assert got["unit"] == "count" and got["value"] == pytest.approx(
        (b["interest.rows_scanned"] - a["interest.rows_scanned"]) / flushes)
    # the rehearsal's swarm is 2,000 entities in a 2,048-row tier: a
    # tick's closure is a part of it, and holds every row that differed
    diffed = (b["interest.rows_diffed"] - a["interest.rows_diffed"]) / flushes
    assert diffed <= got["value"] < 2048 / 2


def test_hinted_share_is_the_hinted_part_of_the_windows_ticks():
    ctx = recorded()
    # every tick of the rehearsal's window was a delta tick: 100 %
    assert read(NAMES[1], ctx)[NAMES[1]] == {"value": 100.0, "unit": "%"}
    ticks = (ctx["after"]["counters"]["interest.hinted_ticks"]
             - ctx["before"]["counters"]["interest.hinted_ticks"])
    ctx["after"]["counters"]["interest.scanned_ticks"] += ticks
    assert read(NAMES[1], ctx)[NAMES[1]]["value"] == pytest.approx(50.0)
    # a window without a tick reads 0, not a division by zero
    ctx["after"] = ctx["before"]
    assert read(NAMES[1], ctx)[NAMES[1]]["value"] == 0.0


@pytest.mark.parametrize("name", NAMES)
def test_metric_reads_nothing_from_a_program_without_the_counters(name):
    ctx = recorded()
    for scrape in (ctx["before"], ctx["after"]):
        for counter in COUNTERS:
            del scrape["counters"][counter]
    assert read(name, ctx) == {}
