"""The two per-layer metrics PR 28 added are data: each is found by
name, is listed for all three cells under the layer `ticker`, reads a
value from a recorded pair of scrapes of a traced run (a CPU rehearsal:
`recorded_scrapes_pump.json`), and reads NOTHING, without raising,
from a program that has no such series (the parent commit's pump
observes no `tick.period_ms` and counts no `tick.late_flushes`)."""

import json

import pytest

from benchmark import layers
from benchmark.harness import load_json
from benchmark.tests.util import ROOT

CELLS = ["crowd-1m.hot-cube", "crowd-1m.pair-flood",
         "entity-100k-even.random-walk"]
NAMES = ["tick_period_ms", "tick_late_share"]


def recorded() -> dict:
    rec = json.loads((ROOT / "benchmark" / "tests"
                      / "recorded_scrapes_pump.json").read_text())
    return {"before": rec["before"], "after": rec["after"],
            "ticks": [], "window_unix": (0.0, 1.0)}


def bench_entry(name: str) -> dict:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    [entry] = [m for m in bench["per_layer"] if m["name"] == name]
    return entry


def read(name: str, ctx: dict, cell: str = CELLS[0]) -> dict:
    return layers.read_all({"per_layer": [bench_entry(name)]}, cell, ctx)


@pytest.mark.parametrize("name, unit", zip(NAMES, ["ms", "%"]))
def test_metric_is_found_by_name_and_listed_for_every_cell(name, unit):
    entry = bench_entry(name)
    # a later PR may append its cells: these three come first
    assert entry["workloads"][:3] == CELLS
    assert entry["moves"] == "deliver_p50_ms"
    assert (entry["layer"], entry["unit"], entry["better"],
            entry["source"]) == ("ticker", unit, "lower", "program_counter")
    spec = load_json("layer_metrics", name)
    shared = ("name", "layer", "unit", "moves", "better")
    assert {k: spec[k] for k in shared} == {k: entry[k] for k in shared}
    for cell in CELLS:
        assert set(read(name, recorded(), cell)) == {name}


def test_period_is_the_mean_of_the_windows_observations():
    ctx = recorded()
    a, b = (ctx[side]["latency"]["tick.period_ms"]
            for side in ("before", "after"))
    n = b["count"] - a["count"]
    assert n > 50
    got = read("tick_period_ms", ctx)["tick_period_ms"]
    assert got["unit"] == "ms" and got["value"] == pytest.approx(
        (b["mean_ms"] * b["count"] - a["mean_ms"] * a["count"]) / n)
    # the rehearsal's flushes fit in the interval: the period IS 50 ms
    assert 50.0 <= got["value"] < 56.0


def test_late_share_is_the_late_part_of_the_windows_flushes():
    ctx = recorded()
    flushes = (ctx["after"]["counters"]["tick.flushes"]
               - ctx["before"]["counters"]["tick.flushes"])
    # no flush of the rehearsal's window overran: 0 %, and a value
    assert read("tick_late_share", ctx)["tick_late_share"] == {
        "value": 0.0, "unit": "%"}
    ctx["after"]["counters"]["tick.late_flushes"] += 30
    assert read("tick_late_share", ctx)["tick_late_share"][
        "value"] == pytest.approx(100.0 * 30 / flushes)
    ctx["after"]["counters"]["tick.late_flushes"] += flushes - 30
    assert read("tick_late_share", ctx)["tick_late_share"][
        "value"] == pytest.approx(100.0)
    # a window without a flush reads 0, not a division by zero
    ctx["after"] = ctx["before"]
    assert read("tick_late_share", ctx)["tick_late_share"][
        "value"] == 0.0


@pytest.mark.parametrize("name", NAMES)
def test_metric_reads_nothing_from_a_program_without_the_series(name):
    ctx = recorded()
    for scrape in (ctx["before"], ctx["after"]):
        del scrape["counters"]["tick.late_flushes"]
        del scrape["latency"]["tick.period_ms"]
    assert read(name, ctx) == {}
