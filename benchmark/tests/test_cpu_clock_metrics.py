"""The nine per-layer metrics PR 38 added are data: each is found by
name, listed with an explicit `workloads`, and reads a value from a
recorded pair of scrapes of a traced run (CPU rehearsals of this tree:
`recorded_scrapes_cpu_clock.json`, sides `entity` and `crowd`). Eight
go through readers the benchmark had (`counter_delta`, `counter_ratio`,
`histogram_mean`); `recv_take_us_per_msg` through the one reader this
PR brings, `sources/counter_quotient.py`. From the PARENT commit's
scrapes (side `parent`: no `cpu_ms` / `off_cpu_ms` anywhere, no
`zmq_recv` gauge, no `sim.knn_fetch_ms`) every one of them reads
NOTHING, without raising."""

import json

import pytest

from benchmark import layers
from benchmark.harness import load_json
from benchmark.sources import counter_quotient
from benchmark.tests.util import ROOT

ENTITY = "entity-100k-even.random-walk"
CROWD = ["crowd-1m.hot-cube", "crowd-1m.pair-flood", "worlds-64x10k.hot-cube"]
ALL = [*CROWD[:2], ENTITY, CROWD[2]]

#: name -> (layer, unit, source of BENCHMARK.json, the cells, the
#: recorded side it is read from)
METRICS = {
    "loop_off_cpu_ms_per_s": ("event loop", "ms/s", "program_counter", ALL,
                              "entity"),
    "deliver_write_off_cpu_ms": ("delivery", "ms", "program_span", ALL,
                                 "entity"),
    "tick_dispatch_off_cpu_ms": ("router + staging", "ms", "program_span",
                                 CROWD, "crowd"),
    "sim_integrate_off_cpu_ms": ("entity plane", "ms", "program_span",
                                 [ENTITY], "entity"),
    "sim_apply_off_cpu_ms": ("entity plane", "ms", "program_span", [ENTITY],
                             "entity"),
    "sim_knn_fetch_ms": ("entity plane", "ms", "program_counter", [ENTITY],
                         "entity"),
    "sim_knn_off_cpu_ms": ("entity plane", "ms", "program_counter", [ENTITY],
                           "entity"),
    "recv_suspend_share": ("transports + codec", "%", "program_counter", ALL,
                           "entity"),
    "recv_take_us_per_msg": ("transports + codec", "us", "program_counter",
                             ALL, "crowd"),
}


def recorded(side: str) -> dict:
    rec = json.loads((ROOT / "benchmark" / "tests"
                      / "recorded_scrapes_cpu_clock.json").read_text())[side]
    return {"before": rec["before"], "after": rec["after"], "ticks": [],
            "window_unix": tuple(rec["window_unix"])}


def bench() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def bench_entry(name: str) -> dict:
    [entry] = [m for m in bench()["per_layer"] if m["name"] == name]
    return entry


def read(name: str, ctx: dict, cell: str) -> dict:
    return layers.read_all({"per_layer": [bench_entry(name)]}, cell, ctx)


def grew(ctx: dict, *path) -> float:
    def at(scrape):
        for key in path:
            scrape = scrape[key]
        return scrape
    return at(ctx["after"]) - at(ctx["before"])


@pytest.mark.parametrize("name", METRICS)
def test_metric_is_found_by_name_and_reads_a_value(name):
    layer, unit, source, cells, side = METRICS[name]
    entry = bench_entry(name)
    # a later PR may append its cells: these come first
    assert entry["workloads"][:len(cells)] == cells
    assert (entry["layer"], entry["unit"], entry["better"], entry["moves"],
            entry["source"]) == (layer, unit, "lower", "deliver_p50_ms",
                                 source)
    spec = load_json("layer_metrics", name)
    shared = ("name", "layer", "unit", "moves", "better")
    assert {k: spec[k] for k in shared} == {k: entry[k] for k in shared}
    got = read(name, recorded(side), cells[0])
    assert set(got) == {name} and got[name]["unit"] == unit
    # not its cell's to report
    other = ENTITY if ENTITY not in cells else None
    if name.startswith("sim_"):
        other = CROWD[0]
    if other is not None:
        assert read(name, recorded(side), other) == {}


def test_all_nine_come_out_of_one_read_of_their_entries():
    """As `run.py` reads them, `layers.read_all` over BENCHMARK.json's
    entries (these nine: the older ones want a device trace, tick
    traces and the generator's readings, which a trimmed scrape lacks):
    each cell's line carries exactly the metrics that list it."""
    nine = {"per_layer": [bench_entry(name) for name in METRICS]}
    entity = layers.read_all(nine, ENTITY, recorded("entity"))
    crowd = layers.read_all(nine, CROWD[1], recorded("crowd"))
    assert set(entity) == {n for n, m in METRICS.items() if ENTITY in m[3]}
    assert set(crowd) == {n for n, m in METRICS.items() if CROWD[1] in m[3]}
    assert len(entity) == 8 and len(crowd) == 5
    assert layers.read_all(nine, ENTITY, recorded("parent")) == {}


@pytest.mark.parametrize("name", METRICS)
def test_metric_reads_nothing_from_the_parent_commit(name):
    cells = METRICS[name][3]
    assert read(name, recorded("parent"), cells[0]) == {}


@pytest.mark.parametrize("name, span", [
    ("deliver_write_off_cpu_ms", "deliver.write"),
    ("sim_integrate_off_cpu_ms", "tick.sim.integrate"),
    ("sim_apply_off_cpu_ms", "tick.sim.apply"),
])
def test_a_spans_off_cpu_time_is_its_growth_a_tick(name, span):
    ctx = recorded("entity")
    ticks = grew(ctx, "counters", "tick.flushes")
    assert ticks > 50
    got = read(name, ctx, ENTITY)[name]["value"]
    assert got == pytest.approx(
        grew(ctx, "gauges", "spans", span, "off_cpu_ms") / ticks)
    # the identity the gauge is built on, in both scrapes
    for scrape in (ctx["before"], ctx["after"]):
        row = scrape["gauges"]["spans"][span]
        assert row["clocked"] == row["count"]
        assert row["cpu_ms"] + row["off_cpu_ms"] == pytest.approx(
            row["clocked_ms"], abs=0.0011)
        assert row["clocked_ms"] <= row["wall_ms"] + 0.001
    # a span around an await is in the table with its wall alone
    assert "clocked" not in ctx["after"]["gauges"]["spans"]["tick.deliver"]


def test_the_loops_off_cpu_time_is_busy_less_cpu_a_second():
    ctx = recorded("entity")
    lo, hi = ctx["window_unix"]
    name = "loop_off_cpu_ms_per_s"
    got = read(name, ctx, ENTITY)[name]["value"]
    assert got == pytest.approx(
        grew(ctx, "gauges", "loop_time", "off_cpu_ms") / (hi - lo))
    assert grew(ctx, "gauges", "loop_time", "off_cpu_ms") == pytest.approx(
        grew(ctx, "gauges", "loop_time", "busy_ms")
        - grew(ctx, "gauges", "loop_time", "cpu_ms"), abs=0.002)


def test_the_receives_two_readings():
    ctx = recorded("crowd")
    messages = grew(ctx, "gauges", "zmq_recv", "messages")
    assert messages > 500
    share = read("recv_suspend_share", ctx, CROWD[1])["recv_suspend_share"]
    assert share["value"] == pytest.approx(
        100.0 * grew(ctx, "gauges", "zmq_recv", "suspends") / messages)
    assert 0.0 < share["value"] <= 100.0
    take = read("recv_take_us_per_msg", ctx, CROWD[1])["recv_take_us_per_msg"]
    assert take["value"] == pytest.approx(
        grew(ctx, "gauges", "zmq_recv", "take_ns") / messages / 1e3)
    for scrape in (ctx["before"], ctx["after"]):
        recv = scrape["gauges"]["zmq_recv"]
        assert recv["suspends"] <= recv["messages"] + 1


def test_the_quotient_reads_nothing_where_nothing_was_counted():
    spec = load_json("layer_metrics", "recv_take_us_per_msg")["source"]
    ctx = recorded("crowd")
    assert counter_quotient.read(spec, ctx) > 0
    # a window without a message: no cost a message, not a division
    assert counter_quotient.read(spec, {**ctx, "after": ctx["before"]}) is None
    # a server without the gauge
    for scrape in (ctx["before"], ctx["after"]):
        del scrape["gauges"]["zmq_recv"]
    assert counter_quotient.read(spec, ctx) is None


def test_the_knn_legs_are_means_of_the_windows_fetching_ticks():
    ctx = recorded("entity")
    for name, hist in [("sim_knn_fetch_ms", "sim.knn_fetch_ms"),
                       ("sim_knn_off_cpu_ms", "sim.knn_off_cpu_ms")]:
        a, b = (ctx[side]["latency"][hist] for side in ("before", "after"))
        n = b["count"] - a["count"]
        assert n > 20
        assert read(name, ctx, ENTITY)[name]["value"] == pytest.approx(
            (b["mean_ms"] * b["count"] - a["mean_ms"] * a["count"]) / n)
    # the fetch is a part of the wait it is read beside
    fetch = read("sim_knn_fetch_ms", ctx, ENTITY)["sim_knn_fetch_ms"]["value"]
    assert 0 < fetch
