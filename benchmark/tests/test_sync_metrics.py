"""The two per-layer metrics PR 25 added are data: each is found by
name, reads a value from a recorded pair of scrapes (a CPU rehearsal:
`recorded_scrapes_sync.json`), is listed for both cells, and reads
NOTHING, without raising, from a program that has no such series (the
parent commit counts no `delivery.sync_frames`; a program older than
PR 24 has no `deliver.write` span)."""

import json

import pytest

from benchmark import layers
from benchmark.harness import load_json
from benchmark.tests.util import ROOT

CELLS = ["crowd-1m.hot-cube", "crowd-1m.pair-flood"]


def recorded() -> dict:
    rec = json.loads((ROOT / "benchmark" / "tests"
                      / "recorded_scrapes_sync.json").read_text())
    return {"before": rec["before"], "after": rec["after"],
            "ticks": rec["ticks"], "window_unix": tuple(rec["window_unix"])}


def bench_entry(name: str) -> dict:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    [entry] = [m for m in bench["per_layer"] if m["name"] == name]
    return entry


def read(name: str, ctx: dict, cell: str = CELLS[0]) -> dict:
    return layers.read_all({"per_layer": [bench_entry(name)]}, cell, ctx)


@pytest.mark.parametrize("name, unit, better", [
    ("deliver_sync_share", "%", "higher"),
    ("deliver_write_ms", "ms", "lower"),
])
def test_metric_is_found_by_name_and_listed_for_both_cells(name, unit, better):
    entry = bench_entry(name)
    assert entry["workloads"] == CELLS and entry["moves"] == "deliver_p50_ms"
    assert (entry["layer"], entry["unit"], entry["better"]) == (
        "delivery", unit, better)
    spec = load_json("layer_metrics", name)
    assert {k: spec[k] for k in ("name", "layer", "unit", "moves", "better")
            } == {k: entry[k] for k in ("name", "layer", "unit", "moves",
                                        "better")}
    for cell in CELLS:
        assert set(read(name, recorded(), cell)) == {name}


def test_sync_share_is_the_synchronous_part_of_all_frames():
    ctx = recorded()
    # every frame of the rehearsal went straight into its socket
    assert read("deliver_sync_share", ctx)["deliver_sync_share"] == {
        "value": 100.0, "unit": "%"}
    # 40 of the window's frames through the awaited path instead
    frames = (ctx["after"]["counters"]["delivery.sync_frames"]
              - ctx["before"]["counters"]["delivery.sync_frames"])
    ctx["after"]["counters"]["delivery.sync_frames"] -= 40
    ctx["after"]["counters"]["delivery.awaited_frames"] += 40
    assert read("deliver_sync_share", ctx)["deliver_sync_share"][
        "value"] == pytest.approx(100.0 * (frames - 40) / frames)
    # a window that delivered nothing reads 0, not a division by zero
    ctx["after"] = ctx["before"]
    assert read("deliver_sync_share", ctx)["deliver_sync_share"][
        "value"] == 0.0


def test_write_ms_is_the_mean_of_the_windows_write_spans():
    ctx = recorded()
    spans = [s["dur_ms"] for t in ctx["ticks"] for s in t["spans"]
             if s["name"] == "deliver.write"]
    assert len(spans) == len(ctx["ticks"]) > 0
    assert all(s["tags"]["sync_frames"] >= 0 for t in ctx["ticks"]
               for s in t["spans"] if s["name"] == "deliver.write")
    assert read("deliver_write_ms", ctx)["deliver_write_ms"][
        "value"] == pytest.approx(sum(spans) / len(spans))


@pytest.mark.parametrize("name", ["deliver_sync_share", "deliver_write_ms"])
def test_metric_reads_nothing_from_a_program_without_the_series(name):
    ctx = recorded()
    for scrape in (ctx["before"], ctx["after"]):
        scrape["counters"] = {
            "tick.flushes": scrape["counters"]["tick.flushes"]}
    for tick in ctx["ticks"]:
        tick["spans"] = [s for s in tick["spans"]
                         if not s["name"].startswith("deliver.")]
    assert read(name, ctx) == {}
