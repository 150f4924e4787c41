"""The per-layer metrics PR 32 added for the mesh backend are data: each
is found by name and listed for `worlds-64x10k.hot-cube` alone; the
counter ones read a value from a recorded pair of scrapes of a traced
run (a CPU rehearsal on four virtual devices: `recorded_scrapes_mesh.json`),
the trace ones from a device trace built by hand; and each reads
NOTHING, without raising, from a program that has no such series (the
parent commit's mesh backend counts no `mesh_*`, publishes no
`device.mesh_fetch_ms` and calls both of its mesh programs `jit_fn`)."""

import json

import pytest

from benchmark import layers
from benchmark.harness import load_json
from benchmark.tests.util import ROOT

CELL = "worlds-64x10k.hot-cube"
COUNTED = {"mesh_row_share": "%", "mesh_dispatch_tick_share": "%",
           "mesh_query_rows_per_tick": "count",
           "mesh_merge_bytes_per_tick": "bytes", "mesh_fetch_ms": "ms"}
TRACED = {"mesh_resolve_device_ms": "ms", "mesh_resolve_roofline": "%",
          "mesh_merge_ms_per_s": "ms/s"}


def recorded() -> dict:
    rec = json.loads((ROOT / "benchmark" / "tests"
                      / "recorded_scrapes_mesh.json").read_text())
    plane = {"busy_ns": 3_000_000, "ops": {
        # XLA names an all-reduce after the jax primitive as often as not
        "%all-reduce.7": [400_000, 40], "%pmax.18": [200_000, 20],
        "%fusion.3": [2_400_000, 60]}, "modules": {
        "jit_mesh_resolve_csr(77)": [2_000_000, 20],
        "jit_mesh_repack(78)": [1_000_000, 10]}}
    return {"before": rec["before"], "after": rec["after"], "ticks": [],
            "window_unix": (0.0, 6.0), "device_kind": "TPU v5 lite",
            "shapes": {"match_call": {"queries": 8.0, "targets": 1640.0}},
            "trace": {"window_ns": [0, 2_000_000_000], "busy_ns": 3_000_000,
                      "gaps": [], "devices": {
                          f"/device:TPU:{i}": plane for i in range(4)}}}


def bench_entry(name: str) -> dict:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    [entry] = [m for m in bench["per_layer"] if m["name"] == name]
    return entry


def read(name: str, ctx: dict, cell: str = CELL) -> dict:
    return layers.read_all({"per_layer": [bench_entry(name)]}, cell, ctx)


@pytest.mark.parametrize("name, unit", [*COUNTED.items(), *TRACED.items()])
def test_metric_is_found_by_name_and_listed_for_the_mesh_cell(name, unit):
    entry = bench_entry(name)
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "deliver_p50_ms" and entry["unit"] == unit
    assert entry["layer"] == ("kernels" if name in TRACED
                              else "device backend")
    assert entry["source"] == ("device_trace" if name in TRACED
                               else "program_counter")
    spec = load_json("layer_metrics", name)
    shared = ("name", "layer", "unit", "moves", "better")
    assert {k: spec[k] for k in shared} == {k: entry[k] for k in shared}
    assert set(read(name, recorded())) == {name}
    # a one-chip cell is not asked for it
    assert read(name, recorded(), "crowd-1m.hot-cube") == {}


def test_the_counter_metrics_are_the_windows_deltas():
    ctx = recorded()
    dev = {side: ctx[side]["gauges"]["spatial_device"]
           for side in ("before", "after")}

    def grew(key):
        return dev["after"][key] - dev["before"][key]

    flushes = (ctx["after"]["counters"]["tick.flushes"]
               - ctx["before"]["counters"]["tick.flushes"])
    assert flushes > 100 and grew("delta_recomputed") == grew("mesh_query_rows")
    got = {name: read(name, ctx)[name]["value"] for name in COUNTED}
    assert got["mesh_row_share"] == pytest.approx(
        100.0 * grew("delta_recomputed")
        / (grew("delta_recomputed") + grew("delta_reused")))
    assert 19.0 < got["mesh_row_share"] < 22.0      # the mix's fresh fifth
    assert got["mesh_dispatch_tick_share"] == pytest.approx(
        100.0 * grew("mesh_dispatches") / flushes)
    assert got["mesh_dispatch_tick_share"] <= 100.0
    assert got["mesh_query_rows_per_tick"] == pytest.approx(
        grew("mesh_query_rows") / flushes)
    assert got["mesh_merge_bytes_per_tick"] == pytest.approx(
        grew("mesh_merge_bytes") / flushes)
    a, b = (ctx[side]["latency"]["device.mesh_fetch_ms"]
            for side in ("before", "after"))
    assert got["mesh_fetch_ms"] == pytest.approx(
        (b["mean_ms"] * b["count"] - a["mean_ms"] * a["count"])
        / (b["count"] - a["count"]))
    # the leg is observed only by ticks that fetched regions: fewer
    # than the ticks that observed the fetch leg it is made of
    assert b["count"] - a["count"] == grew("mesh_region_fetches") < flushes


def test_the_trace_metrics_are_a_mean_over_the_device_planes():
    ctx = recorded()
    got = {name: read(name, ctx)[name]["value"] for name in TRACED}
    # 3 ms of the two programs over 30 executions a device
    assert got["mesh_resolve_device_ms"] == pytest.approx(0.1)
    # 0.6 ms of all-reduce (`all-reduce`, `pmax`) a device in a 2 s trace
    assert got["mesh_merge_ms_per_s"] == pytest.approx(0.3)
    # a call is its resolve and its repack: 3 ms / 20 calls = 150 us;
    # least: 4 x 1,640 B over 200 GB/s = 32.8 ns (interconnect-bound)
    assert got["mesh_resolve_roofline"] == pytest.approx(
        100.0 * (4 * 1640 / 200e9) / 150e-6)
    assert got["mesh_resolve_roofline"] < 100.0


@pytest.mark.parametrize("name", [*COUNTED, *TRACED])
def test_metric_reads_nothing_from_the_parents_program(name):
    ctx = recorded()
    for scrape in (ctx["before"], ctx["after"]):
        del scrape["latency"]["device.mesh_fetch_ms"]
        for key in ("mesh_dispatches", "mesh_query_rows", "mesh_merge_bytes",
                    "mesh_region_fetches"):
            del scrape["gauges"]["spatial_device"][key]
    for plane in ctx["trace"]["devices"].values():
        plane["modules"] = {"jit_fn(77)": [2_000_000, 20],
                            "jit_pack_all(78)": [1_000_000, 10]}
    got = read(name, ctx)
    # what the parent already had still reads: the reuse cache's counts
    # and the all-reduce operations
    assert set(got) == ({name} if name in ("mesh_row_share",
                                           "mesh_merge_ms_per_s") else set())
    ctx["trace"] = None
    assert read(name, ctx) == ({} if name in TRACED else got)
