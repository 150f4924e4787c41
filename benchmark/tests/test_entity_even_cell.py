"""The cell `entity-100k-even.random-walk` (ISSUE 27): its files are found
by name, its per-layer metrics are data that read a recorded pair of
scrapes (a CPU rehearsal: `recorded_scrapes_entity.json`) and read
NOTHING, without raising, from a program that lacks the spans and
counters (the parent commit, which the driver runs with these files laid
over it); and the cell runs end to end at its rehearsal sizes, exact with
the program as it is and `correct` false with a bfloat16 position column
in the server. Three server boots: ~2 minutes.
"""

import json

import pytest

from benchmark import harness, layers, roofline
from benchmark.harness import load_json
from benchmark.tests.test_rehearsal import last_line, run
from benchmark.tests.util import ROOT

CELL = "entity-100k-even.random-walk"
SCRAPED = [
    "sim_integrate_ms", "sim_knn_ms", "sim_apply_ms", "sim_interest_diff_ms",
    "sim_interest_encode_ms", "loop_sim_ms_per_tick", "sim_ticks_per_s",
    "sim_delta_tick_share", "interest_entries_per_tick",
    "interest_rows_diffed_per_tick",
]
TRACED = ["knn_device_ms", "knn_roofline"]
#: what PR 27 added to the program: the parent has none of these
ADDED_IN_PR_27 = ["sim_interest_diff_ms", "sim_interest_encode_ms",
                  "interest_entries_per_tick", "interest_rows_diffed_per_tick"]


def recorded() -> dict:
    rec = json.loads((ROOT / "benchmark" / "tests"
                      / "recorded_scrapes_entity.json").read_text())
    return {"before": rec["before"], "after": rec["after"],
            "ticks": rec["ticks"], "window_unix": tuple(rec["window_unix"])}


def bench() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def bench_entry(name: str) -> dict:
    [entry] = [m for m in bench()["per_layer"] if m["name"] == name]
    return entry


def test_the_cell_and_its_configuration_are_found_by_name():
    b = bench()
    [entry] = [w for w in b["workloads"] if w["name"] == CELL]
    [config] = [c for c in b["configs"] if c["name"] == entry["config"]]
    cell = harness.Cell(CELL, rehearsal=False)
    assert cell.workload["config"] == config["name"] == cell.config["name"]
    assert config["file"] == f"benchmark/configs/{config['name']}.json"
    assert config["reduced"] == cell.config["reduced"] == ["chips"]
    assert entry["chips"] == cell.workload["chips"] == 1
    assert cell.deployments.__name__.endswith("entity_swarm_even")
    assert cell.traffic.__name__.endswith("entity_walk")
    # the guarantees are PR 23's, word for word
    assert cell.config["guarantees"] == load_json(
        "configs", "entity-knn-100k")["guarantees"]
    assert cell.config["server_args"] == load_json(
        "configs", "entity-knn-100k")["server_args"]
    # every metric that lists the cell moves a metric the cell reports
    for m in b["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["moves"] in {"deliver_p50_ms", "setup_s"}


@pytest.mark.parametrize("name", SCRAPED + TRACED)
def test_metric_file_matches_its_entry(name):
    entry, spec = bench_entry(name), load_json("layer_metrics", name)
    assert entry["workloads"] == [CELL] and entry["moves"] == "deliver_p50_ms"
    assert spec["layer"] == entry["layer"] and spec["unit"] == entry["unit"]
    assert spec["better"] == entry["better"]


@pytest.mark.parametrize("name", SCRAPED)
def test_metric_reads_the_recorded_scrapes(name):
    entry = bench_entry(name)
    out = layers.read_all({"per_layer": [entry]}, CELL, recorded())
    assert set(out) == {name} and out[name]["unit"] == entry["unit"]
    assert out[name]["value"] > 0.0


def test_the_recorded_numbers_hang_together():
    ctx = recorded()
    m = {k: v["value"] for k, v in layers.read_all(
        {"per_layer": [bench_entry(n) for n in SCRAPED]}, CELL, ctx).items()}
    # the two legs are inside the apply span, the apply on the loop's account
    assert (m["sim_interest_diff_ms"] + m["sim_interest_encode_ms"]
            <= m["sim_apply_ms"] <= m["loop_sim_ms_per_tick"])
    assert m["sim_delta_tick_share"] == 100.0
    assert m["sim_ticks_per_s"] == 113 / 6.0
    # the diff looks at the rows that moved: never more than the 960
    # messages of the window sent updates (2 walkers and 2 probes each),
    # fewer where a tick took two updates of one probe; and each of them
    # is an entry at every one of its ~6 watchers
    rows = m["interest_rows_diffed_per_tick"] * 113
    assert 0.9 * 960 * 4 < rows <= 960 * 4
    assert 5 * rows < m["interest_entries_per_tick"] * 113 < 7 * rows


@pytest.mark.parametrize("name", ADDED_IN_PR_27)
def test_metric_reads_nothing_from_the_parent(name):
    ctx = recorded()
    for scrape in (ctx["before"], ctx["after"]):
        scrape["counters"] = {"tick.flushes": scrape["counters"]["tick.flushes"]}
        for span in list(scrape["gauges"]["spans"]):
            if span.startswith("tick.sim.interest."):
                del scrape["gauges"]["spans"][span]
    for tick in ctx["ticks"]:
        tick["spans"] = [s for s in tick["spans"]
                         if not s["name"].startswith("tick.sim.interest.")]
    assert layers.read_all({"per_layer": [bench_entry(name)]}, CELL, ctx) == {}


def test_knn_metrics_read_a_reduced_trace_and_nothing_without_one():
    ctx = recorded()
    entries = {"per_layer": [bench_entry(n) for n in TRACED]}
    assert layers.read_all(entries, CELL, ctx) == {}         # no trace at all
    ops = {"%_knn_jit.2": [4_000_000, 10], "%knn_select.3": [6_000_000, 10],
           "%fusion.1": [9_000_000, 30]}
    ctx.update(
        device_kind="TPU v5 lite",
        trace={"window_ns": [0, 2_000_000_000], "busy_ns": 19_000_000,
               "devices": {"/device:TPU:0": {"busy_ns": 19_000_000, "ops": ops,
                                             "modules": {}}}},
        shapes={"knn_call": {"entities": 4000.0, "k": 32, "window": 64}})
    out = layers.read_all(entries, CELL, ctx)
    assert out["knn_device_ms"]["value"] == pytest.approx(0.5)
    least, bound = roofline.least_seconds(
        roofline.knn_select(4000.0, 32, 64), "TPU v5 lite")
    assert bound == "memory"
    assert out["knn_roofline"]["value"] == pytest.approx(100 * least / 0.5e-3)
    assert 0.0 < out["knn_roofline"]["value"] < 1.0
    ctx["shapes"] = {}                  # a deployment without `shapes`
    assert set(layers.read_all(entries, CELL, ctx)) == {"knn_device_ms"}


def test_rehearsal_and_its_bf16_control():
    """Ledgers exact at the rehearsal sizes (2,000 entities, 8 peers, 2
    probes a peer with 6 watchers each: 1,920 reflections/s owed in every
    seed); with a bfloat16 position column in the server, `correct` comes
    out false."""
    for seed in ("2147483659", "3000000215"):
        line = last_line(run("--trace", "0", "--rehearsal", cell=CELL,
                             seconds="4", seed=seed))
        assert line["correct"] is True and line["failed"] == 0
        assert line["attempted"] == 4 * 1920
        assert line["metrics"]["delivered_per_s"]["value"] == 1920.0
    line = last_line(run("--trace", "0", "--rehearsal", "--server-module",
                         "benchmark.tests.bf16_server", cell=CELL,
                         seconds="4"))
    assert line["correct"] is False and line["failed"] > 0
