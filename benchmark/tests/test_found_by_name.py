"""A workload, a configuration and a layer-metric file dropped beside
the others are found by name, with no edit to a file that is there."""

import json

import pytest

from benchmark import harness, layers
from benchmark.tests.util import ROOT

HERE = ROOT / "benchmark"


@pytest.fixture
def dropped():
    made = []

    def drop(kind: str, name: str, body: dict):
        path = HERE / kind / f"{name}.json"
        path.write_text(json.dumps(body))
        made.append(path)

    yield drop
    for path in made:
        path.unlink()


def test_new_files_are_found_by_name(dropped):
    config = json.loads((HERE / "configs" / "mmo-crowd-1m.json").read_text())
    config["data"]["rows"] = 30000
    config["data"]["connected"] = {
        "crowded_cubes": 1, "crowded_take": 3, "pair_cubes": 1}
    dropped("configs", "zz-test-config", config)
    workload = json.loads(
        (HERE / "workloads" / "crowd-1m.hot-cube.json").read_text())
    workload.update(config="zz-test-config", rate=50, arrival="poisson")
    dropped("workloads", "zz-test.cell", workload)
    dropped("layer_metrics", "zz_test_metric", {
        "name": "zz_test_metric", "layer": "ticker", "unit": "count",
        "moves": "deliver_p50_ms", "better": "lower",
        "source": {"kind": "counter_delta",
                   "path": ["counters", "tick.flushes"]}})

    cell = harness.Cell("zz-test.cell", rehearsal=False)
    deployment = cell.deployment(5)
    assert deployment.rows == 30000 and len(deployment.connected) == 5
    plan = harness.chunk_plan(cell, deployment, 5, 1, 4.0)
    assert len(plan["offset_ns"]) == 200
    msg, peer = cell.traffic.expected(plan, deployment)
    assert len(msg) > 0

    bench = {"per_layer": [{"name": "zz_test_metric", "unit": "count",
                            "workloads": ["zz-test.cell"]}]}
    ctx = {"before": {"counters": {"tick.flushes": 10}},
           "after": {"counters": {"tick.flushes": 25}},
           "ticks": [], "window_unix": (0.0, 1.0)}
    assert layers.read_all(bench, "zz-test.cell", ctx) == {
        "zz_test_metric": {"value": 15.0, "unit": "count"}}
    # a cell the metric does not list is left alone
    assert layers.read_all(bench, "crowd-1m.hot-cube", ctx) == {}


@pytest.mark.parametrize("pattern,n", [("even", 100), ("poisson", 100),
                                       ({"burst": {"n": 10, "every_ms": 500}},
                                        40)])
def test_arrival_patterns(pattern, n):
    import numpy as np

    from benchmark.traffic.local_message import arrivals

    t = arrivals(pattern, 50.0, 2.0, np.random.default_rng(0))
    assert len(t) == n and (np.diff(t) >= 0).all() and t.max() < 2e9


def test_every_file_benchmark_json_names_exists():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file()
    for w in bench["workloads"]:
        body = json.loads((HERE / "workloads" / f"{w['name']}.json").read_text())
        assert body["config"] == w["config"] and body["chips"] == w["chips"]
    for m in bench["per_layer"]:
        body = json.loads(
            (HERE / "layer_metrics" / f"{m['name']}.json").read_text())
        assert body["layer"] == m["layer"] and body["unit"] == m["unit"]
        assert (HERE / "sources" / f"{body['source']['kind']}.py").is_file()
