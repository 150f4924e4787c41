"""The reduction from a device trace to busy time, per-operation time,
idle gaps and a roofline share, on a small recorded trace
(`recorded_trace.json`: planes, lines and events in the shape
`trace_reduce.read_xplane` gives, cut from a run of
`crowd-1m.hot-cube` on one TPU v5 lite, PR 23)."""

import json
from pathlib import Path

import pytest

from benchmark import roofline, trace_reduce
from benchmark.sources import device_idle, device_op_time
from benchmark.sources import roofline as roofline_source

HAND = [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            (100, 50, "fusion.1"), (120, 10, "copy.2"),     # nested
            (200, 100, "fusion.1"), (1000, 20, "sort.3")]},
        {"name": "XLA Modules", "events": [
            (100, 200, "jit__match_run_csr_kernel(1)"),
            (1000, 20, "jit_other(2)")]},
    ]},
    {"name": "/host:CPU", "lines": [
        {"name": "python", "events": [
            (0, 2000, "$base_events.py:1 run_forever"),
            (310, 680, "$peers.py:9 _deliver_batch_planed"),
            (400, 10, "$socket.py:3 send")]},
    ]},
]


def test_busy_is_the_union_of_intervals():
    covered, merged = trace_reduce.union_ns([(0, 10), (5, 20), (30, 40)])
    assert covered == 30 and merged == [[0, 20], [30, 40]]


def test_reduce_hand_built_planes():
    red = trace_reduce.reduce_planes(HAND)
    assert red["window_ns"] == [0, 2000]
    dev = red["devices"]["/device:TPU:0"]
    assert dev["busy_ns"] == 50 + 100 + 20          # nested copy not twice
    assert dev["ops"]["fusion.1"] == [150, 2]
    assert dev["modules"]["jit__match_run_csr_kernel(1)"] == [200, 1]
    # longest first: after the last op only the event loop is around
    assert red["gaps"][0] == [1020, 980, "$base_events.py:1 run_forever"]
    # a gap of short callbacks: the frame with most time inside it
    events = [(0, 1000, "$base_events.py:1 run_forever"),
              (10, 100, "$peers.py:9 deliver"), (200, 300, "$peers.py:9 deliver"),
              (600, 50, "$codec.py:3 decode")]
    assert trace_reduce.host_frame(
        [(s, s + d, n) for s, d, n in events], 0, 1000
    ) == "$peers.py:9 deliver (40% of it)"
    # then 300..1000, while the host delivered
    assert red["gaps"][1] == [300, 700, "$peers.py:9 _deliver_batch_planed"]


def test_sources_on_hand_built_planes():
    ctx = {"trace": trace_reduce.reduce_planes(HAND),
           "device_kind": "TPU v5 lite",
           "shapes": {"match_call": {"queries": 20.0, "targets": 5000.0}}}
    assert device_idle.read({}, ctx) == pytest.approx(100 * (1 - 170 / 2000))
    assert device_op_time.read(
        {"line": "modules", "match": "match_run_csr", "per": "call"},
        ctx) == pytest.approx(200 / 1e6)
    share, note = roofline_source.read(
        {"line": "modules", "match": "match_run_csr", "model": "cube_match",
         "shapes": "match_call"}, ctx)
    work = roofline.cube_match(20.0, 5000.0)
    assert share == pytest.approx(100 * work["bytes"] / 819e9 / 200e-9)
    assert "memory-bound" in note


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9")
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_recorded_trace_reduces_with_a_share_under_100():
    path = Path(__file__).parent / "recorded_trace.json"
    rec = json.loads(path.read_text())
    red = trace_reduce.reduce_planes(rec["planes"])
    lo, hi = red["window_ns"]
    assert 0 < red["busy_ns"] < hi - lo
    ctx = {"trace": red, "device_kind": rec["device_kind"],
           "shapes": rec["shapes"]}
    idle = device_idle.read({}, ctx)
    assert 0.0 < idle < 100.0
    spec = json.loads((Path(__file__).parents[1] / "layer_metrics"
                       / "match_roofline.json").read_text())["source"]
    share, _ = roofline_source.read(spec, ctx)
    assert 0.0 < share <= 100.0
    assert red["gaps"] and red["gaps"][0][1] >= red["gaps"][-1][1]
