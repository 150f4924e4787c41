"""One tiny cell end to end on the CPU (the `rehearsal` sizes of its
files: 20,000 rows, 8 peers, 3 s), through the real server child and
real sockets: the last line's keys, `platform: cpu`, no result without
the switch, and `correct` false with the timed path broken underneath
(a float32 quantizer in the server: answers altered where they are
produced). Slow for a unit test (three server boots): ~2 minutes.
"""

import json
import os
import subprocess
import sys

import pytest

from benchmark.tests.util import ROOT

ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def run(*extra, seconds="3", cell="crowd-1m.hot-cube", seed="2147483659"):
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         seed, "--seconds", seconds, *extra],
        cwd=ROOT, env=ENV, capture_output=True, text=True, timeout=600)


def last_line(proc) -> dict:
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_prints_the_contracts_line(trace):
    line = last_line(run("--trace", trace, "--rehearsal"))
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert {"kind", "count", "memory_peak_bytes"} <= set(line["device"])
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    if trace == "0":
        assert set(line["metrics"]) == {m["name"] for m in bench["end_to_end"]}
    else:
        names = {m["name"] for m in bench["per_layer"]}
        assert set(line["metrics"]) <= names
        assert {"gen_late_p95_ms", "tick_flush_ms", "tick_deliver_ms",
                "tick_dispatch_ms"} <= set(line["metrics"])
        assert {"busy_s", "window_s"} <= set(line["device"])
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}


def test_no_chip_no_result():
    proc = run("--trace", "0")
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


def test_float32_quantizer_in_the_server_fails_correct():
    # pair-flood: one delivery a message, so the face messages' extra and
    # missing deliveries are all there is to see; a rate that sends ~30
    proc = run("--trace", "0", "--rehearsal", "--server-module",
               "benchmark.tests.f32_server", cell="crowd-1m.pair-flood",
               seconds="6")
    line = last_line(proc)
    assert line["correct"] is False
    assert line["failed"] > 0


def test_entity_cell_rehearsal_and_its_bf16_control():
    """`entity-100k.random-walk` (not yet a cell of BENCHMARK.json: PERF.md,
    Open questions) at its rehearsal sizes: ledgers exact; with a bfloat16
    position column in the server, `correct` comes out false."""
    line = last_line(run("--trace", "0", "--rehearsal",
                         cell="entity-100k.random-walk", seconds="4"))
    assert line["correct"] is True and line["attempted"] > 0
    line = last_line(run("--trace", "0", "--rehearsal", "--server-module",
                         "benchmark.tests.bf16_server",
                         cell="entity-100k.random-walk", seconds="4"))
    assert line["correct"] is False
