"""`worlds-64x10k.hot-cube` at its rehearsal sizes, end to end on the CPU
with four virtual devices (20,000 rows in 4 worlds, 8 peers, 6 s):
the real server child on `--spatial-backend sharded --mesh-batch 1
--mesh-space 4`, real sockets, the plain reference. ~2 minutes."""

import json
import os
import subprocess
import sys

from benchmark.tests.util import ROOT

CELL = "worlds-64x10k.hot-cube"
#: the server child inherits the environment: four virtual CPU devices
ENV = dict(os.environ, JAX_PLATFORMS="cpu",
           XLA_FLAGS="--xla_force_host_platform_device_count=4")


def test_the_cell_rehearses_on_four_devices_with_its_metrics():
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "2147483659", "--seconds", "6", "--trace", "1", "--rehearsal"],
        cwd=ROOT, env=ENV, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 4
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m["workloads"]}
    assert set(line["metrics"]) <= listed
    # everything but what only a device trace of a chip holds, and the
    # leg of a CSR collect: the rehearsal's ticks hold one or two dirty
    # rows, which the smallest query tier resolves dense (no regions)
    assert listed - set(line["metrics"]) <= {
        "device_idle_share", "mesh_resolve_device_ms",
        "mesh_resolve_roofline", "mesh_merge_ms_per_s", "mesh_fetch_ms"}
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert 15.0 < m["mesh_row_share"] < 30.0
    assert 0.0 < m["mesh_dispatch_tick_share"] <= 100.0
    assert m["mesh_query_rows_per_tick"] > 0
    assert m["mesh_merge_bytes_per_tick"] > 0
    assert m["compiles_in_window"] == 0
