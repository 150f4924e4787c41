"""Per-layer metrics of a traced run: each metric is a data file
`layer_metrics/<name>.json` naming a source kind, and each source kind
a small reader `sources/<kind>.py` with `read(spec, ctx)`. A reader
that finds nothing to read returns None, and the metric is left out of
the line. `ctx` holds the two /metrics scrapes around the window, the
window's tick traces, the reduced device trace and the generator's own
readings (see `run.py`).
"""

from __future__ import annotations

import importlib
import json
import os
import subprocess
import sys
from pathlib import Path

from benchmark.harness import HERE, load_json, say


def reduce_trace(profile_dir: Path, workdir: Path) -> dict | None:
    """The device trace, reduced in a process of its own (reading it
    imports jax; this process never does)."""
    if not list(profile_dir.rglob("*.xplane.pb")):
        say("no device trace was written")
        return None
    out = workdir / "trace.json"
    proc = subprocess.run(
        [sys.executable, str(HERE / "trace_reduce.py"), str(profile_dir),
         str(out), str(workdir / "trace_cut.json")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True)
    if proc.returncode != 0:
        say("trace reduction failed:", proc.stderr[-2000:])
        return None
    return json.loads(out.read_text())


def window_ticks(ctx: dict) -> list:
    lo, hi = ctx["window_unix"]
    return [t for t in ctx["ticks"] if lo <= t["start_unix_s"] < hi]


def read_all(bench: dict, cell_name: str, ctx: dict) -> dict:
    ctx["window_ticks"] = window_ticks(ctx)
    out = {}
    for metric in bench["per_layer"]:
        if cell_name not in metric.get("workloads", [cell_name]):
            continue
        spec = load_json("layer_metrics", metric["name"])
        reader = importlib.import_module(
            f"benchmark.sources.{spec['source']['kind']}")
        value = reader.read(spec["source"], ctx)
        if value is None:
            say(f"layer metric {metric['name']}: nothing to read")
            continue
        if isinstance(value, tuple):       # (value, a note for the log)
            value, note = value
            say(f"layer metric {metric['name']}: {note}")
        out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out


def fill_device(line: dict, ctx: dict) -> None:
    """`busy_s`, `window_s` and the breakdown, from the reduced trace."""
    trace = ctx.get("trace")
    if not trace:
        return
    lo, hi = trace["window_ns"]
    line["device"]["busy_s"] = trace["busy_ns"] / 1e9
    line["device"]["window_s"] = (hi - lo) / 1e9
    ops: dict = {}
    for dev in trace["devices"].values():
        for name, (ns, _) in dev["ops"].items():
            ops[name] = ops.get(name, 0) + ns
    n = max(len(trace["devices"]), 1)
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    line["breakdown"] = {
        "device_ops": [[name, ns / n / 1e9] for name, ns in top],
        "idle_gaps": [[frame, length / 1e9]
                      for _, length, frame in trace["gaps"][:10]],
    }
