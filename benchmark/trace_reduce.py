#!/usr/bin/env python3
"""Reduce a jax profiler trace (`.xplane.pb`) to what the metrics read:

    python benchmark/trace_reduce.py <trace.xplane.pb | dir> <out.json> [<cut.json>]

Run in a process of its own AFTER the server child has exited (reading
a trace imports jax; the harness does not). Output:

    window_ns        [first, last] nanosecond of any event in the trace
    devices          one entry a device plane: busy_ns (union of the
                     intervals in which an operation ran), ops and
                     modules {name: [total ns, count]} as the trace
                     names them
    busy_ns          mean over the device planes
    outline          [plane, line, events, first names] of every line
    gaps             the device's longest idle gaps (first device
                     plane): [start ns, length ns, host frame], the host
                     frame being the innermost event of the host's
                     python line that covers at least half of the gap
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

#: accelerator planes; "/device:CUSTOM:Megascale Trace" and the like are not
DEVICE_PREFIX = ("/device:TPU:", "/device:GPU:")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
#: the python tracer's line is named after the process ("python", "python3");
#: a thread each, the busiest is the event loop's
HOST_PLANE, PYTHON_LINE = "/host:CPU", "python"
N_GAPS = 10


def union_ns(intervals) -> tuple[int, list]:
    """-> (covered ns, merged [start, end] list) of (start, end) pairs."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def idle_gaps(merged, window) -> list:
    """Idle intervals of a device inside `window`, longest first."""
    gaps, at = [], window[0]
    for s, e in merged:
        if s > at:
            gaps.append((at, s - at))
        at = max(at, e)
    if window[1] > at:
        gaps.append((at, window[1] - at))
    return sorted(gaps, key=lambda g: -g[1])


#: frames of the event loop's own machinery: they span everything and
#: say nothing about what the host was doing
LOOP_FILES = ("$base_events.py", "$events.py", "$selectors.py", "$runners.py",
              "$threading.py", "$thread.py", "$_base.py", "$<unknown>")


def host_frame(stack_events, start: int, length: int) -> str:
    """What the host was doing in an idle gap of the device: the
    innermost host event that covers at least half of the gap; where
    none does (a gap of many short callbacks), the frame outside the
    event loop's machinery with most time inside the gap."""
    mid_lo, mid_hi = start + length // 4, start + length - length // 4
    best = None
    inside: dict = {}
    for s, e, name in stack_events:
        if s <= mid_lo and e >= mid_hi:
            if best is None or (e - s) < best[0]:
                best = (e - s, name)
        elif s >= start and e <= start + length and not name.startswith(
                LOOP_FILES):
            inside[name] = inside.get(name, 0) + (e - s)
    if best and not best[1].startswith(LOOP_FILES):
        return best[1]
    if inside:
        name, ns = max(inside.items(), key=lambda kv: kv[1])
        return f"{name} ({100 * ns // max(length, 1)}% of it)"
    return best[1] if best else "(no host frame in it)"


def short(name: str) -> str:
    """An operation as the trace prints it is its whole HLO line: keep
    the instruction's own name (`%fusion.12`), at most 80 characters."""
    return name.split(" = ", 1)[0][:80]


def python_line(planes: list) -> list:
    """Events of the host's busiest python-tracer line."""
    best: list = []
    for plane in planes:
        if plane["name"] == HOST_PLANE:
            for line in plane["lines"]:
                if (line["name"].startswith(PYTHON_LINE)
                        and len(line["events"]) > len(best)):
                    best = line["events"]
    return best


def totals(events) -> dict:
    out: dict = {}
    for _, dur, name in events:
        t = out.setdefault(short(name), [0, 0])
        t[0] += dur
        t[1] += 1
    return out


def reduce_planes(planes: list) -> dict:
    """`planes`: [{"name", "lines": [{"name", "events": [(start ns,
    duration ns, name)]}]}] -- the shape tests build by hand."""
    lo, hi = None, None
    for plane in planes:
        for line in plane["lines"]:
            for s, d, _ in line["events"]:
                lo = s if lo is None or s < lo else lo
                hi = s + d if hi is None or s + d > hi else hi
    if lo is None:
        return {"window_ns": [0, 0], "devices": {}, "busy_ns": 0, "gaps": []}
    window = [int(lo), int(hi)]
    devices, first_merged = {}, None
    for plane in planes:
        if not plane["name"].startswith(DEVICE_PREFIX):
            continue
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        ops = lines.get(OPS_LINE)
        if ops is None:      # a backend without that line: every line
            ops = [ev for ln in plane["lines"] for ev in ln["events"]]
        busy, merged = union_ns((s, s + d) for s, d, _ in ops)
        devices[plane["name"]] = {
            "busy_ns": int(busy),
            "ops": totals(ops),
            "modules": totals(lines.get(MODULES_LINE, [])),
        }
        if first_merged is None:
            first_merged = merged
    stack = [(s, s + d, n) for s, d, n in python_line(planes)]
    gaps = [
        [int(s), int(length), host_frame(stack, s, length)]
        for s, length in idle_gaps(first_merged or [], window)[:N_GAPS]
    ]
    n = max(len(devices), 1)
    return {
        # what the trace holds, for whoever reads one for the first time
        "outline": [[plane["name"], line["name"], len(line["events"]),
                     [short(ev[2]) for ev in line["events"][:3]]]
                    for plane in planes for line in plane["lines"]][:200],
        "window_ns": window, "devices": devices,
        "busy_ns": sum(d["busy_ns"] for d in devices.values()) / n,
        "gaps": gaps,
    }


def read_xplane(path: Path) -> list:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")   # never reach for a chip
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    return [{
        "name": plane.name,
        "lines": [{
            "name": line.name,
            "events": [(int(e.start_ns), int(e.duration_ns), e.name)
                       for e in line.events],
        } for line in plane.lines],
    } for plane in data.planes]


def cut_planes(planes: list, seconds: float = 1.0,
               min_host_ns: int = 100_000) -> list:
    """A small recording for tests: the first `seconds` after the first
    device operation, device lines whole, host events of at least
    `min_host_ns` that overlap it."""
    starts = [ev[0] for p in planes if p["name"].startswith(DEVICE_PREFIX)
              for ln in p["lines"] for ev in ln["events"]]
    if not starts:
        return []
    lo = min(starts)
    hi = lo + int(seconds * 1e9)
    out = []
    for p in planes:
        device = p["name"].startswith(DEVICE_PREFIX)
        if not device and p["name"] != HOST_PLANE:
            continue
        lines = []
        for ln in p["lines"]:
            if not device and not ln["name"].startswith(PYTHON_LINE):
                continue
            evs = [(s, d, short(n)) for s, d, n in ln["events"]
                   if s < hi and s + d > lo and (device or d >= min_host_ns)]
            if evs:
                lines.append({"name": ln["name"], "events": evs})
        out.append({"name": p["name"], "lines": lines})
    return out


def main() -> int:
    src, out = Path(sys.argv[1]), Path(sys.argv[2])
    if src.is_dir():
        found = sorted(src.rglob("*.xplane.pb"))
        if not found:
            raise SystemExit(f"no .xplane.pb under {src}")
        src = found[-1]
    planes = read_xplane(src)
    out.write_text(json.dumps(reduce_planes(planes)))
    if len(sys.argv) > 3:       # also keep a small cut of the raw events
        Path(sys.argv[3]).write_text(json.dumps(cut_planes(planes)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
