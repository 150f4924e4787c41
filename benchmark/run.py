#!/usr/bin/env python3
"""One run of one cell of the benchmark (see BENCHMARK.json, PERF.md and
benchmark/README.md):

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Boots the real server as a child on the chip, drives it over real
ZeroMQ sockets from generator/receiver processes of their own on an
open-loop schedule, holds every delivery to a plain reference, and
prints the result as one JSON object on the last line of stdout. This
process and the generators never import jax: the chip is the server's.
A run that finds no chip exits non-zero and prints no result
(`--rehearsal` lets tests run a cut-down cell on the CPU; its line says
`platform: cpu` and none of its numbers is a measurement).
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from benchmark import harness  # noqa: E402
from benchmark.harness import WINDOW_PHASE, say  # noqa: E402
from benchmark.server import RunFailed, Server, build_native  # noqa: E402

#: device trace: this long. The program's hook (POST /debug/profile)
#: turns jax's python tracer on: millions of events a second on the event
#: loop, so the server crawls while a capture lasts and stalls for
#: seconds when it stops
PROFILE_S = 2.0
TICKS_EVERY_S = 2.0         # the flight recorder keeps 64 ticks (3.2 s)
#: warm-up chunks, at most, to settle the server again after the capture
#: (a traced run must still end inside the time a run is allowed)
RESETTLE_CHUNKS = 6


def percentile(values, q: float) -> float:
    return float(np.percentile(values, q)) if len(values) else float("nan")


class TickPoller(threading.Thread):
    """Traced runs only: read GET /debug/ticks often enough that the
    recorder's ring (64 ticks) loses none of the window's."""

    def __init__(self, server: Server):
        super().__init__(daemon=True)
        self.server, self.ticks, self.loose = server, {}, {}
        self.halt = threading.Event()

    def run(self) -> None:
        while not self.halt.wait(TICKS_EVERY_S):
            self.poll()

    def poll(self) -> None:
        try:
            dump = self.server.get("/debug/ticks")
        except OSError:
            return
        for t in dump["ticks"]:
            self.ticks[t["start_unix_s"]] = t
        for t in dump["loose"]:
            self.loose[(t["name"], t["start_unix_s"])] = t


def device_of(gauges: dict) -> dict:
    d = gauges["spatial_device"]
    return {"platform": d["platform"], "kind": d["device_kind"],
            "count": d["device_count"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tests only: the cell's cut-down `rehearsal` sizes, "
                         "a CPU accepted; never a measurement")
    ap.add_argument("--server-module", default="worldql_server_tpu",
                    help="tests only: a wrapper that breaks the served path "
                         "(benchmark/tests/), to see `correct` come out false")
    ap.add_argument("--keep", default=None,
                    help="copy the server's log, scrapes and trace here")
    args = ap.parse_args(argv)

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = harness.Cell(args.workload, args.rehearsal)
    build_native()
    workdir = Path(tempfile.mkdtemp(prefix="wqlbench."))
    server = workers = poller = None
    try:
        deployment = cell.deployment(args.seed)
        say(f"cell {cell.name} seed {args.seed}: {deployment.rows} rows, "
            f"{deployment.cubes} cubes (fullest {deployment.occupancy_max}), "
            f"{len(deployment.connected)} peers connect")
        server = Server(
            workdir,
            [*cell.config["server_args"], *deployment.server_files(workdir)],
            module=args.server_module, trace=bool(args.trace))
        t_boot = time.monotonic()
        server.start()
        boot_s = time.monotonic() - t_boot
        booted = server.metrics()
        device = device_of(booted["gauges"])
        say(f"server up in {boot_s:.1f} s on {json.dumps(device)}")
        if device["platform"] != "tpu" and not args.rehearsal:
            raise RunFailed(f"the index lives on {device['platform']!r}, not "
                            "on a TPU: no chip, no result")
        want = cell.workload["chips"] if not args.rehearsal else device["count"]
        if device["count"] != want:
            raise RunFailed(f"{device['count']} devices, the cell asks {want}")
        why = deployment.check_booted(booted["gauges"])
        if why:
            raise RunFailed(why)

        workers = harness.Workers(cell, deployment, server, workdir)
        say(f"{workers.n_peers} peers connected from {workers.n} processes")
        drain_s = float(cell.workload["drain_s"])
        chunks, warm_wrong = harness.warm_up(cell, deployment, server, workers,
                                             args.seed)
        profile_dir = workdir / "profile"
        if args.trace:
            # the device trace is taken BEFORE the window, over
            # PROFILE_S of the cell's own traffic, and the server is
            # settled again after it: the capture crawls and stalls
            # (see PROFILE_S), and the window's numbers must not
            poller = TickPoller(server)
            server.post("/debug/profile",
                        {"action": "start", "dir": str(profile_dir)})
            harness.run_chunk(
                cell, deployment, server, workers,
                harness.chunk_plan(cell, deployment, args.seed, 90, PROFILE_S),
                90, PROFILE_S, 0.5)
            server.post("/debug/profile", {"action": "stop"})
            more, wrong = harness.warm_up(cell, deployment, server, workers,
                                          args.seed, bursts=[],
                                          first_phase=200,
                                          max_chunks=RESETTLE_CHUNKS)
            chunks, warm_wrong = chunks + more, warm_wrong + wrong
            poller.start()
        plan = harness.chunk_plan(cell, deployment, args.seed, WINDOW_PHASE,
                                  args.seconds)
        workers.arm("window", plan)
        t_go = t0 = time.monotonic_ns() + int(0.5e9)
        t_end = t0 + int(args.seconds * 1e9)
        workers.go(t_go, t_end + int(drain_s * 1e9), final=True)
        harness.sleep_until(t0 - int(0.2e9))
        before = server.metrics()
        setup_s = (t0 / 1e9) - T_PROCESS_START
        harness.sleep_until(t_end + int(0.1e9))
        after = server.metrics()
        got = workers.collect()
        if poller is not None:
            poller.halt.set()
            poller.join()
            poller.poll()
        final = server.metrics()
        why_after = deployment.check_after(final["gauges"])
        rc = server.stop()
        workers.close()
        say(f"server exited {rc}")

        # ---- after the window: the reference, then the numbers -------
        res = harness.judge_phase(cell, deployment, plan, got, WINDOW_PHASE,
                                  t_go)
        lat = res["latency_ms"]
        errors = harness.moved_errors(booted, final)
        compiles = (final["gauges"]["device"]["compiles"]
                    - before["gauges"]["device"]["compiles"])
        late_ms = got["sent_late_ns"] / 1e6
        checks = {
            # each number compared, beside its limit
            **res["checks"],
            "warmup_answers_wrong": (warm_wrong, 0),
            "unsent": (got["unsent"], 0),
            "error_counters_moved": (len(errors), 0),
            "index_rows_changed": (int(bool(why_after)), 0),
            "server_exit_code": (rc, 0),
        }
        for name, (value, limit) in checks.items():
            say(f"check {name}: {value} (limit {limit})")
        if errors or why_after:
            say("  detail:", json.dumps(errors), why_after or "")
        correct = all(value <= limit for value, limit in checks.values())
        failed = res["failed"] + got["unsent"]
        say(f"window: {res['messages']} messages, {res['attempted']} deliveries "
            f"owed, {len(lat)} good; p50 {percentile(lat, 50):.3f} p95 "
            f"{percentile(lat, 95):.3f} p99 {percentile(lat, 99):.3f} max "
            f"{float(lat.max()) if len(lat) else float('nan'):.3f} ms; generator "
            f"late p95 {percentile(late_ms, 95):.3f} max "
            f"{float(late_ms.max()) if len(late_ms) else 0:.3f} ms; "
            f"{compiles} compiles in the window after {chunks} warm-up chunks; "
            f"{res['frames_parsed']} frames "
            f"parsed in full")
        peak = max(s["gauges"]["device"].get("buffer_bytes", 0)
                   for s in (booted, before, after, final))
        device["memory_peak_bytes"] = int(peak)
        end_to_end = {
            "deliver_p50_ms": (percentile(lat, 50), "ms"),
            "delivered_per_s": (len(lat) / args.seconds, "frames/s"),
            "setup_s": (setup_s, "s"),
        }
        line = {"correct": bool(correct), "attempted": res["attempted"],
                "failed": int(failed), "device": device}
        if args.trace:
            from benchmark import layers

            flushes = max(after["counters"].get("tick.flushes", 0)
                          - before["counters"].get("tick.flushes", 0), 1)
            unix_of_mono = time.time() - time.monotonic()
            ctx = {
                "before": before, "after": after,
                "ticks": list(poller.ticks.values()),
                "loose": list(poller.loose.values()),
                "window_unix": (unix_of_mono + t0 / 1e9,
                                unix_of_mono + t_end / 1e9),
                "generator": {"late_ms": late_ms},
                "device_kind": device["kind"],
                "compiles_in_window": compiles,
                "warmup_chunks": chunks,
                # the window's latencies, for readers of kind run_value
                "deliver_p95_ms": percentile(lat, 95),
                "deliver_p99_ms": percentile(lat, 99),
                "trace": layers.reduce_trace(profile_dir, workdir),
                # a mean device call's shapes, from the traffic the
                # reference knows (never from the program)
                "shapes": getattr(deployment, "shapes",
                                  lambda plan, flushes: {})(plan, flushes),
            }
            line["metrics"] = layers.read_all(bench, cell.name, ctx)
            layers.fill_device(line, ctx)
        else:
            line["metrics"] = {
                m["name"]: {"value": end_to_end[m["name"]][0],
                            "unit": end_to_end[m["name"]][1]}
                for m in bench["end_to_end"]
                if cell.name in m.get("workloads", [cell.name])
            }
        if args.keep:
            keep = Path(args.keep)
            keep.mkdir(parents=True, exist_ok=True)
            shutil.copy(server.log_path, keep / "server.log")
            for name, snap in (("before", before), ("after", after),
                               ("final", final)):
                (keep / f"metrics-{name}.json").write_text(json.dumps(snap))
            if args.trace:
                (keep / "ticks.json").write_text(json.dumps(ctx["ticks"]))
                (keep / "trace.json").write_text(json.dumps(ctx["trace"]))
                if (workdir / "trace_cut.json").exists():
                    shutil.copy(workdir / "trace_cut.json", keep)
        if not correct and not args.rehearsal:
            say("server log tail:\n" + server.log_tail(15))
        print(json.dumps(line), flush=True)
        return 0
    finally:
        if args.keep and server is not None and server.log_path.exists():
            Path(args.keep).mkdir(parents=True, exist_ok=True)
            shutil.copy(server.log_path, Path(args.keep) / "server.log")
        if workers is not None:
            workers.close()
        if server is not None:
            server.kill()
        shutil.rmtree(workdir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
