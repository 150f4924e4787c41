#!/usr/bin/env python3
"""Find a cell's knee, once, on the chip, in one server process:

    python benchmark/sweep.py --workload <cell> --seed <n> --start <msgs/s> --out <file.json>

Steps of `--step-seconds` (10), the rate x 1.5 each, until the backlog
grows. A step HOLDS if at least 99 % of its reference deliveries
arrived by the step's end plus the cell's drain, and the median latency
of its last third is at most 1.25 x that of its first third. The knee
is the last step that holds; the cell's `rate` is 0.8 x it. The steps,
as run, are kept in benchmark/sweeps/<cell>.json.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from benchmark import harness  # noqa: E402
from benchmark.harness import say  # noqa: E402
from benchmark.server import RunFailed, Server, build_native  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--start", type=float, required=True)
    ap.add_argument("--factor", type=float, default=1.5)
    ap.add_argument("--step-seconds", type=float, default=10.0)
    ap.add_argument("--max-steps", type=int, default=12)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()

    cell = harness.Cell(args.workload, args.rehearsal)
    build_native()
    workdir = Path(tempfile.mkdtemp(prefix="wqlsweep."))
    server = workers = None
    steps = []
    try:
        deployment = cell.deployment(args.seed)
        server = Server(workdir, [*cell.config["server_args"],
                                  *deployment.server_files(workdir)])
        server.start()
        gauges = server.metrics()["gauges"]
        device = gauges["spatial_device"]
        if device["platform"] != "tpu" and not args.rehearsal:
            raise RunFailed("no chip: a knee is a device-side measurement")
        workers = harness.Workers(cell, deployment, server, workdir)
        drain_s = float(cell.workload["drain_s"])
        rate = args.start
        harness.warm_up(cell, deployment, server, workers, args.seed, rate)
        step = reruns = 0
        while step < args.max_steps:
            phase = 2 + step + 50 * reruns
            plan = harness.chunk_plan(cell, deployment, args.seed, phase,
                                      args.step_seconds, rate)
            res = harness.run_chunk(cell, deployment, server, workers, plan,
                                    phase, args.step_seconds, drain_s)
            got, t_go = res["got"], res["t_go"]
            if res["compiles"] and reruns < 3:
                # a rate's first step can meet a batch tier the program
                # has not compiled yet: that stall is set-up, not the
                # knee. Run the rate again now that the variant exists
                say(f"rate {rate}: {res['compiles']} compiles in the step, "
                    "run again")
                reruns += 1
                continue
            # latency by thirds of the step, by the message's due time
            due_rel = res["due_offset_s"]
            third = args.step_seconds / 3
            first = res["latency_ms"][due_rel < third]
            last = res["latency_ms"][due_rel >= 2 * third]
            arrived = len(res["latency_ms"]) / max(res["attempted"], 1)
            med_first = float(np.median(first)) if len(first) else None
            med_last = float(np.median(last)) if len(last) else None
            late = got["sent_late_ns"] / 1e6
            holds = bool(arrived >= 0.99 and med_first and med_last
                         and med_last <= 1.25 * med_first)
            steps.append({
                "rate_msgs_per_s": rate, "messages": res["messages"],
                "owed": res["attempted"], "arrived_share": arrived,
                "extra": res["checks"]["extra"][0],
                "duplicated": res["checks"]["duplicated"][0],
                "p50_ms": float(np.percentile(res["latency_ms"], 50)),
                "p95_ms": float(np.percentile(res["latency_ms"], 95)),
                "median_first_third_ms": med_first,
                "median_last_third_ms": med_last,
                "delivered_per_s": len(res["latency_ms"]) / args.step_seconds,
                "gen_late_p95_ms": float(np.percentile(late, 95)),
                "gen_unsent": got["unsent"], "holds": holds,
                "compiles": res["compiles"],
            })
            say(json.dumps(steps[-1]))
            if not holds:
                break
            rate *= args.factor
            step += 1
            reruns = 0
        rc = server.stop()
        held = [s["rate_msgs_per_s"] for s in steps if s["holds"]]
        knee = max(held) if held and not steps[-1]["holds"] else None
        out = {
            "cell": cell.name, "seed": args.seed,
            "device": {"platform": device["platform"],
                       "kind": device["device_kind"],
                       "count": device["device_count"]},
            "step_seconds": args.step_seconds, "factor": args.factor,
            "steps": steps, "knee_msgs_per_s": knee,
            "rate_msgs_per_s": 0.8 * knee if knee else None,
            "server_exit_code": rc,
        }
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(out, indent=1))
        say(f"knee {knee} msgs/s -> rate {out['rate_msgs_per_s']}")
        return 0
    finally:
        if workers is not None:
            workers.close()
        if server is not None:
            server.kill()
        shutil.rmtree(workdir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
