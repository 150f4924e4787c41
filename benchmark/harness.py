"""What a run is made of: the cell's files found by name, the server
child, the generator/receiver processes, the open-loop phases and the
comparison with the reference. `run.py` drives one measured run with
it; `sweep.py` drives a knee sweep in one server process.
"""

from __future__ import annotations

import importlib
import json
import subprocess
import sys
import time
from pathlib import Path

import numpy as np

from benchmark import wire
from benchmark.server import ROOT, RunFailed, Server

HERE = Path(__file__).resolve().parent
#: counters that must not move in a run (chip_smoke.ERROR_COUNTERS, PR 22)
ERROR_COUNTERS = (
    "messages.errors", "broadcast.send_errors", "zmq.recv_errors",
    "peers.evicted_send_failed", "tick.staging_fallbacks",
    "sweeper.remove_errors",
)
#: warm-up: the cell's own traffic in chunks, the first ones with a burst
#: on top (a backlog makes batches, and the program compiles a kernel
#: variant for every batch tier at first use), until a chunk compiles
#: nothing and loses nothing. A workload file's "warmup" overrides any.
WARMUP = {"chunk_s": 3.0, "drain_s": 1.5, "bursts": [16, 64, 256],
          "quiet_chunks": 1, "max_chunks": 20}
WINDOW_PHASE = 1


def say(*parts) -> None:
    print("[bench]", *parts, flush=True)


def load_json(kind: str, name: str) -> dict:
    path = HERE / kind / f"{name}.json"
    if not path.is_file():
        raise RunFailed(f"no {kind[:-1]} file {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for key, value in over.items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = merge(out[key], value)
        else:
            out[key] = value
    return out


class Cell:
    """A workload file, its configuration file and the modules their
    kinds name, found by name alone."""

    def __init__(self, name: str, rehearsal: bool):
        self.name = name
        self.workload = load_json("workloads", name)
        self.config = load_json("configs", self.workload["config"])
        if rehearsal:
            # a chip-less box holds a fraction of the deployment: the
            # files say which (never a measurement)
            self.config = merge(self.config, self.config.get("rehearsal", {}))
            self.workload = merge(self.workload,
                                  self.workload.get("rehearsal", {}))
        self.deployments = importlib.import_module(
            f"benchmark.deployments.{self.config['data']['kind']}")
        self.traffic = importlib.import_module(
            f"benchmark.traffic.{self.workload['traffic_kind']}")

    def deployment(self, seed: int):
        return self.deployments.Deployment(self.config["data"], seed)


class Workers:
    """The generator/receiver processes. Peers are dealt round-robin,
    so that senders and receivers of a crowded cube spread evenly."""

    def __init__(self, cell: Cell, deployment, server: Server, workdir: Path):
        self.workdir = workdir
        self.n = int(cell.config["generator_processes"])
        self.n_peers = n_peers = len(deployment.connected)
        self.owner = np.arange(n_peers) % self.n
        self.procs = []
        for w in range(self.n):
            spec = {
                "host": server.host, "server_port": server.zmq_port,
                "handshake_timeout_s": cell.config["handshake_timeout_s"],
                "worlds": deployment.names,
                "traffic_kind": cell.workload["traffic_kind"],
                "payload_bytes": cell.workload.get("payload_bytes", 0),
                **getattr(cell.traffic, "spec_extra", lambda d: {})(deployment),
                "peers": [{"k": int(k), "uuid": str(deployment.peer_uuid(k))}
                          for k in np.flatnonzero(self.owner == w)],
            }
            path = workdir / f"worker{w}.json"
            path.write_text(json.dumps(spec))
            self.procs.append(subprocess.Popen(
                [sys.executable, str(HERE / "worker.py"), str(path)],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
                cwd=ROOT))
        for p in self.procs:
            self._hear(p, "ready")
        if hasattr(cell.traffic, "setup"):      # e.g. entity registration
            cell.traffic.setup(cell, deployment, self, workdir)

    def _hear(self, proc, key: str):
        line = proc.stdout.readline()
        if not line:
            raise RunFailed(f"a generator process died (exit {proc.wait()})")
        msg = json.loads(line)
        if key not in msg:
            raise RunFailed(f"generator said {msg}, expected {key!r}")
        return msg[key]

    def _tell(self, proc, obj: dict) -> None:
        proc.stdin.write(json.dumps(obj) + "\n")
        proc.stdin.flush()

    def arm(self, tag: str, plan: dict) -> None:
        """Hand every process its senders' share of `plan`."""
        owner = self.owner[plan["sender"]]
        for w, proc in enumerate(self.procs):
            mine = np.flatnonzero(owner == w)
            path = self.workdir / f"{tag}.w{w}.plan.npz"
            np.savez(path, **{k: v[mine] for k, v in plan.items()})
            self._tell(proc, {"plan": str(path)})
        for proc in self.procs:
            self._hear(proc, "armed")

    def go(self, t0_ns: int, end_ns: int, final: bool = False) -> None:
        for proc in self.procs:
            self._tell(proc, {"go": t0_ns, "end_ns": end_ns, "final": final})

    def ask(self, orders: list, key: str) -> list:
        """One order a process; -> their answers under `key`."""
        for proc, order in zip(self.procs, orders):
            self._tell(proc, order)
        return [self._hear(proc, key) for proc in self.procs]

    def collect(self) -> dict:
        """Wait for every process; -> {"parts": what each one's Receiver
        took, "sent_late_ns", "unsent"}."""
        parts = []
        for p in self.procs:
            with np.load(self._hear(p, "done")) as f:
                parts.append({k: f[k] for k in f.files})
        return {
            "parts": parts,
            "sent_late_ns": np.concatenate([p["sent_late_ns"] for p in parts]),
            "unsent": int(sum(int(p["unsent"]) for p in parts)),
        }

    def close(self) -> None:
        for proc in self.procs:
            if proc.poll() is None:
                try:
                    self._tell(proc, {"quit": True})
                except OSError:
                    pass
        for proc in self.procs:
            try:
                proc.wait(timeout=20)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()


def chunk_plan(cell: Cell, deployment, seed: int, phase: int, seconds: float,
               rate=None, burst: int = 0) -> dict:
    """`seconds` of the cell's traffic (at `rate`, if given, instead of
    the file's), with `burst` more messages of the same mix all due one
    second in; message ids stamped with the phase."""
    workload = cell.workload if rate is None else dict(cell.workload,
                                                       rate=rate)
    plan = cell.traffic.plan(workload, deployment, seed, seconds, phase)
    if burst:
        extra = cell.traffic.plan(
            dict(cell.workload, rate=burst, arrival="even"), deployment,
            seed, 1.0, phase + 1000)
        extra["offset_ns"] = np.full(len(extra["offset_ns"]), int(1e9))
        plan = {k: np.concatenate([plan[k], extra[k]]) for k in plan}
        order = np.argsort(plan["offset_ns"], kind="stable")
        plan = {k: v[order] for k, v in plan.items()}
    plan["msg_id"] = wire.make_ids(phase, len(plan["offset_ns"]))
    return plan


def run_chunk(cell: Cell, deployment, server: Server, workers: Workers,
              plan: dict, phase: int, seconds: float, drain_s: float) -> dict:
    """Send one plan from now, wait for its receipts, hold them to the
    reference. -> judge_phase's result, with the compiles it saw."""
    compiles = server.metrics()["gauges"]["device"]["compiles"]
    workers.arm(f"phase{phase}", plan)
    t_go = time.monotonic_ns() + int(0.2e9)
    workers.go(t_go, t_go + int((seconds + drain_s) * 1e9))
    got = workers.collect()
    res = judge_phase(cell, deployment, plan, got, phase, t_go)
    res["compiles"] = (server.metrics()["gauges"]["device"]["compiles"]
                       - compiles)
    res["got"], res["t_go"] = got, t_go
    return res


def warm_up(cell: Cell, deployment, server: Server, workers: Workers,
            seed: int, rate=None, bursts=None, first_phase: int = 100,
            max_chunks=None) -> tuple[int, int]:
    """Warm every shape the cell's traffic reaches. -> (chunks it took,
    answers that were wrong on the way: they count against `correct`).
    `bursts` overrides the workload file's ladder ([] settles the server
    again after a disturbance, such as a profiler capture), `max_chunks`
    the file's cap. A warm-up that reaches the cap without coming out
    quiet says so and the window opens all the same: the cap bounds
    set-up, and what the window's deliveries are is judged on their own."""
    spec = merge(WARMUP, cell.workload.get("warmup", {}))
    bursts = list(spec["bursts"] if bursts is None else bursts)
    cap = int(spec["max_chunks"] if max_chunks is None else max_chunks)
    quiet, wrong_total = 0, 0
    for chunk in range(cap):
        burst = bursts.pop(0) if bursts else 0
        phase = first_phase + chunk
        plan = chunk_plan(cell, deployment, seed, phase, spec["chunk_s"],
                          rate, burst)
        res = run_chunk(cell, deployment, server, workers, plan, phase,
                        spec["chunk_s"], spec["drain_s"])
        lat = res["latency_ms"]
        say(f"warm-up chunk {chunk}: burst {burst}, {res['compiles']} "
            f"compiles, {res['checks']['missing'][0]} of {res['attempted']} "
            f"deliveries "
            f"not in yet, p50 {np.median(lat) if len(lat) else -1:.1f} ms")
        wrong = {k: v for k, (v, limit) in res["checks"].items()
                 if v > limit and k != "missing"}
        if wrong:
            say(f"warm-up chunk {chunk} delivered wrongly: {wrong}")
            wrong_total += sum(wrong.values())
        missing = res["checks"]["missing"][0]
        clean = not burst and not res["compiles"] and not missing
        quiet = quiet + 1 if clean else 0
        if quiet >= int(spec["quiet_chunks"]):
            return chunk + 1, wrong_total
    say(f"the server still compiles or lags after {cap} warm-up chunks: "
        "the window opens all the same")
    return cap, wrong_total


def judge_phase(cell: Cell, deployment, plan: dict, got: dict, phase: int,
                t0_ns: int, dtype=np.float64) -> dict:
    """Hold one phase's receipts to the traffic kind's reference."""
    return cell.traffic.judge(plan, got["parts"], deployment, phase, t0_ns,
                              dtype)


def moved_errors(before: dict, after: dict) -> dict:
    return {name: after["counters"].get(name, 0)
            - before["counters"].get(name, 0) for name in ERROR_COUNTERS
            if after["counters"].get(name, 0) != before["counters"].get(name, 0)}


def sleep_until(t_ns: int) -> None:
    left = (t_ns - time.monotonic_ns()) / 1e9
    if left > 0:
        time.sleep(left)
