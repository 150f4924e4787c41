"""Stand-alone probe of a flush's ZeroMQ write pass (ISSUE 33, step 0).

    python -m tools.zmq_pass_probe [--peers 512] [--passes 110] [--threads 1,4]
    python -m tools.zmq_pass_probe --calibrate
    python -m tools.zmq_pass_probe --watch PID [--seconds 5]

The first form dials ``--peers`` PUSH sockets of ONE context to as many
PULL sockets in four other processes over loopback and, every 50 ms,
writes 4 and then 13 frames of 100 B to each: once in the Python loop
the per-peer closure of ``transports/zeromq.py`` runs (a shadow socket's
``send(payload, DONTWAIT)``), once in the native pass
(``transports/zmq_pass.py``). It prints a JSON line a mode: the pass's
time, and what the kernel counted a pass. ``wakes``: ``write`` calls of
the sending thread (``syscw`` of ``/proc/<pid>/task/<tid>/io``; a
``zmq_send`` that finds the I/O thread asleep writes its mailbox's
eventfd, and TCP sends go through ``sendto``, which is not counted
there). ``io_sleeps``: voluntary context switches of the thread named
``ZMQbg/IO/0``. Neither needs strace. Needs no accelerator and imports
no jax.

``--calibrate`` prints what this host's kernel charges for the calls a
``zmq_send`` can make (us, p10 / p50 / p90): a trivial call, a ``poll``
and a write on an eventfd nobody waits for, and the SENDER's cost of a
write that wakes a thread blocked in ``epoll_wait`` on it (the I/O
thread's wake). And what the clocks beneath the spans are worth here
(ISSUE 38, step 0): the cost of one ``time.thread_time_ns()``, of one
``resource.getrusage(RUSAGE_THREAD)`` and of one ``perf_counter_ns``,
and four readings that say whether the first two ARE a thread's CPU
clock (``thread_cpu_ms``): 100 ms of a Python busy loop (wants
95-105), ``time.sleep(0.1)`` (wants < 2), a thread blocked in
``Event.wait`` while another spins 100 ms (wants < 2), and two threads
spinning pure Python through 200 ms of wall (want ~100 each: the GIL),
and the smallest step the clock makes (its grain).

The last form reads the same two counts from a running server for
``--seconds`` and prints them a second: the wakes a flush of the
serving process.
"""

from __future__ import annotations

import argparse
import json
import multiprocessing as mp
import os
import resource
import select
import statistics
import threading
import time

import zmq

from worldql_server_tpu.transports import zmq_pass

INTERVAL_S = 0.05
FRAME = b"x" * 100


def thread_counts(pid: int) -> dict[int, dict]:
    """tid -> {comm, write calls, voluntary context switches}."""
    out = {}
    base = f"/proc/{pid}/task"
    for tid in os.listdir(base):
        try:
            with open(f"{base}/{tid}/comm") as f:
                comm = f.read().strip()
            with open(f"{base}/{tid}/status") as f:
                status = f.read()
            vol = int(status.split("voluntary_ctxt_switches:")[1].split()[0])
            try:
                with open(f"{base}/{tid}/io") as f:
                    io = f.read()
                syscw = int(io.split("syscw:")[1].split()[0])
                syscr = int(io.split("syscr:")[1].split()[0])
            except (OSError, IndexError):
                syscw = syscr = None  # no task I/O accounting here
        except (OSError, IndexError):
            continue             # the thread ended between two reads
        out[int(tid)] = {"comm": comm, "syscw": syscw, "syscr": syscr,
                         "vol": vol}
    return out


def _delta(before: dict, after: dict, tid: int, key: str):
    a, b = before.get(tid), after.get(tid)
    if a is None or b is None or a[key] is None or b[key] is None:
        return None
    return b[key] - a[key]


def _per(count, n):
    return None if count is None else count / n


def _io_tid(counts: dict) -> int | None:
    tids = [t for t, c in counts.items() if c["comm"].startswith("ZMQbg/IO")]
    return tids[0] if tids else None


def _receiver(n_socks: int, conn) -> None:
    """``n_socks`` PULL sockets of one process, drained as they become
    readable. A frame's first 8 bytes are the clock of its pass's start
    (``CLOCK_MONOTONIC``, shared by the processes of one host): the
    receiver keeps how long after it each frame was in hand."""
    ctx = zmq.Context()
    poller = zmq.Poller()
    ports = []
    for _ in range(n_socks):
        s = ctx.socket(zmq.PULL)
        ports.append(s.bind_to_random_port("tcp://127.0.0.1"))
        poller.register(s, zmq.POLLIN)
    conn.send(ports)
    got = 0
    lags_us: list[float] = []
    clock = time.monotonic_ns

    def drain(timeout_ms: int) -> None:
        nonlocal got
        for s, _ in poller.poll(timeout_ms):
            try:
                while True:
                    frame = s.recv(zmq.DONTWAIT)
                    lags_us.append(
                        (clock() - int.from_bytes(frame[:8], "little")) / 1e3)
                    got += 1
            except zmq.Again:
                pass

    while True:
        if conn.poll(0):
            if conn.recv() == "lags":       # a mode ended: hand them over
                conn.send(lags_us)
                lags_us = []
                continue
            # drain what is still on the way, then report
            deadline = time.monotonic() + 1.0
            while time.monotonic() < deadline:
                drain(50)
            conn.send(got)
            ctx.destroy(linger=0)
            return
        drain(20)


def _python_pass(sends, frame: bytes, frames_a_peer: int,
                 firsts: list[float]) -> int:
    """The per-peer closure's loop; ``firsts`` gains the time of each
    peer's FIRST send (the one that finds the socket idle)."""
    taken = 0
    clock = time.perf_counter
    for send in sends:
        try:
            t0 = clock()
            send(frame, zmq.DONTWAIT)
            firsts.append(clock() - t0)
            taken += 1
            for _ in range(frames_a_peer - 1):
                send(frame, zmq.DONTWAIT)
                taken += 1
        except zmq.Again:
            pass
    return taken


def probe(peers: int, passes: int, threads: list[int]) -> list[dict]:
    """``threads``: the shares to cut the native pass into, one mode
    each; 0 = what this host's system-call cost gives it."""
    native = zmq_pass.load()
    own = native.threads if native is not None else 1
    procs = []
    ports: list[int] = []
    per = -(-peers // 4)
    for k in range(4):
        n = min(per, peers - k * per)
        here, there = mp.Pipe()
        p = mp.Process(target=_receiver, args=(n, there), daemon=True)
        p.start()
        procs.append((p, here))
    for _, here in procs:
        ports.extend(here.recv())
    ctx = zmq.Context()
    pushes = []
    for port in ports:
        s = ctx.socket(zmq.PUSH)
        s.setsockopt(zmq.LINGER, 0)
        s.connect(f"tcp://127.0.0.1:{port}")
        pushes.append(s)
    time.sleep(1.0)              # every connection up before a pass
    sends = [zmq.Socket.shadow(s).send for s in pushes]
    handles = [s.underlying for s in pushes]
    pid, tid = os.getpid(), threading.get_native_id()
    lines = []
    sent = 0
    for frames_a_peer in (4, 13):
        frames = [[0] * frames_a_peer] * peers
        for mode, shares in [("python", 1)] + [
                ("native", t or own) for t in threads]:
            if mode == "native":
                if native is None:
                    lines.append({"mode": mode, "skipped": "no symbol"})
                    continue
                native.threads = shares
            times = []
            firsts: list[float] = []
            before = thread_counts(pid)
            for _ in range(passes):
                frame = time.monotonic_ns().to_bytes(8, "little") + FRAME[8:]
                t0 = time.perf_counter()
                if mode == "python":
                    taken = _python_pass(sends, frame, frames_a_peer, firsts)
                else:
                    taken = native([frame], handles, frames)[0]
                times.append((time.perf_counter() - t0) * 1e3)
                assert taken == peers * frames_a_peer, (mode, taken)
                sent += taken
                time.sleep(INTERVAL_S)
            after = thread_counts(pid)
            time.sleep(0.2)      # the last pass's frames are in hand
            lags: list[float] = []
            for _, here in procs:
                here.send("lags")
            for _, here in procs:
                lags.extend(here.recv())
            lag_q = statistics.quantiles(lags, n=20)
            io = _io_tid(after)
            wakes = _delta(before, after, tid, "syscw")
            reads = _delta(before, after, tid, "syscr")
            switches = _delta(before, after, tid, "vol")
            sleeps = _delta(before, after, io, "vol") if io else None
            io_writes = _delta(before, after, io, "syscw") if io else None
            q = statistics.quantiles(times, n=10)
            lines.append({
                "mode": mode, "peers": peers,
                "threads": shares,
                "frames_a_peer": frames_a_peer, "passes": passes,
                "pass_ms_p50": statistics.median(times),
                "pass_ms_p10": q[0], "pass_ms_p90": q[-1],
                "pass_ms_max": max(times),
                "slow_passes": sum(
                    t > 2 * statistics.median(times) for t in times),
                # pass start -> the frame in its receiver's hand
                "arrival_ms_p50": statistics.median(lags) / 1e3,
                "arrival_ms_p95": lag_q[-1] / 1e3,
                "arrival_ms_max": max(lags) / 1e3,
                "wakes_a_pass": _per(wakes, passes),
                "reads_a_pass": _per(reads, passes),
                "sender_sleeps_a_pass": _per(switches, passes),
                "first_send_us_p50":
                    statistics.median(firsts) * 1e6 if firsts else None,
                "io_sleeps_a_pass": _per(sleeps, passes),
                "io_writes_a_pass": _per(io_writes, passes),
            })
            print(json.dumps(lines[-1]), flush=True)
    got = 0
    for _, here in procs:
        here.send("stop")
    for p, here in procs:
        got += here.recv()
        p.join(5)
    ctx.destroy(linger=0)
    print(json.dumps({"sent": sent, "received": got}), flush=True)
    assert got == sent, (got, sent)
    return lines


def calibrate() -> dict:
    clock = time.perf_counter

    def deciles_us(call, n=3000) -> dict:
        took = []
        for _ in range(n):
            t0 = clock()
            call()
            took.append((clock() - t0) * 1e6)
        q = statistics.quantiles(took, n=10)
        return {"p10": q[0], "p50": statistics.median(took), "p90": q[-1]}

    one = (1).to_bytes(8, "little")
    quiet = os.eventfd(0, os.EFD_NONBLOCK)
    waited = os.eventfd(0, os.EFD_NONBLOCK)
    poller = select.poll()
    poller.register(quiet, select.POLLIN)
    out = {"clock_pair": deciles_us(lambda: None),
           "getppid": deciles_us(os.getppid),
           "poll_0": deciles_us(lambda: poller.poll(0)),
           "eventfd_write": deciles_us(lambda: os.write(quiet, one))}
    done = threading.Event()

    def waiter() -> None:
        ep = select.epoll()
        ep.register(waited, select.EPOLLIN)
        while not done.is_set():
            if ep.poll(0.2):
                os.read(waited, 8)
        ep.close()

    thread = threading.Thread(target=waiter, daemon=True)
    thread.start()
    time.sleep(0.2)

    def wake() -> None:
        os.write(waited, one)

    took = []
    for _ in range(400):
        t0 = clock()
        wake()
        took.append((clock() - t0) * 1e6)
        time.sleep(0.001)        # the waiter is back in epoll_wait
    done.set()
    thread.join(2)
    q = statistics.quantiles(took, n=10)
    out["eventfd_write_that_wakes"] = {
        "p10": q[0], "p50": statistics.median(took), "p90": q[-1]}
    for fd in (quiet, waited):
        os.close(fd)
    out["perf_counter_ns"] = deciles_us(time.perf_counter_ns)
    out["thread_time_ns"] = deciles_us(time.thread_time_ns)
    out["getrusage_thread"] = deciles_us(_rusage_thread_ns)
    print(json.dumps({"calibrate_us": out}), flush=True)
    sanity = {"thread_time_ns": thread_cpu_sanity(time.thread_time_ns),
              "getrusage_thread": thread_cpu_sanity(_rusage_thread_ns)}
    print(json.dumps({"thread_cpu_ms": sanity}), flush=True)
    return out


def _rusage_thread_ns() -> int:
    use = resource.getrusage(resource.RUSAGE_THREAD)
    return int((use.ru_utime + use.ru_stime) * 1e9)


def thread_cpu_sanity(cpu_ns) -> dict:
    """What ``cpu_ns`` (a clock of the CALLING thread's CPU time) reads,
    in ms, over four stretches whose answer is known (module docstring):
    a wall clock reads 100 / 100 / 100 / 200 + 200, a dead one zeros."""

    def spin(ms: float) -> None:
        end = time.perf_counter() + ms / 1e3
        while time.perf_counter() < end:
            pass

    def read(work, *args) -> float:
        c0 = cpu_ns()
        work(*args)
        return (cpu_ns() - c0) / 1e6

    out = {"busy_100ms": read(spin, 100.0),
           "sleep_100ms": read(time.sleep, 0.1)}
    readings: dict[str, float] = {}

    def on_thread(key: str, work, *args) -> threading.Thread:
        def body() -> None:
            readings[key] = read(work, *args)
        thread = threading.Thread(target=body)
        thread.start()
        return thread

    gate = threading.Event()
    blocked = on_thread("blocked_beside_a_spinner", gate.wait)
    spin(100.0)
    gate.set()
    blocked.join()
    pair = [on_thread(f"two_spinners_200ms_wall.{i}", spin, 200.0)
            for i in (0, 1)]
    for thread in pair:
        thread.join()
    out.update(readings)
    # the clock's grain: the smallest step it makes while this thread
    # spins (a kernel that keeps CPU time by sampling steps by its tick)
    steps, last, end = [], cpu_ns(), time.perf_counter() + 0.1
    while time.perf_counter() < end:
        now = cpu_ns()
        if now != last:
            steps.append((now - last) / 1e6)
            last = now
    out["smallest_step"] = min(steps, default=0.0)
    return {key: round(ms, 4) for key, ms in out.items()}


def watch(pid: int, seconds: float) -> dict:
    before = thread_counts(pid)
    t0 = time.monotonic()
    time.sleep(seconds)
    after = thread_counts(pid)
    dt = time.monotonic() - t0
    out = {"pid": pid, "seconds": dt, "threads": {}}
    for tid, c in after.items():
        wr = _delta(before, after, tid, "syscw")
        rd = _delta(before, after, tid, "syscr")
        vol = _delta(before, after, tid, "vol")
        if (wr or 0) + (vol or 0) < 10 * dt:
            continue             # a quiet thread
        out["threads"][f"{c['comm']}:{tid}"] = {
            "main": tid == pid,
            "write_calls_per_s": _per(wr, dt),
            "read_calls_per_s": _per(rd, dt),
            "voluntary_switches_per_s": _per(vol, dt),
        }
    print(json.dumps(out), flush=True)
    return out


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(prog="python -m tools.zmq_pass_probe")
    parser.add_argument("--peers", type=int, default=512)
    parser.add_argument("--passes", type=int, default=110)
    parser.add_argument("--threads", default="0",
                        help="shares of the native pass, a mode each, as "
                             "in 1,4,8 (0: what this host's system-call "
                             "cost gives it)")
    parser.add_argument("--calibrate", action="store_true")
    parser.add_argument("--watch", type=int, default=0, metavar="PID")
    parser.add_argument("--seconds", type=float, default=5.0)
    args = parser.parse_args(argv)
    if args.calibrate:
        calibrate()
    elif args.watch:
        watch(args.watch, args.seconds)
    else:
        probe(args.peers, args.passes,
              [int(t) for t in args.threads.split(",")])
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
