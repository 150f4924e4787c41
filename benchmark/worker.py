"""One generator/receiver process: a slice of the cell's peers, each
with a PUSH socket to the server and a PULL socket of its own, all on
one shared `zmq.Context`. Never imports jax or the program.

Driven by the harness over stdin/stdout, one JSON object a line:

    argv[1]            a spec file: server address, this worker's peers
    -> {"ready": n}    every peer's handshake was echoed
    <- {"plan": path}  messages to send, due times as offsets (ns)
    -> {"armed": n}    plan loaded, templates built
    <- {"go": t, "end_ns": t, "final": bool}   the offsets' origin and when to stop
                       receiving, on CLOCK_MONOTONIC, which every
                       process of the host shares
    -> {"done": path}  what the traffic kind's Receiver took from the
                       frames, as an .npz beside the plan
    <- {"prepare": path} -> {"prepared": {...}}   set-up that a traffic
                       kind does over the wire (entity registration)
    <- {"quit": true}  close the sockets and exit
"""

from __future__ import annotations

import importlib
import json
import sys
import time
import uuid
from pathlib import Path

import numpy as np
import zmq

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from benchmark import wire  # noqa: E402

SEND_BURST = 64             # sends, then receipts, then sends again
RECV_BURST = 64             # frames taken from one socket in a row


def say(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def hear() -> dict:
    line = sys.stdin.readline()
    if not line:
        raise SystemExit("harness went away")
    return json.loads(line)


class Peers:
    def __init__(self, spec: dict):
        self.ctx = zmq.Context(io_threads=1)
        self.host = spec["host"]
        self.k = [p["k"] for p in spec["peers"]]
        self.uuid = [uuid.UUID(p["uuid"]) for p in spec["peers"]]
        self.push, self.pull = [], []
        self.poller = zmq.Poller()
        self.sock_k = {}
        for k in self.k:
            pull = self.ctx.socket(zmq.PULL)
            pull.setsockopt(zmq.RCVHWM, 100000)
            port = pull.bind_to_random_port(f"tcp://{self.host}")
            push = self.ctx.socket(zmq.PUSH)
            push.setsockopt(zmq.LINGER, 0)
            push.connect(f"tcp://{self.host}:{spec['server_port']}")
            self.push.append(push)
            self.pull.append((pull, port))
            self.poller.register(pull, zmq.POLLIN)
            self.sock_k[pull] = k

    def handshake(self, timeout: float) -> None:
        """The wire's connect: Handshake with the PULL address as the
        parameter, echoed on that socket once the server has dialled
        back. Restored rows belong to the UUID, so they are this peer's
        from the echo on."""
        for push, (_, port), uid in zip(self.push, self.pull, self.uuid):
            push.send(wire.encode(wire.HANDSHAKE, uid,
                                  parameter=f"{self.host}:{port}"))
        waiting = {pull for pull, _ in self.pull}
        deadline = time.monotonic() + timeout
        while waiting:
            left = deadline - time.monotonic()
            if left <= 0:
                raise SystemExit(f"{len(waiting)} handshakes not echoed")
            for sock, _ in self.poller.poll(min(left, 1.0) * 1e3):
                frame = sock.recv()
                if sock in waiting:
                    msg = wire.parse(frame)
                    if msg["instruction"] == wire.HANDSHAKE:
                        if (msg["parameter"] or "").startswith("retry-after"):
                            raise SystemExit("handshake refused: "
                                             + msg["parameter"])
                        waiting.discard(sock)

    def close(self) -> None:
        for push in self.push:
            push.close(linger=0)
        for pull, _ in self.pull:
            pull.close(linger=0)
        self.ctx.term()


def run_plan(peers: Peers, spec: dict, traffic, receiver,
             plan_path: str) -> str:
    plan = np.load(plan_path)
    offset = plan["offset_ns"].tolist()
    push_of = dict(zip(peers.k, peers.push))
    socks = [push_of[k] for k in plan["sender"].tolist()]
    # the traffic kind builds its own frames: frame(i, due_ns) -> bytes
    frame_of = traffic.framer(plan, dict(zip(peers.k, peers.uuid)), spec)
    n = len(offset)
    say({"armed": n})
    order = hear()
    t0, end_ns = int(order["go"]), int(order["end_ns"])
    final = bool(order.get("final"))
    due_l = [t0 + o for o in offset]
    sent_late = np.zeros(n, np.int64)
    now_ns, poll, sock_k = time.monotonic_ns, peers.poller.poll, peers.sock_k
    again, noblock, on_frame = zmq.Again, zmq.NOBLOCK, receiver.on_frame
    i = 0
    while True:
        now = now_ns()
        if now >= end_ns:
            break
        burst = 0
        while i < n and due_l[i] <= now and burst < SEND_BURST:
            try:
                socks[i].send(frame_of(i, due_l[i]), noblock)
            except again:
                break           # the server is not reading: try again
            sent_late[i] = now_ns() - due_l[i]
            i += 1
            burst += 1
        if i < n:
            wait_ms = 0 if due_l[i] - now < 1_500_000 else 1
        else:
            wait_ms = 5
        for sock, _ in poll(wait_ms):
            k = sock_k[sock]
            for _ in range(RECV_BURST):
                try:
                    frame = sock.recv(noblock)
                except again:
                    break
                on_frame(k, now_ns(), frame)
    out = plan_path.replace(".plan.npz", ".got.npz")
    np.savez(out, sent_late_ns=sent_late[:i], unsent=np.int64(n - i),
             **receiver.take(final))
    return out


def main() -> None:
    spec = json.loads(Path(sys.argv[1]).read_text())
    traffic = importlib.import_module(
        f"benchmark.traffic.{spec['traffic_kind']}")
    peers = Peers(spec)
    receiver = traffic.Receiver(spec)     # lives as long as the peers do
    try:
        peers.handshake(float(spec["handshake_timeout_s"]))
        say({"ready": len(peers.k)})
        while True:
            order = hear()
            if order.get("quit"):
                break
            if "prepare" in order:      # the traffic kind's own set-up
                say({"prepared": traffic.prepare(peers, spec, receiver,
                                                 order)})
                continue
            say({"done": run_plan(peers, spec, traffic, receiver,
                                  order["plan"])})
    finally:
        peers.close()


if __name__ == "__main__":
    main()
