"""Traffic of kind `entity_walk`: every peer sends, each `period_ms`,
one LocalMessage that moves `share` of the population's entities (its
own part of them) one step of 1/8 m inside their cube, and all of its
probe entities to a position that encodes the update's sequence number.

A "delivery" here is a probe update reflected at a watching peer: the
latency sample is the due time of the update -> receipt, at a peer that
must see the probe, of the first neighbour-stream frame that carries
the probe at that update's position or a later one (updates that one
tick coalesces are reflected by the same frame). After the window every
peer's ledger (frames replayed by `benchmark/replay.py`) must equal the
reference's neighbour set with every position equal to the last update
sent.

A workload file holds: senders ("all"), period_ms, share, drain_s, and
`settle_timeout_s` for the registration during set-up.
"""

from __future__ import annotations

import json
import time
import uuid

import numpy as np
import zmq

from benchmark import wire
from benchmark.deployments.entity_swarm import HI, LO, STEP
from benchmark.replay import ReplayClient

BASE = HI - LO                  # digits of a probe's sequence number
REGISTER_BATCH = 400            # entities a registration message


def seq_to_eighths(seq: np.ndarray) -> np.ndarray:
    seq = np.asarray(seq, np.int64)
    return np.stack([seq % BASE, (seq // BASE) % BASE, seq // BASE ** 2],
                    axis=-1) + LO


def eighths_to_seq(e: np.ndarray) -> np.ndarray:
    d = np.asarray(e, np.int64) - LO
    return d[..., 0] + BASE * d[..., 1] + BASE ** 2 * d[..., 2]


def plan(workload: dict, deployment, seed: int, seconds: float,
         phase: int) -> dict:
    """The schedule of one phase. Walks `deployment.eighths` forward:
    phases are planned in the order they are sent."""
    d = deployment
    rng = np.random.default_rng([seed, 0x77616C6B, phase])
    period = float(workload["period_ms"]) / 1e3
    if "rate" in workload and workload.get("rate_overrides_period"):
        period = d.n_peers / float(workload["rate"])
    ticks = int(round(seconds / period))
    peers = d.n_peers
    moves = max(int(round(float(workload["share"]) * d.n / peers)), 0)
    is_probe = np.zeros(d.n, bool)
    is_probe[d.probes] = True
    walkers = [np.flatnonzero((d.owner == k) & ~is_probe) for k in range(peers)]
    probe_slots = [np.flatnonzero(d.owner[d.probes] == k) for k in range(peers)]
    width = moves + max((len(p) for p in probe_slots), default=0)
    n = ticks * peers
    ent = np.full((n, width), -1, np.int64)
    pos = np.zeros((n, width, 3))
    probe_j = np.full((n, width), -1, np.int64)
    probe_seq = np.zeros((n, width), np.int64)
    for t in range(ticks):
        for k in range(peers):
            i = t * peers + k
            if moves and len(walkers[k]):
                who = rng.choice(walkers[k], min(moves, len(walkers[k])),
                                 replace=False)
                axis = rng.integers(0, 3, len(who))
                step = rng.choice([-1, 1], len(who))
                d.eighths[who, axis] = np.clip(
                    d.eighths[who, axis] + step, LO, HI - 1)
                ent[i, :len(who)] = who
            slots = probe_slots[k]
            if len(slots):
                d.probe_seq[slots] += 1
                d.eighths[d.probes[slots]] = seq_to_eighths(d.probe_seq[slots])
                lo = width - len(slots)
                ent[i, lo:] = d.probes[slots]
                probe_j[i, lo:] = slots
                probe_seq[i, lo:] = d.probe_seq[slots]
            live = ent[i] >= 0
            pos[i, live] = (d.corner[ent[i, live]]
                            + d.eighths[ent[i, live]] * STEP)
    offset = ((np.repeat(np.arange(ticks), peers)
               + (np.tile(np.arange(peers), ticks) + 0.5) / peers)
              * period * 1e9).astype(np.int64)
    return {"offset_ns": offset,
            "sender": np.tile(np.arange(peers), ticks).astype(np.int64),
            "ent": ent, "position": pos, "probe_j": probe_j,
            "probe_seq": probe_seq}


def spec_extra(deployment) -> dict:
    """What a generator process needs beyond its peers."""
    d = deployment
    return {
        "entity_uuid_hi": int(d.entity_uuid(0).int >> 64),
        "probe_uuids": [str(d.entity_uuid(int(i))) for i in d.probes],
    }


def _uuid(hi: int, i: int) -> str:
    return str(uuid.UUID(int=(hi << 64) | (i + 1)))


def framer(plan_: dict, uuid_of: dict, spec: dict):
    world, hi = spec["worlds"][0], spec["entity_uuid_hi"]
    sender = plan_["sender"].tolist()
    ent, pos = plan_["ent"], plan_["position"]

    def frame(i: int, due_ns: int) -> bytes:
        live = np.flatnonzero(ent[i] >= 0)
        return wire.encode(
            wire.LOCAL_MESSAGE, uuid_of[sender[i]], world,
            entities=[(_uuid(hi, int(ent[i, j])), pos[i, j].tolist(), world,
                       None) for j in live])

    return frame


class Receiver:
    """Keeps every frame with its receipt time while a plan runs; parses
    them (ledgers, probe sightings) when the plan is over."""

    def __init__(self, spec: dict):
        self.clients = {p["k"]: ReplayClient() for p in spec["peers"]}
        self.probe_of = {u: j for j, u in enumerate(spec["probe_uuids"])}
        self.hi = spec["entity_uuid_hi"]
        self.frames: list = []
        self.other = 0

    def on_frame(self, k: int, at_ns: int, frame: bytes) -> None:
        self.frames.append((k, at_ns, frame))

    def digest(self) -> list:
        """Parse what is stored. -> probe sightings (k, probe, seq, at)."""
        seen = []
        probe_of = self.probe_of
        for k, at, frame in self.frames:
            msg = wire.parse(frame)
            if not self.clients[k].apply(msg):
                if msg["instruction"] not in wire.PROTOCOL_FRAMES:
                    self.other += 1
                continue
            for e in msg["entities"]:
                j = probe_of.get(e["uuid"])
                if j is not None and not (e["flex"] is not None
                                          and len(e["flex"]) < 12):
                    seen.append((k, j, e["position"], at))
        self.frames = []
        return seen

    def take(self, final: bool = False) -> dict:
        seen = self.digest()
        out = {
            "seen_peer": np.asarray([s[0] for s in seen], np.int64),
            "seen_probe": np.asarray([s[1] for s in seen], np.int64),
            "seen_pos": np.asarray([s[2] for s in seen], np.float64
                                   ).reshape(-1, 3),
            "seen_at_ns": np.asarray([s[3] for s in seen], np.int64),
            "other_frames": np.int64(self.other),
            "deltas_refused": np.int64(sum(
                c.deltas_refused for c in self.clients.values())),
            "gaps_seen": np.int64(sum(
                c.gaps_seen for c in self.clients.values())),
        }
        self.other = 0
        if final:                   # the ledgers, whole
            peer, ent, pos = [], [], []
            for k, c in self.clients.items():
                for world in c.worlds.values():
                    for u, p in world.items():
                        peer.append(k)
                        ent.append((uuid.UUID(u).int & (2 ** 64 - 1)) - 1)
                        pos.append(p)
            out.update(ledger_peer=np.asarray(peer, np.int64),
                       ledger_ent=np.asarray(ent, np.int64),
                       ledger_pos=np.asarray(pos, np.float64).reshape(-1, 3))
        return out


def prepare(peers, spec: dict, receiver: Receiver, order: dict) -> dict:
    """Set-up in a generator process: register this process's peers'
    entities over the wire, then take frames in until every peer's
    ledger has the size the reference gives it."""
    todo = json.loads(open(order["prepare"]).read())
    world = spec["worlds"][0]
    push_of = dict(zip(peers.k, peers.push))
    uuid_of = dict(zip(peers.k, peers.uuid))
    for k in peers.k:
        mine, pos = todo["entities"][str(k)], todo["positions"][str(k)]
        for lo in range(0, len(mine), REGISTER_BATCH):
            push_of[k].send(wire.encode(
                wire.LOCAL_MESSAGE, uuid_of[k], world,
                entities=[(_uuid(receiver.hi, i), p, world, None)
                          for i, p in zip(mine[lo:lo + REGISTER_BATCH],
                                          pos[lo:lo + REGISTER_BATCH])]))
    want = {int(k): n for k, n in todo["ledger_sizes"].items()}
    deadline = time.monotonic() + float(todo["timeout_s"])

    def settled() -> bool:
        return all(
            sum(len(w) for w in receiver.clients[k].worlds.values()) == n
            for k, n in want.items())

    while not settled():
        if time.monotonic() > deadline:
            return {"settled": False}
        for sock, _ in peers.poller.poll(200):
            k = peers.sock_k[sock]
            while True:
                try:
                    frame = sock.recv(zmq.NOBLOCK)
                except zmq.Again:
                    break
                receiver.on_frame(k, time.monotonic_ns(), frame)
        receiver.digest()
    return {"settled": True}


def setup(cell, deployment, workers, workdir) -> None:
    """Harness side of the set-up: hand every process its peers'
    entities, wait until every ledger has settled."""
    d = deployment
    pos = d.pos
    sizes = {k: int(len(d.visible_to(k))) for k in range(d.n_peers)}
    orders = []
    for w in range(workers.n):
        ks = np.flatnonzero(workers.owner == w)
        todo = {
            "entities": {str(k): np.flatnonzero(d.owner == k).tolist()
                         for k in ks},
            "positions": {str(k): pos[d.owner == k].tolist() for k in ks},
            "ledger_sizes": {str(k): sizes[int(k)] for k in ks},
            "timeout_s": cell.workload["settle_timeout_s"],
        }
        path = workdir / f"prepare.w{w}.json"
        path.write_text(json.dumps(todo))
        orders.append({"prepare": str(path)})
    for answer in workers.ask(orders, "prepared"):
        if not answer.get("settled"):
            raise RuntimeError("the peers' ledgers did not settle in "
                               f"{cell.workload['settle_timeout_s']} s")


def judge(plan_: dict, parts: list, deployment, phase: int, t0_ns: int,
          dtype=np.float64) -> dict:
    """Hold the probe sightings (and, after the last phase, the
    ledgers) to the reference. `dtype` is the precision the reference's
    positions are held in: float64 is exact; the lower-precision control
    (bfloat16-like rounding of positions) must fail."""
    d = deployment
    seen = {k: np.concatenate([p[k] for p in parts])
            for k in ("seen_peer", "seen_probe", "seen_pos", "seen_at_ns")}
    corner = d.corner[d.probes[seen["seen_probe"]]]
    eighths = np.rint((seen["seen_pos"] - corner) / STEP)
    off_grid = int((np.abs((seen["seen_pos"] - corner) / STEP - eighths)
                    > 0).sum())
    seq_seen = eighths_to_seq(eighths)
    # updates of this phase, by probe
    live = plan_["probe_j"] >= 0
    up_probe = plan_["probe_j"][live]
    up_seq = plan_["probe_seq"][live]
    up_due = (t0_ns + np.broadcast_to(
        plan_["offset_ns"][:, None], live.shape)[live])
    lat, attempted, missing, early = [], 0, 0, 0
    order = np.lexsort((seen["seen_at_ns"], seen["seen_probe"],
                        seen["seen_peer"]))
    sp, sj = seen["seen_peer"][order], seen["seen_probe"][order]
    ss, sa = seq_seen[order], seen["seen_at_ns"][order]
    group = sp * len(d.probes) + sj
    starts = np.flatnonzero(np.r_[True, group[1:] != group[:-1]])
    ends = np.r_[starts[1:], len(group)]
    by_group = {int(group[s]): (s, e) for s, e in zip(starts, ends)}
    for j in np.unique(up_probe):
        mine = up_probe == j
        seqs, dues = up_seq[mine], up_due[mine]
        for k in d.watchers(int(d.probes[j])):
            attempted += len(seqs)
            span = by_group.get(int(k) * len(d.probes) + int(j))
            if span is None:
                missing += len(seqs)
                continue
            s, e = span
            newest = np.maximum.accumulate(ss[s:e])
            at = np.searchsorted(newest, seqs, side="left")
            ok = at < (e - s)
            missing += int((~ok).sum())
            lat.append((sa[s:e][at[ok]] - dues[ok]) / 1e6)
            early += int((lat[-1] < 0).sum())
    checks = {
        "missing": (missing, 0),      # probe updates not reflected
        "positions_off_grid": (off_grid, 0),
        "reflected_before_due": (early, 0),   # a position nobody sent yet
        "frames_not_of_the_stream": (
            int(sum(int(p["other_frames"]) for p in parts)), 0),
        "deltas_refused": (
            int(sum(int(p["deltas_refused"]) for p in parts)), 0),
        "gaps_seen": (int(sum(int(p["gaps_seen"]) for p in parts)), 0),
    }
    if all("ledger_peer" in p for p in parts):
        checks.update(ledger_checks(parts, d, dtype))
    return {
        "messages": len(plan_["offset_ns"]), "attempted": attempted,
        "failed": missing + early + sum(v for n, (v, _) in checks.items()
                                if n.startswith("ledger")),
        "latency_ms": np.concatenate(lat) if lat else np.zeros(0),
        "due_offset_s": np.zeros(0), "checks": checks, "frames_parsed": 0,
    }


def ledger_checks(parts: list, d, dtype=np.float64) -> dict:
    peer = np.concatenate([p["ledger_peer"] for p in parts])
    ent = np.concatenate([p["ledger_ent"] for p in parts])
    pos = np.concatenate([p["ledger_pos"] for p in parts])
    want_pos = d.pos.astype(dtype).astype(np.float64)
    wrong_set = 0
    for k in range(d.n_peers):
        have = np.sort(ent[peer == k])
        want = d.visible_to(k)
        wrong_set += len(np.setxor1d(have, want))
    ok = (ent >= 0) & (ent < d.n)
    wrong_pos = int((pos[ok] != want_pos[ent[ok]]).any(axis=1).sum())
    return {"ledger_entities_wrong": (int(wrong_set + (~ok).sum()), 0),
            "ledger_positions_wrong": (wrong_pos, 0)}
