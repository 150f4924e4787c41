"""Traffic of kind `local_message`: an open-loop schedule of
LocalMessages drawn from a workload file's parameters and the seed.

Every seed gives the same number of messages, the same arrival offsets
and the same mix; the seed moves which positions are drawn. A workload
file (see benchmark/README.md) holds:

    senders         a sender group of the deployment ("crowded", "pairs")
    rate            messages per second, all senders together
    arrival         "even" | "poisson" | {"burst": {"n": 64, "every_ms": 500}}
    position_mix    shares of "own" (the sender's own position), "face"
                    (own position moved 2**-30 m to either side of a face
                    of the sender's cube) and "fresh" (uniform in the span)
    including_self_every   every n-th message is IncludingSelf
    payload_bytes   size of the flex payload
"""

from __future__ import annotations

import numpy as np

from benchmark import wire
from benchmark.reference import compare, cube_labels

FACE_STEP = 2.0 ** -30
SAMPLE_EVERY = 509          # raw frames kept for the full parse
SAMPLE_CAP = 2000


def arrivals(spec, rate: float, seconds: float, rng) -> np.ndarray:
    """-> sorted offsets (ns) of the messages of `seconds` seconds."""
    n = int(round(rate * seconds))
    if spec == "even":
        t = (np.arange(n) + 0.5) / rate
    elif spec == "poisson":
        t = np.sort(rng.uniform(0.0, seconds, n))
    elif isinstance(spec, dict) and "burst" in spec:
        b, every = int(spec["burst"]["n"]), spec["burst"]["every_ms"] / 1e3
        t = (np.arange(n) // b) * every
        t = t[t < seconds]
    else:
        raise ValueError(f"unknown arrival pattern {spec!r}")
    return (t * 1e9).astype(np.int64)


def plan(workload: dict, deployment, seed: int, seconds: float,
         phase: int) -> dict:
    """-> the schedule of one phase (0 warm-up, 1 window) as arrays:
    offset_ns, sender (connected-peer index), wid, position, including_self."""
    rng = np.random.default_rng([seed, 0x6C6D7367, phase])
    offset = arrivals(workload["arrival"], float(workload["rate"]), seconds, rng)
    n = len(offset)
    group = deployment.sender_groups()[workload["senders"]]
    # round-robin over a seeded order of the senders
    sender = rng.permutation(group)[np.arange(n) % len(group)]
    pos = deployment.peer_position(sender).copy()
    wid = deployment.peer_world(sender).astype(np.int64)
    mix = workload["position_mix"]
    kinds = ("own", "face", "fresh")
    shares = np.array([float(mix.get(k, 0.0)) for k in kinds])
    if abs(shares.sum() - 1.0) > 1e-9:
        raise ValueError("position_mix must sum to 1")
    # the same count of each kind for every seed, in a seeded order
    counts = np.floor(shares * n).astype(int)
    counts[0] += n - counts.sum()
    kind = rng.permutation(np.repeat(np.arange(3), counts))
    size = deployment.size
    face = np.flatnonzero(kind == 1)
    if len(face):
        axis = rng.integers(0, 3, len(face))
        own = pos[face, axis]
        hi = cube_labels(own, size).astype(np.float64)
        hi = np.where(own < 0, hi + size, hi)        # the cube's upper face
        lo = hi - size
        # a face at 0 is no test of float32 (its steps there are finer
        # than 2**-30): take the cube's other face
        target = np.where(np.abs(hi) >= np.abs(lo), hi, lo)
        side = rng.choice([-FACE_STEP, FACE_STEP], len(face))
        pos[face, axis] = target + side
    fresh = np.flatnonzero(kind == 2)
    if len(fresh):
        pos[fresh] = rng.uniform(-deployment.span, deployment.span,
                                 (len(fresh), 3))
    every = int(workload.get("including_self_every", 0))
    incl = (np.arange(n) % every == 0) if every else np.zeros(n, bool)
    return {"offset_ns": offset, "sender": sender.astype(np.int64),
            "wid": wid, "position": pos, "including_self": incl}


def expected(plan_: dict, deployment, dtype=np.float64):
    """Reference deliveries of a plan: (message index, peer index)."""
    return deployment.index().expected(
        plan_["wid"], plan_["position"], plan_["sender"],
        plan_["including_self"], dtype)


def framer(plan_: dict, uuid_of: dict, spec: dict):
    """For a generator process: -> frame(i, due_ns), the wire bytes of
    the plan's i-th message, from one pre-built template a (sender,
    world, replication)."""
    names, payload = spec["worlds"], int(spec["payload_bytes"])
    templates: dict = {}
    stamps = []
    for key in zip(plan_["sender"].tolist(), plan_["wid"].tolist(),
                   plan_["including_self"].tolist()):
        t = templates.get(key)
        if t is None:
            k, wid, incl = key
            t = templates[key] = wire.Template(
                uuid_of[k], names[wid],
                wire.INCLUDING_SELF if incl else wire.EXCEPT_SELF, payload)
        stamps.append(t.stamp)
    ids = plan_["msg_id"].tolist()
    pos = plan_["position"].tolist()

    def frame(i: int, due_ns: int) -> bytes:
        x, y, z = pos[i]
        return stamps[i](ids[i], due_ns, x, y, z)

    return frame


class Receiver:
    """For a generator process: takes the stamp of every frame as it
    arrives (no parse), keeps a sample of raw frames for the full parse
    after the window."""

    def __init__(self, spec: dict):
        self._reset()

    def _reset(self) -> None:
        self.ids, self.dues, self.ats, self.ks = [], [], [], []
        self.sample, self.seen, self.unstamped = [], 0, 0

    def on_frame(self, k: int, at_ns: int, frame: bytes) -> None:
        stamp = wire.peek(frame)
        if stamp is None:
            # the protocol's own frames (a PeerConnect of a late joiner);
            # anything else unstamped is an answer the cell did not ask for
            if wire.parse(frame)["instruction"] not in wire.PROTOCOL_FRAMES:
                self.unstamped += 1
            return
        self.ids.append(stamp[0])
        self.dues.append(stamp[1])
        self.ats.append(at_ns)
        self.ks.append(k)
        self.seen += 1
        if self.seen % SAMPLE_EVERY == 1 and len(self.sample) < SAMPLE_CAP:
            self.sample.append((k, frame))

    def take(self, final: bool = False) -> dict:
        """-> what was received since the last take, as arrays."""
        out = {
            "msg_id": np.asarray(self.ids, np.uint64),
            "due_ns": np.asarray(self.dues, np.int64),
            "at_ns": np.asarray(self.ats, np.int64),
            "peer": np.asarray(self.ks, np.int64),
            "unstamped": np.int64(self.unstamped),
            "sample_peer": np.asarray([k for k, _ in self.sample], np.int64),
            "sample_len": np.asarray([len(f) for _, f in self.sample],
                                     np.int64),
            "sample_bytes": np.frombuffer(
                b"".join(f for _, f in self.sample), np.uint8),
        }
        self._reset()
        return out


def judge(plan_: dict, parts: list, deployment, phase: int, t0_ns: int,
          dtype=np.float64) -> dict:
    """Hold what the generator processes received (`parts`, one dict of
    arrays a process) to the reference. -> attempted, failed, the
    latencies (ms, receipt - due) of the good deliveries with their due
    offsets, and every number compared beside its limit (`checks`)."""
    got = {k: np.concatenate([p[k] for p in parts])
           for k in ("msg_id", "due_ns", "at_ns", "peer")}
    exp_msg, exp_peer = expected(plan_, deployment, dtype)
    sel = wire.phase_of(got["msg_id"]) == phase
    idx = wire.index_of(got["msg_id"][sel])
    res = compare(exp_msg, exp_peer, idx, got["peer"][sel],
                  len(deployment.connected))
    at, due = got["at_ns"][sel], got["due_ns"][sel]
    # a stamp that came back changed is an answer altered on the way
    n = len(plan_["offset_ns"])
    want_due = t0_ns + plan_["offset_ns"][np.minimum(idx, n - 1)]
    stamp_changed = int(((idx >= n) | (want_due != due)).sum())
    good = res.pop("good")
    checks = {
        "missing": (res["missing"], 0), "extra": (res["extra"], 0),
        "duplicated": (res["duplicated"], 0),
        "stamp_changed": (stamp_changed, 0),
        "unstamped_frames": (int(sum(int(p["unstamped"]) for p in parts)), 0),
        "sampled_frames_wrong": (
            _samples_wrong(parts, plan_, deployment, t0_ns), 0),
    }
    return {
        "messages": n, "attempted": res["attempted"],
        "failed": res["missing"] + res["extra"] + res["duplicated"]
        + stamp_changed,
        "latency_ms": (at - due)[good] / 1e6,
        "due_offset_s": (due[good] - t0_ns) / 1e9,
        "checks": checks,
        "frames_parsed": int(sum(len(p["sample_len"]) for p in parts)),
    }


def _samples_wrong(parts: list, plan_: dict, deployment, t0_ns: int) -> int:
    """Full parse of the sampled frames: each must be the LocalMessage
    that was sent (sender, world, position, payload). Frames of another
    phase are not looked at. -> frames wrong."""
    by_id = {int(i): n for n, i in enumerate(plan_["msg_id"].tolist())}
    phase = int(wire.phase_of(plan_["msg_id"][:1])[0]) if by_id else -1
    wrong = 0
    for p in parts:
        ends = np.cumsum(p["sample_len"])
        raw = p["sample_bytes"].tobytes()
        for lo, hi in zip(ends - p["sample_len"], ends):
            frame = raw[lo:hi]
            stamp = wire.peek(frame)
            if stamp is None or int(wire.phase_of([stamp[0]])[0]) != phase:
                continue
            msg = wire.parse(frame)
            n = by_id.get(stamp[0])
            if n is None or msg["instruction"] != wire.LOCAL_MESSAGE:
                wrong += 1
                continue
            ok = (
                msg["sender"] == str(
                    deployment.peer_uuid(int(plan_["sender"][n])))
                and msg["world"] == deployment.names[int(plan_["wid"][n])]
                and tuple(msg["position"]) == tuple(
                    plan_["position"][n].tolist())
                and msg["flex"] is not None
                and msg["flex"][:wire.STAMP.size] == wire.STAMP.pack(
                    wire.MAGIC, stamp[0], t0_ns + int(plan_["offset_ns"][n]))
            )
            wrong += not ok
    return wrong
