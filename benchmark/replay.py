"""The wire's client side of an interest-managed entity stream: the
state a compliant peer holds after replaying `entity.frame.full` /
`fullc` / `delta` frames stamped `<kind>:<epoch hex8>:<seq hex8>`.

A copy of the contract `worldql_server_tpu/interest/replay.py::ReplayClient`
enforces (PR 18), kept with the yardstick and reading frames with
`benchmark/wire.py`: a delta applies only on a contiguous same-epoch
sequence; a gap desyncs the client until a new epoch opens with a
keyframe. `deltas_refused` and `gaps_seen` must stay 0.
"""

from __future__ import annotations

FULL, FULL_CONT, DELTA = ("entity.frame.full", "entity.frame.fullc",
                          "entity.frame.delta")


def parse_stamp(parameter):
    if parameter is None or not parameter.startswith("entity.frame."):
        return None
    parts = parameter.rsplit(":", 2)
    if len(parts) != 3 or parts[0] not in (FULL, FULL_CONT, DELTA):
        return None
    try:
        return parts[0], int(parts[1], 16), int(parts[2], 16)
    except ValueError:
        return None


class ReplayClient:
    def __init__(self):
        self.worlds: dict = {}        # world -> {uuid str -> (x, y, z)}
        self.epoch, self.next_seq, self.desync = -1, 0, True
        self.frames_applied = self.fulls_applied = self.deltas_applied = 0
        self.gaps_seen = self.deltas_refused = self.discarded = 0

    def apply(self, msg: dict) -> bool:
        """Apply one parsed frame (`wire.parse`); -> whether it changed
        the state."""
        stamped = parse_stamp(msg["parameter"])
        if stamped is None:
            return False
        kind, epoch, seq = stamped
        if epoch > self.epoch:
            if kind == FULL and seq == 0:
                self.worlds.clear()
                self.epoch, self.next_seq, self.desync = epoch, 0, False
            else:
                self.deltas_refused += kind == DELTA
                self.desync = True
                self.discarded += 1
                return False
        elif epoch < self.epoch:
            self.discarded += 1
            return False
        if seq != self.next_seq:
            self.gaps_seen += 1
            self.desync = True
        if self.desync:
            self.deltas_refused += kind == DELTA
            self.discarded += 1
            return False
        self.next_seq = seq + 1
        world = self.worlds.setdefault(msg["world"], {})
        if kind == FULL:
            world.clear()
        for ent in msg["entities"]:
            if ent["flex"] is not None and len(ent["flex"]) < 12:
                world.pop(ent["uuid"], None)          # a tombstone
            else:
                world[ent["uuid"]] = ent["position"]
        if not world:
            self.worlds.pop(msg["world"], None)
        self.frames_applied += 1
        if kind == DELTA:
            self.deltas_applied += 1
        else:
            self.fulls_applied += 1
        return True
