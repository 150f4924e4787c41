"""Deployment data of kind `entity_swarm`: a seeded population of
entities, `per_cube` to a cube, each owned by one of a few fat peers
(game servers), registered over the wire during set-up.

The shape of `chip_smoke.Swarm` (PR 22), copied so that the yardstick
owns it. Coordinates are multiples of 1/8 m: exact in the plane's
float32 columns, not in bfloat16. With at most `per_cube` <= k entities
a cube, every co-cube entity of another peer is one of an entity's k
nearest, so a peer sees exactly the entities of other peers in the
cubes where it owns one (`visible_to`): the reference neighbour set.
"""

from __future__ import annotations

import uuid

import numpy as np

STEP = 0.125
#: positions stay in [lo, hi) eighths from the cube's low corner, a
#: metre from every face, so that no walk leaves its cube
LO, HI = 8, 120


class Deployment:
    def __init__(self, recipe: dict, seed: int):
        rng = np.random.default_rng([seed, 0x656E7473])
        self.recipe = recipe
        self.size = size = int(recipe["cube_size"])
        self.span = span = float(recipe["span"])
        self.n = n = int(recipe["entities"])
        self.per_cube = per = int(recipe["per_cube"])
        self.n_peers = int(recipe["peers"])
        self.rows = n
        axis = int(span * 2 / size)
        n_cubes = -(-n // per)
        self.cube_id = np.repeat(rng.permutation(axis ** 3)[:n_cubes], per)[:n]
        c = self.cube_id
        self.corner = np.stack(
            [c % axis, (c // axis) % axis, c // (axis * axis)], axis=1
        ) * float(size) - span
        self.eighths = rng.integers(LO, HI, (n, 3))      # current, as sent
        self.owner = rng.integers(0, self.n_peers, n)
        self.names = ["world_0"]
        self.connected = np.arange(self.n_peers)
        self.cubes, self.occupancy_max = n_cubes, per
        # probes: entities whose every update is timed, a few a peer
        self.probes = np.sort(rng.permutation(n)[:int(recipe["probes"])])
        self.probe_seq = np.zeros(len(self.probes), np.int64)
        self._tag = seed & 0xFFFFFFFFFF

    @property
    def pos(self) -> np.ndarray:
        """Every entity's position as last sent (float64, exact)."""
        return self.corner + self.eighths * STEP

    def sender_groups(self) -> dict:
        return {"all": np.arange(self.n_peers)}

    def peer_uuid(self, k: int) -> uuid.UUID:
        return uuid.UUID(int=((0x5045455200000000 | self._tag) << 64) | (int(k) + 1))

    def entity_uuid(self, i: int) -> uuid.UUID:
        return uuid.UUID(int=((0x454E5400000000 | self._tag) << 64) | (int(i) + 1))

    def visible_to(self, k: int) -> np.ndarray:
        mine = self.owner == k
        return np.flatnonzero(np.isin(self.cube_id, self.cube_id[mine]) & ~mine)

    def watchers(self, i: int) -> np.ndarray:
        """Peers that must see entity i: owners of its cube-mates, bar
        its own owner."""
        mates = self.owner[self.cube_id == self.cube_id[i]]
        return np.setdiff1d(mates, [self.owner[i]])

    def server_files(self, workdir) -> list[str]:
        return []

    def check_booted(self, gauges: dict) -> str | None:
        sim = gauges.get("entity_sim")
        if sim is None:
            return "the server runs no entity plane"
        if gauges["spatial_device"]["platform"] == "tpu" and not sim["pallas"]:
            return "the kNN resolve does not take the compiled Pallas kernel"
        return None

    def check_after(self, gauges: dict) -> str | None:
        got = gauges["entity_sim"]["entities"]
        return None if got == self.n else f"{got} entities, expected {self.n}"
