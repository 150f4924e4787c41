"""Deployment data of kind `crowd_snapshot`: a seeded Zipf crowd of
subscription rows, restored by the server through `--index-snapshot`.

The generator is the shape of BASELINE.json's config 5 as `bench.py`'s
`make_positions` and `chip_smoke.World` draw it (Zipf s=1 popularity
over the cube grid, occupancy capped, the excess waterfilled down the
ranking), copied here so that the yardstick owns it. The cube of every
row is computed by `benchmark/reference.py`, not by the program.
"""

from __future__ import annotations

import json
import uuid

import numpy as np

from benchmark.reference import ConnectedIndex, cube_labels, pack_keys


def zipf_cube_counts(rng, n: int, cells_axis: int, cap: int):
    """-> (cell ids, occupancy) of a Zipf(1) crowd of `n` rows."""
    n_ranked = min(max(n // 4, 1024), cells_axis ** 3)
    cell_ids = rng.permutation(cells_axis ** 3)[:n_ranked]
    p = 1.0 / np.arange(1, n_ranked + 1, dtype=np.float64)
    counts = rng.multinomial(n, p / p.sum())
    excess = int(np.maximum(counts - cap, 0).sum())
    counts = np.minimum(counts, cap)
    free = cap - counts
    counts += np.minimum(free, np.maximum(excess - (np.cumsum(free) - free), 0))
    if int(counts.sum()) != n:
        raise ValueError("waterfill must conserve rows")
    return cell_ids, counts


class Deployment:
    """Row i belongs to peer i. `connected` lists the rows whose peers
    connect, crowded-cube rows first, then the pairs."""

    def __init__(self, recipe: dict, seed: int):
        rng = np.random.default_rng([seed, 0x63726F77])
        self.recipe = recipe
        self.size = size = int(recipe["cube_size"])
        self.rows = rows = int(recipe["rows"])
        span, cap = float(recipe["span"]), int(recipe["occupancy_cap"])
        n_worlds = int(recipe["worlds"])
        axis = int(span * 2 / size)
        self.span = span
        self.names = [f"world_{w}" for w in range(n_worlds)]
        self.row_wid = (np.arange(rows) * n_worlds // rows).astype(np.int32)
        per_world = np.bincount(self.row_wid, minlength=n_worlds)
        cid = np.concatenate([
            np.repeat(*zipf_cube_counts(rng, int(n), axis, cap))
            for n in per_world
        ])
        corner = np.stack(
            [cid % axis, (cid // axis) % axis, cid // (axis * axis)], axis=1
        ) * float(size) - span
        # strictly inside the cube, a metre from every face
        self.positions = corner + rng.uniform(1.0, size - 1.0, (rows, 3))
        self.row_cube = cube_labels(self.positions, size)
        # peer i's UUID: a tag and the seed's low bits high, i + 1 low
        self.peer_hi = np.full(
            rows, 0x57514C0000000000 | (seed & 0xFFFFFFFFFF), np.uint64)
        self.peer_lo = np.arange(1, rows + 1, dtype=np.uint64)
        self._pick_connected(rng, recipe["connected"])

    def _pick_connected(self, rng, want: dict) -> None:
        keys = pack_keys(self.row_wid, self.row_cube, self.size)
        order = np.argsort(keys, kind="stable")
        uniq, starts, occ = np.unique(
            keys[order], return_index=True, return_counts=True)
        ranked = np.argsort(-occ, kind="stable")
        crowded, seen_worlds = [], set()
        for g in ranked:
            wid = int(self.row_wid[order[starts[g]]])
            if want.get("crowded_from_distinct_worlds") and wid in seen_worlds:
                continue
            seen_worlds.add(wid)
            crowded.append(g)
            if len(crowded) == int(want["crowded_cubes"]):
                break
        take = int(want["crowded_take"])
        rows = [order[starts[g]:starts[g] + min(take, occ[g])] for g in crowded]
        self.n_crowded = int(sum(len(r) for r in rows))
        twos = np.flatnonzero(occ == 2)
        if len(twos) < int(want["pair_cubes"]):
            raise ValueError("too few cubes of occupancy 2 for the pairs")
        for g in rng.permutation(twos)[:int(want["pair_cubes"])]:
            rows.append(order[starts[g]:starts[g] + 2])
        self.connected = np.concatenate(rows).astype(np.int64)
        if len(set(self.connected.tolist())) != len(self.connected):
            raise ValueError("connected rows must be distinct")
        self.occupancy_max = int(occ.max())
        self.cubes = int(len(uniq))

    # -- what the harness asks of any deployment ----------------------

    def sender_groups(self) -> dict:
        """name -> connected-peer indices that may send."""
        n = len(self.connected)
        return {"crowded": np.arange(self.n_crowded),
                "pairs": np.arange(self.n_crowded, n),
                "all": np.arange(n)}

    def peer_uuid(self, k: int) -> uuid.UUID:
        row = int(self.connected[k])
        return uuid.UUID(
            int=(int(self.peer_hi[row]) << 64) | int(self.peer_lo[row]))

    def peer_position(self, k) -> np.ndarray:
        return self.positions[self.connected[k]]

    def peer_world(self, k) -> np.ndarray:
        return self.row_wid[self.connected[k]]

    def index(self) -> ConnectedIndex:
        c = self.connected
        return ConnectedIndex(self.row_wid[c], self.positions[c], self.size)

    def resolved_targets(self, plan: dict) -> int:
        """Rows the index holds in the cubes of a plan's messages,
        connected or not: what the device must resolve (for the
        roofline's shapes; the sender is not taken out)."""
        keys = pack_keys(self.row_wid, self.row_cube, self.size)
        uniq, occ = np.unique(keys, return_counts=True)
        q = pack_keys(plan["wid"], cube_labels(plan["position"], self.size),
                      self.size)
        g = np.minimum(np.searchsorted(uniq, q), len(uniq) - 1)
        return int(np.where(uniq[g] == q, occ[g], 0).sum())

    def shapes(self, plan: dict, flushes: int) -> dict:
        """A mean device call's shapes, for the roofline readers."""
        return {"match_call": {
            "queries": len(plan["offset_ns"]) / flushes,
            "targets": self.resolved_targets(plan) / flushes}}

    def server_files(self, workdir) -> list[str]:
        """Write what the server boots from; -> extra server args. The
        product's own snapshot format (`spatial/snapshot.py`, version 1)."""
        path = str(workdir / "index.npz")
        np.savez(
            path,
            version=np.int64(1),
            cube_size=np.int64(self.size),
            worlds=np.frombuffer(json.dumps(self.names).encode(), np.uint8),
            peer_hi=self.peer_hi,
            peer_lo=self.peer_lo,
            row_wid=self.row_wid,
            row_cube=self.row_cube.astype(np.int64),
            row_pid=np.arange(self.rows, dtype=np.int64),
        )
        return ["--index-snapshot", path]

    def check_booted(self, gauges: dict) -> str | None:
        """-> why the booted server is not this deployment, or None."""
        got = gauges["spatial_device"]["subscriptions"]
        if got != self.rows:
            return f"{got} rows on the device, expected {self.rows}"
        return None

    check_after = check_booted     # index rows unchanged by the run
