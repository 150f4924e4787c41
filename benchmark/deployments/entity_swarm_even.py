"""Deployment data of kind `entity_swarm_even`: the swarm of
`entity_swarm`, with owners and probes dealt so that every seed owes the
same number of deliveries and registers the same number of entities a
peer.

`entity_swarm` draws owners and probes independently, so a probe has
9-15 watchers and a peer 0-11 probes: the deliveries a run owes are a
function of its seed (67,420-67,900 frames/s over four seeds; ledger,
PR 26, refused as too noisy for the bound on `delivered_per_s`). Here

* owners are a seeded permutation of `arange(n) % peers`: every peer
  owns n // peers or one more entity in every seed;
* every peer has `probes_per_peer` probes, drawn with the seed among its
  entities that have exactly `watchers_per_probe` watchers. Too few such
  entities is an error: there is no fallback that would owe another
  number.

So an update round owes peers x probes_per_peer x watchers_per_probe
reflections in every seed. Everything else is `entity_swarm`'s.
"""

from __future__ import annotations

import numpy as np

from benchmark.deployments import entity_swarm


class Deployment(entity_swarm.Deployment):
    def __init__(self, recipe: dict, seed: int):
        super().__init__({**recipe, "probes": 0}, seed)
        rng = np.random.default_rng([seed, 0x6576656E])
        n, peers = self.n, self.n_peers
        self.k = int(recipe["k"])
        self.owner = rng.permutation(np.arange(n) % peers)
        per_peer = int(recipe["probes_per_peer"])
        want = int(recipe["watchers_per_probe"])
        watchers = self.watcher_counts()
        probes = []
        for k in range(peers):
            mine = np.flatnonzero((self.owner == k) & (watchers == want))
            if len(mine) < per_peer:
                raise ValueError(
                    f"peer {k} owns {len(mine)} entities with exactly {want} "
                    f"watchers, the recipe asks {per_peer} probes a peer")
            probes.append(rng.choice(mine, per_peer, replace=False))
        self.probes = np.sort(np.concatenate(probes))
        self.probe_seq = np.zeros(len(self.probes), np.int64)

    def watcher_counts(self) -> np.ndarray:
        """len(self.watchers(i)) for every entity i: the distinct owners
        in its cube, bar its own."""
        _, cube = np.unique(self.cube_id, return_inverse=True)
        pairs = np.unique(cube * self.n_peers + self.owner)
        return (np.bincount(pairs // self.n_peers) - 1)[cube]

    def shapes(self, plan: dict, flushes: int) -> dict:
        """The mean kNN call of the window, counted low: the distinct
        entities the plan updates between two sim ticks (`flushes` of
        them, evenly spaced over the plan) are the least rows any
        implementation must resolve again; k and the candidate window
        (2k) are the configuration's."""
        live = plan["ent"] >= 0
        span = int(plan["offset_ns"].max()) + 1
        tick = plan["offset_ns"] * int(flushes) // span
        touched = np.unique(
            np.broadcast_to(tick[:, None], live.shape)[live] * self.n
            + plan["ent"][live])
        return {"knn_call": {"entities": len(touched) / flushes,
                             "k": self.k, "window": 2 * self.k}}
