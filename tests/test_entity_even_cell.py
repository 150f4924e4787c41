"""The `entity-knn-100k-even` deployment and the spans and counters its
cell reads (ISSUE 27), without a server.

PR 26's cell was refused because the deliveries a run owed were a
function of its seed. `benchmark/deployments/entity_swarm_even.py` deals
owners and probes so that every seed owes the same; the first half pins
that on the configuration's own recipe. The second half runs a real
`EntityPlane` with an `InterestManager` over a small swarm of the same
kind and holds `tick.sim.interest.diff` / `.encode` and the counters
`interest.entries` / `interest.rows_diffed` to what the plain reference
(`Deployment.visible_to` / `watchers`, `benchmark/replay.py`) counts.
"""

import json
from pathlib import Path

import numpy as np
import pytest

from benchmark import wire
from benchmark.deployments import entity_swarm_even
from benchmark.replay import ReplayClient
from benchmark.traffic import entity_walk

ROOT = Path(__file__).resolve().parent.parent
CONFIG = json.loads(
    (ROOT / "benchmark/configs/entity-knn-100k-even.json").read_text())
WORKLOAD = json.loads(
    (ROOT / "benchmark/workloads/entity-100k-even.random-walk.json").read_text())
SEEDS = [1, 2, 3, 3000000215, 2 ** 31 + 77]
#: 256 probes x 13 watchers, reflected once an update round
OWED_A_ROUND = 3328


# region: the deal


@pytest.mark.parametrize("seed", SEEDS)
def test_every_seed_is_dealt_the_same_work(seed):
    d = entity_swarm_even.Deployment(CONFIG["data"], seed)
    owned = np.bincount(d.owner, minlength=d.n_peers)
    assert owned.sum() == 100_000 and set(owned) <= {1562, 1563}
    assert len(d.probes) == len(set(d.probes.tolist())) == 256
    assert np.bincount(d.owner[d.probes], minlength=64).tolist() == [4] * 64
    watchers = [len(d.watchers(int(i))) for i in d.probes]
    assert watchers == [13] * 256
    assert sum(watchers) == OWED_A_ROUND
    # the vectorised count the deal draws from is `watchers`, entity by entity
    some = np.random.default_rng(seed).integers(0, d.n, 50)
    assert d.watcher_counts()[some].tolist() == [
        len(d.watchers(int(i))) for i in some]


def perfect_receiver(plan: dict, d, t0_ns: int) -> list:
    """What the generator processes would hand `judge` had every probe
    update been reflected, a millisecond after it was due, at every
    peer the reference says watches the probe."""
    peer, probe, pos, at = [], [], [], []
    for i, j in zip(*np.nonzero(plan["probe_j"] >= 0)):
        p = int(plan["probe_j"][i, j])
        for k in d.watchers(int(d.probes[p])):
            peer.append(k)
            probe.append(p)
            pos.append(plan["position"][i, j])
            at.append(t0_ns + plan["offset_ns"][i] + 1_000_000)
    return [{"seen_peer": np.asarray(peer, np.int64),
             "seen_probe": np.asarray(probe, np.int64),
             "seen_pos": np.asarray(pos, np.float64).reshape(-1, 3),
             "seen_at_ns": np.asarray(at, np.int64),
             "other_frames": np.int64(0), "deltas_refused": np.int64(0),
             "gaps_seen": np.int64(0)}]


def test_every_seed_owes_the_same_deliveries():
    owed = []
    for seed in SEEDS:
        d = entity_swarm_even.Deployment(CONFIG["data"], seed)
        plan = entity_walk.plan(WORKLOAD, d, seed, 0.5, 1)
        # a message: 16 of its peer's entities a step, and its 4 probes
        assert plan["ent"].shape == (10 * 64, 20) and (plan["ent"] >= 0).all()
        res = entity_walk.judge(plan, perfect_receiver(plan, d, 10 ** 9), d,
                                1, 10 ** 9)
        assert res["failed"] == 0 and len(res["latency_ms"]) == res["attempted"]
        assert all(v <= limit for v, limit in res["checks"].values())
        owed.append(res["attempted"])
    assert owed == [10 * OWED_A_ROUND] * len(SEEDS)     # 66,560 a second


def test_too_few_entities_with_that_many_watchers_is_an_error():
    recipe = {**CONFIG["data"], "entities": 2000, "peers": 8}
    assert len(entity_swarm_even.Deployment(
        {**recipe, "probes_per_peer": 2, "watchers_per_probe": 6}, 1).probes) == 16
    with pytest.raises(ValueError, match="watchers"):
        # 16 entities a cube of 8 peers: nobody has 8 watchers
        entity_swarm_even.Deployment(
            {**recipe, "probes_per_peer": 2, "watchers_per_probe": 8}, 1)
    with pytest.raises(ValueError, match="probes a peer"):
        entity_swarm_even.Deployment(
            {**recipe, "probes_per_peer": 400, "watchers_per_probe": 6}, 1)


def test_knn_call_shapes_count_the_plans_distinct_entities():
    d = entity_swarm_even.Deployment(CONFIG["data"], 5)
    plan = entity_walk.plan(WORKLOAD, d, 5, 1.0, 1)
    updates = int((plan["ent"] >= 0).sum())
    distinct = len(np.unique(plan["ent"]))
    assert d.shapes(plan, 1) == {"knn_call": {
        "entities": distinct, "k": 32, "window": 64}}
    # the more sim ticks share the plan, the fewer rows each must resolve,
    # and never more than were sent
    per_call = [d.shapes(plan, f)["knn_call"]["entities"] for f in (1, 7, 20)]
    assert per_call == sorted(per_call, reverse=True)
    assert distinct / 7 <= per_call[1] <= updates / 7
    assert per_call[2] == updates / 20        # a period's entities are distinct


# endregion

# region: the apply leg's spans and counters


def test_interest_spans_and_counters_equal_the_references_counts():
    from tests.test_entity_sim import ent_msg, make_plane
    from worldql_server_tpu.engine.metrics import Metrics
    from worldql_server_tpu.interest import InterestManager
    from worldql_server_tpu.observability.spans import Tracer
    from worldql_server_tpu.protocol.types import Entity, Vector3

    d = entity_swarm_even.Deployment(
        {**CONFIG["data"], "entities": 320, "peers": 4,
         "probes_per_peer": 1, "watchers_per_probe": 3}, 11)
    world = d.names[0]
    _, plane = make_plane(k=32)
    metrics = Metrics()
    plane.interest = InterestManager(metrics=metrics)
    traces = []
    tracer = Tracer(enabled=True, on_trace=traces.append)
    clients = {k: ReplayClient() for k in range(d.n_peers)}
    peer_k = {d.peer_uuid(k): k for k in range(d.n_peers)}

    def send(entities):
        pos = d.pos
        for k in range(d.n_peers):
            mine = [i for i in entities if d.owner[i] == k]
            if mine:
                plane.ingest(ent_msg(d.peer_uuid(k), [Entity(
                    uuid=d.entity_uuid(i), position=Vector3(*pos[i]),
                    world_name=world) for i in mine], world=world))

    def tick() -> int:
        """One traced sim tick; -> the entries its frames carry, as the
        yardstick's replay of them counts."""
        trace = tracer.begin("tick")
        with trace.span("tick.sim.integrate"):
            handle = plane.dispatch_tick()
        with trace.span("tick.sim.knn"):
            result = plane.collect_tick(handle)
        with trace.span("tick.sim.apply"):
            pairs = plane.apply(result, trace)
        trace.finish()
        entries = 0
        for frame, targets in pairs:
            msg = wire.parse(frame.wire)
            for peer in targets:
                assert clients[peer_k[peer]].apply(msg)
                entries += len(msg["entities"])
        return entries

    def counters() -> dict:
        c = metrics.snapshot()["counters"]
        return {k: c.get(k, 0) for k in ("interest.entries",
                                         "interest.rows_diffed")}

    visible = sum(len(d.visible_to(k)) for k in range(d.n_peers))
    send(range(d.n))
    assert tick() == visible                # the keyframes: every ledger whole
    # every row is new to the empty snapshot, every new peer's view walked
    assert counters() == {"interest.entries": visible,
                          "interest.rows_diffed": d.n + visible}
    spans = {s.name: s for s in traces[-1].spans}
    apply_id = spans["tick.sim.apply"].id
    assert spans["tick.sim.interest.diff"].parent == apply_id
    assert spans["tick.sim.interest.encode"].parent == apply_id
    assert (spans["tick.sim.interest.diff"].dur_ms
            + spans["tick.sim.interest.encode"].dur_ms
            <= spans["tick.sim.apply"].dur_ms)
    totals = tracer.span_totals()           # unsampled, as every span's
    assert totals["tick.sim.interest.diff"]["count"] == 1
    assert totals["tick.sim.interest.encode"]["count"] == 1

    # a step of 1/8 m for a few entities: a delta entry at each watcher
    moved = [int(i) for i in d.probes] + [0, 17, 200]
    d.eighths[moved, 0] += 1
    send(moved)
    owed = sum(len(d.watchers(i)) for i in moved)
    assert tick() == owed
    # ... after which the diff looks at the rows that moved, no others
    assert counters() == {"interest.entries": visible + owed,
                          "interest.rows_diffed": d.n + visible + len(moved)}
    for k, client in clients.items():
        ledger = client.worlds[world]
        want = d.visible_to(k)
        assert set(ledger) == {str(d.entity_uuid(int(i))) for i in want}
        for i in want:
            assert ledger[str(d.entity_uuid(int(i)))] == tuple(d.pos[i])
        assert client.deltas_refused == 0 and client.gaps_seen == 0

    # an untraced apply opens nothing and counts the same way
    d.eighths[moved, 1] += 1
    send(moved)
    plane.apply(plane.collect_tick(plane.dispatch_tick()))
    assert counters()["interest.entries"] == visible + 2 * owed
    assert tracer.span_totals()["tick.sim.interest.diff"]["count"] == 2


# endregion
