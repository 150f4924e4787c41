"""Delta ticks (ISSUE 13): temporal coherence, parity-pinned.

The contract under test: with ``delta_ticks`` armed, every observable
result — query fan-out lists lane for lane, entity positions/cubes/
targets, frames on the wire — is IDENTICAL to the full-recompute
path across arbitrary churn schedules, while the engine provably
reuses the clean majority (and the device does sublinear work). The
off mode stays byte-for-byte the pre-delta pipeline.
"""

import asyncio
import time
import urllib.request
import uuid

import numpy as np
import pytest

from worldql_server_tpu.engine.config import Config
from worldql_server_tpu.engine.metrics import Metrics
from worldql_server_tpu.engine.peers import Peer, PeerMap
from worldql_server_tpu.engine.router import Router
from worldql_server_tpu.engine.server import WorldQLServer
from worldql_server_tpu.engine.ticker import TickBatcher
from worldql_server_tpu.entities.plane import EntityPlane
from worldql_server_tpu.protocol import deserialize_message
from worldql_server_tpu.protocol.types import (
    Entity, Instruction, Message, Vector3,
)
from worldql_server_tpu.robustness import failpoints
from worldql_server_tpu.robustness.overload import OverloadGovernor
from worldql_server_tpu.storage.memory_store import MemoryRecordStore
from worldql_server_tpu.robustness.resilient import ResilientBackend
from worldql_server_tpu.spatial.delta_ticks import (
    TemporalCoherence, row_signatures,
)
from worldql_server_tpu.spatial.hashing import spatial_keys
from worldql_server_tpu.spatial.quantize import cube_coords_batch
from worldql_server_tpu.spatial.tpu_backend import TpuSpatialBackend

from tests.client_util import ZmqClient, free_port
from tests.prom_parser import validate_exposition


def run(coro):
    return asyncio.run(coro)


# region: TemporalCoherence units


def test_coherence_dirty_sequence_is_exact():
    co = TemporalCoherence()
    co.note_key(100)
    seq_then = co.seq
    co.store(h1=7, h2=77, key=100, seq=seq_then, targets=("a",))
    # clean cube at the entry's sequence: replays
    reused, dirty = co.partition([7], [77])
    assert reused == [["a"]] and dirty == []
    # a LATER mutation of the cube invalidates exactly that entry
    co.note_key(100)
    reused, dirty = co.partition([7], [77])
    assert reused == [None] and dirty == [0]
    # a mutation of a DIFFERENT cube does not
    co.store(h1=7, h2=77, key=100, seq=co.seq, targets=("a",))
    co.note_key(999)
    reused, dirty = co.partition([7], [77])
    assert reused == [["a"]] and dirty == []


def test_coherence_h2_mismatch_and_floor_reject():
    co = TemporalCoherence()
    co.store(h1=5, h2=50, key=1, seq=co.seq, targets=())
    # 128-bit verify: an h1 collision with a different h2 recomputes
    assert co.partition([5], [51]) == ([None], [0])
    # wholesale invalidation rejects racing inserts stamped before it
    stale_seq = co.seq
    co.invalidate_all()
    co.store(h1=5, h2=50, key=1, seq=stale_seq, targets=())
    assert co.partition([5], [50]) == ([None], [0])


def test_coherence_cache_bound_resets_not_grows():
    co = TemporalCoherence(max_entries=4)
    for i in range(10):
        co.store(h1=i, h2=i, key=i, seq=co.seq, targets=())
    assert len(co.cache) <= 4
    assert co.cache_resets >= 1


def test_row_signatures_fold_every_column():
    wid = np.array([3], np.int32)
    pos = np.array([[1.0, 2.0, 3.0]])
    sid = np.array([9], np.int32)
    repl = np.array([0], np.int8)
    base = row_signatures(wid, pos, sid, repl)
    for cols in (
        (wid + 1, pos, sid, repl),
        (wid, pos + 1e-12, sid, repl),
        (wid, pos, sid + 1, repl),
        (wid, pos, sid, repl + 1),
    ):
        other = row_signatures(*cols)
        assert (base[0] != other[0]).all() or (base[1] != other[1]).all()
    again = row_signatures(wid, pos.copy(), sid, repl)
    assert base[0][0] == again[0][0] and base[1][0] == again[1][0]


# endregion

# region: query-path parity property


def _staged(q_pos, sid, m):
    return (
        np.zeros(m, np.int32),
        np.ascontiguousarray(q_pos[:m]),
        sid[:m],
        np.zeros(m, np.int8),
    )


def test_delta_query_parity_under_randomized_churn():
    """>= 200 ticks of randomized churn — moves, joins, leaves, peer
    removals, query churn, forced query-tier changes — keep the delta
    path lane-for-lane identical to full recompute, with reuse and
    the O(K) tombstone scatter provably firing."""
    rng = np.random.default_rng(1234)
    n, m = 256, 64
    bes = [
        TpuSpatialBackend(16, compact_threshold=64),
        TpuSpatialBackend(16, compact_threshold=64),
    ]
    assert bes[0].configure_delta_ticks("auto")
    peers = [uuid.UUID(int=i + 1) for i in range(n)]
    pos = rng.uniform(-250, 250, (n, 3))
    cubes = cube_coords_batch(pos, 16)
    live = np.ones(n, bool)
    for be in bes:
        be.bulk_add_subscriptions("w", peers, cubes)
        be.flush()
    q_pos = pos[rng.integers(0, n, m)].copy()
    sid = np.full(m, -1, np.int32)

    for tick in range(210):
        op = rng.random()
        if op < 0.22:  # moves through the base+delta path
            mv = np.unique(rng.integers(0, n, int(rng.integers(1, 5))))
            mv = mv[live[mv]]
            if mv.size:
                new_cubes = cube_coords_batch(
                    rng.uniform(-250, 250, (mv.size, 3)), 16
                )
                for be in bes:
                    be.bulk_move_subscriptions(
                        "w", [peers[i] for i in mv], cubes[mv],
                        [peers[i] for i in mv], new_cubes,
                    )
                cubes[mv] = new_cubes
        elif op < 0.36:  # leaves (tombstones)
            i = int(rng.integers(0, n))
            if live[i]:
                for be in bes:
                    be.remove_subscription(
                        "w", peers[i], tuple(int(c) for c in cubes[i])
                    )
                live[i] = False
        elif op < 0.48:  # joins (delta appends)
            dead = np.flatnonzero(~live)
            if dead.size:
                i = int(dead[0])
                new_cube = cube_coords_batch(
                    rng.uniform(-250, 250, (1, 3)), 16
                )
                for be in bes:
                    be.bulk_add_subscriptions("w", [peers[i]], new_cube)
                cubes[i] = new_cube[0]
                live[i] = True
        elif op < 0.56:  # wholesale peer removal
            i = int(rng.integers(0, n))
            if live[i]:
                for be in bes:
                    be.remove_peer(peers[i])
                live[i] = False
        elif op < 0.72:  # query churn (fresh positions)
            rows = rng.integers(0, m, 3)
            q_pos[rows] = rng.uniform(-250, 250, (3, 3))
        # forced tier changes: three fixed batch sizes (pow2 tiers)
        mm = (m, 32, 16)[int(rng.integers(0, 12)) % 3 if tick % 7 == 0
                         else 0]
        cols = _staged(q_pos, sid, mm)
        outs = [
            be.collect_local_batch(be.dispatch_staged_batch(*cols))
            for be in bes
        ]
        assert outs[0] == outs[1], f"tick {tick} diverged"
    on = bes[0]
    assert on.delta_reused > 0, "reuse never fired"
    assert on.delta_sync_scatters > 0, "tombstone scatter never fired"
    assert on.delta_recomputed > 0
    # the off backend never touched the coherence machinery
    assert bes[1].delta_reused == 0 and bes[1].delta_recomputed == 0


def test_low_churn_pass_replays_the_clean_majority():
    """The steady regime delta ticks exist for: the same batch tick
    over tick with ~1 % of its query rows fresh and a couple of
    subscriptions moving. More than four rows in five replay, and every
    tick equals the full recompute lane for lane."""
    rng = np.random.default_rng(4242)
    n, m, warm, ticks = 2048, 256, 2, 10
    bes = [TpuSpatialBackend(16), TpuSpatialBackend(16)]
    assert bes[0].configure_delta_ticks("auto")
    peers = [uuid.UUID(int=i + 1) for i in range(n)]
    pos = rng.uniform(-400, 400, (n, 3))
    cubes = cube_coords_batch(pos, 16)
    for be in bes:
        be.bulk_add_subscriptions("w", peers, cubes)
        be.flush()
    q_pos = pos[rng.integers(0, n, m)].copy()
    sid = np.full(m, -1, np.int32)
    reuse = []
    for tick in range(warm + ticks):
        rows = np.unique(rng.integers(0, m, max(2, m // 100)))
        q_pos[rows] = pos[rng.integers(0, n, rows.size)]
        mv = np.unique(rng.integers(0, n, 2))
        new_cubes = cube_coords_batch(
            rng.uniform(-400, 400, (mv.size, 3)), 16
        )
        movers = [peers[i] for i in mv]
        for be in bes:
            be.bulk_move_subscriptions(
                "w", movers, cubes[mv], movers, new_cubes
            )
        cubes[mv] = new_cubes
        cols = _staged(q_pos, sid, m)
        outs = [
            be.collect_local_batch(be.dispatch_staged_batch(*cols))
            for be in bes
        ]
        assert outs[0] == outs[1], f"tick {tick} diverged"
        if tick >= warm:
            stats = bes[0].last_delta_stats
            assert stats["fallback"] == ""
            reuse.append(stats["reused"] / stats["batch"])
    assert np.mean(reuse) > 0.8, reuse


def test_delta_off_is_pinned_to_the_pre_delta_pipeline():
    """--delta-ticks off: the handle shapes, counters and coherence
    state are untouched — byte-for-byte the old dispatch pipeline."""
    be = TpuSpatialBackend(16)
    peers = [uuid.UUID(int=i + 1) for i in range(8)]
    pos = np.random.default_rng(0).uniform(-50, 50, (8, 3))
    be.bulk_add_subscriptions("w", peers, cube_coords_batch(pos, 16))
    be.flush()
    cols = _staged(pos, np.full(8, -1, np.int32), 8)
    handle = be.dispatch_staged_batch(*cols)
    assert handle[1][0] in ("csr", "dense")  # never a "tc" handle
    be.collect_local_batch(handle)
    assert be.delta_reused == be.delta_recomputed == 0
    assert not be._coherence.cache and not be._coherence.dirty
    assert be.delta_sync_scatters == 0


def test_sharded_backend_supports_delta_via_flat_region_replay():
    """ISSUE 14 satellite (the PR 13 leftover): result reuse runs on
    the mesh — clean queries replay from the shard-local (host) cache,
    dirty partitions dispatch through the mesh kernels' per-shard flat
    regions — pinned lane-for-lane against a full-recompute mesh twin
    under randomized churn. The delta-SYNC tombstone scatter stays
    conservatively off (the mesh replicates the delta segment)."""
    from worldql_server_tpu.parallel import (
        ShardedTpuSpatialBackend, make_fanout_mesh,
    )

    rng = np.random.default_rng(77)
    n, m = 128, 32
    mesh = make_fanout_mesh(2, 4)
    bes = [
        ShardedTpuSpatialBackend(16, mesh, compact_threshold=64),
        ShardedTpuSpatialBackend(16, mesh, compact_threshold=64),
    ]
    assert bes[0].configure_delta_ticks("auto"), \
        "mesh must accept delta ticks"
    assert not bes[0]._delta_scatter_supported()
    peers = [uuid.UUID(int=i + 1) for i in range(n)]
    pos = rng.uniform(-250, 250, (n, 3))
    cubes = cube_coords_batch(pos, 16)
    live = np.ones(n, bool)
    for be in bes:
        be.bulk_add_subscriptions("w", peers, cubes)
        be.flush()
    q_pos = pos[rng.integers(0, n, m)].copy()
    sid = np.full(m, -1, np.int32)

    for tick in range(80):
        op = rng.random()
        if op < 0.2:  # moves
            mv = np.unique(rng.integers(0, n, int(rng.integers(1, 4))))
            mv = mv[live[mv]]
            if mv.size:
                new_cubes = cube_coords_batch(
                    rng.uniform(-250, 250, (mv.size, 3)), 16
                )
                for be in bes:
                    be.bulk_move_subscriptions(
                        "w", [peers[i] for i in mv], cubes[mv],
                        [peers[i] for i in mv], new_cubes,
                    )
                cubes[mv] = new_cubes
        elif op < 0.32:  # leaves
            i = int(rng.integers(0, n))
            if live[i]:
                for be in bes:
                    be.remove_subscription(
                        "w", peers[i], tuple(int(c) for c in cubes[i])
                    )
                live[i] = False
        elif op < 0.44:  # joins
            dead = np.flatnonzero(~live)
            if dead.size:
                i = int(dead[0])
                new_cube = cube_coords_batch(
                    rng.uniform(-250, 250, (1, 3)), 16
                )
                for be in bes:
                    be.bulk_add_subscriptions("w", [peers[i]], new_cube)
                cubes[i] = new_cube[0]
                live[i] = True
        elif op < 0.6:  # query churn
            rows = rng.integers(0, m, 2)
            q_pos[rows] = rng.uniform(-250, 250, (2, 3))
        mm = (m, 16)[1 if tick % 11 == 0 else 0]  # forced tier change
        cols = _staged(q_pos, sid, mm)
        outs = [
            be.collect_local_batch(be.dispatch_staged_batch(*cols))
            for be in bes
        ]
        assert outs[0] == outs[1], f"sharded tick {tick} diverged"
    assert bes[0].delta_reused > 0, "mesh reuse never fired"
    assert bes[0].delta_recomputed > 0
    assert bes[1].delta_reused == 0 and bes[1].delta_recomputed == 0


# endregion

# region: resilience (rebuild/failover mid-run)


def test_delta_parity_through_resilience_rebuild_and_failover():
    """A mid-run ResilientBackend rebuild — and later a full failover
    to the CPU mirror — keeps the delta wrapper's results identical
    to a full-recompute wrapper fed the same mutations and the same
    fault schedule (the symmetric x2 failpoint hits both)."""
    rng = np.random.default_rng(77)
    n, m = 128, 32

    def make(mode):
        def factory():
            inner = TpuSpatialBackend(16)
            inner.configure_delta_ticks(mode)
            return inner

        return ResilientBackend(
            factory(), factory=factory, failover_after=3,
        )

    bes = [make("on"), make("off")]
    peers = [uuid.UUID(int=i + 1) for i in range(n)]
    pos = rng.uniform(-150, 150, (n, 3))
    cubes = cube_coords_batch(pos, 16)
    for be in bes:
        be.bulk_add_subscriptions("w", peers, cubes)
        be.flush()
    q_pos = pos[rng.integers(0, n, m)].copy()
    sid = np.full(m, -1, np.int32)
    failpoints.registry.reset()
    try:
        for tick in range(30):
            if tick == 10:
                # one dispatch failure EACH → both wrappers rebuild
                failpoints.registry.set("backend.dispatch", "error:1:x2")
            if tick == 20:
                # sustained failures → both fail over to the mirror
                failpoints.registry.set("backend.dispatch", "error:1")
            if tick in (12, 22):  # churn lands on the fresh inner/mirror
                mv = np.arange(5)
                new_cubes = cube_coords_batch(
                    rng.uniform(-150, 150, (5, 3)), 16
                )
                for be in bes:
                    be.bulk_move_subscriptions(
                        "w", [peers[i] for i in mv], cubes[mv],
                        [peers[i] for i in mv], new_cubes,
                    )
                cubes[mv] = new_cubes
            cols = _staged(q_pos, sid, m)
            outs = [
                be.collect_local_batch(
                    be.dispatch_staged_batch(*cols, fallback=None)
                )
                for be in bes
            ]
            assert outs[0] == outs[1], f"tick {tick} diverged"
    finally:
        failpoints.registry.reset()
    assert bes[0].rebuilds >= 1 and bes[0].failed_over
    assert bes[1].rebuilds >= 1 and bes[1].failed_over


# endregion

# region: overload forced-state tick (ticker-level parity)


class _TickerHarness:
    def __init__(self, delta: str):
        config = Config()
        self.backend = TpuSpatialBackend(config.sub_region_size)
        self.backend.configure_delta_ticks(delta)
        self.peer_map = PeerMap(on_remove=self.backend.remove_peer)
        self.gov = OverloadGovernor(max_batch=64, metrics=Metrics())
        from worldql_server_tpu.engine.staging import QueryStaging

        self.ticker = TickBatcher(
            self.backend, self.peer_map, 10.0, max_batch=64,
            governor=self.gov, staging=QueryStaging(self.backend),
        )
        self.router = Router(
            self.peer_map, self.backend, MemoryRecordStore(config),
            ticker=self.ticker,
        )
        self.inboxes = {}

    async def add_peer(self):
        peer_uuid = uuid.uuid4()
        inbox = self.inboxes.setdefault(peer_uuid, [])

        async def send_raw(data):
            inbox.append(deserialize_message(data))

        await self.peer_map.insert(
            Peer(peer_uuid, "loopback", send_raw, "test")
        )
        return peer_uuid


def test_delta_parity_through_forced_overload_tick():
    """An `overload` forced-state tick (governor driven to SHED_HIGH
    via the deterministic failpoint) admits/sheds identically on the
    delta and full paths — delivered frames match peer for peer."""

    async def scenario():
        hs = [_TickerHarness("on"), _TickerHarness("off")]
        pos = Vector3(1.0, 1.0, 1.0)
        peer_ids = []
        for h in hs:
            a = await h.add_peer()
            b = await h.add_peer()
            peer_ids.append((a, b))
            for p in (a, b):
                await h.router.handle_message(Message(
                    instruction=Instruction.AREA_SUBSCRIBE,
                    sender_uuid=p, world_name="world", position=pos,
                ))
        failpoints.registry.reset()
        try:
            for phase in ("ok", "shed_high", "ok"):
                failpoints.registry.set(
                    "overload.force_state", f"state:{phase}"
                )
                for h in hs:
                    for _ in range(4):
                        await h.router.handle_message(Message(
                            instruction=Instruction.LOCAL_MESSAGE,
                            sender_uuid=peer_ids[hs.index(h)][0],
                            world_name="world", position=pos,
                            parameter=phase,
                        ))
                    await h.ticker.flush()
            counts = []
            for h, (a, b) in zip(hs, peer_ids):
                got = [
                    (m.parameter, m.instruction)
                    for m in h.inboxes[b]
                ]
                counts.append(got)
            assert counts[0] == counts[1]
            assert hs[0].backend.delta_reused > 0
        finally:
            failpoints.registry.reset()

    run(scenario())


# endregion

# region: entity-plane parity property


def _ent_msg(sender, entities, parameter=None):
    return Message(
        instruction=Instruction.LOCAL_MESSAGE, sender_uuid=sender,
        world_name="w", entities=entities, parameter=parameter,
    )


def _vel_flex(v):
    return np.asarray(v, np.float32).astype("<f4").tobytes()


def _audit_kept_columns(pl):
    """What the plane keeps beside its columns equals a recount: the
    key of every live slot, the count of live movers."""
    cap = pl._cap
    live = pl._live[:cap]
    assert np.array_equal(
        pl._key[:cap][live],
        spatial_keys(pl._wid[:cap], pl._cube[:cap], 0)[live],
    )
    assert pl._n_moving == int(np.count_nonzero(
        live & (pl._vel[:cap] != 0.0).any(axis=1)
    ))


@pytest.mark.parametrize("case", ["movers", "still"])
def test_delta_sim_parity_under_randomized_churn(case):
    """>= 200 sim ticks of randomized churn — client updates, joins,
    leaves, movers, a forced capacity-tier change, and a mid-run
    abort — keep the delta plane's live targets, positions, cubes and
    frame count identical to the full-recompute plane. ``still`` is
    the benchmark's shape: nobody has a velocity, 16 entities share a
    cube, and every tick the wire steps a few of them, some across a
    cube face. Both cases also hold a still entity the device reflects
    at the bounds while the wire had not touched it, and a slot that
    changes hands between a dispatch and its apply."""
    rng = np.random.default_rng(31)
    owner = uuid.UUID(int=4242)
    still = case == "still"
    bounds = 390.0

    def make(mode):
        be = TpuSpatialBackend(16)
        return EntityPlane(
            be, None, cube_size=16, k=4, dt=0.05, bounds=bounds,
            delta_ticks=mode,
        )

    planes = [make("on"), make("off")]
    ids = [uuid.uuid4() for _ in range(224)]
    vel = np.zeros((224, 3), np.float32)
    if still:
        corners = rng.integers(-20, 20, (14, 3)) * 16.0
        pos = np.repeat(corners, 16, axis=0) + rng.uniform(1, 15, (224, 3))
    else:
        pos = rng.uniform(-350, 350, (224, 3))
        vel[:12] = rng.uniform(-25, 25, (12, 3))  # a few movers, rest idle
    alive = set(range(200))

    def send(entities, parameter=None):
        for pl in planes:
            pl.ingest(_ent_msg(owner, entities, parameter))

    def ent(eid, p, v=None):
        return Entity(uuid=eid, world_name="w", position=Vector3(*p),
                      flex=_vel_flex(v) if v is not None else None)

    send([ent(ids[i], pos[i], vel[i] if vel[i].any() else None)
          for i in sorted(alive)])

    def tick(pl, between=None):
        handle = pl.dispatch_tick()
        assert handle is not None
        if between is not None:
            between(pl)
        return pl.apply(pl.collect_tick(handle))

    # outside the random churn: `far` is registered three bounds out
    # with no velocity, so the device reflects it TWICE, a tick each
    # (1178 -> -398 -> -382), the second time over the cube face at
    # -384 while it is not dirty: only its cube-mate `mate` is, which
    # steps over the same face; `leaver`'s slot goes to `heir` while a
    # tick that computes it is in flight
    far, mate, leaver, heir = (uuid.uuid4() for _ in range(4))

    next_id = 200
    for t in range(205):
        op = rng.random()
        if op < 0.15 and alive:  # client position update
            i = sorted(alive)[int(rng.integers(0, len(alive)))]
            p = rng.uniform(-350, 350, 3)
            send([ent(ids[i], p)])
        elif op < 0.25 and alive:  # leave
            i = sorted(alive)[int(rng.integers(0, len(alive)))]
            alive.discard(i)
            send([Entity(uuid=ids[i])], parameter="entity.remove")
        elif op < 0.35 and next_id < 224:  # join
            i = next_id
            next_id += 1
            alive.add(i)
            send([ent(ids[i], pos[i])])
        if still and alive:
            # the cell's walk: steps inside the cube, one in four of
            # them over a face into the next cube
            walkers = rng.choice(sorted(alive), 5, replace=False)
            for i in walkers:
                cur = planes[0]._pos[planes[0]._slot_of[ids[i]]]
                step = rng.integers(-1, 2, 3) * (
                    16.0 if rng.random() < 0.25 else 0.125
                )
                send([ent(ids[i], np.clip(cur + step, -380, 380))])
        between = None
        if t == 30:
            send([ent(far, (3 * bounds + 8.0, 3.0, 3.0)),
                  ent(mate, (-388.0, 4.0, 4.0))])
        elif t == 31:
            slot = planes[0]._slot_of[far]
            assert planes[0]._pos[slot, 0] == -bounds - 8.0
            assert not planes[0]._window_dirty[slot]
            send([ent(mate, (-383.0, 4.0, 4.0))])
        elif t == 32:
            slot = planes[0]._slot_of[far]
            assert planes[0]._pos[slot, 0] == 8.0 - bounds
            assert planes[0]._cube[slot, 0] != cube_coords_batch(
                np.array([[-bounds - 8.0, 3.0, 3.0]]), 16)[0, 0]
        elif t == 60:
            send([ent(leaver, pos[0] + 1.0)])
        elif t == 61:
            send([ent(leaver, pos[0] + 2.0)])  # dirty: the tick computes it

            def between(pl):
                slot = pl._slot_of[leaver]
                pl.ingest(_ent_msg(owner, [Entity(uuid=leaver)],
                                   parameter="entity.remove"))
                pl.ingest(_ent_msg(owner, [ent(heir, pos[1] + 1.0)]))
                assert pl._slot_of[heir] == slot
        if t == 100:
            # mid-run abort: the in-flight tick drops on BOTH planes
            for pl in planes:
                h = pl.dispatch_tick()
                assert h is not None
                pl.abort_tick()
        frames = [tick(pl, between) for pl in planes]
        cap = planes[0]._cap
        assert planes[0]._cap == planes[1]._cap
        live = planes[0]._live[:cap]
        assert (live == planes[1]._live[:cap]).all()
        assert np.array_equal(
            planes[0]._pos[:cap][live], planes[1]._pos[:cap][live]
        ), f"tick {t}: positions diverged"
        assert np.array_equal(
            planes[0]._cube[:cap][live], planes[1]._cube[:cap][live]
        ), f"tick {t}: cubes diverged"
        assert len(frames[0]) == len(frames[1]), f"tick {t}"
        wires = [sorted(
            getattr(f, "wire", None) or b"" for f, _ in fr
        ) for fr in frames]
        assert wires[0] == wires[1], f"tick {t}: frame bytes diverged"
        for pl in planes:
            _audit_kept_columns(pl)
    on = planes[0]
    assert on.delta_sim_ticks > 100
    assert on.delta_reused > 0
    assert on.delta_mispredicts == 0
    assert planes[1].delta_sim_ticks == 0
    # both ways to the closure ran: the kept column tested with isin
    # (keys written since the last dispatch) and the sorted view
    assert 0 < on.dispatch_scan_rows
    assert on.quantised_rows < planes[1].quantised_rows


def test_delta_tick_host_work_follows_the_dirty_window():
    """A delta tick of a still world quantises no more rows than were
    dirty and, once the key column has stood still for a tick, reads
    no pass as long as the tier; a world with ONE mover pays the
    velocity scan again, and stops paying it when the mover stops."""
    owner = uuid.UUID(int=7)
    metrics = Metrics()
    pl = EntityPlane(
        TpuSpatialBackend(16), None, cube_size=16, k=4, dt=0.05,
        bounds=1000.0, delta_ticks="on", metrics=metrics,
    )
    rng = np.random.default_rng(5)
    corners = rng.permutation(20 ** 3)[:40]
    corners = np.stack(
        [corners % 20, corners // 20 % 20, corners // 400], 1
    ) * 16.0 - 160.0
    pos = np.repeat(corners, 16, axis=0) + rng.uniform(1, 15, (640, 3))
    ids = [uuid.uuid4() for _ in range(640)]
    pl.ingest(_ent_msg(owner, [
        Entity(uuid=ids[i], world_name="w", position=Vector3(*pos[i]))
        for i in range(640)
    ]))
    cap = pl._cap
    assert cap == 1024

    def tick(updates=()):
        """One tick after the given (index, position[, velocity])
        updates: (rows quantised, rows read in tier-long passes)."""
        if updates:
            pl.ingest(_ent_msg(owner, [
                Entity(uuid=ids[u[0]], world_name="w",
                       position=Vector3(*u[1]),
                       flex=_vel_flex(u[2]) if len(u) > 2 else None)
                for u in updates
            ]))
        before = dict(metrics.counters)
        pl.apply(pl.collect_tick(pl.dispatch_tick()))
        return tuple(
            metrics.counters[name] - before.get(name, 0)
            for name in ("sim.quantised_rows", "sim.dispatch_scan_rows")
        )

    def steps(rows, by=0.125):
        return [(i, pl._pos[pl._slot_of[ids[i]]] + by) for i in rows]

    assert tick() == (cap, 0)                 # cold: one full tick
    assert tick() == (0, 0)                   # nothing dirty: a replay
    # the registrations wrote keys: tested with isin this tick, sorted
    # at the next (the column stood still), binary-searched from then
    assert tick(steps([3, 99, 200])) == (3, cap)
    assert tick(steps([3, 99, 200, 401])) == (4, cap)
    assert tick(steps([5, 17, 300])) == (3, 0)
    assert tick(steps(range(0, 640, 64))) == (10, 0)
    assert pl.delta_sim_ticks == 5 and pl.full_sim_ticks == 1
    # a step over a cube face: churn writes a key as the tick applies
    assert tick([(8, pos[8] + (16.0, 0.0, 0.0))]) == (1, 0)
    assert pl.last_churn == 1
    assert tick(steps([8])) == (1, cap)       # stale: isin
    assert tick(steps([8])) == (1, cap)       # stood still: sorted
    assert tick(steps([8])) == (1, 0)
    # one mover: it is dirty every tick, and found by the scan
    moving = (1.0, 0.0, 0.0)
    assert tick([(40, pos[40], moving)]) == (1, cap)
    assert pl._n_moving == 1
    assert tick() == (1, cap)                 # dirty by the scan alone
    assert tick(steps([3])) == (2, cap)
    # it stops: the scan goes with it
    stop = pl._pos[pl._slot_of[ids[40]]].copy()
    assert tick([(40, stop, (0.0, 0.0, 0.0))]) == (1, 0)
    assert pl._n_moving == 0
    assert tick(steps([3])) == (1, 0)
    assert pl.delta_mispredicts == 0
    assert pl.stats()["quantised_rows"] == metrics.counters[
        "sim.quantised_rows"]
    assert pl.stats()["dispatch_scan_rows"] == metrics.counters[
        "sim.dispatch_scan_rows"]


def test_predicted_cubes_replay_the_kernel_reflection():
    """The dispatch predicts a dirty row's landing cube by replaying
    the kernel's integration: ONE reflection a tick, so a still row
    five bounds out lands outside the other wall, and the closure
    audit finds it where it was predicted."""
    owner = uuid.UUID(int=11)
    pl = EntityPlane(TpuSpatialBackend(16), None, cube_size=16, k=4,
                     bounds=392.0, delta_ticks="on")
    eid = uuid.uuid4()
    pl.ingest(_ent_msg(owner, [     # bystanders: one dirty row is no churn
        Entity(uuid=uuid.uuid4(), world_name="w",
               position=Vector3(20.0 * i, 1.0, 1.0)) for i in range(7)
    ]))

    def place_and_tick(x):
        pl.ingest(_ent_msg(owner, [Entity(
            uuid=eid, world_name="w", position=Vector3(x, 3.0, 3.0))]))
        pl.apply(pl.collect_tick(pl.dispatch_tick()))
        return float(pl._pos[pl._slot_of[eid], 0])

    assert place_and_tick(10.0) == 10.0                 # cold: full
    assert place_and_tick(5 * 392.0 + 0.5) == -1176.5   # a delta tick
    assert place_and_tick(-1176.5) == 392.5
    assert pl.delta_sim_ticks == 2 and pl.index_moves == 2
    assert pl.delta_mispredicts == 0


def test_delta_sim_tier_change_falls_back_and_recovers():
    owner = uuid.UUID(int=9)
    be = TpuSpatialBackend(16)
    pl = EntityPlane(be, None, cube_size=16, k=4, delta_ticks="on")
    rng = np.random.default_rng(2)
    pl.ingest(_ent_msg(owner, [
        Entity(uuid=uuid.uuid4(), world_name="w",
               position=Vector3(*rng.uniform(-100, 100, 3)))
        for _ in range(40)
    ]))

    def tick():
        return pl.apply(pl.collect_tick(pl.dispatch_tick()))

    tick()  # cold → full
    tick()  # replay
    assert pl.delta_sim_ticks >= 1
    before_full = pl.full_sim_ticks
    # registration burst past the 256 tier → grow → full fallback
    pl.ingest(_ent_msg(owner, [
        Entity(uuid=uuid.uuid4(), world_name="w",
               position=Vector3(*rng.uniform(-100, 100, 3)))
        for _ in range(300)
    ]))
    tick()
    assert pl._cap > 256
    assert pl.full_sim_ticks == before_full + 1
    tick()  # and delta resumes at the new tier
    assert pl.last_delta_stats.get("fallback") == ""


def test_non_pow2_cube_size_disables_entity_delta():
    be = TpuSpatialBackend(12)
    pl = EntityPlane(be, None, cube_size=12, delta_ticks="on")
    assert not pl._delta_ticks


# endregion

# region: the splice compares before it writes (ISSUE 42)

SPL_K = 8
SPL_TICKS = 20
_SPL_PEERS = [uuid.UUID(int=0x5000 + i) for i in range(5)]
#: one compile a shape for every plane of every case: the planes of a
#: trio close over the same static parameters
_SPL_SHARED: dict = {}


class _NamesTheClosure(EntityPlane):
    """The plane as it was before ISSUE 42: a delta tick names its
    whole closure to the interest manager."""

    def _apply_delta(self, result):
        owed = self._owed
        mark = len(owed[0]) if owed is not None else 0
        out = super()._apply_delta(result)
        if owed is not None:
            del owed[0][mark:]
            owed[0].append(result["rows"])
        return out


def _raw_order(pl):
    """The op's own answer for the plane's columns, recipients nearest
    first: what the plane's tick sorts away."""
    import jax

    from worldql_server_tpu.ops.tick import EntityState, make_tick_fn

    if "op" not in _SPL_SHARED:
        _SPL_SHARED["op"] = jax.jit(make_tick_fn(
            cube_size=16, k=SPL_K, dt=0.05, bounds=1000.0, pallas=False))
    cap = pl._cap
    _, targets, _ = _SPL_SHARED["op"](EntityState(
        pl._pos[:cap].copy(), np.zeros((cap, 3), np.float32),
        pl._wid[:cap].copy(), pl._pid[:cap].copy()))
    return np.asarray(targets)


class _Trio:
    """Three planes fed the same wire, tick by tick: the plane as it is
    (``new``), a twin that names every closure row to its manager as
    the plane did before, and a reference that runs full ticks and
    names nothing (``changed=None``: the whole scan). Ten cubes side by
    side along x, six entities each, five owners, nobody moving."""

    def __init__(self):
        from worldql_server_tpu.interest import InterestManager, ReplayClient

        def make(cls, mode):
            pl = cls(TpuSpatialBackend(16), None, cube_size=16, k=SPL_K,
                     dt=0.05, bounds=1000.0, delta_ticks=mode,
                     metrics=Metrics())
            pl._tick_fn = _SPL_SHARED.setdefault("tick", pl._tick_fn)
            pl.interest = InterestManager(metrics=Metrics())
            return pl

        self.new = make(EntityPlane, "on")
        self.twin = make(_NamesTheClosure, "on")
        self.ref = make(EntityPlane, "off")
        self.planes = (self.new, self.twin, self.ref)
        self.clients = {p: ReplayClient() for p in _SPL_PEERS}
        self.ids: dict = {}      # (cube, j) -> uuid
        self.owner: dict = {}
        self.pos: dict = {}
        self.roster: list = []   # slots allocated or released, this tick
        self.served = 0
        for c in range(10):
            for j in range(6):
                self.register((c, j), (16.0 * c + 2 + 2 * j, 3.0 + j, 5.0),
                              _SPL_PEERS[(c + j) % 5])

    # -- the wire

    def send(self, owner, entities, parameter=None):
        for pl in self.planes:
            pl.ingest(_ent_msg(owner, entities, parameter))

    def register(self, name, xyz, owner, vel=None):
        self.ids[name], self.owner[name] = uuid.uuid4(), owner
        self.place(name, xyz, vel)
        self.roster.append(self.new._slot_of[self.ids[name]])

    def place(self, name, xyz, vel=None, planes=None):
        self.pos[name] = np.asarray(xyz, np.float32)
        ent = Entity(uuid=self.ids[name], world_name="w",
                     position=Vector3(*(float(v) for v in xyz)),
                     flex=_vel_flex(vel) if vel is not None else None)
        for pl in planes or self.planes:
            pl.ingest(_ent_msg(self.owner[name], [ent]))

    def step(self, name, d=(0.125, 0.0, 0.0)):
        self.place(name, self.pos[name] + np.asarray(d, np.float32))

    def remove(self, name):
        self.roster.append(self.new._slot_of[self.ids[name]])
        self.send(self.owner[name], [Entity(uuid=self.ids[name])],
                  parameter="entity.remove")
        del self.pos[name]

    # -- one tick of all three, held to each other

    def tick(self, skip=False, between=None):
        """Returns ``new``'s result and pairs; frames (bytes, order,
        recipients) are the same from all three."""
        out, result = [], None
        for pl in self.planes:
            handle = pl.dispatch_tick()
            assert handle is not None
            if between is not None:
                between(pl)
            res = pl.collect_tick(handle)
            result = result or res
            out.append(pl.apply(res, skip_frames=skip))
        wires = [[(m.wire, list(to)) for m, to in pairs] for pairs in out]
        assert wires[0] == wires[1], "the plane against its old self"
        assert wires[0] == wires[2], "the plane against full ticks"
        for m, to in out[0]:
            for peer in to:
                assert self.clients[peer].apply(m)
        self.served += bool(out[0])
        self.roster.clear()
        return result, out[0]

    def counters(self, pl):
        return pl.interest.metrics.snapshot()["counters"]

    def settle(self):
        """The three managers hold the same ledgers, and they are what
        a client that applied ``new``'s frames holds (as float32 bytes:
        a NaN is a position here)."""
        for peer in _SPL_PEERS:
            held = [pl.interest.ledger(peer, pl._peer_ids[peer])
                    for pl in self.planes]
            assert held[0] == held[1] == held[2]
            got = self.clients[peer].snapshot().get("w", {})
            assert {k.bytes: np.asarray(v, np.float32).tobytes()
                    for k, v in got.items()} == {
                key: pos_b for key, (_w, pos_b) in held[0].items()}
            stats = self.clients[peer].stats()
            assert stats["deltas_refused"] == 0 and stats["gaps_seen"] == 0
        for name in ("interest.rows_diffed", "interest.entries"):
            assert self.counters(self.new)[name] \
                == self.counters(self.twin)[name] \
                == self.counters(self.ref)[name]


def _spl_step_inside(w, t, seen):
    for j in range(3):
        w.step(((t + j) % 10, j))


def _spl_order_only(w, t, seen):
    """One entity a tick jumps inside its cube: its cube-mates' sets
    stand, the order the op hands them in does not."""
    cube, rng = t % 10, np.random.default_rng(t)
    before = _raw_order(w.new)
    w.place((cube, 0), (16.0 * cube + rng.integers(8, 120) / 8.0,
                        rng.integers(8, 120) / 8.0, 5.0))

    def after(result, mover=w.new._slot_of[w.ids[(cube, 0)]]):
        now = _raw_order(w.new)
        order_only = (before != now).any(axis=1) & (
            np.sort(before, axis=1) == np.sort(now, axis=1)).all(axis=1)
        order_only[mover] = False
        assert np.isin(np.flatnonzero(order_only), result["rows"]).all()
        seen["order_only_rows"] = seen.get("order_only_rows", 0) \
            + int(order_only.sum())
        if order_only.any() and result.get("mode") == "delta":
            # the closure held them and the splice named the mover alone
            assert seen["spliced_this_tick"] == 1
    return {"after": after}


def _spl_cube_crossing(w, t, seen):
    if t % 2 == 0 and t // 2 < 9:
        w.step((t // 2, 1), (16.0, 0.0, 0.0))     # into the next cube
    w.step(((t + 5) % 10, 3))

    def after(result):
        seen["churn"] = seen.get("churn", 0) + w.new.last_churn
    return {"after": after}


def _spl_roster(w, t, seen):
    if t % 3 == 0:
        w.register(("new", t), (16.0 * (t % 10) + 9.5, 9.0, 9.0),
                   _SPL_PEERS[t % 5])
    elif t % 3 == 1:
        w.remove(("new", t - 1))
    if t % 6 == 5:                  # a slot released and taken at once
        w.remove((t % 10, 2))
        w.register(("heir", t), (16.0 * (t % 10) + 7.25, 2.0, 2.0),
                   _SPL_PEERS[(t + 1) % 5])
    w.step(((t + 3) % 10, 4))


def _spl_touched_midflight(w, t, seen):
    cube = t % 10
    w.step((cube, 0))

    late = {name: w.pos[name] + np.float32(0.25)
            for name in ((cube, 1), ((cube + 4) % 10, 2))}

    def between(pl):
        # lands after the dispatch: the tick computed both rows (one
        # in its closure, one it replays) from positions the wire has
        # since replaced
        for name, xyz in late.items():
            w.place(name, xyz, planes=[pl])
    return {"between": between}


def _spl_skip_streak(w, t, seen):
    for j in range(2):
        w.step(((t + 2 * j) % 10, j + 1))
    return {"skip": 4 <= t <= 9 or t in (13, 14)}


def _spl_neg_zero_and_nan(w, t, seen):
    if t == 2:
        w.place((0, 0), (-0.0, 3.0, 5.0))   # 0 quantises into cube 0 too
    elif t == 4:
        w.place((1, 0), (float("nan"), 3.0, 5.0))
    elif t == 9:
        w.place((0, 0), (0.0, 3.0, 5.0))    # other bits, the same place
    elif t == 11:
        w.place((1, 0), (float("nan"), 3.0, 5.0))   # the same NaN again
    elif t == 14:
        w.place((1, 0), (18.0, -0.0, 5.0))
    # their cube-mates keep both cubes in the closure
    w.step((0, 1 + t % 4))
    w.step((1, 1 + t % 4))


def _spl_occupancy_over_k(w, t, seen):
    if t == 0:
        for j in range(6):          # 12 in cube 3, k = 8
            w.register(("crowd", j), (16.0 * 3 + 1.5 + j, 12.0, 9.0 + j % 2),
                       _SPL_PEERS[j % 5])
    rng = np.random.default_rng(100 + t)
    w.place((3, t % 6), (16.0 * 3 + rng.integers(8, 120) / 8.0,
                         rng.integers(8, 120) / 8.0, 5.0))

    def after(result):
        slot = w.new._slot_of[w.ids[(3, 0)]]
        assert w.new._last_counts[slot] == 12 > SPL_K
    return {"after": after}


def _spl_replay(w, t, seen):
    if t % 3 != 2:
        w.step((t % 10, 0))
        return {}

    def after(result):
        assert result["mode"] == "replay"
        seen["replays"] = seen.get("replays", 0) + 1
    return {"after": after}


def _spl_abort_then_full(w, t, seen):
    w.step((t % 10, 5))
    if t in (7, 15):
        for pl in w.planes:
            assert pl.dispatch_tick() is not None
            pl.abort_tick()
        w.step(((t + 1) % 10, 5))

        def after(result):
            assert result["mode"] == "full"
            seen["fulls"] = seen.get("fulls", 0) + 1
        return {"after": after}


def _spl_movers(w, t, seen):
    """Somebody integrates: a mover's row differs every tick, and the
    compare costs its one pass and saves nothing for that row."""
    if t == 0:
        for c in (2, 6, 8):
            w.place((c, 0), w.pos[(c, 0)], vel=(2.5, 0.0, 0.0))
    w.step(((t + 1) % 10, 3))

    def after(result):
        if t > 0 and result.get("mode") == "delta":
            assert seen["spliced_this_tick"] >= 3
    return {"after": after}


SPLICE_CASES = {
    "step_inside": _spl_step_inside,
    "order_only": _spl_order_only,
    "cube_crossing": _spl_cube_crossing,
    "roster": _spl_roster,
    "touched_midflight": _spl_touched_midflight,
    "skip_streak": _spl_skip_streak,
    "neg_zero_and_nan": _spl_neg_zero_and_nan,
    "occupancy_over_k": _spl_occupancy_over_k,
    "replay": _spl_replay,
    "abort_then_full": _spl_abort_then_full,
    "movers": _spl_movers,
}


@pytest.mark.parametrize("case", list(SPLICE_CASES))
def test_spliced_rows_serve_the_frames_of_the_whole_closure(case):
    """A delta tick names to the interest manager the closure rows it
    found changed, not the closure. Tick by tick the frames are the
    bytes, in the order and to the recipients, of a twin that names
    every closure row (the plane before ISSUE 42) and of a plane that
    runs full ticks and names nothing; at the end the three ledgers are
    one, and are what a client holds."""
    w = _Trio()
    w.tick()                        # cold: a full tick on every plane
    w.tick()                        # nothing dirty: a replay
    seen: dict = {}
    for t in range(SPL_TICKS):
        plan = SPLICE_CASES[case](w, t, seen) or {}
        before = w.new.spliced_rows
        result, _pairs = w.tick(skip=plan.get("skip", False),
                                between=plan.get("between"))
        seen["spliced_this_tick"] = w.new.spliced_rows - before
        if "after" in plan:
            plan["after"](result)
    # a last served tick settles whatever a shed streak still owed
    w.tick()
    w.settle()

    new, twin = w.new, w.twin
    assert new.delta_mispredicts == 0 == twin.delta_mispredicts
    assert new.delta_sim_ticks >= SPL_TICKS - 2 and w.served >= 10
    assert new.delta_recomputed == twin.delta_recomputed
    # the mechanism engaged: fewer rows written and named than compared
    # (a crossing, a registration or a removal changes the answer of
    # both cubes' rows; a step or a mover that of its own row)
    wide = case in ("cube_crossing", "roster", "occupancy_over_k")
    assert 0 < new.spliced_rows < new.delta_recomputed / (1 if wide else 2)
    assert new.stats()["spliced_rows"] == new.metrics.counters[
        "sim.spliced_rows"] == new.spliced_rows
    scanned = [w.counters(pl)["interest.rows_scanned"] for pl in w.planes]
    assert scanned[0] < scanned[1] < scanned[2]
    assert w.counters(new)["interest.hinted_ticks"] \
        == w.counters(twin)["interest.hinted_ticks"] > 0
    assert w.counters(w.ref).get("interest.hinted_ticks") is None
    if case == "order_only":
        assert seen["order_only_rows"] >= 10
    if case == "cube_crossing":
        assert seen["churn"] >= 9 and new.index_moves == w.ref.index_moves
    if case == "replay":
        assert seen["replays"] >= 6
    if case == "abort_then_full":
        assert seen["fulls"] == 2 and new.full_sim_ticks == 3
    if case == "skip_streak":
        assert new.frames_skipped == 8


def test_spliced_rows_are_the_rows_whose_answer_changed():
    """The counts the benchmark reads: a delta tick's
    ``sim.spliced_rows`` is the number of live rows whose answer
    (recipients as a set with multiplicity, count, position bits)
    differs from what the retained columns held, wherever a full tick
    puts them; the retained columns then equal the full tick's; and the
    manager's ``interest.rows_scanned`` grows by those rows and the
    roster's."""
    w = _Trio()
    w.tick()
    w.tick()
    new, ref = w.new, w.ref
    total = 0
    for t in range(12):
        _spl_roster(w, t, {})
        w.step((t % 10, 0), (0.0, 0.125, 0.0))
        if t % 4 == 1:
            w.step(((t + 2) % 10, 1), (16.0, 0.0, 0.0))
        roster = np.unique(np.asarray(w.roster, np.intp))
        cap = new._cap
        held = [c.copy() for c in
                (new._last_pos, new._last_targets, new._last_counts)]
        truth: dict = {}
        collect = ref.collect_tick

        def spy(handle, collect=collect):
            truth.update(collect(handle))
            return truth
        ref.collect_tick = spy
        scanned0 = w.counters(new)["interest.rows_scanned"]
        spliced0 = new.metrics.counters.get("sim.spliced_rows", 0)
        result, _ = w.tick()
        ref.collect_tick = collect
        assert result["mode"] == "delta" and truth["mode"] == "full"
        live = new._live[:cap]
        differ = live & (
            (held[0].view(np.uint32) != truth["pos"].view(np.uint32)).any(1)
            | (held[1] != truth["targets"]).any(axis=1)
            | (held[2] != truth["counts"]))
        assert differ[result["rows"]].sum() == differ.sum()
        for kept, full in zip(
                (new._last_pos, new._last_targets, new._last_counts),
                (truth["pos"], truth["targets"], truth["counts"])):
            assert np.array_equal(kept[live].view(np.uint32),
                                  np.asarray(full)[live].view(np.uint32))
        spliced = new.metrics.counters["sim.spliced_rows"] - spliced0
        assert spliced == int(differ.sum())
        assert w.counters(new)["interest.rows_scanned"] - scanned0 \
            == len(np.union1d(np.flatnonzero(differ), roster))
        total += spliced
    assert 12 <= total < new.delta_recomputed


# endregion

# region: e2e — mostly-idle world over real ZMQ shows reuse in /metrics


def test_e2e_mostly_idle_world_reuse_fraction_in_metrics():
    """Boot the real server (tpu backend + entity sim + delta auto),
    park a mostly-idle world on it over real ZMQ, and read
    ``wql_delta_reuse_fraction > 0.8`` from a strict-parsed /metrics
    scrape — the ISSUE 13 observability acceptance."""

    async def scenario():
        http_port = free_port()
        config = Config(
            store_url="memory://",
            http_port=http_port,
            ws_enabled=False,
            zmq_server_port=free_port(),
            zmq_server_host="127.0.0.1",
        )
        config.spatial_backend = "tpu"
        config.tick_interval = 0.02
        config.entity_sim = True
        config.entity_k = 4
        config.delta_ticks = "auto"
        config.precompile_tiers = False
        server = WorldQLServer(config)
        await server.start()
        try:
            a = await ZmqClient.connect(config.zmq_server_port)
            b = await ZmqClient.connect(config.zmq_server_port)
            # two IDLE co-cube entities (frames still flow, nothing
            # moves) plus a subscription that never changes cubes
            ea, eb = uuid.uuid4(), uuid.uuid4()
            await a.send(Message(
                instruction=Instruction.LOCAL_MESSAGE,
                world_name="arena",
                entities=[Entity(uuid=ea, world_name="arena",
                                 position=Vector3(1, 2, 3))],
            ))
            await b.send(Message(
                instruction=Instruction.LOCAL_MESSAGE,
                world_name="arena",
                entities=[Entity(uuid=eb, world_name="arena",
                                 position=Vector3(2, 2, 3))],
            ))
            await a.send(Message(
                instruction=Instruction.AREA_SUBSCRIBE,
                world_name="arena", position=Vector3(1, 2, 3),
            ))
            plane = server.entity_plane
            deadline = time.perf_counter() + 10
            while plane.entity_count < 2:
                assert time.perf_counter() < deadline
                await asyncio.sleep(0.02)
            # let the idle world tick: replay ticks accumulate reuse
            deadline = time.perf_counter() + 20
            while plane.delta_reused < 20:
                assert time.perf_counter() < deadline, plane.stats()
                await asyncio.sleep(0.05)

            def get(path):
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{http_port}{path}"
                ) as resp:
                    return resp.read().decode()

            text = await asyncio.to_thread(get, "/metrics")
            types, samples = validate_exposition(text)
            values = {name: value for name, _, value in samples}
            assert types["wql_delta_reuse_fraction"] == "gauge"
            fraction = values["wql_delta_reuse_fraction"]
            assert fraction > 0.8, f"reuse_fraction {fraction}"
            assert values.get("wql_delta_sim_reused", 0) > 0
            await a.close()
            await b.close()
        finally:
            await server.stop()

    run(scenario())


# endregion
