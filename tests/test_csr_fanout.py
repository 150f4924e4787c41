"""CSR result-compaction path (spatial/tpu_backend.py).

The CSR layout is what the bench and distributed delivery consume; the
run-window assembly (counts = RAW run lengths, per-(query, segment)
8-lane-row regions, -1 holes where a lane was tombstoned or
replication-filtered) must be indistinguishable from the dense result
for every workload shape. These tests pin that equivalence against the
dense path and the CPU oracle — through the PRODUCT decoder
(_decode_csr), so the wire layout and its walk cannot drift apart —
including the capacity-overflow sentinel contract.
"""

import uuid

import numpy as np

from worldql_server_tpu.protocol.types import Replication
from worldql_server_tpu.spatial.cpu_backend import CpuSpatialBackend
from worldql_server_tpu.spatial.tpu_backend import TpuSpatialBackend

W = "world"


def _peers(n, base=0):
    return [uuid.UUID(int=base + i + 1) for i in range(n)]


def csr_lists(b, counts, flat, m):
    """Decode through the backend's own CSR walk, mapped back to dense
    peer ids for comparison with dense_lists."""
    lists = b._decode_csr(np.asarray(counts), np.asarray(flat), m)
    return [sorted(b._peer_ids[u] for u in lst) for lst in lists]


def dense_lists(tgt):
    return [sorted(int(t) for t in row if t >= 0) for row in tgt]


def build_hot_cold(hot_cubes=6, hot_occupancy=40, cold=200):
    """Index with a few hot cubes (runs far above one CSR row) and many
    singleton cubes — the Zipf shape the run-window CSR serves."""
    b = TpuSpatialBackend(16, compact_threshold=32)
    rng = np.random.default_rng(3)
    cubes, peers = [], []
    pid = 0
    for h in range(hot_cubes):
        for _ in range(hot_occupancy):
            cubes.append([16 * (h + 1), 16, 16])
            peers.append(uuid.UUID(int=pid + 1))
            pid += 1
    for c in range(cold):
        cubes.append([16 * (c + 1), 16 * 50, 16])
        peers.append(uuid.UUID(int=pid + 1))
        pid += 1
    b.bulk_add_subscriptions(W, peers, np.asarray(cubes, np.int64))
    b.flush()
    b.wait_compaction()
    assert b._base_k > 8  # hot runs span multiple CSR rows
    # cube labels are max-corner multiples: label c covers (c-16, c],
    # so c - 0.5 is a position inside cube c
    return b, np.asarray(cubes, np.float64) - 0.5, peers


def query_batch(b, positions, senders, repl=Replication.EXCEPT_SELF):
    m = len(positions)
    world_ids = np.zeros(m, np.int32)
    sender_ids = np.asarray(
        [b._peer_ids.get(s, -1) for s in senders], np.int32
    )
    repls = np.full(m, int(repl), np.int8)
    return world_ids, np.asarray(positions, np.float64), sender_ids, repls


def test_csr_matches_dense_with_hot_cubes():
    b, sub_pos, peers = build_hot_cold()
    rng = np.random.default_rng(7)
    qidx = rng.integers(0, len(sub_pos), 300)
    batch = query_batch(b, sub_pos[qidx], [peers[i] for i in qidx])

    dense = b.match_arrays(*batch)
    m, res = b.match_arrays_async(*batch, csr_cap=16384)
    counts, flat, total = res
    assert int(total) <= 16384
    got = csr_lists(b, counts, flat, m)
    want = dense_lists(dense)
    assert got == want
    # hot queries really did span multiple CSR rows
    assert max(len(x) for x in want) > 8


def test_csr_matches_dense_across_segments_and_replication():
    """Delta segment + base segment + every replication mode."""
    b, sub_pos, peers = build_hot_cold(hot_cubes=3, hot_occupancy=30)
    # post-compaction adds land in the delta segment, one of them hot
    extra = _peers(25, base=10_000)
    for p in extra:
        b.add_subscription(W, p, (16 * 1, 16, 16))
    b.flush()
    assert b._delta_bundle is not None

    rng = np.random.default_rng(11)
    for repl in Replication:
        qidx = rng.integers(0, len(sub_pos), 120)
        batch = query_batch(
            b, sub_pos[qidx], [peers[i] for i in qidx], repl
        )
        dense = b.match_arrays(*batch)
        m, res = b.match_arrays_async(*batch, csr_cap=8192)
        counts, flat, total = res
        assert csr_lists(b, counts, flat, m) == dense_lists(dense)


def test_csr_agrees_with_cpu_oracle():
    from worldql_server_tpu.protocol.types import Vector3
    from worldql_server_tpu.spatial.backend import LocalQuery

    b, sub_pos, peers = build_hot_cold(hot_cubes=4, hot_occupancy=24)
    cpu = CpuSpatialBackend(16)
    for p, pos in zip(peers, sub_pos):
        cpu.add_subscription(W, p, Vector3(*pos))

    rng = np.random.default_rng(13)
    qidx = rng.integers(0, len(sub_pos), 200)
    senders = [peers[i] for i in qidx]
    batch = query_batch(b, sub_pos[qidx], senders)
    m, res = b.match_arrays_async(*batch, csr_cap=8192)
    counts, flat, _ = res
    got = csr_lists(b, counts, flat, m)
    queries = [
        LocalQuery(W, Vector3(*sub_pos[i]), peers[i],
                   Replication.EXCEPT_SELF)
        for i in qidx
    ]
    for g, want in zip(got, cpu.match_local_batch(queries)):
        assert g == sorted(b._peer_ids[p] for p in want)


def test_capacity_overflow_signals_retry():
    """A row-padded layout that outgrows t_cap → total returns the
    impossible t_cap + 1 so callers retry with doubled capacity —
    never a silently truncated result."""
    hot_cubes = 80
    b, sub_pos, peers = build_hot_cold(
        hot_cubes=hot_cubes, hot_occupancy=20, cold=10
    )
    # one query per hot cube → 80 × ceil(20/8)*8 = 1920 padded slots
    qpos = np.asarray(
        [[16 * (h + 1) - 0.5, 15.5, 15.5] for h in range(hot_cubes)]
    )
    batch = query_batch(b, qpos, [uuid.uuid4()] * hot_cubes)
    m, res = b.match_arrays_async(*batch, csr_cap=1024)
    counts, flat, total = res
    # sentinel (dispatched_cap + 1, where the dispatcher may have
    # raised the requested 1024 to the zone-A floor) — the contract is
    # total > requested cap, never a silently truncated result
    assert int(total) > 1024
    assert int(total) != hot_cubes * 20

    # the documented retry (doubled capacity) succeeds and is exact;
    # counts are RAW run lengths, and with absent senders no lane is
    # filtered, so the raw total is the delivered total
    m, res = b.match_arrays_async(*batch, csr_cap=4096)
    counts, flat, total = res
    assert int(total) == hot_cubes * 20
    dense = b.match_arrays(*batch)
    assert csr_lists(b, counts, flat, m) == dense_lists(dense)


def test_chunked_assembly_boundaries():
    """The zone-B assembly maps over fixed-size row blocks (a full
    2^17 tier + a 2^14 tail tier). Shrink both tiers so tiny indexes
    exercise every split shape — full-only, tail-only, both tiers,
    and a partial final tail block — and pin CSR ≡ dense at each."""
    import jax

    import worldql_server_tpu.spatial.tpu_backend as tb

    b, sub_pos, peers = build_hot_cold(hot_cubes=5, hot_occupancy=28)
    rng = np.random.default_rng(23)
    qidx = rng.integers(0, len(sub_pos), 140)
    batch = query_batch(b, sub_pos[qidx], [peers[i] for i in qidx])
    want = dense_lists(b.match_arrays(*batch))

    old = tb._ZONE_B_CHUNK, tb._ZONE_B_TAIL_CHUNK
    try:
        tb._ZONE_B_CHUNK, tb._ZONE_B_TAIL_CHUNK = 16, 4
        # the jit kernel caches on (nseg, t_cap) and would replay
        # traces made with the full-size tiers
        jax.clear_caches()
        # csr_cap hints sweep rows_cap_b across chunk boundaries:
        # below one tail block, exact full blocks, full+tail, and a
        # ragged final tail block
        for cap in (2048, 3072, 4096, 6144, 8192):
            m, res = b.match_arrays_async(*batch, csr_cap=cap)
            counts, flat, total = res
            if int(total) > cap:
                continue          # undersized hint — retry contract
            assert csr_lists(b, counts, flat, m) == want, cap
    finally:
        tb._ZONE_B_CHUNK, tb._ZONE_B_TAIL_CHUNK = old
        jax.clear_caches()


def test_raw_counts_exceed_filtered_lists():
    """counts are RAW run lengths: a sender inside a hot cube still
    counts itself in counts (its lane ships as a -1 hole under
    EXCEPT_SELF) while the decoded list excludes it."""
    b, sub_pos, peers = build_hot_cold(hot_cubes=1, hot_occupancy=20)
    batch = query_batch(b, sub_pos[:1], [peers[0]])
    m, res = b.match_arrays_async(*batch, csr_cap=2048)
    counts, flat, total = res
    assert int(np.asarray(counts)[0].sum()) == 20      # raw, incl. self
    assert len(csr_lists(b, counts, flat, m)[0]) == 19  # filtered


def test_delivery_path_uses_csr_and_falls_back_dense_on_overflow():
    """dispatch/collect_local_batch (the server's tick path) ships CSR;
    a tick whose fan-out outgrows the capacity hint must deliver
    exactly the same lists via the dense fallback and raise the hint."""
    from worldql_server_tpu.protocol.types import Vector3
    from worldql_server_tpu.spatial.backend import LocalQuery

    b, sub_pos, peers = build_hot_cold(hot_cubes=4, hot_occupancy=40)
    cpu = CpuSpatialBackend(16)
    for p, pos in zip(peers, sub_pos):
        cpu.add_subscription(W, p, Vector3(*pos))

    queries = [
        LocalQuery(W, Vector3(*sub_pos[i]), peers[i],
                   Replication.EXCEPT_SELF)
        for i in range(0, len(sub_pos), 2)
    ]
    want = [sorted(w, key=str) for w in cpu.match_local_batch(queries)]

    def got_lists(res):
        return [sorted(g, key=str) for g in res]

    # normal path (hint is ample)
    assert got_lists(b.match_local_batch(queries)) == want

    # force overflow: a tiny hint makes total > t_cap, taking the
    # dense fallback at collect time
    b._delivery_cap = 1
    handle = b.dispatch_local_batch(queries)
    _, (kind, t_cap, (_, _, total), _), _ = handle
    assert kind == "csr"
    assert int(total) > t_cap  # really overflowed
    got = got_lists(b.collect_local_batch(handle))
    assert got == want
    assert b._delivery_cap > 1  # hint grew for future ticks
    # and the grown hint serves the CSR path again
    assert got_lists(b.match_local_batch(queries)) == want

    # a batch whose capacity hint reaches the true fan-out ceiling
    # (m * sum K) dispatches dense instead — CSR saves nothing there,
    # and a persistent overflow always escapes this way
    b._delivery_cap = 1 << 20
    handle1 = b.dispatch_local_batch(queries[:1])
    assert handle1[1][0] == "dense"
    assert got_lists(b.collect_local_batch(handle1)) == want[:1]

    # ...and the inflated hint decays back toward observed need
    before = b._delivery_cap
    for _ in range(3):
        b.match_local_batch(queries)
    assert b._delivery_cap < before


def _require_devices(n: int):
    import jax
    import pytest

    if jax.device_count() < n:
        pytest.skip(f"needs {n} devices")


def build_hot_cold_sharded(mesh, hot_cubes=6, hot_occupancy=40, cold=200):
    from worldql_server_tpu.parallel import ShardedTpuSpatialBackend

    b = ShardedTpuSpatialBackend(16, mesh, compact_threshold=32)
    cubes, peers = [], []
    pid = 0
    for h in range(hot_cubes):
        for _ in range(hot_occupancy):
            cubes.append([16 * (h + 1), 16, 16])
            peers.append(uuid.UUID(int=pid + 1))
            pid += 1
    for c in range(cold):
        cubes.append([16 * (c + 1), 16 * 50, 16])
        peers.append(uuid.UUID(int=pid + 1))
        pid += 1
    b.bulk_add_subscriptions(W, peers, np.asarray(cubes, np.int64))
    b.flush()
    b.wait_compaction()
    assert b._base_k > 8
    return b, np.asarray(cubes, np.float64) - 0.5, peers


def test_sharded_csr_matches_dense():
    """The mesh kernel's run-window CSR (global raw counts pmax-merged
    over 'space', per-batch-shard flat regions) must equal the dense
    mesh result — including queries whose hot run lives on a single
    space shard."""
    _require_devices(8)
    from worldql_server_tpu.parallel import make_fanout_mesh

    mesh = make_fanout_mesh(2, 4)
    b, sub_pos, peers = build_hot_cold_sharded(mesh)
    # post-compaction delta rows too, one hot
    for p in _peers(20, base=50_000):
        b.add_subscription(W, p, (16 * 2, 16, 16))
    b.flush()
    assert b._delta_bundle is not None

    rng = np.random.default_rng(23)
    for repl in Replication:
        qidx = rng.integers(0, len(sub_pos), 160)
        batch = query_batch(
            b, sub_pos[qidx], [peers[i] for i in qidx], repl
        )
        dense = b.match_arrays(*batch)
        m, res = b.match_arrays_async(*batch, csr_cap=32768)
        counts, flat, total = res
        assert int(total) <= 32768
        assert csr_lists(b, counts, flat, m) == dense_lists(dense)


def test_sharded_capacity_overflow_signals_retry():
    """One batch shard overflowing its local region budget must raise
    the global retry sentinel."""
    _require_devices(8)
    from worldql_server_tpu.parallel import make_fanout_mesh

    mesh = make_fanout_mesh(2, 4)
    hot_cubes = 160
    b, sub_pos, peers = build_hot_cold_sharded(
        mesh, hot_cubes=hot_cubes, hot_occupancy=20, cold=10
    )
    # 160 × 24 = 3840 padded slots split over 2 batch shards — a
    # csr_cap of 2048 gives each shard 1024, well under its ~1920
    qpos = np.asarray(
        [[16 * (h + 1) - 0.5, 15.5, 15.5] for h in range(hot_cubes)]
    )
    batch = query_batch(b, qpos, [uuid.uuid4()] * hot_cubes)
    m, res = b.match_arrays_async(*batch, csr_cap=2048)
    counts, flat, total = res
    assert int(total) > 2048          # sentinel
    assert int(total) != hot_cubes * 20

    m, res = b.match_arrays_async(*batch, csr_cap=16384)
    counts, flat, total = res
    assert int(total) == hot_cubes * 20
    dense = b.match_arrays(*batch)
    assert csr_lists(b, counts, flat, m) == dense_lists(dense)


def test_sharded_tiny_multiseg_tick_with_decayed_cap():
    """A small multi-segment tick after the capacity hint decayed must
    not trip the zone-A floor assert on any batch shard (the global
    floor's slack is per-dispatch, each shard needs its own)."""
    _require_devices(8)
    from worldql_server_tpu.protocol.types import Vector3
    from worldql_server_tpu.spatial.backend import LocalQuery
    from worldql_server_tpu.parallel import make_fanout_mesh

    mesh = make_fanout_mesh(4, 2)
    b, sub_pos, peers = build_hot_cold_sharded(
        mesh, hot_cubes=2, hot_occupancy=12, cold=40
    )
    for p in _peers(6, base=90_000):   # delta segment exists
        b.add_subscription(W, p, (16 * 1, 16, 16))
    b.flush()
    assert b._delta_bundle is not None
    b._delivery_cap = 1                # decayed hint
    queries = [
        LocalQuery(W, Vector3(*sub_pos[i]), peers[i],
                   Replication.EXCEPT_SELF)
        for i in range(5)
    ]
    got = b.match_local_batch(queries)
    assert len(got) == 5 and all(len(g) >= 1 for g in got)


def test_key1_collision_rejected_by_second_key():
    """The exactness contract: a query whose FIRST key matches a
    stored run but whose second key differs (the absent-cube collision
    case, ~2^-64) must resolve empty — on the dense and CSR paths
    alike."""
    from worldql_server_tpu.spatial.hashing import (
        PAD_KEY, QUERY_PAD_KEY2, next_pow2, pad_to,
    )

    b, sub_pos, peers = build_hot_cold(hot_cubes=2, hot_occupancy=20)
    segs, ks, kinds = b._segments()
    # craft queries aimed at REAL stored key1s with corrupted key2s —
    # corrupting the TOP bits, which both the probe's 32-bit verify
    # tag and the binary fallback's full compare reject (a real
    # collision's key2 differs in all bits with overwhelming odds)
    stored_k1 = np.asarray(segs[0][0])[:8].copy()
    stored_k2 = np.asarray(segs[0][1])[:8].copy()
    m = len(stored_k1)
    cap = next_pow2(m)
    queries = (
        pad_to(stored_k1, cap, PAD_KEY),
        pad_to(stored_k2 ^ (np.int64(0x5A5A) << np.int64(40)), cap,
               QUERY_PAD_KEY2),
        pad_to(np.full(m, -1, np.int32), cap, np.int32(-1)),
        pad_to(np.zeros(m, np.int8), cap, np.int8(0)),
    )
    dense = np.asarray(b._dispatch(queries, segs, ks, kinds))[:m]
    assert (dense == -1).all()
    counts, flat, total = b._dispatch_csr(queries, segs, ks, kinds, 1024)
    assert int(total) == 0 and int(np.asarray(counts)[:m].sum()) == 0
    # and the same queries with the TRUE key2 resolve non-empty
    queries_ok = (queries[0], pad_to(stored_k2, cap, QUERY_PAD_KEY2),
                  queries[2], queries[3])
    dense_ok = np.asarray(b._dispatch(queries_ok, segs, ks, kinds))[:m]
    assert (dense_ok >= 0).any()


def test_sharded_between_caps_total_decodes_without_dense_reresolve():
    """ADVICE r5 (parallel/sharded_backend.py): the sharded dispatch
    used to raise t_cap above the value recorded in the payload, so a
    tick whose total landed between the recorded cap and the kernel's
    raised cap failed collect_local_batch's sentinel test and took a
    spurious dense re-resolve EVERY tick. The per-shard floor now runs
    through ``_csr_effective_cap`` before the payload records it: a
    between-caps total must decode directly — no dense fallback — and
    still match the dense result exactly."""
    _require_devices(8)
    from worldql_server_tpu.protocol.types import Vector3
    from worldql_server_tpu.spatial.backend import LocalQuery
    from worldql_server_tpu.spatial.hashing import next_pow2
    from worldql_server_tpu.spatial.tpu_backend import CSR_ROW
    from worldql_server_tpu.parallel import make_fanout_mesh

    mesh = make_fanout_mesh(8, 1)  # batch-heavy: big per-shard floor
    b, sub_pos, peers = build_hot_cold_sharded(
        mesh, hot_cubes=16, hot_occupancy=40, cold=40
    )
    # hot delta segment in an UNQUERIED cube: nseg=2 and a fan-out
    # ceiling high enough that the CSR path stays selected
    for p in _peers(30, base=70_000):
        b.add_subscription(W, p, (16 * 1, 16 * 50, 16))
    b.flush()
    assert b._delta_bundle is not None

    b._delivery_cap = 1  # decayed hint: the floors decide the cap
    # the band exists where the per-shard floor passes the unsharded
    # one: at a query tier of 16 over 8 batch shards. The mesh's own
    # smallest tier (32, PR 32) closes it, so pin the tier this guards
    b.MIN_QUERY_TIER = 8
    m = 16
    queries = [
        LocalQuery(W, Vector3(*sub_pos[h * 40]), uuid.uuid4(),
                   Replication.EXCEPT_SELF)
        for h in range(m)
    ]

    handle = b.dispatch_local_batch(queries)
    _, payload, _ = handle
    assert payload[0] == "csr", "floors must not reach the dense ceiling"
    recorded_cap = payload[1]
    total = int(payload[2][2])
    # the tick really sits in the between-caps band the bug covered:
    # above the UNSHARDED floor the payload used to record ...
    segs, ks, _ = b._segments()
    base_floor = next_pow2(max(
        b._delivery_cap, CSR_ROW * b._query_cap(m) * len(segs) + 64
    ))
    assert base_floor < total <= recorded_cap

    calls: list[int] = []
    real_dispatch = b._dispatch

    def counting_dispatch(*args, **kwargs):
        calls.append(1)
        return real_dispatch(*args, **kwargs)

    b._dispatch = counting_dispatch
    got = b.collect_local_batch(handle)
    b._dispatch = real_dispatch
    assert calls == [], "between-caps total must not dense re-resolve"

    # and the decoded fan-out is exactly the dense result
    batch = query_batch(
        b, [sub_pos[h * 40] for h in range(m)], [uuid.uuid4()] * m
    )
    want = dense_lists(b.match_arrays(*batch))
    assert [sorted(b._peer_ids[u] for u in lst) for lst in got] == want
