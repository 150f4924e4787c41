"""Interest-managed fan-out (ISSUE 18): per-recipient delta frames,
LOD cadence tiers, and per-peer bandwidth budgets.

The contract under test, from ``interest/manager.py``'s docstring:
every frame is stamped ``<kind>:<epoch>:<seq>`` with seq contiguous
per peer within an epoch; every loss path lands in ``mark_resync`` and
forces the next frame full under a bumped epoch; LOD deferral and
bandwidth deferral are LOSSLESS (the diff accumulates, nothing is
truncated); and the :class:`ReplayClient` oracle proves it — its
``deltas_refused`` counter staying at zero IS the "no recipient ever
applies a delta against a frame it never got" guarantee.

The churn property at the bottom drives a REAL ``EntityPlane`` (full
wire ingest + device ticks, ``--delta-ticks on`` variant included) and
checks replayed state against the ground-truth visible set — the exact
state the ``--interest off`` stream conveys — every tick.
"""

import itertools
import random
import uuid

import numpy as np
import pytest

from worldql_server_tpu.engine.config import Config
from worldql_server_tpu.engine.metrics import Metrics
from worldql_server_tpu.entities import EntityPlane
from worldql_server_tpu.interest import (
    InterestManager,
    ReplayClient,
    parse_stamp,
    stamp,
)
from worldql_server_tpu.interest.manager import (
    DEMOTE_KEYFRAME,
    FRAME_CHUNK,
    pack_entries,
    PARAM_DELTA,
    PARAM_FULL,
    PARAM_FULL_CONT,
)
from worldql_server_tpu.interest.replay import LegacyClient
from worldql_server_tpu.protocol import deserialize_message


# region: stamp grammar


def test_delivering_a_pre_encoded_frame_never_decodes_it():
    """The delivery path reads ``trace_ctx`` off every message; on a
    pre-encoded frame that fell through to ``__getattr__`` and decoded
    the whole frame to answer None — a million Entity objects a tick at
    100K entities. These bytes are no message: a decode would raise."""
    import asyncio

    from worldql_server_tpu.engine.peers import PeerMap
    from worldql_server_tpu.entities.plane import WireFrame
    from worldql_server_tpu.interest.manager import _WireFrame

    for frame in (WireFrame(b"\xff" * 8), _WireFrame(b"\xff" * 8)):
        pairs = [(frame, [uuid.uuid4()])]
        assert asyncio.run(PeerMap().deliver_batch(pairs)) == 0
        assert frame.trace_ctx is None


def test_stamp_roundtrip_and_fixed_width():
    s = stamp(PARAM_DELTA, 7, 300)
    assert s == "entity.frame.delta:00000007:0000012c"
    assert parse_stamp(s) == (PARAM_DELTA, 7, 300)
    # fixed width holds across the whole u32 range — that is what lets
    # a cohort template be byte-patched per peer
    assert len(stamp(PARAM_FULL, 0, 0)) == len(stamp(PARAM_FULL, 2**32 - 1, 1))
    assert parse_stamp(stamp(PARAM_FULL_CONT, 1, 2)) == (PARAM_FULL_CONT, 1, 2)


def test_parse_stamp_rejects_unstamped_parameters():
    assert parse_stamp("entity.frame") is None          # legacy frame
    assert parse_stamp("entity.frame.delta") is None    # bare kind
    assert parse_stamp("entity.frame.delta:zz:00") is None
    assert parse_stamp("entity.remove") is None
    assert parse_stamp(None) is None


# endregion

# region: fake plane (the five columns build_pairs reads)


class FakePlane:
    """Just the plane surface the manager touches: live/pos/uuid/world
    columns plus the peer registry. No device, no index."""

    def __init__(self, cap=2048, worlds=("arena",)):
        self._cap = cap
        self._live = np.zeros(cap, bool)
        self._pos = np.zeros((cap, 3), np.float32)
        self._uuid_bytes = np.zeros((cap, 16), np.uint8)
        self._wid = np.full(cap, -1, np.int32)
        self._world_names = list(worlds)
        self._peer_ids: dict[uuid.UUID, int] = {}
        self._peer_uuids: list[uuid.UUID] = []
        self._peer_slots: dict[int, set[int]] = {}
        self._wire = None  # object encode path

    def pid(self, peer: uuid.UUID) -> int:
        p = self._peer_ids.get(peer)
        if p is None:
            p = self._peer_ids[peer] = len(self._peer_uuids)
            self._peer_uuids.append(peer)
        return p

    def put(self, slot, ent, pos, wid=0, owner=None):
        self._live[slot] = True
        self._uuid_bytes[slot] = np.frombuffer(ent.bytes, np.uint8)
        self._pos[slot] = pos
        self._wid[slot] = wid
        if owner is not None:
            self._peer_slots.setdefault(self.pid(owner), set()).add(slot)

    def drop(self, slot):
        self._live[slot] = False


def run_tick(mgr, plane, vis):
    """One manager tick: ``vis`` maps entity slot -> recipient pids."""
    cap = plane._cap
    k = max((len(v) for v in vis.values()), default=1)
    targets = np.full((cap, k), -1, np.int64)
    for slot, pids in vis.items():
        targets[slot, : len(pids)] = pids
    return mgr.build_pairs(plane, plane._pos, targets, cap)


def frames_for(pairs, peer):
    return [m for m, targets in pairs if peer in targets]


def params(pairs):
    return [m.parameter for m, _ in pairs]


# endregion

# region: delta lifecycle on the fake plane


def test_first_contact_quiet_delta_tombstone_resync_flow():
    plane = FakePlane()
    mgr = InterestManager()
    viewer = uuid.uuid4()
    vp = plane.pid(viewer)
    e1, e2 = uuid.uuid4(), uuid.uuid4()
    plane.put(0, e1, (1.0, 0.0, 0.0))
    plane.put(1, e2, (2.0, 0.0, 0.0))
    rc = ReplayClient()

    # tick 1: first contact is a keyframe opening epoch 1 at seq 0
    pairs = run_tick(mgr, plane, {0: [vp], 1: [vp]})
    assert params(pairs) == [stamp(PARAM_FULL, 1, 0)]
    for m in frames_for(pairs, viewer):
        assert rc.apply(m)
    assert rc.snapshot() == {"arena": {
        e1: (1.0, 0.0, 0.0), e2: (2.0, 0.0, 0.0),
    }}

    # tick 2: nothing moved — no frame, no seq consumed
    assert run_tick(mgr, plane, {0: [vp], 1: [vp]}) == []

    # tick 3: one entity moves — a delta carrying only that entity
    plane._pos[0] = (5.0, 0.0, 0.0)
    pairs = run_tick(mgr, plane, {0: [vp], 1: [vp]})
    assert params(pairs) == [stamp(PARAM_DELTA, 1, 1)]
    assert len(pairs[0][0].entities) == 1
    rc.apply(pairs[0][0])
    assert rc.worlds["arena"][e1] == (5.0, 0.0, 0.0)
    assert rc.worlds["arena"][e2] == (2.0, 0.0, 0.0)

    # tick 4: e2 leaves — a delta tombstone deletes it client-side
    pairs = run_tick(mgr, plane, {0: [vp]})
    assert params(pairs) == [stamp(PARAM_DELTA, 1, 2)]
    rc.apply(pairs[0][0])
    assert set(rc.worlds["arena"]) == {e1}

    # loss: the next frame opens epoch 2 with a complete keyframe
    mgr.mark_resync(viewer)
    pairs = run_tick(mgr, plane, {0: [vp]})
    assert params(pairs) == [stamp(PARAM_FULL, 2, 0)]
    rc.apply(pairs[0][0])
    assert rc.snapshot() == {"arena": {e1: (5.0, 0.0, 0.0)}}
    assert rc.stats()["deltas_refused"] == 0
    assert rc.stats()["gaps_seen"] == 0
    assert mgr.stats()["resyncs"] == 1


def test_mark_resync_is_idempotent_and_unknown_peer_safe():
    mgr = InterestManager()
    mgr.mark_resync(uuid.uuid4())          # never seen: no-op
    assert mgr.resyncs == 0
    plane = FakePlane()
    viewer = uuid.uuid4()
    vp = plane.pid(viewer)
    plane.put(0, uuid.uuid4(), (1, 1, 1))
    run_tick(mgr, plane, {0: [vp]})
    mgr.mark_resync(viewer)
    mgr.mark_resync(viewer)                # second is a no-op
    assert mgr.resyncs == 1


def test_world_hop_tombstones_old_world_and_enters_new():
    plane = FakePlane(worlds=("arena", "lobby"))
    mgr = InterestManager()
    viewer = uuid.uuid4()
    vp = plane.pid(viewer)
    ent = uuid.uuid4()
    plane.put(0, ent, (1, 0, 0), wid=0)
    rc = ReplayClient()
    for m, _ in run_tick(mgr, plane, {0: [vp]}):
        rc.apply(m)
    plane._wid[0] = 1
    pairs = run_tick(mgr, plane, {0: [vp]})
    # leave(arena) + enter(lobby), contiguous seqs, both applied
    kinds = [parse_stamp(m.parameter)[0] for m, _ in pairs]
    assert kinds == [PARAM_DELTA, PARAM_DELTA]
    for m, _ in pairs:
        assert rc.apply(m)
    assert rc.snapshot() == {"lobby": {ent: (1.0, 0.0, 0.0)}}


def test_vacated_world_ships_empty_full_clear_marker():
    plane = FakePlane()
    mgr = InterestManager()
    viewer = uuid.uuid4()
    vp = plane.pid(viewer)
    plane.put(0, uuid.uuid4(), (1, 1, 1))
    rc = ReplayClient()
    for m, _ in run_tick(mgr, plane, {0: [vp]}):
        rc.apply(m)
    assert rc.snapshot() != {}
    # the peer's ledger survives a resync even when nothing is visible
    # anymore: the new epoch must CLEAR the stale world
    mgr.mark_resync(viewer)
    plane.drop(0)
    pairs = run_tick(mgr, plane, {})
    assert params(pairs) == [stamp(PARAM_FULL, 2, 0)]
    assert pairs[0][0].entities in (None, [])
    rc.apply(pairs[0][0])
    assert rc.snapshot() == {}


def test_cohort_dedup_shares_template_across_recipients():
    plane = FakePlane()
    mgr = InterestManager()
    a, b = uuid.uuid4(), uuid.uuid4()
    pa, pb = plane.pid(a), plane.pid(b)
    plane.put(0, uuid.uuid4(), (3, 3, 3))
    pairs = run_tick(mgr, plane, {0: [pa, pb]})
    # identical content -> ONE encode, two stamped copies
    assert len(pairs) == 2
    assert mgr.templates_reused == 1
    wires = {m.wire for m, _ in pairs}
    assert len(wires) == 1       # same epoch:seq cursor position too
    ra, rb = ReplayClient(), ReplayClient()
    for m in frames_for(pairs, a):
        ra.apply(m)
    for m in frames_for(pairs, b):
        rb.apply(m)
    assert ra.snapshot() == rb.snapshot() != {}

    # next tick: one mover, still one template for both recipients —
    # and the per-peer stamp patch touches ONLY the stamp bytes
    plane._pos[0] = (4, 4, 4)
    pairs = run_tick(mgr, plane, {0: [pa, pb]})
    assert len(pairs) == 2 and mgr.templates_reused == 2
    for m, targets in pairs:
        (ra if a in targets else rb).apply(m)
    assert ra.snapshot() == rb.snapshot()
    assert ra.stats()["deltas_refused"] == rb.stats()["deltas_refused"] == 0


def test_a_cohort_is_its_every_byte_not_its_first_entry():
    """Two frames that start alike (same kind, world, length, first
    uuid and first position: the cheap mark the lookup goes by) and
    differ further on are two cohorts, this tick and the next."""
    plane = FakePlane()
    mgr = InterestManager()
    a, b = uuid.UUID(int=101), uuid.UUID(int=102)
    pa, pb = plane.pid(a), plane.pid(b)
    shared, only_a, only_b = (uuid.UUID(int=i) for i in (1, 2, 3))
    plane.put(0, shared, (1, 1, 1))
    plane.put(1, only_a, (2, 2, 2))
    plane.put(2, only_b, (2, 2, 2))
    vis = {0: [pa, pb], 1: [pa], 2: [pb]}
    pairs = run_tick(mgr, plane, vis)
    assert mgr.templates_reused == 0
    seen = {t[0]: {e.uuid for e in m.entities} for m, t in pairs}
    assert seen == {a: {shared, only_a}, b: {shared, only_b}}
    # next tick: the second entry of each moves, to the same place
    plane._pos[1] = plane._pos[2] = (5, 5, 5)
    plane._pos[0] = (4, 4, 4)
    pairs = run_tick(mgr, plane, vis)
    assert mgr.templates_reused == 0 and len(pairs) == 2
    seen = {t[0]: {e.uuid for e in m.entities} for m, t in pairs}
    assert seen == {a: {shared, only_a}, b: {shared, only_b}}


def test_a_template_of_the_last_tick_serves_the_same_content_again():
    """A keyframe a late joiner needs is, byte for byte, the one the
    first peer got a tick ago: last tick's template is reused."""
    plane = FakePlane()
    mgr = InterestManager()
    a, b = uuid.uuid4(), uuid.uuid4()
    pa, pb = plane.pid(a), plane.pid(b)
    plane.put(0, uuid.uuid4(), (3, 3, 3))
    (first, _), = run_tick(mgr, plane, {0: [pa]})
    assert mgr.templates_reused == 0
    (second, to), = run_tick(mgr, plane, {0: [pa, pb]})
    assert to == [b] and mgr.templates_reused == 1
    assert second.wire == first.wire        # both at epoch 1, seq 0
    # ... and is gone a tick later: the cache holds one tick
    c = uuid.uuid4()
    run_tick(mgr, plane, {0: [pa, pb]})
    run_tick(mgr, plane, {0: [pa, pb, plane.pid(c)]})
    assert mgr.templates_reused == 1


def test_desynced_cursor_stamps_diverge_but_both_converge():
    plane = FakePlane()
    mgr = InterestManager()
    a, b = uuid.uuid4(), uuid.uuid4()
    pa, pb = plane.pid(a), plane.pid(b)
    plane.put(0, uuid.uuid4(), (3, 3, 3))
    ra, rb = ReplayClient(), ReplayClient()
    # a joins one tick before b: cursors diverge, content still shared
    for m in frames_for(run_tick(mgr, plane, {0: [pa]}), a):
        ra.apply(m)
    plane._pos[0] = (4, 4, 4)
    pairs = run_tick(mgr, plane, {0: [pa, pb]})
    by_peer = {tuple(t): m.parameter for m, t in pairs}
    assert by_peer[(a,)] == stamp(PARAM_DELTA, 1, 1)
    assert by_peer[(b,)] == stamp(PARAM_FULL, 1, 0)
    for m, targets in pairs:
        (ra if a in targets else rb).apply(m)
    assert ra.snapshot() == rb.snapshot()


# endregion

# region: LOD cadence


def test_far_updates_defer_to_cadence_and_never_drop():
    plane = FakePlane()
    mgr = InterestManager(near_radius=10.0, far_every_k=4)
    viewer = uuid.uuid4()
    vp = plane.pid(viewer)
    # the viewer's own entity anchors its subscription center
    plane.put(0, uuid.uuid4(), (0, 0, 0), owner=viewer)
    near, far = uuid.uuid4(), uuid.uuid4()
    plane.put(1, near, (1, 0, 0))
    plane.put(2, far, (100, 0, 0))
    vis = {0: [vp], 1: [vp], 2: [vp]}
    rc = ReplayClient()
    for m, _ in run_tick(mgr, plane, vis):
        rc.apply(m)
    assert rc.worlds["arena"][far] == (100.0, 0.0, 0.0)

    # move BOTH every tick for a full far period: the near entity
    # updates every tick, the far one exactly once — and its one
    # update carries the LATEST position (deferral is lossless)
    far_updates = 0
    for t in range(1, 5):
        plane._pos[1] = (1.0 + t, 0.0, 0.0)
        plane._pos[2] = (100.0 + t, 0.0, 0.0)
        for m, _ in run_tick(mgr, plane, vis):
            before = rc.worlds["arena"].get(far)
            rc.apply(m)
            if rc.worlds["arena"].get(far) != before:
                far_updates += 1
        assert rc.worlds["arena"][near] == (1.0 + t, 0.0, 0.0)
    assert far_updates == 1
    # the one update carried the position AS OF its due tick; the tail
    # move is deferred, not dropped — it ships on the next due tick
    assert rc.worlds["arena"][far] == (103.0, 0.0, 0.0)
    for _ in range(4):
        for m, _ in run_tick(mgr, plane, vis):
            rc.apply(m)
    assert rc.worlds["arena"][far] == (104.0, 0.0, 0.0)
    assert rc.stats()["gaps_seen"] == 0
    st = mgr.stats()
    assert st["near"] >= 1 and st["far"] >= 1


def test_far_departure_defers_to_cadence_then_tombstones():
    plane = FakePlane()
    mgr = InterestManager(near_radius=10.0, far_every_k=4)
    viewer = uuid.uuid4()
    vp = plane.pid(viewer)
    plane.put(0, uuid.uuid4(), (0, 0, 0), owner=viewer)
    far = uuid.uuid4()
    plane.put(1, far, (50, 0, 0))
    rc = ReplayClient()
    for m, _ in run_tick(mgr, plane, {0: [vp], 1: [vp]}):
        rc.apply(m)
    assert far in rc.worlds["arena"]
    plane.drop(1)
    # the leave ships on the next far-due tick, not instantly — but it
    # DOES ship within one full period
    for _ in range(4):
        for m, _ in run_tick(mgr, plane, {0: [vp]}):
            rc.apply(m)
    assert far not in rc.worlds.get("arena", {})
    assert rc.stats()["deltas_refused"] == 0


def test_governor_shed_widens_far_cadence_and_degrades_near():
    mgr = InterestManager(near_radius=10.0, far_every_k=4)
    assert mgr.stats()["far_every_k"] == 4
    mgr.note_governor(2, False)
    assert mgr.stats()["far_every_k"] == 16
    mgr.note_governor(9, True)          # level clamps at 3
    assert mgr.stats()["far_every_k"] == 32

    # degraded tick tier halves the near cadence but stays lossless
    plane = FakePlane()
    viewer = uuid.uuid4()
    vp = plane.pid(viewer)
    ent = uuid.uuid4()
    plane.put(0, ent, (1, 0, 0))
    mgr2 = InterestManager()
    rc = ReplayClient()
    for m, _ in run_tick(mgr2, plane, {0: [vp]}):
        rc.apply(m)
    mgr2.note_governor(0, True)
    sent = 0
    for t in range(1, 5):
        plane._pos[0] = (1.0 + t, 0.0, 0.0)
        pairs = run_tick(mgr2, plane, {0: [vp]})
        sent += len(pairs)
        for m, _ in pairs:
            rc.apply(m)
    assert sent == 2                    # every other tick
    # the tail move rides the next due tick — deferred, never lost
    for m, _ in run_tick(mgr2, plane, {0: [vp]}):
        rc.apply(m)
    assert rc.worlds["arena"][ent] == (5.0, 0.0, 0.0)


# endregion

# region: bandwidth budgets


def bw_manager(rate=100):
    now = [1000.0]
    mgr = InterestManager(bandwidth_bytes=rate, clock=lambda: now[0])
    return mgr, now


def test_unaffordable_tick_defers_whole_and_walks_demote_ladder():
    mgr, now = bw_manager()
    plane = FakePlane()
    viewer = uuid.uuid4()
    vp = plane.pid(viewer)
    ent = uuid.uuid4()
    plane.put(0, ent, (1, 0, 0))
    rc = ReplayClient()
    for m, _ in run_tick(mgr, plane, {0: [vp]}):
        rc.apply(m)
    st = mgr._peers[viewer]

    # drain the bucket; with a frozen clock nothing refills
    st.tokens = 0.0
    plane._pos[0] = (2, 0, 0)
    assert run_tick(mgr, plane, {0: [vp]}) == []       # deferred whole
    assert st.demote == 1 and mgr.deferrals == 1
    assert st.seq == 1                                  # no seq burned
    # at demote=FAR the retry waits for the far cadence; walk ticks
    # (still broke) until the due-tick attempt escalates the ladder
    for _ in range(mgr.far_every_k):
        if st.demote == DEMOTE_KEYFRAME:
            break
        plane._pos[0] += 1.0
        assert run_tick(mgr, plane, {0: [vp]}) == []
        st.tokens = 0.0
    assert st.demote == DEMOTE_KEYFRAME and mgr.bytes_shed == 0

    # refill: the peer is in keyframe-only mode, so the catch-up frame
    # is a FULL on the far cadence — and it carries the latest state
    st.tokens = mgr.bandwidth_burst
    for _ in range(mgr.far_every_k):
        plane._pos[0] = (9, 0, 0)
        for m, _ in run_tick(mgr, plane, {0: [vp]}):
            rc.apply(m)
    assert rc.worlds["arena"][ent] == (9.0, 0.0, 0.0)
    assert rc.stats()["deltas_refused"] == 0
    assert st.demote < DEMOTE_KEYFRAME                  # walked back up


def test_bytes_shed_counts_only_unaffordable_keyframes():
    mgr, now = bw_manager()
    plane = FakePlane()
    viewer = uuid.uuid4()
    vp = plane.pid(viewer)
    plane.put(0, uuid.uuid4(), (1, 0, 0))
    run_tick(mgr, plane, {0: [vp]})
    st = mgr._peers[viewer]
    st.tokens = 0.0
    st.demote = DEMOTE_KEYFRAME
    # keyframe-only + unaffordable on a due tick: the ONE shed point
    shed = 0
    for _ in range(mgr.far_every_k + 1):
        plane._pos[0] += 1.0
        run_tick(mgr, plane, {0: [vp]})
        shed = mgr.bytes_shed
        st.tokens = 0.0
    assert shed > 0
    assert mgr.stats()["bytes_shed"] == shed


def test_zero_budget_means_no_bandwidth_gating():
    plane = FakePlane()
    mgr = InterestManager(bandwidth_bytes=0)
    viewer = uuid.uuid4()
    vp = plane.pid(viewer)
    plane.put(0, uuid.uuid4(), (1, 0, 0))
    for t in range(5):
        plane._pos[0] = (1.0 + t, 0, 0)
        assert len(run_tick(mgr, plane, {0: [vp]})) == 1
    assert mgr.deferrals == 0 and mgr.bytes_shed == 0


# endregion

# region: chunking + oversized deltas


def test_large_keyframe_chunks_full_then_fullc():
    plane = FakePlane(cap=2048)
    mgr = InterestManager()
    viewer = uuid.uuid4()
    vp = plane.pid(viewer)
    n = FRAME_CHUNK + 40
    ents = [uuid.uuid4() for _ in range(n)]
    vis = {}
    for i, e in enumerate(ents):
        plane.put(i, e, (float(i), 0, 0))
        vis[i] = [vp]
    pairs = run_tick(mgr, plane, vis)
    kinds = [parse_stamp(m.parameter)[0] for m, _ in pairs]
    assert kinds == [PARAM_FULL, PARAM_FULL_CONT]
    assert [parse_stamp(m.parameter)[2] for m, _ in pairs] == [0, 1]
    rc = ReplayClient()
    for m, _ in pairs:
        assert rc.apply(m)
    assert len(rc.worlds["arena"]) == n


def test_oversized_delta_ships_as_consecutive_delta_frames():
    plane = FakePlane(cap=2048)
    mgr = InterestManager()
    viewer = uuid.uuid4()
    vp = plane.pid(viewer)
    n = FRAME_CHUNK + 40
    vis = {}
    for i in range(n):
        plane.put(i, uuid.uuid4(), (float(i), 0, 0))
        vis[i] = [vp]
    rc = ReplayClient()
    for m, _ in run_tick(mgr, plane, vis):
        rc.apply(m)
    # every entity moves: a >FRAME_CHUNK delta is chunked like a
    # keyframe is, in the SAME epoch — escalating it to a keyframe made
    # a peer that fell behind fall further (20K-entry keyframes a tick
    # at 100K entities once a tick outgrew FRAME_CHUNK updates a peer)
    plane._pos[:n, 1] = 7.0
    pairs = run_tick(mgr, plane, vis)
    stamps = [parse_stamp(m.parameter) for m, _ in pairs]
    assert stamps == [(PARAM_DELTA, 1, 2), (PARAM_DELTA, 1, 3)]
    assert [len(m.entities) for m, _ in pairs] == [FRAME_CHUNK, 40]
    for m, _ in pairs:
        assert rc.apply(m)
    assert all(
        p == (float(i), 7.0, 0.0)
        for i, p in ((i, rc.worlds["arena"][uuid.UUID(
            bytes=plane._uuid_bytes[i].tobytes()
        )]) for i in range(n))
    )
    assert rc.stats()["deltas_refused"] == 0
    assert mgr.stats()["resyncs"] == 0


# endregion

# region: the snapshot diff (synced peers keep no ledger of their own)


def ledger_of(mgr, plane, peer):
    """uuid -> position, read off the manager's side of the contract."""
    return {
        uuid.UUID(bytes=key): tuple(
            float(v) for v in np.frombuffer(pos_b, np.float32))
        for key, (_wid, pos_b) in mgr.ledger(
            peer, plane._peer_ids[peer]).items()
    }


def test_a_synced_peer_holds_its_view_of_the_snapshot_and_no_dict():
    plane = FakePlane()
    mgr = InterestManager()
    a, b = uuid.uuid4(), uuid.uuid4()
    pa, pb = plane.pid(a), plane.pid(b)
    ents = [uuid.uuid4() for _ in range(3)]
    for i, e in enumerate(ents):
        plane.put(i, e, (float(i), 0, 0))
    rca, rcb = ReplayClient(), ReplayClient()
    vis = {0: [pa, pb], 1: [pa], 2: [pb]}
    for m, targets in run_tick(mgr, plane, vis):
        (rca if a in targets else rcb).apply(m)
    # first contact walked each ledger once; from here the snapshot IS it
    for peer in (a, b):
        assert mgr._peers[peer].synced and mgr._peers[peer].state is None
    assert ledger_of(mgr, plane, a) == rca.snapshot()["arena"] == {
        ents[0]: (0.0, 0, 0), ents[1]: (1.0, 0, 0)}
    assert ledger_of(mgr, plane, b) == rcb.snapshot()["arena"]
    assert mgr.stats()["near"] == 4            # rows x recipients

    # one row moves: an entry at each recipient of THAT row, nobody else
    plane._pos[1] = (1.0, 5.0, 0.0)
    pairs = run_tick(mgr, plane, vis)
    assert [(parse_stamp(m.parameter)[0], t) for m, t in pairs] == [
        (PARAM_DELTA, [a])]
    assert [e.uuid for e in pairs[0][0].entities] == [ents[1]]

    # a loss: the peer's view is taken off the snapshot for the keyframe
    # walk, then dropped again once the keyframe is committed
    mgr.mark_resync(b)
    plane._pos[2] = (2.0, 5.0, 0.0)
    pairs = run_tick(mgr, plane, vis)
    assert [parse_stamp(m.parameter) for m, _ in pairs] == [(PARAM_FULL, 2, 0)]
    rcb.apply(pairs[0][0])
    assert mgr._peers[b].synced and mgr._peers[b].state is None
    assert ledger_of(mgr, plane, b) == rcb.snapshot()["arena"] == {
        ents[0]: (0.0, 0, 0), ents[2]: (2.0, 5.0, 0)}
    assert mgr.ledger(uuid.uuid4(), 7) == {}


def test_reordered_targets_and_a_second_pid_cost_no_one_an_entry():
    """The kNN lists a row's recipients nearest first, and a recipient
    once per neighbour it owns: the order and the repeats change with
    every step of a cube-mate; the SET is what a peer's view is."""
    plane = FakePlane()
    mgr = InterestManager()
    a, b, c = (uuid.uuid4() for _ in range(3))
    pa, pb, pc = plane.pid(a), plane.pid(b), plane.pid(c)
    plane.put(0, uuid.uuid4(), (1, 1, 1))
    plane.put(1, uuid.uuid4(), (2, 2, 2))
    assert len(run_tick(mgr, plane, {0: [pa, pb, pb], 1: [pa]})) == 2
    assert run_tick(mgr, plane, {0: [pb, pa, pa], 1: [pa, pa]}) == []
    # a third recipient of row 0: a keyframe for it, nothing for a and b
    pairs = run_tick(mgr, plane, {0: [pb, pc, pa], 1: [pa]})
    assert [(parse_stamp(m.parameter)[0], t) for m, t in pairs] == [
        (PARAM_FULL, [c])]
    # ... and it leaves again: its tombstone alone
    pairs = run_tick(mgr, plane, {0: [pa, pb], 1: [pa]})
    assert [(parse_stamp(m.parameter)[0], t) for m, t in pairs] == [
        (PARAM_DELTA, [c])]
    assert pairs[0][0].entities[0].flex == b"\x00"
    assert mgr.stats()["near"] == 3


def test_slot_reuse_and_a_row_change_through_the_snapshot():
    plane = FakePlane()
    mgr = InterestManager()
    viewer = uuid.uuid4()
    vp = plane.pid(viewer)
    e1, e2, e3 = uuid.uuid4(), uuid.uuid4(), uuid.uuid4()
    plane.put(0, e1, (1, 0, 0))
    plane.put(1, e2, (2, 0, 0))
    rc = ReplayClient()
    for m, _ in run_tick(mgr, plane, {0: [vp], 1: [vp]}):
        rc.apply(m)
    # e1 is removed and e3 takes its slot inside one tick; e2 re-registers
    # into another row at the same position
    plane.put(0, e3, (3, 0, 0))
    plane.drop(1)
    plane.put(5, e2, (2, 0, 0))
    pairs = run_tick(mgr, plane, {0: [vp], 5: [vp]})
    assert params(pairs) == [stamp(PARAM_DELTA, 1, 1)]
    sent = {e.uuid: e.flex for e in pairs[0][0].entities}
    assert sent == {e1: b"\x00", e3: None, e2: None}   # no tombstone for e2
    rc.apply(pairs[0][0])
    assert rc.snapshot() == {"arena": {e3: (3.0, 0, 0), e2: (2.0, 0, 0)}}
    assert ledger_of(mgr, plane, viewer) == rc.snapshot()["arena"]
    # the plane grows, and the targets get wider and narrower again
    plane2 = FakePlane(cap=4096)
    for name in ("_live", "_pos", "_uuid_bytes", "_wid"):
        getattr(plane2, name)[:2048] = getattr(plane, name)
    plane2._peer_ids, plane2._peer_uuids = plane._peer_ids, plane._peer_uuids
    plane2.put(3000, e1, (9, 9, 9))
    other = plane2.pid(uuid.uuid4())
    pairs = run_tick(mgr, plane2, {0: [vp, other, other], 5: [vp], 3000: [vp]})
    for m, targets in pairs:
        if viewer in targets:
            assert parse_stamp(m.parameter)[0] == PARAM_DELTA
            assert [e.uuid for e in m.entities] == [e1]
            rc.apply(m)
    assert run_tick(mgr, plane2, {0: [vp, other], 5: [vp], 3000: [vp]}) == []
    assert len(rc.snapshot()["arena"]) == 3
    assert rc.stats()["deltas_refused"] == rc.stats()["gaps_seen"] == 0


def test_degraded_cadence_takes_every_peer_off_the_snapshot_and_back():
    plane = FakePlane()
    mgr = InterestManager()
    viewer = uuid.uuid4()
    vp = plane.pid(viewer)
    e = uuid.uuid4()
    plane.put(0, e, (1, 0, 0))
    rc = ReplayClient()
    for m, _ in run_tick(mgr, plane, {0: [vp]}):
        rc.apply(m)
    assert mgr._peers[viewer].synced
    mgr.note_governor(0, True)          # near rows every second tick
    sent = 0
    for step in range(4):
        plane._pos[0] = (1, float(step + 1), 0)
        pairs = run_tick(mgr, plane, {0: [vp]})
        assert not mgr._peers[viewer].synced
        assert mgr._peers[viewer].state is not None
        for m, _ in pairs:
            sent += 1
            rc.apply(m)
    assert sent == 2
    mgr.note_governor(0, False)
    plane._pos[0] = (1, 9.0, 0)
    for m, _ in run_tick(mgr, plane, {0: [vp]}):
        rc.apply(m)
    assert mgr._peers[viewer].synced and mgr._peers[viewer].state is None
    assert rc.snapshot() == {"arena": {e: (1.0, 9.0, 0.0)}}
    assert ledger_of(mgr, plane, viewer) == rc.snapshot()["arena"]
    assert rc.stats()["deltas_refused"] == 0


@pytest.mark.parametrize("kw", [{"near_radius": 10.0},
                                {"bandwidth_bytes": 1 << 20}])
def test_cadences_and_budgets_keep_every_ledger_per_peer(kw):
    """They defer single rows, so no two peers need hold what the
    snapshot says: such a manager never syncs a peer to it."""
    plane = FakePlane()
    mgr = InterestManager(**kw)
    viewer = uuid.uuid4()
    vp = plane.pid(viewer)
    e = uuid.uuid4()
    plane.put(0, e, (1, 0, 0), owner=viewer)
    run_tick(mgr, plane, {0: [vp]})
    plane._pos[0] = (2, 0, 0)
    run_tick(mgr, plane, {0: [vp]})
    st = mgr._peers[viewer]
    assert not st.synced and set(st.state) == {e.bytes}
    assert mgr._snap.targets.size == 0
    assert ledger_of(mgr, plane, viewer) == {e: (2.0, 0.0, 0.0)}


# endregion

# region: ReplayClient oracle semantics


def _frame(kind, epoch, seq, world="arena", ents=()):
    from worldql_server_tpu.protocol.types import (
        NIL_UUID, Entity, Instruction, Message, Vector3,
    )

    return Message(
        instruction=Instruction.LOCAL_MESSAGE,
        parameter=stamp(kind, epoch, seq),
        sender_uuid=NIL_UUID,
        world_name=world,
        entities=[
            Entity(uuid=e, position=Vector3(*p), world_name=world,
                   flex=b"\x00" if dead else None)
            for e, p, dead in ents
        ],
    )


def test_replay_refuses_deltas_past_a_gap_until_new_epoch():
    rc = ReplayClient()
    e = uuid.uuid4()
    assert rc.apply(_frame(PARAM_FULL, 1, 0, ents=[(e, (1, 1, 1), False)]))
    # seq 1 lost; seq 2 arrives: gap -> desync, frame discarded
    assert not rc.apply(_frame(PARAM_DELTA, 1, 2, ents=[(e, (9, 9, 9), False)]))
    assert rc.gaps_seen == 1 and rc.deltas_refused == 1
    assert rc.worlds["arena"][e] == (1.0, 1.0, 1.0)    # state unpoisoned
    # more same-epoch traffic stays refused
    assert not rc.apply(_frame(PARAM_DELTA, 1, 3))
    assert rc.deltas_refused == 2
    # recovery REQUIRES a new epoch opening with full@0
    assert not rc.apply(_frame(PARAM_DELTA, 2, 0))      # delta can't open
    assert rc.deltas_refused == 3
    assert rc.apply(_frame(PARAM_FULL, 3, 0, ents=[(e, (2, 2, 2), False)]))
    assert not rc.desync
    assert rc.worlds["arena"][e] == (2.0, 2.0, 2.0)


def test_replay_discards_stale_epoch_stragglers():
    rc = ReplayClient()
    assert rc.apply(_frame(PARAM_FULL, 2, 0))
    assert not rc.apply(_frame(PARAM_DELTA, 1, 5))      # closed epoch
    assert rc.discarded == 1 and rc.deltas_refused == 0
    assert not rc.apply(_frame(PARAM_FULL, 2, 0))       # replayed dup
    assert rc.gaps_seen == 1                            # seq 0 != next 1


def test_replay_full_replaces_world_and_fullc_appends():
    rc = ReplayClient()
    a, b, c = uuid.uuid4(), uuid.uuid4(), uuid.uuid4()
    rc.apply(_frame(PARAM_FULL, 1, 0, ents=[(a, (1, 0, 0), False)]))
    rc.apply(_frame(PARAM_DELTA, 1, 1, ents=[(b, (2, 0, 0), False)]))
    # a new full REPLACES the world; its fullc continuation appends
    rc.apply(_frame(PARAM_FULL, 1, 2, ents=[(c, (3, 0, 0), False)]))
    rc.apply(_frame(PARAM_FULL_CONT, 1, 3, ents=[(a, (4, 0, 0), False)]))
    assert rc.snapshot() == {"arena": {
        c: (3.0, 0.0, 0.0), a: (4.0, 0.0, 0.0),
    }}


def test_legacy_client_folds_frames_and_removes():
    from worldql_server_tpu.protocol.types import (
        Entity, Instruction, Message, Vector3,
    )

    lc = LegacyClient()
    e = uuid.uuid4()
    lc.apply(Message(
        instruction=Instruction.LOCAL_MESSAGE, parameter="entity.frame",
        sender_uuid=uuid.uuid4(), world_name="w",
        entities=[Entity(uuid=e, position=Vector3(1, 2, 3), world_name="w")],
    ))
    assert lc.snapshot() == {"w": {e: (1.0, 2.0, 3.0)}}
    lc.apply(Message(
        instruction=Instruction.LOCAL_MESSAGE, parameter="entity.remove",
        sender_uuid=uuid.uuid4(), world_name="w",
        entities=[Entity(uuid=e)],
    ))
    assert lc.snapshot() == {}


# endregion

# region: encode parity (native vs object path) + off-path pin


def _entries(n, wid=0, tomb_every=0):
    out = []
    for i in range(n):
        dead = tomb_every and i % tomb_every == 0
        out.append((
            uuid.uuid4().bytes, wid,
            np.array([i, i * 2, i * 3], np.float32).tobytes(), bool(dead),
        ))
    return sorted(out)


def test_template_native_matches_object_path_byte_for_byte():
    from worldql_server_tpu.protocol import entity_wire

    wire = entity_wire.shared()
    if wire is None or not wire.can_encode_interest:
        pytest.skip("native interest encoder unavailable")
    plane = FakePlane()
    mgr = InterestManager()
    for entries in (_entries(3, tomb_every=2), _entries(1), []):
        plane._wire = wire
        native = mgr._encode_template(
            plane, PARAM_DELTA, 0, *pack_entries(entries))
        plane._wire = None
        obj = mgr._encode_template(
            plane, PARAM_DELTA, 0, *pack_entries(entries))
        assert native == obj
        # and the patched result still deserializes with the stamp
        buf = bytearray(native[0])
        buf[native[1]:native[1] + 8] = b"0000000a"
        buf[native[2]:native[2] + 8] = b"00000005"
        msg = deserialize_message(bytes(buf))
        assert parse_stamp(msg.parameter) == (PARAM_DELTA, 10, 5)


def _native_wire():
    from worldql_server_tpu.protocol import entity_wire

    wire = entity_wire.shared()
    if wire is None or not wire.can_encode_interest:
        pytest.skip("native interest encoder unavailable")
    return wire


def _frame_columns(rng, n, share):
    """``n`` entries with random keys, tombstones at ``share``, and the
    positions a compare by value would get wrong in the first rows."""
    keys = rng.integers(0, 256, (n, 16), dtype=np.uint8)
    pos = rng.normal(0.0, 300.0, (n, 3)).astype(np.float32)
    odd = np.array([[-0.0, np.nan, 1e-45], [np.inf, -1e-40, 0.0]], np.float32)
    pos[:min(n, 2)] = odd[:n]
    tomb = (rng.random(n) < share).astype(np.uint8)
    if share == 1:
        tomb[:] = 1
    return keys, pos, tomb


def _object_frame(kind, world, keys, pos, tomb):
    """``serialize_message`` of the Message an interest frame is."""
    from worldql_server_tpu.interest.manager import TOMBSTONE_FLEX
    from worldql_server_tpu.protocol import serialize_message
    from worldql_server_tpu.protocol.types import (
        NIL_UUID, Entity, Instruction, Message, Vector3,
    )

    return serialize_message(Message(
        instruction=Instruction.LOCAL_MESSAGE,
        parameter=stamp(kind, 0, 0),
        sender_uuid=NIL_UUID,
        world_name=world,
        entities=[
            Entity(uuid=uuid.UUID(bytes=key.tobytes()),
                   position=Vector3(*map(float, p)), world_name=world,
                   flex=b"\x00" if dead else None)
            for key, p, dead in zip(keys, pos, tomb)
        ],
    ))


def _encode_batch(wire, frames):
    """``[(kind, world, keys, pos, tomb)]`` through the batch export:
    each frame's bytes, and the place the export names for its
    parameter."""
    bounds = np.concatenate(([0], np.cumsum([len(f[2]) for f in frames])))
    got, at, recorded = wire.encode_interest_frames(
        [stamp(f[0], 0, 0).encode() for f in frames],
        [f[1].encode() for f in frames], bounds,
        np.concatenate([f[2] for f in frames]),
        np.concatenate([f[3] for f in frames]).astype(np.float64),
        np.concatenate([f[4] for f in frames]),
    )
    assert recorded == bounds[-1]
    return [bytes(frame) for frame in got], at


@pytest.mark.parametrize("share", [0, 0.3, 1])
@pytest.mark.parametrize("kind", [PARAM_DELTA, PARAM_FULL, PARAM_FULL_CONT])
@pytest.mark.parametrize("n", [0, 1, 2, 3, 5, 8, 17, 263, 512])
def test_batch_export_matches_serialize_message_byte_for_byte(n, kind, share):
    """Every alignment state a frame can stand in: entity counts
    around the 8-byte period, world names of 0-11 bytes, the three
    stamped parameters, tombstones nowhere, mixed and everywhere;
    -0.0, NaN, infinities and subnormals ride the first rows."""
    wire = _native_wire()
    rng = np.random.default_rng(n * 31 + len(kind))
    for world_len in range(12):
        world = ("wörld-nameXY" if world_len % 2 else "w" * 12)[:world_len]
        keys, pos, tomb = _frame_columns(rng, n, share)
        (got,), (at,) = _encode_batch(wire, [(kind, world, keys, pos, tomb)])
        want = _object_frame(kind, world, keys, pos, tomb)
        assert got == want, (n, kind, share, world)
        # the stamp's place is the caller's own bytes, found once here
        assert at == want.find(stamp(kind, 0, 0).encode())
        if n:
            back = deserialize_message(got)
            assert len(back.entities) == n
            assert [e.flex is not None for e in back.entities] \
                == tomb.astype(bool).tolist()


@pytest.mark.parametrize("seed", range(6))
def test_a_frame_of_a_batch_does_not_depend_on_its_neighbours(seed):
    """Several frames of DIFFERENT worlds, kinds and sizes in one
    call, an empty clear marker among them: each is what it is alone,
    in every order."""
    wire = _native_wire()
    rng = np.random.default_rng(seed)
    worlds = ["", "a", "arena", "annex-world", "arena"]
    frames = []
    for i in range(7):
        n = int(rng.choice([0, 1, 2, 7, 64, 263, 512]))
        kind = [PARAM_DELTA, PARAM_FULL, PARAM_FULL_CONT][int(rng.integers(3))]
        world = worlds[int(rng.integers(len(worlds)))]
        frames.append((kind, world) + _frame_columns(
            rng, n, float(rng.choice([0, 0.3, 1]))))
    frames.append((PARAM_FULL, "gone", ) + _frame_columns(rng, 0, 0))
    want = [_object_frame(*f) for f in frames]
    for order in (range(len(frames)), reversed(range(len(frames))),
                  rng.permutation(len(frames)).tolist()):
        order = list(order)
        got, _at = _encode_batch(wire, [frames[i] for i in order])
        assert got == [want[i] for i in order]


class _CountedWire:
    """The native wire, its interest encode calls counted."""

    def __init__(self, wire):
        self._wire = wire
        self.calls = 0

    can_encode_interest = True

    def encode_interest_frames(self, *args):
        self.calls += 1
        return self._wire.encode_interest_frames(*args)


def _churn_run(wire, budget):
    """One seeded churn on the fake plane: entries, moves, departures
    and world hops every tick, peers 0 and 1 with the same view (a
    cohort), a resync of peer 2, and (``budget``) an empty bucket for
    peer 3 over a dozen ticks. Returns every tick's ``(frame bytes,
    recipients)``, the manager, its metrics and each tick's native
    encode calls."""
    rng = np.random.default_rng(44)
    plane = FakePlane(cap=192, worlds=("arena", "annex-world"))
    plane._wire = wire
    metrics = Metrics()
    now = [1000.0]
    mgr = InterestManager(bandwidth_bytes=budget, metrics=metrics,
                          clock=lambda: now[0])
    peers = [uuid.UUID(int=i + 1) for i in range(5)]
    pids = [plane.pid(p) for p in peers]
    vis: dict[int, list[int]] = {}
    ticks, calls = [], []
    for tick in range(36):
        now[0] += 0.05
        for slot in rng.choice(192, 24, replace=False).tolist():
            roll = rng.random()
            if slot not in vis or roll < 0.25:      # enter, or re-key
                seen = [p for p in pids[2:] if rng.random() < 0.5]
                if rng.random() < 0.7:
                    seen += pids[:2]                # the cohort, as one
                plane.put(slot, uuid.UUID(bytes=rng.bytes(16)),
                          rng.normal(0, 50, 3), wid=int(rng.integers(2)))
                vis[slot] = seen or [pids[4]]
            elif roll < 0.4:                        # leave
                plane.drop(slot)
                del vis[slot]
            elif roll < 0.5:                        # hop world
                plane._wid[slot] ^= 1
            else:                                   # move
                plane._pos[slot] += rng.normal(0, 1, 3).astype(np.float32)
        if tick == 14:
            mgr.mark_resync(peers[2])
        if budget and 8 <= tick < 20 and peers[3] in mgr._peers:
            mgr._peers[peers[3]].tokens = 0.0
        before = getattr(wire, "calls", 0)
        pairs = run_tick(mgr, plane, vis)
        calls.append(getattr(wire, "calls", 0) - before)
        ticks.append([(m.wire, tuple(t)) for m, t in pairs])
    ledgers = [mgr.ledger(p, plane.pid(p)) for p in peers]
    return ticks, mgr, metrics.snapshot()["counters"], calls, ledgers


@pytest.mark.parametrize("budget", [0, 2000])
def test_the_manager_frames_are_the_object_paths(budget):
    """The same churn driven twice, through the batch export and
    through the object encoder: every ``(frame bytes, recipient)``
    pair equal and in order, the same cohort hits, deferrals, shed
    bytes and ledgers; the native side makes at most ONE encode call
    a tick and writes every entry it encodes from a record."""
    wire = _CountedWire(_native_wire())
    native, n_mgr, n_count, calls, n_ledgers = _churn_run(wire, budget)
    obj, o_mgr, o_count, _none, o_ledgers = _churn_run(None, budget)
    for tick, (a, b) in enumerate(zip(native, obj)):
        assert a == b, tick
    assert sum(map(len, native)) > 100
    for name in ("templates_reused", "deferrals", "bytes_shed", "resyncs"):
        assert getattr(n_mgr, name) == getattr(o_mgr, name), name
    assert n_mgr.templates_reused > 0
    if budget:
        assert n_mgr.deferrals > 0 and n_mgr.bytes_shed > 0
    assert n_ledgers == o_ledgers and any(n_ledgers)
    assert max(calls) == 1
    assert n_count["interest.encode_calls"] == sum(calls)
    assert n_count["interest.entries_recorded"] \
        == n_count["interest.entries_encoded"] \
        == o_count["interest.entries_encoded"] > 0
    assert "interest.encode_calls" not in o_count
    assert "interest.entries_recorded" not in o_count
    for name in ("interest.entries", "delta.frames_reused"):
        assert n_count[name] == o_count[name]


@pytest.mark.parametrize("name, want", [
    ("interest_encode_calls_per_tick", 1.0),
    ("interest_record_entry_share", 100.0),
])
def test_the_encode_metrics_read_their_counters_or_nothing(name, want):
    """The two per-layer metrics of ISSUE 44 as the benchmark declares
    them: read off a scrape that holds the three counters, and nothing
    (no raise) off a server that has none, as this PR's parent is."""
    import importlib
    import json
    from pathlib import Path

    root = Path(__file__).resolve().parent.parent
    bench = json.loads((root / "BENCHMARK.json").read_text())
    entry, = [m for m in bench["per_layer"] if m["name"] == name]
    spec = json.loads(
        (root / "benchmark/layer_metrics" / f"{name}.json").read_text())
    assert entry["workloads"] == ["entity-100k-even.random-walk"]
    for key in ("layer", "unit", "moves", "better"):
        assert entry[key] == spec[key], key
    assert (entry["layer"], entry["moves"], entry["source"]) \
        == ("entity plane", "deliver_p50_ms", "program_counter")
    read = importlib.import_module(
        f"benchmark.sources.{spec['source']['kind']}").read

    def scrape(ticks, **counters):
        return {"counters": {"tick.flushes": ticks, "interest.entries": 9,
                             **{f"interest.{k}": v
                                for k, v in counters.items()}}}

    ctx = {"before": scrape(100), "after": scrape(1000),
           "window_unix": (0.0, 45.0)}
    assert read(spec["source"], ctx) is None
    ctx = {"before": scrape(100, encode_calls=90, entries_encoded=5_000,
                            entries_recorded=5_000),
           "after": scrape(1000, encode_calls=990, entries_encoded=95_000,
                           entries_recorded=95_000),
           "window_unix": (0.0, 45.0)}
    assert read(spec["source"], ctx) == want


def test_interest_off_is_the_default_and_legacy_frames_unstamped():
    config = Config()
    assert config.interest == "off"
    # the legacy broadcast parameter is NOT a stamped frame: off-path
    # wire bytes carry no sequence fields at all
    from worldql_server_tpu.entities import PARAM_FRAME

    assert parse_stamp(PARAM_FRAME) is None


def test_config_validates_interest_fields():
    def errs(**kw):
        c = Config()
        c.store_url = "memory://"
        for k, v in kw.items():
            setattr(c, k, v)
        try:
            c.validate()
        except ValueError as exc:
            return str(exc)
        return ""

    assert "interest" in errs(interest="sometimes")
    assert "entity_sim" in errs(interest="on", entity_sim=False)
    assert errs(interest="on", entity_sim=True, spatial_backend="tpu",
                tick_interval=0.05) == ""
    assert "lod_near_radius" in errs(lod_near_radius=-1)
    assert "lod_far_every_k" in errs(lod_far_every_k=0)
    assert "peer_bandwidth_bytes" in errs(peer_bandwidth_bytes=-5)


# endregion

# region: the hinted diff against the whole scan (ISSUE 30)


class Herd:
    """``EntityPlane``'s apply leg in small, for two managers at once:
    the columns ``build_pairs`` reads, the retained targets a delta
    tick splices its closure into, and the rows owed to the manager's
    next call, kept as ``plane.py`` keeps them (``_owed``) and settled
    by its own ``_take_owed``. Entities sit ``PER_CUBE`` to a cube; a row's
    recipients are the owners of the others in its cube, and come back
    in a fresh ORDER whenever anything in the cube changed. With
    ``compare`` the herd names as the plane does since ISSUE 42: of a
    closure only the rows whose position bits or sorted recipients are
    not what it kept from the tick before (it keeps them at every
    tick, a shed one too); without, the whole closure."""

    PER_CUBE = 4

    def __init__(self, seed: int, cap: int = 32, peers: int = 5, k: int = 6,
                 compare: bool = False):
        self.compare = compare
        self.rng = np.random.default_rng(seed)
        self.plane = FakePlane(cap=cap, worlds=("arena", "annex"))
        self.peers = [uuid.UUID(int=seed * 1000 + i + 1) for i in range(peers)]
        for peer in self.peers:
            self.plane.pid(peer)
        self.k = k
        self.targets = np.full((cap, k), -1, np.int32)
        self.cube = np.full(cap, -1, np.int64)
        self.owner = np.full(cap, -1, np.int64)
        self.dirty: set[int] = set()
        self._owed = None
        self.cold = True            # the next tick must be a full one

    # -- the roster (plane._alloc_slot / _release_slot)

    def alloc(self, slot, pid, cube, ent=None, wid=0):
        ent = ent or uuid.UUID(bytes=self.rng.bytes(16))
        self.plane.put(slot, ent, self.rng.integers(0, 64, 3) / 8.0, wid)
        self.cube[slot], self.owner[slot] = cube, pid
        self.dirty.add(cube)
        if self._owed is not None:
            self._owed[1].append(slot)
        return ent

    def release(self, slot):
        self.dirty.add(int(self.cube[slot]))
        self.plane._live[slot] = False
        self.plane._uuid_bytes[slot] = 0
        self.plane._wid[slot] = -1
        self.targets[slot] = -1
        self.cube[slot] = self.owner[slot] = -1
        if self._owed is not None:
            self._owed[1].append(slot)

    def free_slot(self):
        return int(np.flatnonzero(~self.plane._live)[0])

    def some_live(self):
        return int(self.rng.choice(np.flatnonzero(self.plane._live)))

    # -- traffic

    def move(self, slot):
        self.plane._pos[slot] += np.float32(0.125)
        self.dirty.add(int(self.cube[slot]))

    def hop(self, slot, cube):
        """To another cube: its old neighbours lose a recipient, its
        new ones gain one, and it moved."""
        self.dirty.update((int(self.cube[slot]), cube))
        self.cube[slot] = cube
        self.plane._pos[slot] += np.float32(0.125)

    def stir(self, cube):
        """The cube resolves again and nothing in it changed: the
        same recipients in another order."""
        self.dirty.add(cube)

    def grow(self):
        plane, cap = self.plane, self.plane._cap * 2

        def grown(a, fill):
            out = np.full((cap,) + a.shape[1:], fill, a.dtype)
            out[: len(a)] = a
            return out

        plane._live = grown(plane._live, False)
        plane._pos = grown(plane._pos, 0.0)
        plane._uuid_bytes = grown(plane._uuid_bytes, 0)
        plane._wid = grown(plane._wid, -1)
        plane._cap = cap
        self.targets = grown(self.targets, -1)
        self.cube, self.owner = grown(self.cube, -1), grown(self.owner, -1)
        self.cold = True

    def _changed(self, rows):
        """Keep the answer of ``rows``; which of them had another."""
        now_t = np.sort(self.targets[rows], axis=1)
        now_p = self.plane._pos[rows].view(np.uint32)
        differ = ((now_t != self.kept_t[rows]).any(axis=1)
                  | (now_p != self.kept_p[rows]).any(axis=1))
        self.kept_t[rows], self.kept_p[rows] = now_t, now_p
        return rows[differ]

    # -- one applied tick, and the settlement that precedes build_pairs

    def _resolve(self, rows):
        live = self.plane._live
        for r in rows.tolist():
            near = np.flatnonzero(live & (self.cube == self.cube[r]))
            seen_by = self.owner[near[near != r]]
            seen_by = self.rng.permutation(seen_by[seen_by != self.owner[r]])
            self.targets[r] = -1
            self.targets[r, : len(seen_by[: self.k])] = seen_by[: self.k]

    def tick(self, full=False) -> str:
        live = self.plane._live
        if full or self.cold:
            self._resolve(np.flatnonzero(live))
            self._owed, self.cold, kind = None, False, "full"
            self.kept_t = np.sort(self.targets, axis=1)
            self.kept_p = self.plane._pos.view(np.uint32).copy()
        elif not self.dirty:
            kind = "replay"
        else:
            rows = np.flatnonzero(live & np.isin(self.cube, list(self.dirty)))
            self._resolve(rows)
            if self.compare:
                rows = self._changed(rows)
            if self._owed is not None and rows.size:
                self._owed[0].append(rows)
            kind = "delta"
        self.dirty.clear()
        return kind

    def take_owed(self):
        return EntityPlane._take_owed(self)      # the plane's own


#: case -> (what happens at tick t on top of the walk, what must have
#: been seen for the case to count). Every case is the same >= 200
#: ticks of moves and stirred cubes; these are laid over them.
def _reuse_slot(herd, t, managers):
    if t % 7 == 3:                   # released, and another's at once
        slot = herd.some_live()
        pid, cube = int(herd.owner[slot]), int(herd.cube[slot])
        herd.release(slot)
        herd.alloc(slot, (pid + 1) % len(herd.peers), cube)
    elif t % 7 == 5:                 # released now, re-allocated later
        herd.release(herd.some_live())
    elif t % 7 == 6:
        herd.alloc(herd.free_slot(), t % len(herd.peers), t % 5)


def _change_row(herd, t, managers):
    if t % 5 == 2:                   # the same entity under another row
        slot = herd.some_live()
        ent = uuid.UUID(bytes=herd.plane._uuid_bytes[slot].tobytes())
        pid, cube = int(herd.owner[slot]), int(herd.cube[slot])
        at = herd.plane._pos[slot].copy()
        herd.release(slot)
        new = herd.free_slot() if t % 10 == 2 else slot
        herd.alloc(new, pid, cube, ent, wid=(t // 5) % 2)
        if t % 15 == 2:
            herd.plane._pos[new] = at


def _hop(herd, t, managers):
    if t % 3 == 1:                   # a move, a gain and a loss at once
        slot = herd.some_live()
        herd.hop(slot, (int(herd.cube[slot]) + 1) % 5)
        herd.move(herd.some_live())


def _contact_and_resync(herd, t, managers):
    if t == 60:                      # a peer nobody has framed yet
        herd.peers.append(uuid.UUID(int=0xFEED))
        herd.alloc(herd.free_slot(), herd.plane.pid(herd.peers[-1]), 1)
    if t % 40 == 25:
        for mgr in managers:
            mgr.mark_resync(herd.peers[t % len(herd.peers)])


def _degraded(herd, t, managers):
    if t % 50 in (20, 31):
        for mgr in managers:
            mgr.note_governor(0, t % 50 == 20)


def _walk_only(herd, t, managers):
    """Nothing on top of the walk: the test's own loop makes the case."""


def _grow(herd, t, managers):
    if t in (71, 151):
        herd.grow()


HERD_CASES = {
    "order_only": _walk_only,
    "move_gain_loss": _hop,
    "slot_reused": _reuse_slot,
    "row_changed": _change_row,
    "skip_frames": _walk_only,
    "full_between": _walk_only,
    "replay": _walk_only,
    "contact_and_resync": _contact_and_resync,
    "degraded_cadence": _degraded,
    "tier_growth": _grow,
    # a TPU hands [N, K] columns back column-major
    "column_major": _walk_only,
}


@pytest.mark.parametrize("case", [*HERD_CASES, "all_at_once"])
@pytest.mark.parametrize("names", ["closure", "compared"])
def test_hinted_diff_equals_the_whole_scan(names, case):
    """Two managers over one seeded sequence of 220 ticks: one is told
    which rows may differ (as ``EntityPlane`` tells it), one never.
    Tick by tick the pairs are the same bytes to the same recipients
    in the same order and the counters the benchmark reads agree; at
    the end every peer's ledger is the same, and is what a client that
    applied the frames holds. ``names``: the caller names every row of
    a closure, or (ISSUE 42) vouches for the closure rows it compared
    itself and names the others."""
    seed = sorted([*HERD_CASES, "all_at_once"]).index(case) + 1
    herd = Herd(seed, compare=names == "compared")
    rng = random.Random(seed)
    told, untold = (InterestManager(metrics=Metrics()) for _ in range(2))
    managers = (told, untold)
    clients: dict = {}
    for slot in range(20):
        herd.alloc(slot, slot % len(herd.peers), slot // Herd.PER_CUBE)
    events = (list(HERD_CASES.values()) if case == "all_at_once"
              else [HERD_CASES[case]])
    every = case == "all_at_once"
    seen = {"full": 0, "delta": 0, "replay": 0, "skipped": 0,
            "silent_stirs": 0}

    def counts(mgr):
        return mgr.metrics.snapshot()["counters"]

    for t in range(220):
        still = (case == "replay" or every) and t % 6 == 4
        if not still:
            for event in events:
                event(herd, t, managers)
            if (case == "order_only" or every) and t % 4 == 2:
                herd.stir(t % 5)             # and nothing else
            else:
                for _ in range(rng.randrange(3)):
                    herd.move(herd.some_live())
                herd.stir(rng.randrange(5))
        full = (case == "full_between" or every) and t % 9 == 8
        kind = herd.tick(full=full and not still)
        seen[kind] += 1
        if (case == "skip_frames" or every) and t % 8 in (5, 6) and t > 8:
            seen["skipped"] += 1             # apply(skip_frames=True)
            continue
        cap = herd.plane._cap
        before = dict(counts(told))
        layout = (np.asfortranarray if case == "column_major" or every
                  else np.asarray)
        a = told.build_pairs(herd.plane, layout(herd.plane._pos),
                             layout(herd.targets), cap, None,
                             herd.take_owed())
        b = untold.build_pairs(herd.plane, herd.plane._pos, herd.targets,
                               cap)
        assert [(m.wire, to) for m, to in a] == \
            [(m.wire, to) for m, to in b], f"tick {t} ({kind})"
        for name in ("interest.rows_diffed", "interest.entries"):
            assert counts(told).get(name) == counts(untold).get(name), (t, name)
        assert (told._visible == untold._visible).all()
        assert told.stats() == untold.stats()
        if not a and kind == "delta" and (
                counts(told)["interest.rows_scanned"]
                > before["interest.rows_scanned"]) == (names == "closure"):
            # a stirred cube: the rows were read and said nothing, or
            # (compared by the caller) were not read at all
            seen["silent_stirs"] += 1
        for m, to in a:
            for peer in to:
                assert clients.setdefault(peer, ReplayClient()).apply(m)

    # the case happened, and the hint was what answered it
    c = counts(told)
    assert c["interest.hinted_ticks"] > 150 - 60 * every
    assert c["interest.hinted_ticks"] + c["interest.scanned_ticks"] \
        == told._ticks == untold._ticks
    assert counts(untold).get("interest.hinted_ticks") is None
    assert c["interest.rows_scanned"] < counts(untold)[
        "interest.rows_scanned"] / 2
    assert seen["delta"] > 100 and seen["full"] >= 1
    if case in ("order_only", "all_at_once"):
        assert seen["silent_stirs"] >= 10 + 30 * (not every)
    if case in ("replay", "all_at_once"):
        assert seen["replay"] >= 30
    if case in ("skip_frames", "all_at_once"):
        assert seen["skipped"] >= 50
    if case in ("full_between", "all_at_once"):
        assert seen["full"] >= 20
    if case in ("tier_growth", "all_at_once"):
        assert herd.plane._cap == 128 and seen["full"] >= 3
    if case in ("contact_and_resync", "all_at_once"):
        assert told.resyncs == untold.resyncs >= 4
        assert uuid.UUID(int=0xFEED) in clients

    for peer in herd.peers:
        pid = herd.plane._peer_ids[peer]
        held = told.ledger(peer, pid)
        assert held == untold.ledger(peer, pid)
        got = clients[peer].snapshot() if peer in clients else {}
        for wid, world in enumerate(herd.plane._world_names):
            assert got.get(world, {}) == {
                uuid.UUID(bytes=key): tuple(
                    float(v) for v in np.frombuffer(pos_b, np.float32))
                for key, (w, pos_b) in held.items() if w == wid
            }
        if peer in clients:
            assert clients[peer].stats()["deltas_refused"] == 0
            assert clients[peer].stats()["gaps_seen"] == 0


# endregion

# region: churn property on a REAL plane


@pytest.mark.parametrize("delta_ticks", ["off", "on"])
def test_churn_property_replay_matches_ground_truth(delta_ticks):
    """>=200 ticks of joins/leaves/movers/forced drops/cadence changes
    against a real EntityPlane. With LOD off, every tick's diff is
    complete, so each peer's ReplayClient must equal the ground-truth
    visible set — the exact state the ``--interest off`` stream
    conveys — after EVERY tick, and ``deltas_refused`` stays 0."""
    from tests.test_entity_sim import ent_msg, make_plane
    from worldql_server_tpu.protocol.types import Entity, Vector3

    backend, plane = make_plane(k=4)
    if delta_ticks == "on":
        assert backend.configure_delta_ticks("on")
        plane._delta_ticks = True
    mgr = InterestManager()
    plane.interest = mgr

    rng = random.Random(0xC0FFEE)
    peers = [uuid.uuid4() for _ in range(6)]
    owned: dict[uuid.UUID, list] = {p: [] for p in peers}
    clients = {p: ReplayClient() for p in peers}
    ids = itertools.count()

    def spawn(peer):
        e = uuid.uuid4()
        p = Vector3(rng.uniform(0, 60), rng.uniform(0, 60), 0.0)
        plane.ingest(ent_msg(peer, [
            Entity(uuid=e, position=p, world_name="w")
        ]))
        owned[peer].append(e)

    for p in peers[:4]:
        spawn(p)
        spawn(p)

    frames_total = delta_frames = 0
    for t in range(220):
        roll = rng.random()
        if roll < 0.15 and any(owned.values()):
            peer = rng.choice([p for p in peers if owned[p]])
            e = owned[peer].pop(rng.randrange(len(owned[peer])))
            plane.ingest(ent_msg(peer, [Entity(uuid=e)],
                                 parameter="entity.remove"))
        elif roll < 0.35:
            spawn(rng.choice(peers))
        elif roll < 0.45:
            # forced drop / reconnect: any loss path lands here
            victim = rng.choice(peers)
            mgr.mark_resync(victim)
        elif roll < 0.5:
            mgr.note_governor(rng.randrange(3), rng.random() < 0.5)
            mgr.note_governor(0, False)     # back to full cadence
        # movers
        for peer in peers:
            for e in owned[peer]:
                if rng.random() < 0.5:
                    plane.ingest(ent_msg(peer, [Entity(
                        uuid=e,
                        position=Vector3(rng.uniform(0, 60),
                                         rng.uniform(0, 60), 0.0),
                        world_name="w",
                    )]))

        handle = plane.dispatch_tick()
        if handle is None:
            continue
        result = plane.collect_tick(handle)
        cap = result["cap"]
        pairs = plane.apply(result)
        for m, targets in pairs:
            frames_total += 1
            if parse_stamp(m.parameter)[0] == PARAM_DELTA:
                delta_frames += 1
            for peer in targets:
                assert clients[peer].apply(m)

        # ground truth straight off the plane columns: what a
        # --interest off recipient would have been told this tick
        for peer in peers:
            pid = plane._peer_ids.get(peer)
            if pid is None:
                continue
            expect = {}
            for key, (wid, pos_b) in mgr.ledger(peer, pid).items():
                x, y, z = np.frombuffer(pos_b, np.float32)
                expect[uuid.UUID(bytes=key)] = (
                    float(x), float(y), float(z)
                )
            got = clients[peer].snapshot().get("w", {})
            assert got == expect, f"tick {t} peer divergence"

    # the oracle's core guarantees, over the whole run
    for rc in clients.values():
        s = rc.stats()
        assert s["deltas_refused"] == 0
        assert s["gaps_seen"] == 0
    assert frames_total > 0 and delta_frames > 0
    assert mgr.resyncs > 0


def test_a_mostly_static_world_costs_a_fraction_of_the_broadcast_bytes():
    """What interest management is for: a static majority in one cube
    and a moving minority, the same ticks served twice through real
    ``deliver_batch``. Off re-sends every visible entity to every
    watcher every tick; on sends each watcher what changed. The
    watchers' replayed state equals the server's ledger, no delta is
    refused, and the bytes delivered a tick fall at least five times."""
    import asyncio

    from tests.test_entity_sim import ent_msg, make_plane, vel_flex
    from worldql_server_tpu.engine.peers import Peer, PeerMap
    from worldql_server_tpu.protocol.types import (
        Entity, Instruction, Vector3,
    )

    rng = np.random.default_rng(1813)
    peers = [uuid.UUID(int=i + 1) for i in range(4)]
    static = [
        [(uuid.UUID(int=100 * (p + 1) + i), rng.uniform(4, 12, 3))
         for i in range(4)]
        for p in range(len(peers))
    ]
    movers = [(uuid.UUID(int=9000 + i), rng.uniform(6, 10, 3))
              for i in range(2)]
    warm, ticks = 3, 12

    async def serve(interest: bool):
        _, plane = make_plane(k=8)
        mgr = None
        if interest:
            mgr = plane.interest = InterestManager()
        peer_map = PeerMap()
        inboxes = {p: [] for p in peers}
        for p in peers:
            async def send_raw(data, inbox=inboxes[p]):
                m = deserialize_message(data)
                if m.instruction == Instruction.LOCAL_MESSAGE:
                    inbox.append(m)
            await peer_map.insert(Peer(p, "loopback", send_raw, "test"))
        for p, ents in zip(peers, static):
            plane.ingest(ent_msg(p, [
                Entity(uuid=e, position=Vector3(*xyz), world_name="w")
                for e, xyz in ents
            ]))
        plane.ingest(ent_msg(peers[0], [
            Entity(uuid=e, position=Vector3(*xyz), world_name="w",
                   flex=vel_flex(1.0, 0.5))
            for e, xyz in movers
        ]))
        bytes0 = 0
        for t in range(warm + ticks):
            if t == warm:
                bytes0 = peer_map.bytes_delivered
            handle = plane.dispatch_tick()
            await peer_map.deliver_batch(
                plane.apply(plane.collect_tick(handle))
            )
        return (peer_map.bytes_delivered - bytes0) / ticks, inboxes, \
            mgr, plane

    off, off_inboxes, _, _ = asyncio.run(serve(False))
    on, on_inboxes, mgr, plane = asyncio.run(serve(True))
    assert all(off_inboxes[p] for p in peers) and off > 0
    deltas = 0
    for p in peers:
        rc = ReplayClient()
        for m in on_inboxes[p]:
            assert rc.apply(m)
        s = rc.stats()
        assert s["deltas_refused"] == 0 and s["gaps_seen"] == 0
        deltas += s["deltas_applied"]
        ledger = ledger_of(mgr, plane, p)
        assert rc.snapshot()["w"] == ledger
        assert len(ledger) > 1
    assert deltas > 0, "movement never rode a delta frame"
    assert off >= 5.0 * on, (off, on)


def test_churn_ledger_equals_visible_set_without_lod():
    """The ledger-vs-targets cross-check the property above leans on:
    with LOD off and no bandwidth cap, the manager's committed state
    for a peer IS the visible set from the tick's targets matrix."""
    from tests.test_entity_sim import ent_msg, make_plane
    from worldql_server_tpu.protocol.types import Entity, Vector3

    backend, plane = make_plane(k=4)
    mgr = InterestManager()
    plane.interest = mgr
    rng = random.Random(7)
    peers = [uuid.uuid4() for _ in range(3)]
    ents = {}
    for p in peers:
        for _ in range(3):
            e = uuid.uuid4()
            ents[e] = p
            plane.ingest(ent_msg(p, [Entity(
                uuid=e, position=Vector3(rng.uniform(0, 30),
                                         rng.uniform(0, 30), 0.0),
                world_name="w",
            )]))
    handle = plane.dispatch_tick()
    result = plane.collect_tick(handle)
    cap = result["cap"]
    targets = np.array(result["targets"])
    plane.apply(result)
    live = plane._live[:cap]
    for peer in peers:
        pid = plane._peer_ids[peer]
        visible_rows = {
            int(r) for r in np.flatnonzero(live)
            if pid in targets[r][targets[r] >= 0]
        }
        key_to_row = {
            plane._uuid_bytes[r].tobytes(): int(r)
            for r in np.flatnonzero(live)
        }
        ledger_rows = {key_to_row[k] for k in mgr.ledger(peer, pid)}
        assert ledger_rows == visible_rows


# endregion
