"""Per-recipient interest management: delta frames, LOD cadence,
bandwidth budgets.

The entity plane's tick result says, for every entity row, which peers
should see it. The pre-interest pipeline ships that as one
``entity.frame`` LocalMessage per (entity, tick) to every recipient —
O(recipients × neighbors × tick-rate) wire bytes no matter how little
moved. The :class:`InterestManager` replaces that leg per recipient
with a DIFF against the last state the peer provably received:

* **wire contract** — every frame's parameter is stamped by
  :func:`stamp`: ``entity.frame.full:<epoch>:<seq>`` /
  ``entity.frame.fullc:<epoch>:<seq>`` (chunk continuation) /
  ``entity.frame.delta:<epoch>:<seq>`` with fixed-width hex fields.
  ``seq`` is monotone and contiguous per peer within an ``epoch``; any
  loss path bumps the epoch and forces the next frame full, so a
  client (and the parity oracle) can PROVE it never applied a delta
  against a frame it never got: a same-epoch gap is a server bug, an
  epoch bump is a declared resync. Entered/moved neighbors ride as
  normal positioned entities; departed neighbors ride the same frame
  as tombstones (1-byte ``flex`` marker — short flex is already
  ignored by the velocity decode, so old readers see a harmless
  entity).
* **resync contract** — :meth:`InterestManager.mark_resync` is the ONE
  hook every loss path calls: reconnect/session-resume, undelivered
  frames to a parked session, ring-full drops, worker loss, overload
  eviction. It is idempotent and cheap (a flag); the next built frame
  for that peer opens a new epoch with a complete keyframe.
* **LOD cadence** — recipients partition per tick into near/far by the
  distance of each neighbor row to the recipient's own entity centroid
  (``lod_near_radius``; 0 = all near). Near rows deliver every tick;
  far rows every ``lod_far_every_k`` ticks (per-peer phase, so far
  bursts de-synchronize). Deferral is LOSSLESS: an off-cadence far
  update is simply retained in the diff base and ships on the next due
  tick — never dropped. The overload governor widens k
  (:meth:`note_governor`) instead of skipping frames blindly.
* **bandwidth budgets** — a token bucket per peer
  (``peer_bandwidth_bytes``/s). An unaffordable tick is DEFERRED whole
  (no state commit, no seq consumed — the diff accumulates), and the
  peer walks a demotion ladder: normal → forced-far cadence →
  keyframe-only. Only an unaffordable *keyframe* at the bottom of the
  ladder counts ``delivery.bytes_shed``; a delta is never truncated,
  so eventual-state parity holds under any budget.
* **cohort dedup** — peers whose frame would carry identical content
  share ONE encode (native or object path); per-peer epoch:seq stamps
  are byte-patched into a copy. This generalizes PR 14's
  ``delta.frames_reused`` from clean-cohort replay to dirty cohorts
  with identical diffs.

* **one snapshot, not a ledger a peer** — with neither LOD cadence nor
  budget configured every tick commits every visible row for every
  peer, so each peer holds exactly its view of the columns the last
  tick's diff read (:class:`_Snapshot`). The tick's diff is then the
  rows that differ from the snapshot, for all peers at once
  (:meth:`InterestManager._snapshot_specs`); a peer's ledger is walked
  row by row (:meth:`InterestManager._walk_specs`) only at first
  contact, on a resync and under a degraded near cadence. Cadences
  and budgets defer single rows: such a manager walks every ledger
  every tick, as it always did.

This module is also the sequence-stamp authority: the ``tools/check``
rule ``unsequenced-frame`` fails any stamped-frame parameter literal
built outside it.
"""

from __future__ import annotations

import logging
import time
import uuid as uuid_mod

import numpy as np

from ..observability.spans import NULL_TRACE
from ..protocol.types import NIL_UUID, Entity, Instruction, Message, Vector3

logger = logging.getLogger(__name__)

#: stamped-frame parameter bases (see :func:`stamp`) — the lint rule
#: `unsequenced-frame` pins construction of these to THIS module
PARAM_FULL = "entity.frame.full"
PARAM_FULL_CONT = "entity.frame.fullc"
PARAM_DELTA = "entity.frame.delta"

#: max entities per frame: chunked fulls stay under the native decode
#: object cap (WQL_MAX_OBJS = 1024) with headroom
FRAME_CHUNK = 512

#: 1-byte flex marking a departed neighbor (any flex < 12 bytes is
#: ignored by the entity velocity decode, so pre-interest readers see
#: a harmless entity at its last position)
TOMBSTONE_FLEX = b"\x00"

#: demotion ladder states (bandwidth pressure)
DEMOTE_NONE = 0      # normal near/far cadence
DEMOTE_FAR = 1       # every row on the far cadence
DEMOTE_KEYFRAME = 2  # full keyframes on the far cadence, nothing else

_NIL_KEY = NIL_UUID.bytes


def stamp(kind: str, epoch: int, seq: int) -> str:
    """The ONE constructor for stamped frame parameters:
    ``<kind>:<epoch hex8>:<seq hex8>``. Fixed-width fields make every
    stamp of a kind the same length, which is what lets a cohort
    template be byte-patched per peer."""
    return f"{kind}:{epoch & 0xFFFFFFFF:08x}:{seq & 0xFFFFFFFF:08x}"


def parse_stamp(parameter: str) -> tuple[str, int, int] | None:
    """``(kind, epoch, seq)`` from a stamped frame parameter, or None
    when the parameter is not a stamped frame (e.g. the legacy
    ``entity.frame``)."""
    if parameter is None or not parameter.startswith("entity.frame."):
        return None
    parts = parameter.rsplit(":", 2)
    if len(parts) != 3:
        return None
    kind = parts[0]
    if kind not in (PARAM_FULL, PARAM_FULL_CONT, PARAM_DELTA):
        return None
    try:
        return kind, int(parts[1], 16), int(parts[2], 16)
    except ValueError:
        return None


#: a cohort template's parameter: the kind's stamp with zeroed fields,
#: which every recipient's copy overwrites with its own epoch and seq
_PLACEHOLDER = {
    kind: stamp(kind, 0, 0).encode()
    for kind in (PARAM_FULL, PARAM_FULL_CONT, PARAM_DELTA)
}


class _WireFrame:
    """Pre-encoded outbound frame (mirror of entities.plane.WireFrame,
    local so the manager has no import cycle with the plane)."""

    __slots__ = ("wire", "_msg")

    #: born here, not routed in: no router trace context — and the
    #: delivery path's ``getattr(message, "trace_ctx", None)`` must find
    #: that HERE, not fall through to a decode of the whole frame
    trace_ctx = None

    def __init__(self, wire: bytes):
        self.wire = wire
        self._msg = None

    def __getattr__(self, name):
        msg = object.__getattribute__(self, "_msg")
        if msg is None:
            from ..protocol import deserialize_message

            msg = deserialize_message(self.wire)
            object.__setattr__(self, "_msg", msg)
        return getattr(msg, name)


def pack_entries(entries: list) -> tuple:
    """``[(uuid16, wid, pos_f32_bytes, tombstone)]`` as the columns a
    frame spec carries: ``(keys u8[n,16], pos f32[n,3], tomb u8[n])``."""
    n = len(entries)
    return (
        np.frombuffer(b"".join(e[0] for e in entries), np.uint8).reshape(n, 16),
        np.frombuffer(b"".join(e[2] for e in entries), np.float32).reshape(n, 3),
        np.fromiter((e[3] for e in entries), np.uint8, n),
    )


def _same_content(cohorts, frame):
    """The ``[frame, template]`` of ``cohorts`` whose frame holds
    ``frame``'s entries byte for byte (bit for bit: -0.0 and NaN are
    positions too), or None."""
    for cohort in cohorts or ():
        held = cohort[0]
        if (held[2].tobytes() == frame[2].tobytes()
                and held[3].tobytes() == frame[3].tobytes()
                and held[4].tobytes() == frame[4].tobytes()):
            return cohort
    return None


def _fitted(a: np.ndarray, shape: tuple, fill) -> np.ndarray:
    """``a`` grown to ``shape`` (never shrunk), new cells ``fill``."""
    if a.shape == shape:
        return a
    out = np.full(shape, fill, a.dtype)
    out[tuple(slice(0, n) for n in a.shape)] = a
    return out


class _Snapshot:
    """The columns the last tick's diff read, row by row. A ``synced``
    peer holds exactly its view of them — the live rows whose targets
    list it, each under that row's uuid, world and position — so the
    next tick's diff is the rows that differ from here, and no ledger
    is kept per peer.

    ``targets`` holds a row's recipients in canonical form (sorted, a
    dead row's all -1), so a row whose recipients came back in another
    ORDER equals its snapshot, and only the rows a diff kept are ever
    written. ``EntityPlane`` keeps its retained columns in the same
    form (its tick returns every row sorted), so the rows it compares
    against them itself are compared as this snapshot would. Who owes
    whom which rows: the caller of
    :meth:`InterestManager.build_pairs` may vouch that every row it
    does not name still holds what the last call read; the snapshot
    then answers for those rows unread, and the named ones are
    compared. A caller that names none is owed the whole scan."""

    __slots__ = ("live", "keys", "wid", "pos", "targets")

    def __init__(self):
        self.live = np.zeros(0, bool)
        self.keys = np.zeros((0, 16), np.uint8)
        self.wid = np.zeros(0, np.int32)
        self.pos = np.zeros((0, 3), np.float32)
        self.targets = np.zeros((0, 0), np.int32)

    def fit(self, cap: int, k: int) -> tuple[int, int]:
        """Grow to at least ``cap`` rows of ``k`` targets."""
        cap = max(cap, self.targets.shape[0])
        k = max(k, self.targets.shape[1])
        self.live = _fitted(self.live, (cap,), False)
        self.keys = _fitted(self.keys, (cap, 16), 0)
        self.wid = _fitted(self.wid, (cap,), -1)
        self.pos = _fitted(self.pos, (cap, 3), 0.0)
        self.targets = _fitted(self.targets, (cap, k), -1)
        return cap, k

    def ledgers_of(self, pids: list) -> dict:
        """pid -> the ledger its view is: uuid16 bytes -> (wid,
        pos_f32x3 bytes) of the live rows whose targets list it."""
        mask = np.isin(self.targets, pids) & self.live[:, None]
        rows = np.nonzero(mask)[0]
        seen = self.targets[mask]
        return {
            pid: {
                self.keys[r].tobytes(): (int(self.wid[r]),
                                         self.pos[r].tobytes())
                for r in np.unique(rows[seen == pid]).tolist()
            }
            for pid in pids
        }


class _PeerState:
    """One recipient's delivery ledger: the diff base (what the peer
    holds if it applied every frame), the epoch:seq cursor, the resync
    flag, and the bandwidth bucket."""

    __slots__ = (
        "epoch", "seq", "state", "resync", "demote", "tokens",
        "refilled_at", "deferrals",
    )

    def __init__(self, now: float, burst: float):
        self.epoch = 0
        self.seq = 0
        #: uuid16 bytes -> (wid, pos_f32x3 bytes) the peer holds; None
        #: while ``synced``: the peer then holds exactly its view of the
        #: manager's snapshot, and nothing is kept per peer
        self.state: dict[bytes, tuple[int, bytes]] | None = {}
        self.resync = True          # first frame of a peer is a keyframe
        self.demote = DEMOTE_NONE
        self.tokens = burst
        self.refilled_at = now
        self.deferrals = 0

    @property
    def synced(self) -> bool:
        return self.state is None


class InterestManager:
    def __init__(
        self,
        *,
        near_radius: float = 0.0,
        far_every_k: int = 4,
        bandwidth_bytes: int = 0,
        metrics=None,
        clock=time.monotonic,
    ):
        self.near_radius = float(near_radius)
        self.far_every_k = max(1, int(far_every_k))
        self.bandwidth_bytes = int(bandwidth_bytes)
        #: bucket capacity: one second of budget, floored so a single
        #: keyframe at game shapes is always affordable from idle
        self.bandwidth_burst = float(max(self.bandwidth_bytes, 65536)) \
            if self.bandwidth_bytes else 0.0
        self.metrics = metrics
        self._clock = clock
        self._peers: dict[uuid_mod.UUID, _PeerState] = {}
        self._ticks = 0
        self._shed_level = 0
        self._tier_degraded = False
        #: cohort template cache, swapped wholesale per tick like the
        #: plane's _frame_cache: a mark of the content -> the cohorts
        #: that bear it, each ``[frame spec, (head, tail, size)]``
        self._templates: dict = {}
        #: the diff base of every synced peer, and how many rows list
        #: each pid in it (neither LOD cadences nor budgets: those defer
        #: single rows, so every peer's ledger is then its own)
        self._snap = _Snapshot()
        self._visible = np.zeros(0, np.int64)
        # counters / last-tick gauges
        self.resyncs = 0
        self.bytes_shed = 0
        self.deferrals = 0
        self.templates_reused = 0
        self.last_delta_frames = 0
        self.last_full_frames = 0
        self.last_near = 0
        self.last_far = 0
        self.last_demoted = 0
        self.last_bytes = 0

    # region: resync + lifecycle hooks

    def mark_resync(self, peer: uuid_mod.UUID) -> None:
        """THE loss-path hook (idempotent): the next frame built for
        this peer opens a new epoch with a full keyframe. Called on
        ring drops, worker loss, undelivered-to-parked frames, session
        resume, send errors and overload eviction — a delta can never
        leak past a gap because every gap lands here first."""
        st = self._peers.get(peer)
        if st is None or st.resync:
            return
        st.resync = True
        self.resyncs += 1
        if self.metrics is not None:
            self.metrics.inc("interest.resyncs")

    def forget_peer(self, peer: uuid_mod.UUID) -> None:
        self._peers.pop(peer, None)

    def ledger(self, peer: uuid_mod.UUID, pid: int) -> dict:
        """What ``peer`` (the plane's ``pid``) holds if it applied every
        frame: uuid16 bytes -> (wid, pos_f32x3 bytes). The parity
        oracle's side of the contract (tests, chip_smoke); a synced
        peer's is read off the snapshot."""
        st = self._peers.get(peer)
        if st is None or pid < 0:
            return {}
        if not st.synced:
            return dict(st.state)
        return self._snap.ledgers_of([pid])[pid]

    def note_governor(self, shed_level: int, tier_degraded: bool) -> None:
        """Overload coupling: SHED tiers widen the far cadence
        (k << level) and a degraded tick tier halves the near cadence —
        the lossless replacement for blind frame skipping."""
        self._shed_level = max(0, min(3, int(shed_level)))
        self._tier_degraded = bool(tier_degraded)

    # endregion

    # region: frame building

    def build_pairs(self, plane, pos, targets, cap: int,
                    trace=None, changed=None) -> list:
        """Replace ``EntityPlane._build_frames`` for one applied tick:
        per-recipient delta/full frames instead of per-entity
        broadcast. Returns the same ``(message, [target_uuid])`` pair
        shape ``PeerMap.deliver_batch`` consumes. The two legs are
        spans of the tick's ``trace`` (inside ``tick.sim.apply``):
        ``tick.sim.interest.diff`` (who sees which rows, each peer's
        ledger diff) and ``tick.sim.interest.encode``.

        ``changed`` is the caller's word on which rows of the columns
        (``pos``, ``targets``, the plane's ``_live`` / ``_uuid_bytes`` /
        ``_wid``) may differ from what the LAST call read:
        ``(rows, roster)``, sorted unique row indices, ``roster`` the
        part of ``rows`` whose ``live``, uuid or world may differ too.
        The caller owes every such row since that call, those of ticks
        it applied without calling here included; the diff then reads
        those rows alone. How the caller knows is its own affair: it
        may name every row it recomputed, or vouch for the ones it
        compared itself and found bit for bit what its columns held
        (``EntityPlane._apply_delta`` does, so ``rows`` is about the
        rows this diff keeps); a row named in vain costs its compare
        and nothing else. The sort of the named rows stays: a caller
        may hand recipients in any order. None (the default): the
        caller cannot name them, and every row is compared. The frames
        are the same either way."""
        self._ticks += 1
        if trace is None:
            trace = NULL_TRACE
        with trace.span("tick.sim.interest.diff"):
            specs = self._diff_specs(plane, pos, targets, cap, changed)
        with trace.span("tick.sim.interest.encode"):
            pairs = self._encode_specs(plane, specs)
        self.last_bytes = sum(len(m.wire) for m, _ in pairs)
        return pairs

    def _diff_specs(self, plane, pos, targets, cap: int,
                    changed=None) -> list:
        """Every recipient's frame decision for this tick:
        ``[(uuid, state, frame_specs, new_state, is_resync, complete)]``
        with a frame spec ``(kind, world, keys, pos, tomb)`` (columns of
        :func:`pack_entries`). ``new_state`` None: the peer stays
        synced to the snapshot; ``complete``: ``new_state`` is the
        peer's whole view, so committing it syncs the peer."""
        live = plane._live[:cap]
        # row-major: every leg below gathers ROWS, and a device may
        # hand the column back column-major (no copy when it is not)
        targets = np.ascontiguousarray(np.asarray(targets)[:cap])
        self.last_near = self.last_far = self.last_demoted = 0
        if self.near_radius > 0.0 or self.bandwidth_bytes:
            specs, looked_at = self._walk_specs(
                plane, pos, targets, live, None, False,
            )
        else:
            specs, walk, looked_at = self._snapshot_specs(
                plane, pos, targets, live, changed,
            )
            if walk is None or walk:
                walked, n = self._walk_specs(
                    plane, pos, targets, live, walk,
                    not self._tier_degraded,
                )
                specs += walked
                looked_at += n
            if not self._tier_degraded:
                self.last_near = int(self._visible.sum())   # all near
        if self.metrics is not None and looked_at:
            self.metrics.inc("interest.rows_diffed", looked_at)
        return specs

    def _snapshot_specs(self, plane, pos, targets, live, changed):
        """Diff this tick's columns against the snapshot: the rows that
        differ give every synced peer's delta at once (entered or moved
        rows as positioned entries, rows that left or changed identity
        as tombstones), then the snapshot takes those rows. Which rows
        are read follows ``changed`` (:meth:`build_pairs`): the rows
        the caller names, ``live`` / uuid / world for its roster slots
        only; every row when it names none, or when the snapshot had
        to grow (a new tier is nobody's to vouch for). Returns
        ``(specs, walk, rows that differed)``; ``walk`` is the pids whose
        ledger :meth:`_walk_specs` must walk instead (first contact,
        resync), or None for every peer (degraded near cadence)."""
        snap = self._snap
        cap = len(live)
        shape = snap.targets.shape
        n, k = snap.fit(*targets.shape)     # rows past cap: dead now
        uuids = plane._peer_uuids
        live = _fitted(live, (n,), False)
        targets = _fitted(targets, (n, k), -1)
        keys = _fitted(plane._uuid_bytes[:cap], (n, 16), 0)
        wid = _fitted(plane._wid[:cap], (n,), -1)
        pos32 = _fitted(
            np.ascontiguousarray(pos[:cap], np.float32), (n, 3), 0.0,
        )

        # who rides the snapshot this tick, whose ledger is walked
        fast = np.zeros(len(uuids), bool)
        walk: set[int] = set()
        for u, st in self._peers.items():
            pid = plane._peer_ids.get(u)
            if pid is None:
                continue
            if st.synced and not st.resync and not self._tier_degraded:
                fast[pid] = True
            elif st.synced or st.state or (
                pid < len(self._visible) and self._visible[pid] > 0
            ):
                walk.add(pid)

        # the rows to read (dead on both sides: nothing to say), and
        # the places among them whose identity is read too
        whole = changed is None or shape != (n, k)
        if whole:
            at = np.flatnonzero(live | snap.live)
            roster = slice(None)
        else:
            def named(a):
                a = np.asarray(a, np.intp)
                a = a[a < n]
                return a[live[a] | snap.live[a]]

            at, roster = named(changed[0]), named(changed[1])
            roster = np.searchsorted(at, roster)
        if self.metrics is not None:
            self.metrics.inc("interest.rows_scanned", n if whole else len(at))
            self.metrics.inc("interest.scanned_ticks" if whole
                             else "interest.hinted_ticks")

        # rows that differ (bit for bit: -0.0 and NaN are positions
        # too). The snapshot's recipients are sorted, so one gather and
        # one sort of the new side settle a row whose targets only
        # changed ORDER: it equals its snapshot and drops out here.
        differs = (
            pos32.take(at, axis=0).view(np.uint32)
            != snap.pos.take(at, axis=0).view(np.uint32)
        ).any(axis=1)
        ro = at[roster]
        differs[roster] |= (
            (live[ro] != snap.live[ro]) | (wid[ro] != snap.wid[ro])
            | (keys[ro].view(np.uint64)
               != snap.keys[ro].view(np.uint64)).any(axis=1)
        )
        new_t = targets.take(at, axis=0)
        new_t[~live[at]] = -1
        new_t.sort(axis=1)
        keep = differs | (new_t != snap.targets.take(at, axis=0)).any(axis=1)
        rows, new_t, content = at[keep], new_t[keep], differs[keep]
        old_t = snap.targets[rows]
        m = len(rows)

        def pairs(t):
            """(pid, index into ``rows``) of a sorted targets block."""
            first = np.ones(t.shape, bool)
            first[:, 1:] = t[:, 1:] != t[:, :-1]
            mask = first & (t >= 0)
            idx = np.broadcast_to(np.arange(m)[:, None], t.shape)[mask]
            return t[mask].astype(np.int64) * m + idx

        specs: list = []
        if m:
            new_c, old_c = pairs(new_t), pairs(old_t)
            stays = np.isin(new_c, old_c, assume_unique=True)
            stayed = np.isin(old_c, new_c, assume_unique=True)
            new_pid, new_at = np.divmod(new_c, m)
            old_pid, old_at = np.divmod(old_c, m)
            gained, lost = new_pid[~stays], old_pid[~stayed]
            top = int(max(new_pid.max(initial=-1), old_pid.max(initial=-1))) + 1
            self._visible = _fitted(
                self._visible, (max(top, len(self._visible)),), 0,
            )
            self._visible += (
                np.bincount(gained, minlength=len(self._visible))
                - np.bincount(lost, minlength=len(self._visible))
            )
            for pid in np.unique(gained).tolist():
                if pid < len(uuids) and not fast[pid]:
                    walk.add(pid)

            # every identity a kept row has (places 0..m-1) or had
            # (m..2m-1), in (world, uuid) order: ``place`` ranks them
            # all apart (ties as they stand here), ``ident`` ranks the
            # same identity the same
            ids_w = np.concatenate([wid[rows], snap.wid[rows]])
            ids_k = np.concatenate([keys[rows], snap.keys[rows]]).view(">u8")
            by_id = np.lexsort((ids_k[:, 1], ids_k[:, 0], ids_w))
            place = np.empty(2 * m, np.int64)
            place[by_id] = np.arange(2 * m)
            s_w, s_k = ids_w[by_id], ids_k[by_id]
            fresh = np.ones(2 * m, bool)
            fresh[1:] = (s_w[1:] != s_w[:-1]) | (s_k[1:] != s_k[:-1]).any(axis=1)
            ident = np.empty(2 * m, np.int64)
            ident[by_id] = np.cumsum(fresh)

            # entries of the peers that ride the snapshot
            rekeyed = ident[:m] != ident[m:]
            fast = _fitted(fast, (max(top, len(fast)),), False)
            put = (~stays | content[new_at]) & fast[new_pid]
            drop = (~stayed | rekeyed[old_at]) & fast[old_pid]
            e_pid, e_at = new_pid[put], new_at[put]
            t_pid, t_at = old_pid[drop], old_at[drop] + m
            if len(t_pid) and len(e_pid):
                # an entity that only changed row is no departure
                gone = ~np.isin(t_pid * (2 * m) + ident[t_at],
                                e_pid * (2 * m) + ident[e_at])
                t_pid, t_at = t_pid[gone], t_at[gone]
            # one integer an entry, ordered as the frames are:
            # (recipient, world, uuid)
            e_pid = np.concatenate([e_pid, t_pid])
            e_at = np.concatenate([e_at, t_at])
            order = np.argsort(e_pid * (2 * m) + place[e_at])
            e_pid, e_at = e_pid[order], e_at[order]
            src = rows[e_at % m]
            e_keys, e_wid, e_pos = keys[src], wid[src], pos32[src]
            tomb = e_at >= m         # these ride as what the row WAS
            left = src[tomb]
            e_keys[tomb], e_wid[tomb] = snap.keys[left], snap.wid[left]
            e_pos[tomb] = snap.pos[left]
            specs = self._delta_specs(
                uuids, e_pid, e_wid, e_keys, e_pos, tomb.view(np.uint8),
            )

        # a synced peer about to be walked holds its view of the
        # snapshot as it is NOW, before it takes this tick's rows
        behind = [pid for pid in (range(len(uuids)) if self._tier_degraded
                                  else walk)
                  if uuids[pid] in self._peers
                  and self._peers[uuids[pid]].synced]
        if behind:
            for pid, held in snap.ledgers_of(behind).items():
                self._peers[uuids[pid]].state = held
        snap.live[rows] = live[rows]
        snap.keys[rows] = keys[rows]
        snap.wid[rows] = wid[rows]
        snap.pos[rows] = pos32[rows]
        snap.targets[rows] = new_t
        return specs, (None if self._tier_degraded else walk), m

    def _delta_specs(self, uuids, pid, wid, keys, pos, tomb) -> list:
        """Entries of many peers, ordered by (recipient, world, uuid),
        as per-peer delta frame specs: one run of frames a recipient
        and world, ``FRAME_CHUNK`` entries a frame."""
        if not len(pid):
            return []
        cut = np.flatnonzero((pid[1:] != pid[:-1]) | (wid[1:] != wid[:-1])) + 1
        starts = np.concatenate(([0], cut)).tolist()
        frames_of: dict[int, list] = {}
        for a, b in zip(starts, starts[1:] + [len(pid)]):
            frames_of.setdefault(int(pid[a]), []).extend(
                (PARAM_DELTA, int(wid[a]), keys[c:min(c + FRAME_CHUNK, b)],
                 pos[c:min(c + FRAME_CHUNK, b)],
                 tomb[c:min(c + FRAME_CHUNK, b)])
                for c in range(a, b, FRAME_CHUNK)
            )
        return [
            (uuids[p], self._peers[uuids[p]], frames, None, False, True)
            for p, frames in frames_of.items()
        ]

    def _walk_specs(self, plane, pos, targets, live, only, complete):
        """Walk the ledgers of the peers ``only`` (None: every peer
        that sees a row or holds one) against their visible rows.
        Returns ``(specs, rows looked at)``."""
        valid = targets >= 0 if only is None else np.isin(
            targets, list(only),
        )
        rows = np.flatnonzero(live & valid.any(axis=1))

        # invert row->targets into per-recipient visible row lists
        by_pid: dict[int, np.ndarray] = {}
        if rows.size:
            tgt = targets[rows]
            mask = valid[rows]
            r_idx = np.repeat(rows, tgt.shape[1])[mask.ravel()]
            p_idx = tgt.ravel()[mask.ravel()]
            order = np.argsort(p_idx, kind="stable")
            p_sorted, r_sorted = p_idx[order], r_idx[order]
            bounds = np.flatnonzero(np.diff(p_sorted)) + 1
            for chunk, pid_val in zip(
                np.split(r_sorted, bounds),
                p_sorted[np.concatenate(([0], bounds))],
            ):
                by_pid[int(pid_val)] = np.unique(chunk)

        # peers with retained state but nothing visible still need
        # their departures delivered
        peers = set(only) if only is not None else set(by_pid)
        if only is None:
            for u, st in self._peers.items():
                if st.state:
                    pid = plane._peer_ids.get(u)
                    if pid is not None:
                        peers.add(pid)

        near_every = 2 if self._tier_degraded else 1
        far_every = self.far_every_k << self._shed_level
        specs = []
        looked_at = 0
        for pid in sorted(peers):
            if pid >= len(plane._peer_uuids):
                continue
            u = plane._peer_uuids[pid]
            st = self._peers.get(u)
            if st is None:
                st = self._peers[u] = _PeerState(
                    self._clock(), self.bandwidth_burst
                )
            vrows = by_pid.get(pid)
            if vrows is not None:
                looked_at += int(vrows.size)
            spec = self._peer_spec(
                plane, pos, pid, st, vrows, near_every, far_every,
            )
            if spec is not None:
                frames, new_state, is_resync = spec
                specs.append((
                    u, st,
                    [(kind, wid) + pack_entries(entries)
                     for kind, wid, entries in frames],
                    new_state, is_resync, complete,
                ))
            elif complete and not st.resync:
                # nothing to say: the ledger IS the view
                st.state = None
        return specs, looked_at

    def _center_of(self, plane, pid: int):
        """The recipient's subscription center: centroid of its own
        live entities (None = no entities, everything is near)."""
        slots = plane._peer_slots.get(pid)
        if not slots:
            return None
        idx = np.fromiter(slots, np.intp, count=len(slots))
        return plane._pos[idx].mean(axis=0)

    def _peer_spec(self, plane, pos, pid, st, vrows, near_every,
                   far_every):
        """One recipient's frame decision for this tick. Returns
        ``(frame_specs, new_state)`` or None (nothing due). A
        frame_spec is ``(kind, world, entries)`` with entries
        ``[(uuid16, wid, pos_f32_bytes, tombstone)]``; stamping and
        encoding happen later so identical content can share one
        template."""
        demote = st.demote
        if demote:
            self.last_demoted += 1
        phase = (self._ticks + pid) % far_every == 0
        near_due = (self._ticks + pid) % near_every == 0
        resync = st.resync

        center = None
        if self.near_radius > 0.0 and not resync:
            center = self._center_of(plane, pid)

        new_state: dict[bytes, tuple[int, bytes]] = {}
        n_near = n_far = 0
        if vrows is not None and vrows.size:
            vpos = pos[vrows].astype(np.float32, copy=False)
            if resync or (self.near_radius <= 0.0 and demote == DEMOTE_NONE):
                near_mask = np.ones(len(vrows), bool)
            elif demote != DEMOTE_NONE:
                near_mask = np.zeros(len(vrows), bool)
            elif center is None:
                near_mask = np.ones(len(vrows), bool)
            else:
                d2 = ((vpos - center.astype(np.float32)) ** 2).sum(axis=1)
                near_mask = d2 <= np.float32(self.near_radius) ** 2
            n_near = int(near_mask.sum())
            n_far = len(vrows) - n_near
            for i, row in enumerate(vrows.tolist()):
                key = plane._uuid_bytes[row].tobytes()
                wid = int(plane._wid[row])
                prev = st.state.get(key)
                due = near_mask[i] and near_due or (not near_mask[i]) and phase
                if resync or due or prev is None and near_mask[i] and near_due:
                    new_state[key] = (wid, vpos[i].tobytes())
                elif prev is not None:
                    new_state[key] = prev      # off-cadence: retain
                # else: off-cadence far ENTER — defer until due
        self.last_near += n_near
        self.last_far += n_far

        # departures: keys the peer holds that are no longer visible.
        # Far-tier departures (by retained position) defer to the far
        # cadence like every other far change; resync drops the ledger
        # wholesale via the epoch bump.
        if not resync:
            for key, (wid, pos_b) in st.state.items():
                if key in new_state:
                    continue
                is_far = False
                if self.near_radius > 0.0 and center is not None \
                        and st.demote == DEMOTE_NONE:
                    old = np.frombuffer(pos_b, np.float32)
                    d2 = float(((old - center.astype(np.float32)) ** 2).sum())
                    is_far = d2 > self.near_radius ** 2
                elif st.demote != DEMOTE_NONE:
                    is_far = True
                if is_far and not phase:
                    new_state[key] = (wid, pos_b)  # defer the leave

        if resync:
            if not new_state and not st.state:
                return None            # nothing to clear, nothing to send
            frames = self._full_specs(new_state, st.state)
            return frames, new_state, True
        if demote == DEMOTE_KEYFRAME:
            if not phase:
                return None
            frames = self._full_specs(new_state, st.state)
            return (frames, new_state, False) if frames else None

        # delta: entered/moved as positioned entities, left as
        # tombstones, grouped per world
        by_world: dict[int, list] = {}
        for key, (wid, pos_b) in new_state.items():
            prev = st.state.get(key)
            if prev is None or prev[1] != pos_b or prev[0] != wid:
                if prev is not None and prev[0] != wid:
                    # world hop = leave old world + enter new
                    by_world.setdefault(prev[0], []).append(
                        (key, prev[0], prev[1], True)
                    )
                by_world.setdefault(wid, []).append(
                    (key, wid, pos_b, False)
                )
        for key, (wid, pos_b) in st.state.items():
            if key not in new_state:
                by_world.setdefault(wid, []).append((key, wid, pos_b, True))
        if not by_world:
            return None
        frames = []
        for wid, entries in sorted(by_world.items()):
            entries.sort()
            frames += [
                (PARAM_DELTA, wid, entries[c0:c0 + FRAME_CHUNK])
                for c0 in range(0, len(entries), FRAME_CHUNK)
            ]
        return frames, new_state, False

    def _full_specs(self, new_state, old_state):
        """Chunked keyframe specs covering every world in the new
        state — plus an EMPTY full for a world the peer still holds
        that vanished entirely (the clear marker)."""
        by_world: dict[int, list] = {}
        for key, (wid, pos_b) in new_state.items():
            by_world.setdefault(wid, []).append((key, wid, pos_b, False))
        for key, (wid, _pos) in old_state.items():
            if wid not in by_world and key not in new_state:
                by_world[wid] = []
        frames = []
        for wid, entries in sorted(by_world.items()):
            entries.sort()
            if not entries:
                frames.append((PARAM_FULL, wid, []))
                continue
            for c0 in range(0, len(entries), FRAME_CHUNK):
                kind = PARAM_FULL if c0 == 0 else PARAM_FULL_CONT
                frames.append((kind, wid, entries[c0:c0 + FRAME_CHUNK]))
        return frames

    def _encode_specs(self, plane, specs) -> list:
        """Encode every peer's frame specs with cross-peer cohort
        dedup, apply bandwidth admission, commit ledgers, and emit
        delivery pairs. Two passes: the first resolves every frame's
        cohort key and queues the ones nothing holds, which ONE call
        of :meth:`_encode_templates` encodes for the whole tick; the
        second admits, stamps and commits on the encoded sizes."""
        next_templates: dict = {}
        missed: list = []
        keyed: list = []
        for spec in specs:
            cohorts = []
            for frame in spec[2]:
                kind, wid, keys, pos, tomb = frame
                # a few of a frame's bytes find the cohorts it could
                # belong to, all of its bytes settle it: hashing every
                # frame's ~7.5 KB cost more than the lookup saved
                mark = (kind, wid, len(tomb), keys[:1].tobytes(),
                        pos[:1].tobytes())
                held = next_templates.setdefault(mark, [])
                cohort = _same_content(held, frame)
                if cohort is None:
                    cohort = _same_content(self._templates.get(mark), frame)
                    # [frame, template]: None until encoded below
                    cohort = [frame, None if cohort is None else cohort[1]]
                    held.append(cohort)
                    if cohort[1] is None:
                        missed.append(cohort)
                        cohorts.append(cohort)
                        continue
                cohorts.append(cohort)
                self.templates_reused += 1
                if self.metrics is not None:
                    self.metrics.inc("delta.frames_reused")
            keyed.append(cohorts)
        if missed:
            for cohort, tpl in zip(missed, self._encode_templates(
                plane, [cohort[0] for cohort in missed],
            )):
                cohort[1] = tpl

        pairs = []
        entries_sent = 0
        now = self._clock()
        self.last_delta_frames = self.last_full_frames = 0
        for (u, st, frames, new_state, is_resync, complete), cohorts in zip(
            specs, keyed,
        ):
            encoded = [cohort[1] for cohort in cohorts]
            nbytes = sum(tpl[2] for tpl in encoded)

            if self.bandwidth_bytes and not self._afford(st, nbytes, now):
                # lossless deferral: nothing sent, nothing committed —
                # the diff simply accumulates into the next frame
                self.deferrals += 1
                st.deferrals += 1
                if st.demote < DEMOTE_KEYFRAME:
                    st.demote += 1
                    self.last_demoted += 1
                elif is_resync or st.resync or not any(
                    frame[0] == PARAM_DELTA for frame in frames
                ):
                    # bottom of the ladder AND the keyframe itself is
                    # unaffordable: the ONLY shed point, counted
                    self.bytes_shed += nbytes
                    if self.metrics is not None:
                        self.metrics.inc("delivery.bytes_shed", nbytes)
                continue

            if is_resync:
                st.epoch += 1
                st.seq = 0
                st.resync = False
            epoch = st.epoch & 0xFFFFFFFF
            for frame, (head, tail, _size) in zip(frames, encoded):
                # ONE copy a recipient: the template around its stamp
                pairs.append((_WireFrame(b"".join((
                    head, b"%08x:%08x" % (epoch, st.seq & 0xFFFFFFFF), tail,
                ))), [u]))
                st.seq += 1
                if frame[0] == PARAM_DELTA:
                    self.last_delta_frames += 1
                else:
                    self.last_full_frames += 1
                entries_sent += len(frame[2])
            if new_state is not None:
                # a walked ledger; when it is the peer's whole view
                # the peer is synced and the snapshot stands for it
                st.state = None if complete else new_state
        self._templates = next_templates
        if self.metrics is not None and entries_sent:
            self.metrics.inc("interest.entries", entries_sent)
        return pairs

    def _afford(self, st, nbytes: int, now: float) -> bool:
        rate = float(self.bandwidth_bytes)
        st.tokens = min(
            self.bandwidth_burst,
            st.tokens + (now - st.refilled_at) * rate,
        )
        st.refilled_at = now
        if st.tokens >= nbytes:
            st.tokens -= nbytes
            if st.demote and st.tokens >= self.bandwidth_burst * 0.5:
                st.demote -= 1          # headroom: walk back up
            return True
        return False

    def _encode_templates(self, plane, frames) -> list:
        """The cohort templates of ``[(kind, world, keys, pos, tomb)]``:
        a frame's wire bytes around a zeroed stamp, ``(head, tail,
        size)`` with the 17 stamp bytes ``<epoch hex8>:<seq hex8>``
        between ``head`` and ``tail``. ONE native call for them all
        when the library has the symbol (entities stamped from
        fixed-layout records); the object path, frame by frame, is
        byte-identical (pinned by test)."""
        names = plane._world_names
        worlds = [names[frame[1]] if 0 <= frame[1] < len(names) else ""
                  for frame in frames]
        params = [_PLACEHOLDER[frame[0]] for frame in frames]
        counts = [len(frame[2]) for frame in frames]
        if self.metrics is not None:
            self.metrics.inc("interest.entries_encoded", sum(counts))
        wire = getattr(plane, "_wire", None)
        if wire is not None and getattr(wire, "can_encode_interest", False):
            bounds = np.zeros(len(frames) + 1, np.int64)
            np.cumsum(counts, out=bounds[1:])
            bufs, at, recorded = wire.encode_interest_frames(
                params, [world.encode() for world in worlds], bounds,
                np.concatenate([frame[2] for frame in frames]),
                # float64 once for the tick, not a frame
                np.concatenate([frame[3] for frame in frames],
                               dtype=np.float64),
                np.concatenate([frame[4] for frame in frames]),
            )
            if self.metrics is not None:
                self.metrics.inc("interest.encode_calls")
                self.metrics.inc("interest.entries_recorded", recorded)
        else:
            from ..protocol import serialize_message

            bufs = [
                serialize_message(Message(
                    instruction=Instruction.LOCAL_MESSAGE,
                    parameter=param.decode(),
                    sender_uuid=NIL_UUID,
                    world_name=world,
                    entities=[
                        Entity(
                            uuid=uuid_mod.UUID(bytes=key.tobytes()),
                            position=Vector3(
                                float(p[0]), float(p[1]), float(p[2])),
                            world_name=world,
                            flex=TOMBSTONE_FLEX if dead else None,
                        )
                        for key, p, dead in zip(keys, pos, tomb)
                    ],
                ))
                for param, world, (_kind, _wid, keys, pos, tomb)
                in zip(params, worlds, frames)
            ]
            # the stamp is the caller's own bytes: always encoded
            at = [buf.index(param) for buf, param in zip(bufs, params)]
        templates = []
        for frame, buf, param_at in zip(frames, bufs, at):
            e_off = param_at + len(frame[0]) + 1
            templates.append((buf[:e_off], buf[e_off + 17:], len(buf)))
        return templates

    def _encode_template(self, plane, kind: str, wid: int, keys, pos,
                         tomb):
        """One cohort's template, a batch of one, as ``(wire bytes with
        a zeroed stamp, offset of the epoch field, of the seq
        field)``."""
        (head, tail, _size), = self._encode_templates(
            plane, [(kind, wid, keys, pos, tomb)],
        )
        return (b"".join((head, b"%08x:%08x" % (0, 0), tail)),
                len(head), len(head) + 9)

    # endregion

    def stats(self) -> dict:
        total = self.last_delta_frames + self.last_full_frames
        return {
            "peers": len(self._peers),
            "near": self.last_near,
            "far": self.last_far,
            "demoted": self.last_demoted,
            "delta_frames": self.last_delta_frames,
            "full_frames": self.last_full_frames,
            "delta_ratio": round(
                self.last_delta_frames / total, 4
            ) if total else 0.0,
            "resyncs": self.resyncs,
            "deferrals": self.deferrals,
            "bytes_shed": self.bytes_shed,
            "templates_reused": self.templates_reused,
            "last_bytes": self.last_bytes,
            "far_every_k": self.far_every_k << self._shed_level,
        }
