"""Spatial fan-out sharded over a device mesh.

Scale-out design (BASELINE configs 4-5): the sorted base segment is
split into per-device contiguous key ranges — split points snapped to
cube-run boundaries so every cube's subscriber run lives wholly on one
device. Queries shard over the ``batch`` axis. Each device binary-
searches its local range; exactly one ``space`` shard can match a given
cube, so partial [M/b, K] results (−1 = no match) combine with a single
``pmax`` over ``space`` — one ICI collective per tick, no host hops.

The small delta segment (rows added since the last compaction — see
spatial/tpu_backend.py) is *replicated* across the mesh: every device
matches the full delta locally, the partials concatenate with the base
partials before the ``pmax``, and the merge stays one collective.

SPMD via ``jax.shard_map``; XLA lays out the gathers per shard and the
final combine as an ICI all-reduce(max). Worlds need no special
handling: world id is part of the spatial key, so a world's cubes
scatter across shards (load-balancing Zipf-hotspot worlds) while each
cube stays device-local. CSR result compaction runs in the
same jit after the shard_map — XLA partitions the cumsum/scatter with
the collectives it needs, so compacted results work identically on the
mesh (the distributed delivery path consumes CSR).

Query arrays enter as numpy with explicit ``in_shardings``, so every
H2D transfer rides the ONE jitted dispatch — no per-array
``device_put`` round-trips.
"""

from __future__ import annotations

import numpy as np

from ..spatial import jaxconf  # noqa: F401  (must precede jax import)
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..spatial.hashing import PAD_KEY, n_distinct, next_pow2, pad_to
from ..utils import retrace
from ..spatial.tpu_backend import (
    CSR_ROW,
    CSR_ROW_B,
    SEG_ARRAYS,
    TpuSpatialBackend,
    _alloc_buffers,
    _grow_buffers,
    _scatter_dead,
    _sort_segment_dev,
    _write_chunk,
    match_core,
    pack_csr,
    probe_buckets_for,
    probe_tables,
    run_bounds_all,
    run_csr_assemble,
    run_remainders,
    run_remainders_np,
)

_shard_map = jax.shard_map


def split_at_run_boundaries(keys: np.ndarray, n_shards: int) -> list[int]:
    """Split points for sorted ``keys`` into ``n_shards`` near-equal
    chunks, snapped left to run starts so equal keys never straddle a
    boundary. Returns n_shards+1 offsets."""
    n = len(keys)
    splits = [0]
    for i in range(1, n_shards):
        ideal = (n * i) // n_shards
        if ideal <= splits[-1]:
            splits.append(splits[-1])
            continue
        snapped = int(np.searchsorted(keys, keys[ideal], side="left"))
        splits.append(max(snapped, splits[-1]))
    splits.append(n)
    return splits


class ShardedTpuSpatialBackend(TpuSpatialBackend):
    """Multi-chip backend: same host authority and observable semantics
    as the single-chip backend, base segment sharded over ``mesh``."""

    def __init__(
        self, cube_size: int, mesh: Mesh,
        compact_threshold: int | None = None,
    ):
        super().__init__(cube_size, compact_threshold=compact_threshold)
        if set(mesh.axis_names) != {"batch", "space"}:
            raise ValueError("mesh must have axes ('batch', 'space')")
        self.mesh = mesh
        self.n_batch = mesh.shape["batch"]
        self.n_space = mesh.shape["space"]
        self._kernels: dict[tuple, object] = {}
        # what the mesh adds to a served tick, beside compact_fetches /
        # delta_reused (device_stats exports them): served batches
        # handed to a mesh_resolve program, their real rows before
        # padding, the bytes their pmax merges reduce, and the
        # per-batch-shard result regions fetched and walked. The boot
        # walk (spatial/precompile.py) and the rare overflow
        # re-resolve call the programs below this seam and are not in
        # them.
        self.mesh_dispatches = 0
        self.mesh_query_rows = 0
        self.mesh_merge_bytes = 0
        self.mesh_region_fetches = 0

    def supports_delta_ticks(self) -> bool:
        """Result reuse runs on the mesh via PER-SHARD FLAT-REGION
        replay (ISSUE 14 satellite, the PR 13 leftover): the reuse
        cache and its validity tracking are HOST state shared with the
        single-chip backend (signatures, per-cube dirty sequence,
        `_install_base` floors — all fed by the same mutation paths
        this class inherits), so a clean query replays its cached
        fan-out without touching any device; only the dirty partition
        dispatches, through the ordinary mesh kernels, whose CSR
        results are assembled as per-batch-shard flat regions and
        decoded by this class's own region-walk overrides — the pmax
        merge happens (or is skipped) per sub-batch exactly as it
        would for a full tick. Replay correctness therefore never
        depends on the mesh layout; layout only shapes what the dirty
        partition computes. Pinned lane-for-lane against the
        full-recompute mesh by the randomized-churn parity suite."""
        return True

    def _delta_scatter_supported(self) -> bool:
        # the O(K) tombstone scatter targets the single-device sorted
        # DELTA segment; the mesh replicates that segment, so delta
        # sync keeps the full-sort path (orthogonal to result reuse —
        # reuse replays results, the scatter maintains the hash)
        return False

    # region: shardings

    def _sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    def _base_specs(self):
        # (key, key2, peer, run-remainder, tbl, oflow) — 1-D columns
        # and the [B, 2E] packed probe table as per-shard stacks
        v = P("space", None)
        t = P("space", None, None)
        return (v, v, v, v, t, v)

    def _delta_specs(self):
        v = P(None)
        t = P(None, None)
        return (v, v, v, v, t, v)

    def _query_specs(self):
        # (key, key2, sender, repl)
        return (P("batch"), P("batch"), P("batch"), P("batch"))

    # endregion

    # region: device upload seams

    def _upload_base(self, keys, keys2, pids, k) -> dict:
        # split the LIVE prefix: the host arrays arrive padded to a
        # power of two with PAD_KEY rows (`_install_base`), and a split
        # of the padded length hands the last shards the padding (at
        # 640,000 rows: shards of 262,144 / 262,144 / 115,712 live rows
        # and one of 408,576 pad rows, which also set the capacity a
        # shard, 2^19 where 2^18 holds a quarter of the rows). Pad rows
        # hold nothing, so no shard needs them; `_compact_device` keeps
        # them out of its balance the same way.
        live_n = int(np.searchsorted(keys, PAD_KEY, side="left"))
        keys, keys2, pids = keys[:live_n], keys2[:live_n], pids[:live_n]
        splits = split_at_run_boundaries(keys, self.n_space)
        cap = next_pow2(max(b - a for a, b in zip(splits, splits[1:])))

        def stack(arr: np.ndarray, fill) -> np.ndarray:
            return np.stack([
                pad_to(arr[a:b], cap, fill)
                for a, b in zip(splits, splits[1:])
            ])

        # runs never straddle a shard boundary (splits snap to run
        # starts), so each shard's run-remainder column (and its probe
        # table — shard-local run starts) derives from its own padded
        # key rows
        padded_keys = stack(keys, PAD_KEY)
        rems = np.stack([run_remainders_np(row) for row in padded_keys])
        n_cubes = max(
            n_distinct(keys[a:b]) for a, b in zip(splits, splits[1:])
        )
        sub = self._sharding("space", None)
        sk = jax.device_put(padded_keys, sub)
        sk2 = jax.device_put(stack(keys2, np.int64(0)), sub)
        rem = jax.device_put(rems, sub)
        tbl, oflow = self._probe_stack(sk, sk2, probe_buckets_for(n_cubes))
        return {
            "dev": (
                sk,
                sk2,
                jax.device_put(stack(pids.astype(np.int32), np.int32(-1)),
                               sub),
                rem, tbl, oflow,
            ),
            "cap": self.n_space * cap,
            "splits": np.asarray(splits, np.int64),
            "shard_cap": cap,
        }

    def _probe_stack(self, sk_stack, sk2_stack, n_buckets: int):
        """Per-shard probe tables for a [n_space, cap] base stack —
        vmapped over the shard dim with matching shardings, so each
        device builds the table for its own rows locally."""
        key = ("probe_stack", n_buckets)
        kernel = self._kernels.get(key)
        if kernel is None:
            kernel = self._kernels[key] = jax.jit(
                jax.vmap(
                    lambda sk, sk2: probe_tables(
                        sk, sk2, n_buckets=n_buckets
                    )
                ),
                in_shardings=(
                    self._sharding("space", None),
                    self._sharding("space", None),
                ),
                out_shardings=(
                    self._sharding("space", None, None),
                    self._sharding("space", None),
                ),
            )
            retrace.GUARD.register("sharded.probe_stack", kernel)
        return kernel(sk_stack, sk2_stack)

    #: re-shard (full re-upload) only when the largest shard exceeds
    #: this multiple of the mean — keys are uniform hashes, so the old
    #: key-range boundaries stay balanced as the index grows and the
    #: bound ~never trips in practice
    RESHARD_IMBALANCE = 2.0

    def _compact_device(
        self, snap: dict, cap2: int, host_arrays, k, n_buckets: int
    ) -> dict:
        """Mesh-aware compaction in O(churn) transfer: every shard owns
        a contiguous KEY range (the old split boundaries), the delta is
        already device-resident and replicated, so each shard folds
        (its base rows ∪ the delta rows hashing into its range) locally
        — one vmapped on-device sort per shard, H2D limited to the
        [n_space] boundary-key vector. The old global sort restricted
        to a key range IS the new shard content, so the host mirror
        (folded by ``_compact_work``'s identical stable transform) and
        the device stacks stay row-aligned via the re-derived splits.

        Falls back to a full host re-upload (fresh balanced splits)
        when there is no resident base/boundary state yet or when
        shard sizes drift past RESHARD_IMBALANCE — deferred
        re-sharding: uniform key hashes keep old boundaries balanced,
        so the common compaction ships ~nothing over the link. Runs on
        the compaction worker thread, so neither path touches the
        owning event loop."""
        hk, hk2, hp = host_arrays
        base = snap.get("base_bundle")
        if (
            base is None
            or base.get("splits") is None
            or self.n_space == 1
            or snap.get("delta_buf") is None
        ):
            return self._upload_base(hk, hk2, hp, k)

        old_splits = base["splits"]
        old_bk = snap["bk"]
        # boundary KEYS from the old row splits (first key of each
        # shard after the first); the new fold re-derives row splits
        # from the same keys, so runs still never straddle shards
        bounds = np.empty(self.n_space + 1, np.int64)
        bounds[0] = np.iinfo(np.int64).min
        bounds[-1] = np.int64(PAD_KEY)  # live keys are always < PAD_KEY
        for s in range(1, self.n_space):
            row = int(old_splits[s])
            bounds[s] = old_bk[row] if row < old_bk.size else PAD_KEY

        new_splits = np.empty(self.n_space + 1, np.int64)
        new_splits[0] = 0
        new_splits[-1] = hk.size
        new_splits[1:-1] = np.searchsorted(
            hk, bounds[1:-1], side="left"
        )
        # live rows per shard: dead/pad rows were rewritten to PAD_KEY
        # before the host sort, so the live prefix ends at the first
        # PAD row and the pad tail must not skew balance accounting
        live_n = int(np.searchsorted(hk, PAD_KEY, side="left"))
        edges = np.minimum(new_splits, live_n)
        counts = np.diff(edges)
        mean = max(live_n / self.n_space, 1.0)
        if counts.max() > self.RESHARD_IMBALANCE * mean + 8:
            return self._upload_base(hk, hk2, hp, k)

        cap_shard = next_pow2(int(counts.max()))
        # probe tables sized for the busiest SHARD's cube count, not
        # the global one (global would allocate S× the needed rows)
        n_cubes = 1
        for s in range(self.n_space):
            a, b = int(edges[s]), int(edges[s + 1])
            if b > a:
                n_cubes = max(n_cubes, n_distinct(hk[a:b]))
        n_buckets = probe_buckets_for(n_cubes)
        bk, bk2, bp = base["dev"][:3]
        if cap_shard > bk.shape[1] + snap["delta_buf"][0].shape[0]:
            # cannot happen (new rows <= old rows + delta rows), but a
            # silent wrong-shape fold would corrupt the index — guard
            return self._upload_base(hk, hk2, hp, k)
        dev = self._fold_shards(
            bk, bk2, bp, snap["delta_buf"],
            jnp.asarray(bounds[:-1]), jnp.asarray(bounds[1:]),
            cap_shard, n_buckets,
        )
        return {
            "dev": dev,
            "cap": self.n_space * cap_shard,
            "splits": new_splits,
            "shard_cap": cap_shard,
        }

    def _fold_shards(self, bk, bk2, bp, delta, lo, hi, cap2: int,
                     n_buckets: int):
        """vmapped per-shard fold: local base rows + the delta rows in
        [lo, hi) → fresh sorted shard with run-remainders and probe
        tables. Tombstones and out-of-range delta rows sink past the
        live rows as PAD_KEY (their peers are <0 or their keys padded,
        so no consumer can see them)."""
        key = ("fold_shards", cap2, n_buckets, bk.shape, delta[0].shape)
        kernel = self._kernels.get(key)
        if kernel is None:
            def fold_one(bk, bk2, bp, lo, hi, dk, dk2, dp):
                in_range = (dk >= lo) & (dk < hi) & (dp >= 0)
                dkm = jnp.where(in_range, dk, PAD_KEY)
                dpm = jnp.where(in_range, dp, -1)
                keys = jnp.concatenate(
                    [jnp.where(bp < 0, PAD_KEY, bk), dkm]
                )
                keys2 = jnp.concatenate([bk2, dk2])
                peers = jnp.concatenate([bp, dpm])
                order = jnp.argsort(keys, stable=True)[:cap2]
                sk = keys[order]
                sk2 = keys2[order]
                rem = run_remainders(sk)
                tbl_a, oflow = probe_tables(sk, sk2, n_buckets=n_buckets)
                return (sk, sk2, peers[order], rem, tbl_a, oflow)

            sub = self._sharding("space", None)
            vec = self._sharding("space")
            rep = self._sharding(None)
            tbl = self._sharding("space", None, None)
            kernel = self._kernels[key] = jax.jit(
                jax.vmap(
                    fold_one,
                    in_axes=(0, 0, 0, 0, 0, None, None, None),
                ),
                in_shardings=(sub, sub, sub, vec, vec, rep, rep, rep),
                out_shardings=(sub, sub, sub, sub, tbl, vec),
            )
            retrace.GUARD.register("sharded.fold_shards", kernel)
        return kernel(bk, bk2, bp, lo, hi, *delta)

    # -- delta seams: the delta segment is replicated across the mesh,
    # so allocate/write/sort with explicit replicated out_shardings —
    # otherwise the buffers commit to device 0 and every dispatch
    # re-transfers them to the other shards. --

    def _rep_kernel(self, name: str, fn, static=(), spec=()):
        kernel = self._kernels.get(name)
        if kernel is None:
            kernel = self._kernels[name] = jax.jit(
                fn, static_argnames=static,
                out_shardings=self._sharding(*spec),
            )
            retrace.GUARD.register(f"sharded.{name}", kernel)
        return kernel

    def _alloc_delta_buffer(self, cap: int) -> tuple:
        return self._rep_kernel("alloc_delta", _alloc_buffers, ("cap",))(
            cap=cap
        )

    def _grow_delta_buffer(self, bufs: tuple, cap: int) -> tuple:
        return self._rep_kernel("grow_delta", _grow_buffers, ("cap",))(
            bufs, cap=cap
        )

    def _write_delta_chunk(self, bufs: tuple, chunk: tuple, start: int):
        return self._rep_kernel("write_delta", _write_chunk)(
            bufs, chunk, np.int32(start)
        )

    def _scatter_delta_dead(self, peer_buf, rows: np.ndarray):
        return self._rep_kernel("scatter_delta", _scatter_dead)(
            peer_buf, rows
        )

    def _sort_delta(self, bufs: tuple, n_buckets: int) -> tuple:
        key = ("sort_delta", n_buckets)
        kernel = self._kernels.get(key)
        if kernel is None:
            v, t = self._sharding(None), self._sharding(None, None)
            kernel = self._kernels[key] = jax.jit(
                _sort_segment_dev, static_argnames=("n_buckets",),
                out_shardings=(v, v, v, v, t, v),
            )
            retrace.GUARD.register("sharded.sort_delta", kernel)
        return kernel(*bufs, n_buckets=n_buckets)

    def _scatter_base_dead(self, bundle: dict, rows: np.ndarray) -> dict:
        """Map global sorted-row indices → (shard, local) and tombstone
        with one scatter over the [n_space, cap] peer array."""
        splits = bundle["splits"]
        cap = bundle["shard_cap"]
        shard = np.searchsorted(splits, rows, side="right") - 1
        local = rows - splits[shard]
        pad_n = next_pow2(rows.size)
        shard = pad_to(shard.astype(np.int32), pad_n, np.int32(self.n_space))
        local = pad_to(local.astype(np.int32), pad_n, np.int32(cap))
        dev = bundle["dev"]
        kernel = self._rep_kernel(
            "scatter",
            lambda peer, s, l: peer.at[s, l].set(-1, mode="drop"),
            spec=("space", None),
        )
        return {
            **bundle,
            "dev": (*dev[:2], kernel(dev[2], shard, local), *dev[3:]),
        }

    # endregion

    # region: dispatch

    #: smallest query tier of a mesh program. Every (query tier, t_cap)
    #: pair is a program of its own, compiled at first use on the event
    #: loop (0.57-0.85 s each on four v5e chips), and the adaptive
    #: t_cap walks four to six tiers under each query tier. With the
    #: base class's floor of 8, a served load of 5-35 dirty rows a tick
    #: wandered over the tiers 8, 16 and 32: ~30 programs compiled in
    #: the warm-up of `worlds-64x10k.hot-cube` and one in its window
    #: (PERF.md section 6, PR 32). Padding 8 rows to 32 costs the
    #: devices microseconds; the boot walk's ladder ends here too.
    MIN_QUERY_TIER = 32

    def _query_cap(self, m: int) -> int:
        # Batch capacity must shard evenly over 'batch': power-of-two
        # tier, rounded up to a multiple of n_batch (which need not be
        # a power of two).
        cap = max(next_pow2(m), self.MIN_QUERY_TIER, self.n_batch)
        return -(-cap // self.n_batch) * self.n_batch

    def _make_kernel(self, variant: str, kinds: tuple, ks: tuple, extra):
        """Compile a mesh kernel ('dense' or 'csr'): shard_map match
        (+ pmax merge), one jit, explicit in_shardings.
        ``kinds`` says which segments are space-sharded stacks ('base',
        local view [1, cap]) vs replicated flat arrays ('delta')."""
        mesh = self.mesh
        n_seg = len(kinds)

        na = SEG_ARRAYS

        def local_segs(args):
            for i, kind in enumerate(kinds):
                seg = args[na * i:na * i + na]
                if kind == "base":
                    seg = tuple(a[0] for a in seg)  # drop the shard dim
                yield seg

        def local(*args):
            queries = args[na * n_seg:]
            parts = [
                match_core(seg, *queries, k=k)
                for seg, k in zip(local_segs(args), ks)
            ]
            tgt = parts[0] if n_seg == 1 else jnp.concatenate(parts, axis=1)
            # Exactly one 'space' shard holds any cube's base run, and
            # the delta part is identical on every shard — max is a
            # lossless merge either way.
            with jax.named_scope("mesh.merge"):
                return jax.lax.pmax(tgt, "space")

        in_specs = tuple(
            spec
            for kind in kinds
            for spec in (
                self._base_specs() if kind == "base" else self._delta_specs()
            )
        ) + self._query_specs()

        if variant == "csr":
            # per-batch-shard result budget: each shard assembles its
            # own flat region; the host walks them shard by shard
            t_cap_local = extra // self.n_batch

            def local_csr(*args):
                segs = list(local_segs(args))
                queries = args[na * n_seg:]
                los, cnts_local = run_bounds_all(segs, queries)
                # a run lives on exactly one space shard — the global
                # raw counts (and therefore the layout every shard
                # agrees on) are the pmax union
                with jax.named_scope("mesh.merge"):
                    cnts = [
                        jax.lax.pmax(c, "space") for c in cnts_local
                    ]
                counts, flat, total = run_csr_assemble(
                    segs, los, cnts, cnts_local, queries, t_cap_local
                )
                # owner shards wrote real lanes, the rest -1: max is a
                # lossless merge (same argument as the dense path)
                with jax.named_scope("mesh.merge"):
                    flat = jax.lax.pmax(flat, "space")
                    total = jax.lax.pmax(total, "space")
                return counts, flat, total.reshape(1)

            matched_csr = _shard_map(
                local_csr, mesh=mesh, in_specs=in_specs,
                out_specs=(
                    P("batch", None), P("batch"), P("batch"),
                ),
            )

            # the jitted program's name is what a device trace and a
            # retrace report show (`jit_mesh_resolve_csr`); it must not
            # read as one of the one-chip kernels
            def mesh_resolve_csr(*args):
                counts, flat, totals = matched_csr(*args)
                # any shard overflowing its local budget triggers the
                # global retry sentinel
                total = jnp.where(
                    (totals > t_cap_local).any(),
                    jnp.int32(extra + 1),
                    totals.sum(dtype=jnp.int32),
                )
                return counts, flat, total

            fn = mesh_resolve_csr
        else:
            matched_dense = _shard_map(
                local, mesh=mesh, in_specs=in_specs,
                out_specs=P("batch", None),
            )

            def mesh_resolve_dense(*args):
                return matched_dense(*args)

            fn = mesh_resolve_dense

        in_shardings = tuple(
            NamedSharding(mesh, spec) for spec in in_specs
        )
        return jax.jit(fn, in_shardings=in_shardings)

    def _kernel(self, variant: str, kinds, ks, extra=None):
        key = (variant, kinds, ks, extra)
        kernel = self._kernels.get(key)
        if kernel is None:
            kernel = self._kernels[key] = self._make_kernel(
                variant, kinds, ks, extra
            )
            retrace.GUARD.register(f"sharded.mesh_resolve_{variant}", kernel)
        return kernel

    def _dispatch_encoded(self, m, *args, **kwargs):
        """The served launch: count what was handed to the mesh."""
        handle = super()._dispatch_encoded(m, *args, **kwargs)
        payload = handle[1]
        if payload is not None:
            self.mesh_dispatches += 1
            self.mesh_query_rows += m
            self.mesh_merge_bytes += self._merge_bytes(payload)
        return handle

    def _merge_bytes(self, payload) -> int:
        """Bytes the pmax merges of one resolve call reduce over
        ``space`` (padded rows x lanes x 4, summed over the call's
        merges and its batch shards): the dense program merges its
        [M, sum K] table; the CSR program each segment's [M] raw run
        lengths, the [t_cap] flat result and one total a batch shard."""
        if payload[0] == "dense":
            return 4 * int(payload[1].size)
        counts, flat, _ = payload[2]
        return 4 * (int(counts.size) + int(flat.size) + self.n_batch)

    def collect_local_batch(self, handle):
        """A mesh CSR collect fetches the per-batch-shard counts and
        regions (``_compact_fetch`` or the whole flat) and walks them
        (``_decode_packed`` / ``_decode_csr``) on the collect worker
        thread, where the loop's spans do not reach: their wall is the
        leg ``mesh_fetch_ms`` of the tick's timing. The base class
        brackets exactly those two stretches as ``d2h_ms`` and
        ``decode_ms``, but publishes them for every tick, the reuse
        cache's deviceless ones included, as zeros; this leg exists
        only on a tick whose regions were fetched."""
        out = super().collect_local_batch(handle)
        payload = handle[1]
        if (
            payload is not None and payload[0] == "csr"
            # not re-resolved dense after an overflow: no regions then
            and (timing := self.last_device_timing).get("path") == "csr"
        ):
            timing["mesh_fetch_ms"] = timing["d2h_ms"] + timing["decode_ms"]
        return out

    def _dispatch(self, queries: tuple, segs, ks, kinds):
        flat = [a for seg in segs for a in seg]
        return self._kernel("dense", kinds, ks)(*flat, *queries)

    def _dispatch_csr(self, queries: tuple, segs, ks, kinds, t_cap: int):
        flat = [a for seg in segs for a in seg]
        return self._kernel("csr", kinds, ks, t_cap)(*flat, *queries)

    def _csr_effective_cap(self, t_cap: int, queries: tuple, segs) -> int:
        # every batch shard's local region must cover its own zone-A
        # identity rows PLUS at least one zone-B row — the base
        # class's global floor divided by n_batch can land exactly on
        # the zone-A size for small multi-segment ticks. Raised HERE
        # (not silently inside the dispatch) so dispatch_local_batch
        # records the same cap the kernel's overflow sentinel uses
        # (ADVICE r5: totals between the two caps used to take a
        # spurious dense re-resolve).
        m_local = queries[0].shape[0] // self.n_batch
        need_local = (CSR_ROW * m_local * len(segs)
                      + 2 * CSR_ROW_B)
        return max(t_cap, next_pow2(self.n_batch * need_local))

    def _pack_kernel(self, bucket_local: int, mq: int, nseg: int,
                     flat_len: int):
        """Per-batch-shard pack_csr, vmapped over the shard dim with
        batch shardings so every shard compacts its own flat region
        locally — no cross-device traffic, the merge already happened
        in the CSR kernel's pmax."""
        key = ("pack_csr", bucket_local, mq, nseg, flat_len)
        kernel = self._kernels.get(key)
        if kernel is None:
            nb = self.n_batch

            def mesh_repack(counts, flat):
                c3 = counts.reshape(nb, mq // nb, nseg)
                f2 = flat.reshape(nb, flat_len // nb)
                packed, totals = jax.vmap(
                    lambda c, f: pack_csr(c, f, bucket=bucket_local)
                )(c3, f2)
                return packed.reshape(-1), totals

            kernel = self._kernels[key] = jax.jit(
                mesh_repack,
                in_shardings=(
                    self._sharding("batch", None),
                    self._sharding("batch"),
                ),
                out_shardings=(
                    self._sharding("batch"), self._sharding("batch"),
                ),
            )
            retrace.GUARD.register("sharded.mesh_repack", kernel)
        return kernel

    def _compact_fetch(self, counts, flat, total: int, t_cap: int):
        """Mesh compaction: each batch shard packs its own flat region
        into a local bucket sized for 2x imbalance headroom over a
        perfectly balanced split. Shards report their raw totals; any
        shard overflowing its bucket (imbalance past the headroom)
        falls back to the full fetch — slower, never wrong."""
        nb = self.n_batch
        bucket_local = next_pow2(
            max(-(-2 * total // nb), self.compact_min_bucket)
        )
        if (
            not self._compact_applicable(t_cap)
            or bucket_local * nb * 2 > t_cap
        ):
            return None
        mq, nseg = counts.shape
        kernel = self._pack_kernel(
            bucket_local, mq, nseg, flat.shape[0]
        )
        packed, totals = kernel(counts, flat)
        # fit check first — a tiny [n_batch] fetch, not the payload
        totals_np = np.asarray(totals)  # wql: allow(jax-host-sync) — [n_batch] scalars
        if totals_np.size and int(totals_np.max()) > bucket_local:
            return None
        out = np.asarray(packed)  # wql: allow(jax-host-sync) — compacted collect point
        self._note_fetch(bucket_local * nb, bucket_local * nb)
        return out

    def _decode_packed(self, counts, packed, m: int):
        """The mesh packed result is per-batch-shard buckets
        concatenated; walk each shard's queries against its own
        bucket (mirrors the zoned-layout region walk below)."""
        nb = self.n_batch
        self.mesh_region_fetches += nb
        bucket_local = len(packed) // nb
        m_local = counts.shape[0] // nb
        out: list = []
        for b in range(nb):
            if len(out) >= m:
                break
            out.extend(super()._decode_packed(
                counts[b * m_local:(b + 1) * m_local],
                packed[b * bucket_local:(b + 1) * bucket_local],
                min(m_local, m - len(out)),
            ))
        return out

    def _decode_csr(self, counts, flat, m: int):
        """The mesh flat result is per-batch-shard regions of
        ``t_cap // n_batch`` slots concatenated; walk each shard's
        queries against its own region. The dense-fallback layout
        (counts.ndim == 1) is host-built and global — no regions."""
        if counts.ndim == 1:
            return super()._decode_csr(counts, flat, m)
        nb = self.n_batch
        self.mesh_region_fetches += nb
        t_cap_local = len(flat) // nb
        m_local = counts.shape[0] // nb
        out: list = []
        for b in range(nb):
            if len(out) >= m:
                break
            sub = super()._decode_csr(
                counts[b * m_local:(b + 1) * m_local],
                flat[b * t_cap_local:(b + 1) * t_cap_local],
                min(m_local, m - len(out)),
            )
            out.extend(sub)
        return out

    # endregion

    def _index_devices(self) -> list:
        return sorted(self.mesh.devices.flat, key=lambda d: d.id)

    def device_stats(self) -> dict:
        stats = super().device_stats()
        stats["mesh"] = {"batch": self.n_batch, "space": self.n_space}
        stats["mesh_dispatches"] = self.mesh_dispatches
        stats["mesh_query_rows"] = self.mesh_query_rows
        stats["mesh_merge_bytes"] = self.mesh_merge_bytes
        stats["mesh_region_fetches"] = self.mesh_region_fetches
        # bytes of the space-sharded base each device actually holds —
        # a mesh that put everything on its first device shows here
        per_device: dict[int, int] = {}
        if self._base_bundle is not None:
            for arr in self._base_bundle["dev"]:
                for shard in arr.addressable_shards:
                    per_device[shard.device.id] = (
                        per_device.get(shard.device.id, 0)
                        + shard.data.nbytes
                    )
        stats["base_bytes_per_device"] = {
            str(d): per_device[d] for d in sorted(per_device)
        }
        return stats
