"""The fully-on-device simulation tick — flagship compute path.

One jitted step over SoA entity arrays does everything the reference's
per-message hot loop does (SURVEY §3.2), but for EVERY entity at once:

1. integrate positions (reflecting off the world bounds),
2. re-quantize every entity to its subscription cube,
3. rebuild the spatial hash for the tick (one device sort — the
   "per-tick spatial-hash rebuild" of BASELINE config 5),
4. resolve every entity's broadcast as a stencil over the sort:
   co-cube members are sort-order neighbors, so the ±(K-1) candidate
   window is a pad + stack-of-slices with same-run masks — no random
   gather (a [N, K] element gather costs ~8 ns/element on TPU and
   dominated this tick; the slice stack fuses into one kernel),
5. order each entity's neighbors nearest-first (batched kNN: top-k by
   squared distance over the stencil window).

Static shapes throughout: N entities and degree K are compile-time;
XLA fuses steps 1-2 and the stencil's roll/mask chains. The sort
(step 3) is the asymptotic cost, O(N log N) on-device, no host
round-trips.

Quantization note: this sim path quantizes in f32 on device
(``device_coord_clamp``), semantically mirroring the golden host
quantizer (spatial/quantize.py, cube_area.rs:23-44). The agreement
envelope is PINNED by tests/test_quantizer_envelope.py: exact for all
normal finite inputs when the cube size is a power of two (every f32
step is an exponent shift; tested to |x| <= 2^62), and exact for
|x| <= size * 2^21 for non-power-of-two sizes (the f32 quotient loses
sub-integer resolution near |x|/size ~ 2^24 and diverges heavily past
size * 2^26); f32 subnormals (|x| < 2^-126) are outside the envelope.
Specials match the host exactly (NaN → +size, ±inf → ±i64::MAX,
saturating arithmetic). The authoritative broker path
(spatial/tpu_backend.py) always quantizes host-side in f64; this module
serves the embedded-simulation / benchmark workloads where positions
are device-resident. Hash collisions between distinct cubes merge
their neighbor lists; at ~2⁻⁶⁴ per cube pair this is below sim noise.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

from ..spatial import jaxconf  # must precede the jax import
import jax
import jax.numpy as jnp

from ..spatial.hashing import MIX_GOLDEN, MIX_M1, MIX_M2


class EntityState(NamedTuple):
    """SoA device state for one entity population."""

    position: jax.Array  # [N, 3] f32
    velocity: jax.Array  # [N, 3] f32
    world: jax.Array     # [N] i32 interned world id
    peer: jax.Array      # [N] i32 dense peer id


def device_coord_clamp(x: jax.Array, size: int) -> jax.Array:
    """Subscription-cube quantizer on device (f32 → i64 labels).

    Mirrors the max-corner / sign-symmetric / 0→+size semantics of the
    golden host quantizer (cube_area.rs:23-44).
    """
    size_f = jnp.float32(size)
    i64_max = jnp.int64(2**63 - 1)
    a = jnp.abs(x)
    mult = jnp.where(x < 0, -1, 1).astype(jnp.int64)
    rounded = jnp.ceil(a / size_f) * size_f
    rounded = jnp.where(a == 0.0, size_f, rounded)
    exact = (jnp.mod(a, size_f) == 0.0) & (x != 0.0)
    ri = rounded.astype(jnp.int64)
    # saturating +size like the host (_sat_add): past the int64 cast's
    # saturation point a plain add wraps negative
    bumped = jnp.where(ri > i64_max - size, i64_max, ri + size)
    res = jnp.where(rounded > a, ri, bumped)
    res = jnp.where(exact, a.astype(jnp.int64), res)
    # NaN → +size, ±inf → ±i64::MAX, and saturation-zone finites →
    # ±i64::MAX like the host quantizer's Rust-style saturating casts
    # (XLA's out-of-range float→int casts are platform-defined, so
    # every cast is guarded explicitly). The guard tests ROUNDED — the
    # actual cast input — not `a`: f32 round-up can push `rounded` to
    # exactly 2^63 while `a` is still below it, and rounded >= a always
    # holds, so this also covers the exact-branch cast of `a`.
    res = jnp.where(rounded >= jnp.float32(2.0**63), i64_max, res)
    res = jnp.where(jnp.isinf(x), i64_max, res)
    return jnp.where(jnp.isnan(x), jnp.int64(size), res * mult)


_M1 = jnp.uint64(MIX_M1)
_M2 = jnp.uint64(MIX_M2)
_GOLDEN = jnp.uint64(MIX_GOLDEN)


def _mix(x: jax.Array) -> jax.Array:
    x = (x ^ (x >> jnp.uint64(30))) * _M1
    x = (x ^ (x >> jnp.uint64(27))) * _M2
    return x ^ (x >> jnp.uint64(31))


def device_spatial_keys(
    world: jax.Array, cubes: jax.Array, seed: int = 0
) -> jax.Array:
    """Device twin of spatial/hashing.spatial_keys: [N] i32 world ids +
    [N, 3] i64 cubes → [N] i64 sort keys."""
    h = _mix(jnp.uint64(seed) + _GOLDEN)
    h = _mix(h ^ world.astype(jnp.int64).view(jnp.uint64))
    h = _mix(h ^ cubes[..., 0].view(jnp.uint64))
    h = _mix(h ^ cubes[..., 1].view(jnp.uint64))
    h = _mix(h ^ cubes[..., 2].view(jnp.uint64))
    return h.view(jnp.int64)


def simulation_tick(
    state: EntityState,
    *,
    cube_size: int,
    k: int,
    dt: float = 0.05,
    bounds: float = 1000.0,
    seed: int = 0,
    pallas: bool | None = None,
):
    """One tick: integrate → quantize → rebuild hash → resolve fan-out.

    Returns ``(new_state, targets, counts)`` where ``targets`` is
    [N, K] i32 peer ids each entity broadcasts to this tick (-1 = none;
    except-self), and ``counts`` the exact co-cube population including
    self (callers can detect K-overflow as counts > K).
    """
    n = state.position.shape[0]

    # 1. integrate, reflecting at ±bounds.
    pos = state.position + state.velocity * jnp.float32(dt)
    over = pos > bounds
    under = pos < -bounds
    pos = jnp.where(over, 2.0 * bounds - pos, pos)
    pos = jnp.where(under, -2.0 * bounds - pos, pos)
    vel = jnp.where(over | under, -state.velocity, state.velocity)

    # 2. quantize to subscription cubes.
    cubes = device_coord_clamp(pos, cube_size)

    # 3. per-tick spatial-hash rebuild: one sort.
    keys = device_spatial_keys(state.world, cubes, seed)
    order = jnp.argsort(keys)
    sorted_keys = keys[order]
    sorted_peer = state.peer[order]

    # 4. resolve every entity's broadcast set as a STENCIL over the
    # sort: an entity's co-cube members are its neighbors in sorted
    # order, so the ±(K-1) candidate window is a contiguous slice per
    # shift — no [N, K] random gather. The fixed-degree gather this
    # replaces dominated the tick (27 of 36 ms at 100K entities on
    # v5e: TPU element gathers cost ~8 ns/element). Exact counts
    # still come from the run scan (cheap, and callers use them to
    # detect K-overflow).
    p_idx = jnp.arange(n, dtype=jnp.int32)
    boundary = sorted_keys[1:] != sorted_keys[:-1]
    first = jnp.concatenate([jnp.ones((1,), bool), boundary])
    last = jnp.concatenate([boundary, jnp.ones((1,), bool)])
    run_start = jax.lax.cummax(jnp.where(first, p_idx, 0))
    run_end = jax.lax.cummin(
        jnp.where(last, p_idx + 1, jnp.int32(n)), reverse=True
    )
    counts_sorted = run_end - run_start
    counts = jnp.zeros(n, jnp.int32).at[order].set(counts_sorted)
    # inverse permutation: one cheap [N] scatter, so the final [N, K]
    # un-permute is a row GATHER (take axis 0 — the TPU fast path)
    inv = jnp.zeros(n, jnp.int32).at[order].set(p_idx)

    # 5. true k-nearest selection among the stencil candidates: the
    # ±(K-1) window covers EVERY co-cube member whenever the cube's
    # occupancy L <= K (runs are contiguous in sorted order, so the
    # max sort-order distance between members is L-1). Distance
    # bits and target pack into ONE int64 per candidate so the whole
    # reorder is a single row-sort — lax.top_k costs ~5x more on TPU
    # (measured) for the same result. IEEE bits of a non-negative f32
    # are order-preserving; invalid slots carry the all-ones bit
    # pattern (above +inf AND every NaN — NaN positions are supported
    # inputs, they quantize to cube +size), and equal distances
    # tie-break by peer id (deterministic). With occupancy beyond K
    # the candidate set truncates to the 2(K-1) nearest in sort order
    # (callers detect via counts > K); within it the result is the
    # exact k nearest.
    # The window materializes as a pad + stack-of-slices (one fused
    # concat — a python loop of jnp.roll per shift emits ~2K separate
    # kernel launches, ~20x slower, measured). Run identity compares as
    # a cumsum run id (i32 — exact, and cheaper than the i64 keys);
    # padding rows carry run id -1, so window slots past either array
    # end never match and there is no wraparound to dedup. The self
    # column (shift 0) and duplicate-peer candidates fall to the
    # ``peer != own`` mask, matching the reference's ExceptSelf.
    sorted_pos = pos[order]
    rid = jnp.cumsum(first.astype(jnp.int32))

    if pallas is None:
        pallas = jaxconf.on_tpu()
    # k=1 rides the k=2 window, truncated to one target: a ±(k-1)
    # stencil at k=1 is empty and would silently return NO neighbors,
    # while ±1 finds the single nearest whenever occupancy <= 2 — the
    # same exactness contract (L <= K, overflow visible via counts)
    # every other k gets.
    kw = max(k, 2)
    if pallas:
        # fused Pallas kernel: the whole stencil + k-nearest select in
        # one launch (ops/knn_pallas.py) — ~7x over the XLA stencil at
        # 100K entities on v5e (launch- and HBM-round-trip-bound)
        from .knn_pallas import knn_select

        tgt_sorted = knn_select(rid, sorted_peer, sorted_pos, k=kw)[:, :k]
        targets = jnp.take(tgt_sorted, inv, axis=0)
        return (EntityState(pos, vel, state.world, state.peer),
                targets, counts)

    w = 2 * kw - 1
    rid_p = jnp.pad(rid, (kw - 1, kw - 1), constant_values=-1)
    peer_p = jnp.pad(sorted_peer, (kw - 1, kw - 1), constant_values=-1)
    pos_p = jnp.pad(sorted_pos, ((kw - 1, kw - 1), (0, 0)))
    rid_w = jnp.stack([rid_p[s:s + n] for s in range(w)], axis=1)
    peer_w = jnp.stack([peer_p[s:s + n] for s in range(w)], axis=1)
    pos_w = jnp.stack([pos_p[s:s + n] for s in range(w)], axis=1)
    same = (rid_w == rid[:, None]) & (peer_w != sorted_peer[:, None])
    d2 = jnp.sum((pos_w - sorted_pos[:, None, :]) ** 2, axis=-1).astype(
        jnp.float32
    )
    d2_bits = jnp.where(
        same, jax.lax.bitcast_convert_type(d2, jnp.uint32),
        jnp.uint32(0xFFFFFFFF),
    )
    packed = (d2_bits.astype(jnp.uint64) << jnp.uint64(32)) | (
        (jnp.where(same, peer_w, -1) + 1).astype(jnp.uint64)
        & jnp.uint64(0xFFFFFFFF)
    )
    packed = jnp.sort(packed, axis=1)[:, :k]   # k nearest per entity
    tgt_sorted = (packed & jnp.uint64(0xFFFFFFFF)).astype(jnp.int32) - 1
    targets = jnp.take(tgt_sorted, inv, axis=0)

    return EntityState(pos, vel, state.world, state.peer), targets, counts


def make_tick_fn(cube_size: int = 16, k: int = 32, dt: float = 0.05,
                 bounds: float = 1000.0, pallas: bool | None = None):
    """Close the static params; returns a jittable ``fn(state)``.

    ``pallas=None`` auto-selects the fused Pallas resolve on TPU and
    the XLA stencil elsewhere; both paths are semantically identical
    (tests pin their equivalence)."""
    return partial(simulation_tick, cube_size=cube_size, k=k, dt=dt,
                   bounds=bounds, pallas=pallas)


def example_state(n: int = 1024, n_worlds: int = 4, seed: int = 7) -> EntityState:
    """Deterministic small entity population for compile checks."""
    kp, kv = jax.random.split(jax.random.PRNGKey(seed))
    return EntityState(
        position=jax.random.uniform(
            kp, (n, 3), jnp.float32, minval=-900.0, maxval=900.0
        ),
        velocity=jax.random.uniform(
            kv, (n, 3), jnp.float32, minval=-40.0, maxval=40.0
        ),
        world=(jnp.arange(n, dtype=jnp.int32) % n_worlds),
        peer=jnp.arange(n, dtype=jnp.int32),
    )
