"""64-bit spatial keys: (world_id, cube) → one sortable int64.

The device index orders subscriptions by a single scalar key so range
lookups are two ``searchsorted`` binary searches. A cube identity is
128+ bits (world i32 + three i64 cube coords), so the key is a seeded
splitmix64-style hash. Exactness is preserved:

* at flush time the host checks that distinct cubes got distinct keys
  and rehashes with the next seed on collision (expected ~never:
  ~C²/2⁶⁴), so stored cells are injective per epoch;
* every query carries a SECOND independent 64-bit key
  (:func:`spatial_keys2`) that the device compares against the
  candidate run's stored second key. A query for an absent cube is
  mis-routed only if it collides with a stored cube under BOTH hashes
  (~2⁻¹²⁸ per pair — beyond cosmic-ray territory). Shipping 16 key
  bytes instead of the raw 28-byte (world, cube) identity halves the
  per-query transfer and the device index row width — host↔device
  bandwidth is the fan-out engine's scaling limit, not FLOPs.

All functions are vectorized numpy over uint64 with wrapping overflow —
the hot encode path runs at memory bandwidth.
"""

from __future__ import annotations

import numpy as np

# splitmix64 constants — shared with the device twin
# (ops/tick.device_spatial_keys), which must stay bit-identical.
MIX_M1 = 0xBF58476D1CE4E5B9
MIX_M2 = 0x94D049BB133111EB
MIX_GOLDEN = 0x9E3779B97F4A7C15

_M1 = np.uint64(MIX_M1)
_M2 = np.uint64(MIX_M2)
_GOLDEN = np.uint64(MIX_GOLDEN)

# Padding rows sort after every real key; flush re-seeds if a real key
# ever hashes to this value.
PAD_KEY = np.int64(2**63 - 1)
# World-id sentinel that never matches a real (>= 0) interned world.
NO_WORLD = np.int32(-1)
# Seed-space offset separating the two hash families.
KEY2_OFFSET = 0x5851F42D4C957F2D
# Index padding rows pad key2 with 0; padded QUERIES pad with 1, so a
# padding query probing a segment's padding run (both share PAD_KEY)
# fails the second-key exactness check and counts as an empty run —
# without this, padding queries would register as hot-run overflows in
# the two-tier CSR kernel. (A real query whose key2 happens to be 1 is
# fine: matches still require key1 equality, and padding rows carry
# peer -1 anyway.)
QUERY_PAD_KEY2 = np.int64(1)


def _mix(x: np.ndarray) -> np.ndarray:
    x = (x ^ (x >> np.uint64(30))) * _M1
    x = (x ^ (x >> np.uint64(27))) * _M2
    return x ^ (x >> np.uint64(31))


def spatial_keys(
    world_ids: np.ndarray, cubes: np.ndarray, seed: int = 0
) -> np.ndarray:
    """[N] int32 world ids + [N, 3] int64 cube coords → [N] int64 keys."""
    with np.errstate(over="ignore"):
        h = _mix(np.uint64(seed) + _GOLDEN)
        h = _mix(h ^ world_ids.astype(np.int64).view(np.uint64))
        h = _mix(h ^ cubes[..., 0].view(np.uint64))
        h = _mix(h ^ cubes[..., 1].view(np.uint64))
        h = _mix(h ^ cubes[..., 2].view(np.uint64))
    return h.view(np.int64)


_MASK64 = (1 << 64) - 1


def _mix_int(x: int) -> int:
    x = ((x ^ (x >> 30)) * MIX_M1) & _MASK64
    x = ((x ^ (x >> 27)) * MIX_M2) & _MASK64
    return x ^ (x >> 31)


def spatial_key(world_id: int, cube, seed: int = 0) -> int:
    """``spatial_keys`` of ONE row, in Python ints: a tenth of what
    numpy charges a single call (the per-entity registration path;
    tests pin it bit-identical to the vectorized form)."""
    h = _mix_int((seed + MIX_GOLDEN) & _MASK64)
    for v in (world_id, cube[0], cube[1], cube[2]):
        h = _mix_int(h ^ (int(v) & _MASK64))
    return h - (1 << 64) if h >> 63 else h


def spatial_keys2(
    world_ids: np.ndarray, cubes: np.ndarray, seed: int = 0
) -> np.ndarray:
    """Second, independent key family (same mixer, disjoint seed
    space): the device-side exactness check compares this instead of
    the raw (world, cube) tuple."""
    return spatial_keys(world_ids, cubes, (seed + KEY2_OFFSET) & (2**64 - 1))


def n_distinct(sorted_keys: np.ndarray) -> int:
    """Distinct values in a SORTED key array (>= 1 by convention, so
    probe-table sizing never degenerates to zero buckets). Sizing
    contract partner of tpu_backend.probe_buckets_for — every segment
    build site must count cubes the same way."""
    if sorted_keys.size == 0:
        return 1
    return 1 + int(np.count_nonzero(sorted_keys[1:] != sorted_keys[:-1]))


def next_pow2(n: int, floor: int = 8) -> int:
    """Capacity tier: smallest power of two >= max(n, floor). Bounds
    the number of distinct compiled shapes to log2(capacity)."""
    n = max(n, floor)
    return 1 << (n - 1).bit_length()


def pad_to(arr: np.ndarray, size: int, fill) -> np.ndarray:
    """Pad ``arr`` along axis 0 to ``size`` rows with ``fill``."""
    pad = size - arr.shape[0]
    if pad <= 0:
        return arr
    widths = [(0, pad)] + [(0, 0)] * (arr.ndim - 1)
    return np.pad(arr, widths, constant_values=fill)
