"""Device tick loop correctness vs numpy reference implementations."""

from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np

from worldql_server_tpu.ops.tick import (
    EntityState,
    device_coord_clamp,
    device_spatial_keys,
    example_state,
    make_tick_fn,
)
from worldql_server_tpu.spatial.hashing import spatial_key, spatial_keys
from worldql_server_tpu.spatial.quantize import coord_clamp


def test_device_coord_clamp_matches_host_golden():
    """f32-representable coordinates must quantize exactly like the
    golden host quantizer (cube_area.rs:23-44 semantics)."""
    rng = np.random.default_rng(11)
    # quarters are f32-exact; include exact multiples, zero, negatives
    coords = np.concatenate([
        np.round(rng.uniform(-500, 500, 500) * 4) / 4,
        np.array([0.0, 16.0, -16.0, 32.0, -32.0, 0.25, -0.25, 15.75, -15.75]),
    ]).astype(np.float32)
    for size in (10, 16):
        got = np.asarray(device_coord_clamp(jnp.asarray(coords), size))
        want = np.array([coord_clamp(float(c), size) for c in coords])
        np.testing.assert_array_equal(got, want)


def test_device_keys_match_host_keys():
    """The device hash must agree with the host hash bit-for-bit, so
    host-built indexes and device-built queries interoperate."""
    rng = np.random.default_rng(5)
    worlds = rng.integers(0, 8, 64).astype(np.int32)
    cubes = rng.integers(-1000, 1000, (64, 3)).astype(np.int64)
    host = spatial_keys(worlds, cubes, seed=3)
    dev = np.asarray(
        device_spatial_keys(jnp.asarray(worlds), jnp.asarray(cubes), seed=3)
    )
    np.testing.assert_array_equal(host, dev)


def test_scalar_key_matches_the_vectorized_keys():
    """``spatial_key`` (one row in Python ints: the entity plane's
    registration path) is ``spatial_keys`` bit for bit, the dead world
    (-1), negative cubes and the int64 extremes included."""
    rng = np.random.default_rng(6)
    worlds = rng.integers(-1, 50, 400).astype(np.int32)
    cubes = rng.integers(-2**62, 2**62, (400, 3))
    cubes[:6] = [[0, 0, 0], [-1, -1, -1], [2**63 - 1] * 3,
                 [-2**63] * 3, [16, -32, 48], [1, 0, -1]]
    for seed in (0, 3):
        want = spatial_keys(worlds, cubes, seed)
        got = [spatial_key(worlds[i], cubes[i], seed) for i in range(400)]
        assert got == want.tolist()


def test_tick_counts_and_targets_match_numpy():
    state = example_state(n=512, n_worlds=3)
    k = 64
    tick = jax.jit(make_tick_fn(cube_size=16, k=k))
    new_state, targets, counts = tick(state)

    pos = np.asarray(new_state.position)
    world = np.asarray(state.world)
    peer = np.asarray(state.peer)

    cubes = np.stack(
        [[coord_clamp(float(c), 16) for c in row] for row in pos]
    ).astype(np.int64)
    cells = [tuple([int(world[i])] + list(cubes[i])) for i in range(len(pos))]
    pop = Counter(cells)

    np.testing.assert_array_equal(np.asarray(counts), [pop[c] for c in cells])

    tgt = np.asarray(targets)
    for i in range(len(pos)):
        expect = {int(peer[j]) for j in range(len(pos))
                  if cells[j] == cells[i] and j != i}
        got = {int(t) for t in tgt[i] if t >= 0}
        assert got == expect, f"entity {i}"


def test_tick_reflects_at_bounds():
    state = EntityState(
        position=jnp.array([[999.0, 0.0, -999.0]], jnp.float32),
        velocity=jnp.array([[100.0, 0.0, -100.0]], jnp.float32),
        world=jnp.zeros(1, jnp.int32),
        peer=jnp.zeros(1, jnp.int32),
    )
    tick = make_tick_fn(cube_size=16, k=8, dt=1.0, bounds=1000.0)
    new_state, _, _ = tick(state)
    pos = np.asarray(new_state.position)[0]
    vel = np.asarray(new_state.velocity)[0]
    assert pos[0] == 901.0 and vel[0] == -100.0
    assert pos[2] == -901.0 and vel[2] == 100.0


def test_tick_is_deterministic():
    state = example_state(n=256)
    tick = jax.jit(make_tick_fn(cube_size=16, k=16))
    out1 = tick(state)
    out2 = tick(state)
    for a, b in zip(jax.tree.leaves(out1), jax.tree.leaves(out2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_tick_targets_are_nearest_first():
    """Within an entity's candidate window the targets come back
    ordered by distance — a true kNN selection, not sort-order
    happenstance."""
    # four entities in one cube at staggered x, one far away
    position = jnp.array([
        [1.0, 1.0, 1.0],
        [2.0, 1.0, 1.0],
        [5.0, 1.0, 1.0],
        [9.0, 1.0, 1.0],
        [500.0, 1.0, 1.0],
    ], jnp.float32)
    state = EntityState(
        position=position,
        velocity=jnp.zeros((5, 3), jnp.float32),
        world=jnp.zeros(5, jnp.int32),
        peer=jnp.arange(5, dtype=jnp.int32),
    )
    tick = make_tick_fn(cube_size=16, k=8, dt=0.0)
    _, targets, counts = tick(state)
    tgt = np.asarray(targets)
    # entity 0 at x=1: nearest is peer 1 (dx=1), then 2 (dx=4), then 3
    assert [t for t in tgt[0] if t >= 0] == [1, 2, 3]
    # entity 3 at x=9: nearest is peer 2 (dx=4), then 1 (dx=7), then 0
    assert [t for t in tgt[3] if t >= 0] == [2, 1, 0]
    assert int(counts[4]) == 1  # the far entity is alone


def test_tick_nan_position_still_broadcasts_before_sentinels():
    """A NaN-position entity quantizes to cube +size and participates;
    its co-cube neighbors' rows must keep real targets CONTIGUOUS
    before the -1 padding even though the distance to it is NaN."""
    nan = float("nan")
    position = jnp.array([
        [nan, 1.0, 1.0],     # quantizes to cube (+16, 16, 16)
        [15.0, 1.0, 1.0],    # same cube
        [14.0, 1.0, 1.0],    # same cube
    ], jnp.float32)
    state = EntityState(
        position=position,
        velocity=jnp.zeros((3, 3), jnp.float32),
        world=jnp.zeros(3, jnp.int32),
        peer=jnp.arange(3, dtype=jnp.int32),
    )
    tick = make_tick_fn(cube_size=16, k=8, dt=0.0)
    _, targets, counts = tick(state)
    tgt = np.asarray(targets)
    assert int(counts[1]) == 3
    row = list(tgt[1])
    real = [t for t in row if t >= 0]
    assert set(real) == {0, 2}
    # no real target after the first -1 (contiguity invariant)
    first_pad = row.index(-1) if -1 in row else len(row)
    assert all(t == -1 for t in row[first_pad:])


def test_tick_k1_finds_single_nearest():
    """k=1 must return the single nearest co-cube neighbor (it rides
    the k=2 window internally — a ±0 stencil would silently return no
    neighbors at all), on both the XLA and Pallas(interpret) paths."""
    position = jnp.array([
        [1.0, 1.0, 1.0],
        [2.0, 1.0, 1.0],
        [9.0, 1.0, 1.0],
        [500.0, 1.0, 1.0],
    ], jnp.float32)
    state = EntityState(
        position=position,
        velocity=jnp.zeros((4, 3), jnp.float32),
        world=jnp.zeros(4, jnp.int32),
        peer=jnp.arange(4, dtype=jnp.int32),
    )
    for pallas in (False, True):
        tick = make_tick_fn(cube_size=16, k=1, dt=0.0, pallas=pallas)
        _, targets, counts = tick(state)
        tgt = np.asarray(targets)
        assert tgt.shape == (4, 1)
        assert tgt[0, 0] == 1   # x=1 → nearest is x=2
        assert tgt[1, 0] == 0   # x=2 → nearest is x=1 (dx=1 < dx=7)
        assert tgt[2, 0] in (0, 1)  # occupancy 3 > k: truncated window
        assert tgt[3, 0] == -1  # alone in its cube
        assert int(np.asarray(counts)[3]) == 1
