"""Native fused quantize+hash kernel vs the numpy twins
(native/spatial.cpp ↔ spatial/quantize.py + spatial/hashing.py).

The native path feeds the fan-out engine's query encoding, so any
divergence — especially on the golden quantizer's edge cases — would
silently mis-route messages. Bit-exact agreement is the contract.
"""


import numpy as np
import pytest

from worldql_server_tpu.spatial import native_keys
from worldql_server_tpu.spatial.native_keys import numpy_query_keys



@pytest.fixture(scope="module")
def native(native_lib):
    n = native_keys.load()
    assert n is not None, "native key kernel failed to load"
    return n


EDGE_COORDS = [
    0.0, -0.0, 1.0, -1.0, 15.999999, 16.0, -16.0, 16.000001,
    32.0, -32.0, 5.5, -5.5, 8.0, -8.0, 1e-300, -1e-300,
    1e18, -1e18, 9.3e18, -9.3e18, 1e300, -1e300,
    float("inf"), float("-inf"), float("nan"),
]


def batches():
    rng = np.random.default_rng(99)
    n = len(EDGE_COORDS)
    # every edge coordinate in every axis slot
    for axis in range(3):
        pos = rng.uniform(-100, 100, (n, 3))
        pos[:, axis] = EDGE_COORDS
        yield np.arange(n, dtype=np.int32) % 5, pos
    # dense random sweeps at several scales
    for scale in (10.0, 1e3, 1e9, 1e17):
        pos = rng.uniform(-scale, scale, (512, 3))
        yield rng.integers(0, 50, 512).astype(np.int32), pos
    # exact multiples and near-multiples
    grid = rng.integers(-1000, 1000, (256, 3)).astype(np.float64) * 16.0
    yield np.zeros(256, np.int32), grid
    yield np.zeros(256, np.int32), grid + 1e-9


@pytest.mark.parametrize("cube_size", [10, 16, 48])
@pytest.mark.parametrize("seed", [0, 7, 2**63])
def test_native_matches_numpy_bit_exact(native, cube_size, seed):
    for world_ids, pos in batches():
        nk1, nk2 = native(world_ids, pos, cube_size, seed)
        pk1, pk2 = numpy_query_keys(world_ids, pos, cube_size, seed)
        bad = np.flatnonzero(nk1 != pk1)
        assert bad.size == 0, (
            f"keys1 diverge at rows {bad[:5]}: pos={pos[bad[:5]]}"
        )
        assert (nk2 == pk2).all()


def test_query_keys_dispatches_to_native(native):
    """When the lib is built, the public query_keys path uses it (and
    still agrees with numpy, trivially, via the suite above)."""
    assert native_keys._native is not None
    rng = np.random.default_rng(3)
    pos = rng.uniform(-500, 500, (64, 3))
    wid = rng.integers(0, 4, 64).astype(np.int32)
    got = native_keys.query_keys(wid, pos, 16, 1)
    want = numpy_query_keys(wid, pos, 16, 1)
    assert (got[0] == want[0]).all() and (got[1] == want[1]).all()


# region: fused batch encode (ISSUE 8 — wql_encode_queries)


def _pure_numpy_encode(world_ids, pos, senders, repls, cap, cube_size,
                       seed):
    """Twin of native_keys.numpy_encode_queries that NEVER touches the
    native lib (numpy_query_keys, then pad) — the parity oracle."""
    from worldql_server_tpu.spatial.hashing import (
        PAD_KEY, QUERY_PAD_KEY2, pad_to,
    )

    k1, k2 = numpy_query_keys(world_ids, pos, cube_size, seed)
    return (
        pad_to(k1, cap, PAD_KEY),
        pad_to(k2, cap, QUERY_PAD_KEY2),
        pad_to(np.asarray(senders, np.int32), cap, np.int32(-1)),
        pad_to(np.asarray(repls, np.int8), cap, np.int8(0)),
    )


@pytest.mark.parametrize("cube_size", [10, 16])
@pytest.mark.parametrize("seed", [0, 7])
def test_encode_queries_matches_numpy_lane_for_lane(native, cube_size,
                                                    seed):
    """The fused batch encode (quantize + hash + capacity-tier pad in
    one GIL-releasing pass) is bit-exact with the composed numpy path
    on EVERY lane — encoded and padding alike — across the quantizer
    edge cases."""
    rng = np.random.default_rng(5)
    for world_ids, pos in batches():
        n = len(world_ids)
        senders = rng.integers(-1, 1000, n).astype(np.int32)
        repls = rng.integers(0, 3, n).astype(np.int8)
        for cap in (n, 1 << (n - 1).bit_length() if n > 1 else 1,
                    2 * n + 3):
            got = native.encode(
                world_ids, pos, senders, repls, cap, cube_size, seed
            )
            assert got is not None, "fused encode symbol missing"
            want = _pure_numpy_encode(
                world_ids, pos, senders, repls, cap, cube_size, seed
            )
            for g, w, name in zip(
                got, want, ("keys1", "keys2", "senders", "repls")
            ):
                assert g.dtype == w.dtype, name
                bad = np.flatnonzero(g != w)
                assert bad.size == 0, (
                    f"{name} diverges at lanes {bad[:5]} (cap={cap})"
                )


def test_encode_queries_public_path_and_fallback(native):
    """encode_queries dispatches to the fused kernel when present and
    the composed path agrees; column-length mismatches fail loudly
    instead of reading past the buffer."""
    rng = np.random.default_rng(11)
    pos = rng.uniform(-500, 500, (37, 3))
    wid = rng.integers(0, 4, 37).astype(np.int32)
    sid = rng.integers(-1, 64, 37).astype(np.int32)
    rep = rng.integers(0, 3, 37).astype(np.int8)
    got = native_keys.encode_queries(wid, pos, sid, rep, 64, 16, 1)
    want = native_keys.numpy_encode_queries(wid, pos, sid, rep, 64, 16, 1)
    for g, w in zip(got, want):
        assert (g == w).all()
    assert len(got[0]) == 64 and got[0][-1] == np.iinfo(np.int64).max
    with pytest.raises(ValueError):
        native.encode(wid, pos, sid[:5], rep, 64, 16, 1)
    with pytest.raises(ValueError):
        native.encode(wid, pos, sid, rep, 10, 16, 1)  # cap < n


# endregion


# region: areamap reference probe (ROADMAP 5a)


def test_areamap_probe_returns_calibration_row():
    """The vs_reference probe: a reference-shaped native AreaMap build
    + lookup pass returns sane timings and a deterministic matched
    count under a fixed seed; a stale library (no symbol) degrades to
    None, never wrong."""
    probe = native_keys.areamap_probe(5_000, 2_000, cube_size=16, seed=7)
    if probe is None:
        pytest.skip("native library predates wql_areamap_probe")
    assert probe["subs"] == 5_000 and probe["queries"] == 2_000
    assert probe["build_ms"] > 0
    assert probe["lookup_ns_per_query"] > 0
    assert probe["matched_rows"] >= 0
    again = native_keys.areamap_probe(5_000, 2_000, cube_size=16, seed=7)
    assert again["matched_rows"] == probe["matched_rows"]
    # degenerate shapes refuse instead of reading garbage
    assert native_keys._native._areamap is None or (
        native_keys.areamap_probe(0, 10) is None
    )


# endregion
