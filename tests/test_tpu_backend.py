"""TPU spatial backend: behavior + randomized CPU≡TPU equivalence.

Runs on the virtual CPU mesh (conftest.py); the same code path runs on
real TPU. The property test drives both backends through an identical
randomized mutation/query script and requires identical fan-out sets —
this is the correctness oracle for the device index (SURVEY §4).
"""

import random
import uuid

import numpy as np
import pytest

from worldql_server_tpu.protocol.types import Replication, Vector3
from worldql_server_tpu.spatial.backend import LocalQuery
from worldql_server_tpu.spatial.cpu_backend import CpuSpatialBackend
from worldql_server_tpu.spatial.tpu_backend import TpuSpatialBackend

W = "world"


@pytest.fixture
def b():
    return TpuSpatialBackend(cube_size=16)


def test_point_queries_match_host_authority(b):
    peer = uuid.uuid4()
    b.add_subscription(W, peer, Vector3(6.3, 1.0, 10.5))
    assert b.is_subscribed(W, peer, (16, 16, 16))
    assert b.is_subscribed_any(W, peer)
    assert b.query_cube(W, Vector3(1.0, 1.0, 1.0)) == {peer}


def test_batch_replication_filters(b):
    sender, other1, other2 = uuid.uuid4(), uuid.uuid4(), uuid.uuid4()
    pos = Vector3(5.0, 5.0, 5.0)
    for p in (sender, other1, other2):
        b.add_subscription(W, p, pos)

    results = b.match_local_batch([
        LocalQuery(W, pos, sender, Replication.EXCEPT_SELF),
        LocalQuery(W, pos, sender, Replication.INCLUDING_SELF),
        LocalQuery(W, pos, sender, Replication.ONLY_SELF),
        LocalQuery(W, Vector3(100, 100, 100), sender, Replication.EXCEPT_SELF),
    ])
    assert set(results[0]) == {other1, other2}
    assert set(results[1]) == {sender, other1, other2}
    assert results[2] == [sender]
    assert results[3] == []


def test_batch_after_mutations_reflushes(b):
    peer, other = uuid.uuid4(), uuid.uuid4()
    pos = Vector3(5.0, 5.0, 5.0)
    b.add_subscription(W, peer, pos)
    assert b.match_local_batch(
        [LocalQuery(W, pos, other, Replication.EXCEPT_SELF)]
    ) == [[peer]]

    b.remove_peer(peer)
    assert b.match_local_batch(
        [LocalQuery(W, pos, other, Replication.EXCEPT_SELF)]
    ) == [[]]

    b.add_subscription(W, other, pos)
    assert b.match_local_batch(
        [LocalQuery(W, pos, peer, Replication.EXCEPT_SELF)]
    ) == [[other]]


def test_empty_index_and_empty_batch(b):
    assert b.match_local_batch([]) == []
    assert b.match_local_batch(
        [LocalQuery(W, Vector3(0, 0, 0), uuid.uuid4())]
    ) == [[]]


def test_unknown_world_query(b):
    peer = uuid.uuid4()
    b.add_subscription(W, peer, Vector3(1, 1, 1))
    assert b.match_local_batch(
        [LocalQuery("elsewhere", Vector3(1, 1, 1), uuid.uuid4())]
    ) == [[]]


def test_match_arrays_shape_and_padding(b):
    peers = [uuid.uuid4() for _ in range(20)]
    for p in peers:
        b.add_subscription(W, p, Vector3(1, 1, 1))
    b.flush()
    wid = b._world_ids[W]

    tgt = b.match_arrays(
        np.full(3, wid, dtype=np.int32),
        np.array([[1.0, 1.0, 1.0]] * 3),
        np.full(3, -1, dtype=np.int32),
        np.zeros(3, dtype=np.int8),
    )
    assert tgt.shape[0] == 3
    assert ((tgt >= 0).sum(axis=1) == 20).all()


def test_quantization_edge_positions(b):
    """Exact multiples, zero, negatives — the cube labeling the device
    index must agree with the golden host semantics at the edges
    (cube_area.rs:102-175)."""
    peer = uuid.uuid4()
    cases = [
        (Vector3(0.0, 0.0, 0.0), (16, 16, 16)),
        (Vector3(16.0, -16.0, 0.5), (16, -16, 16)),
        (Vector3(-0.5, 31.9, -31.9), (-16, 32, -32)),
    ]
    for pos, cube in cases:
        b2 = TpuSpatialBackend(16)
        b2.add_subscription(W, peer, cube)
        assert b2.match_local_batch(
            [LocalQuery(W, pos, uuid.uuid4())]
        ) == [[peer]], (pos, cube)


def test_randomized_cpu_tpu_equivalence():
    rng = random.Random(0x5EED)
    cpu = CpuSpatialBackend(16)
    tpu = TpuSpatialBackend(16)
    peers = [uuid.uuid4() for _ in range(40)]
    worlds = ["alpha", "beta", "gamma"]

    def rand_pos():
        return Vector3(
            rng.uniform(-200, 200), rng.uniform(-200, 200), rng.uniform(-200, 200)
        )

    for _round in range(5):
        for _ in range(300):
            op = rng.random()
            w = rng.choice(worlds)
            p = rng.choice(peers)
            if op < 0.6:
                pos = rand_pos()
                assert cpu.add_subscription(w, p, pos) == tpu.add_subscription(
                    w, p, pos
                )
            elif op < 0.9:
                pos = rand_pos()
                assert cpu.remove_subscription(
                    w, p, pos
                ) == tpu.remove_subscription(w, p, pos)
            else:
                assert cpu.remove_peer(p) == tpu.remove_peer(p)

        queries = [
            LocalQuery(
                rng.choice(worlds + ["never"]),
                rand_pos(),
                rng.choice(peers),
                rng.choice(list(Replication)),
            )
            for _ in range(200)
        ]
        cpu_out = cpu.match_local_batch(queries)
        tpu_out = tpu.match_local_batch(queries)
        for i, (c, t) in enumerate(zip(cpu_out, tpu_out)):
            assert set(c) == set(t), f"query {i} diverged"
        assert tpu.subscription_count() == cpu.subscription_count()


def test_device_stats(b):
    peer = uuid.uuid4()
    b.add_subscription(W, peer, Vector3(1, 1, 1))
    b.flush()
    stats = b.device_stats()
    assert stats["subscriptions"] == 1
    assert stats["capacity"] >= 1
    assert stats["peers"] == 1
    assert not stats["dirty"]


@pytest.mark.parametrize("loaded", [False, True], ids=["empty", "loaded"])
def test_device_stats_name_the_platform_the_index_lives_on(b, loaded):
    """`--spatial-backend tpu` on a chip-less host serves from the CPU
    platform; the gauge is where that shows (chip_smoke.py reads it)."""
    if loaded:
        b.add_subscription(W, uuid.uuid4(), Vector3(1, 1, 1))
        b.flush()
    stats = b.device_stats()
    assert (stats["platform"], stats["device_kind"],
            stats["device_count"]) == ("cpu", "cpu", 1)
