"""Sharded backend on the 8-device virtual CPU mesh: behavioral parity
with the single-chip backend and run-boundary split invariants."""

import random
import uuid

import numpy as np
import pytest

from worldql_server_tpu.parallel import ShardedTpuSpatialBackend, make_fanout_mesh
from worldql_server_tpu.parallel.sharded_backend import split_at_run_boundaries
from worldql_server_tpu.protocol.types import Replication, Vector3
from worldql_server_tpu.spatial.backend import LocalQuery
from worldql_server_tpu.spatial.cpu_backend import CpuSpatialBackend

W = "world"


def _require_devices(n: int):
    import jax

    if jax.device_count() < n:
        pytest.skip(f"needs {n} devices")


def test_split_at_run_boundaries():
    keys = np.array([1, 1, 1, 2, 2, 3, 4, 4, 4, 4, 5, 6], dtype=np.int64)
    splits = split_at_run_boundaries(keys, 4)
    assert splits[0] == 0 and splits[-1] == len(keys)
    assert splits == sorted(splits)
    for s in splits[1:-1]:
        if 0 < s < len(keys):
            assert keys[s - 1] != keys[s], "run straddles a shard boundary"


def test_split_single_giant_run():
    keys = np.zeros(10, dtype=np.int64)
    splits = split_at_run_boundaries(keys, 4)
    assert splits[0] == 0 and splits[-1] == 10
    assert all(a <= b for a, b in zip(splits, splits[1:]))


@pytest.mark.parametrize("n_batch,n_space", [(1, 8), (2, 4), (4, 2)])
def test_sharded_matches_cpu(n_batch, n_space):
    _require_devices(n_batch * n_space)
    mesh = make_fanout_mesh(n_batch, n_space)
    rng = random.Random(0xC0FFEE + n_batch)
    cpu = CpuSpatialBackend(16)
    shard = ShardedTpuSpatialBackend(16, mesh)
    peers = [uuid.uuid4() for _ in range(30)]
    worlds = ["alpha", "beta", "gamma", "delta"]

    def rand_pos():
        return Vector3(
            rng.uniform(-150, 150), rng.uniform(-150, 150), rng.uniform(-150, 150)
        )

    for _ in range(600):
        w, p, pos = rng.choice(worlds), rng.choice(peers), rand_pos()
        if rng.random() < 0.8:
            assert cpu.add_subscription(w, p, pos) == shard.add_subscription(w, p, pos)
        else:
            assert cpu.remove_subscription(w, p, pos) == shard.remove_subscription(w, p, pos)

    queries = [
        LocalQuery(
            rng.choice(worlds + ["never"]),
            rand_pos(),
            rng.choice(peers),
            rng.choice(list(Replication)),
        )
        for _ in range(100)
    ]
    for c, t in zip(cpu.match_local_batch(queries), shard.match_local_batch(queries)):
        assert set(c) == set(t)


def test_sharded_mutation_then_requery():
    _require_devices(8)
    mesh = make_fanout_mesh(2, 4)
    b = ShardedTpuSpatialBackend(16, mesh)
    sender, other = uuid.uuid4(), uuid.uuid4()
    pos = Vector3(5, 5, 5)
    b.add_subscription(W, other, pos)
    assert b.match_local_batch([LocalQuery(W, pos, sender)]) == [[other]]
    b.remove_peer(other)
    assert b.match_local_batch([LocalQuery(W, pos, sender)]) == [[]]
    stats = b.device_stats()
    assert stats["mesh"] == {"batch": 2, "space": 4}


@pytest.mark.parametrize("loaded", [False, True], ids=["empty", "loaded"])
def test_sharded_device_stats_show_every_device_holding_a_shard(loaded):
    _require_devices(8)
    # a low compaction threshold folds the bulk load into the base
    b = ShardedTpuSpatialBackend(16, make_fanout_mesh(2, 4),
                                 compact_threshold=16)
    if loaded:
        rng = random.Random(3)
        b.bulk_add_subscriptions(
            W, [uuid.uuid4() for _ in range(4096)],
            [(rng.randrange(-64, 64), rng.randrange(-64, 64),
              rng.randrange(-64, 64)) for _ in range(4096)],
        )
        b.flush()
    stats = b.device_stats()
    assert (stats["platform"], stats["device_count"]) == ("cpu", 8)
    per_device = stats["base_bytes_per_device"]
    if loaded:
        assert len(per_device) == 8 and len(set(per_device.values())) == 1
    else:
        assert per_device == {}


def test_non_pow2_batch_axis():
    """Batch padding must stay divisible by a non-power-of-two batch
    axis (regression: device_put raised on cap=8, n_batch=3)."""
    _require_devices(6)
    mesh = make_fanout_mesh(3, 2)
    b = ShardedTpuSpatialBackend(16, mesh)
    p = uuid.uuid4()
    b.add_subscription(W, p, Vector3(5, 5, 5))
    assert b.match_local_batch([LocalQuery(W, Vector3(5, 5, 5), uuid.uuid4())]) == [[p]]


def test_make_fanout_mesh_validation():
    _require_devices(8)
    with pytest.raises(ValueError):
        make_fanout_mesh(3)  # 8 % 3 != 0
    with pytest.raises(ValueError):
        make_fanout_mesh(4, 4)  # 16 > 8
    mesh = make_fanout_mesh(2)
    assert mesh.shape == {"batch": 2, "space": 4}


def test_sharded_repeated_compaction_churn():
    """≥2 background compactions against a POPULATED device-resident
    base (regression: the second compaction used to rank-mismatch the
    [n_space, cap] base stacks against the flat delta buffer, killing
    the worker and wedging wait_compaction forever)."""
    _require_devices(8)
    mesh = make_fanout_mesh(2, 4)
    rng = random.Random(7)
    cpu = CpuSpatialBackend(16)
    b = ShardedTpuSpatialBackend(16, mesh, compact_threshold=64)
    peers = [uuid.uuid4() for _ in range(64)]

    def rand_pos():
        return Vector3(
            rng.uniform(-300, 300), rng.uniform(-300, 300), rng.uniform(-300, 300)
        )

    for _ in range(4):
        for _ in range(200):
            w = f"w{rng.randrange(3)}"
            p, pos = rng.choice(peers), rand_pos()
            assert cpu.add_subscription(w, p, pos) == b.add_subscription(w, p, pos)
            if rng.random() < 0.2:
                w2, p2, pos2 = f"w{rng.randrange(3)}", rng.choice(peers), rand_pos()
                assert cpu.remove_subscription(w2, p2, pos2) == b.remove_subscription(
                    w2, p2, pos2
                )
        b.flush()
        b.wait_compaction()

    assert b.compactions >= 2, b.device_stats()
    assert b.compaction_failures == 0

    queries = [
        LocalQuery(f"w{rng.randrange(3)}", rand_pos(), rng.choice(peers))
        for _ in range(64)
    ]
    for c, t in zip(cpu.match_local_batch(queries), b.match_local_batch(queries)):
        assert set(c) == set(t)


def test_compaction_worker_failure_surfaces_and_recovers():
    """A worker exception must not wedge the backend: wait_compaction
    raises (instead of hanging), flush keeps serving, and once the
    fault clears the next compaction succeeds."""
    _require_devices(8)
    mesh = make_fanout_mesh(2, 4)
    b = ShardedTpuSpatialBackend(16, mesh, compact_threshold=8)
    sender = uuid.uuid4()
    peers = [uuid.uuid4() for _ in range(32)]
    pos = Vector3(5, 5, 5)

    real_work = b._compact_work
    b._compact_work = lambda snap: (_ for _ in ()).throw(RuntimeError("boom"))

    for p in peers[:16]:
        b.add_subscription(W, p, pos)
    b.flush()  # starts the (doomed) background compaction
    assert b._compaction is not None
    with pytest.raises(RuntimeError):
        b.wait_compaction()
    assert b._compaction is None
    assert b.compaction_failures == 1

    # fault clears → a quiet flush (NO new mutations) must still retry.
    # Restore BEFORE any query: match_local_batch flushes internally and
    # would re-arm a doomed run racing the restore below.
    b._compact_work = real_work
    b.flush()
    assert b._compaction is not None, "failed compaction not re-armed"
    b.wait_compaction()

    # still serving, and the host authority never corrupted
    assert set(b.match_local_batch([LocalQuery(W, pos, sender)])[0]) == set(peers[:16])

    for p in peers[16:]:
        b.add_subscription(W, p, pos)
    b.flush()
    b.wait_compaction()
    assert b.compactions >= 1
    assert set(b.match_local_batch([LocalQuery(W, pos, sender)])[0]) == set(peers)


def test_maybe_initialize_distributed_env_contract(monkeypatch):
    """Unset → single-host no-op; a partial multi-host config fails
    loudly instead of silently running single-host."""
    from worldql_server_tpu.parallel import maybe_initialize_distributed

    monkeypatch.delenv("WQL_DIST_COORDINATOR", raising=False)
    assert maybe_initialize_distributed() is False

    monkeypatch.setenv("WQL_DIST_COORDINATOR", "10.0.0.1:1234")
    monkeypatch.delenv("WQL_DIST_NUM_PROCESSES", raising=False)
    monkeypatch.delenv("WQL_DIST_PROCESS_ID", raising=False)
    with pytest.raises(ValueError, match="WQL_DIST_NUM_PROCESSES"):
        maybe_initialize_distributed()


def test_dist_env_with_wrong_backend_is_a_config_error(monkeypatch):
    from worldql_server_tpu.engine.config import Config

    monkeypatch.setenv("WQL_DIST_COORDINATOR", "10.0.0.1:1234")
    config = Config(store_url="memory://")
    config.spatial_backend = "cpu"
    with pytest.raises(ValueError, match="multi-host requires"):
        config.validate()
    config.spatial_backend = "sharded"
    config.validate()  # sharded accepts it


def test_sharded_compaction_folds_on_device_without_reupload():
    """Steady-state compaction must fold per-shard on device with no
    full-base re-upload: H2D is O(boundary keys), not O(index). A full
    `_upload_base` during compaction is only legitimate for the very
    first base install or a shard-imbalance re-shard."""
    _require_devices(8)
    mesh = make_fanout_mesh(2, 4)
    rng = random.Random(13)
    cpu = CpuSpatialBackend(16)
    b = ShardedTpuSpatialBackend(16, mesh, compact_threshold=64)
    peers = [uuid.uuid4() for _ in range(64)]

    uploads = []
    real_upload = b._upload_base

    def counting_upload(*a, **kw):
        uploads.append(len(a[0]))
        return real_upload(*a, **kw)

    b._upload_base = counting_upload

    def rand_pos():
        return Vector3(
            rng.uniform(-300, 300), rng.uniform(-300, 300),
            rng.uniform(-300, 300),
        )

    # initial load → first base install may upload
    for _ in range(150):
        w = f"w{rng.randrange(3)}"
        p, pos = rng.choice(peers), rand_pos()
        cpu.add_subscription(w, p, pos)
        b.add_subscription(w, p, pos)
    b.flush()
    b.wait_compaction()
    baseline_uploads = len(uploads)

    # steady churn: every subsequent compaction must fold on device
    for _ in range(3):
        for _ in range(120):
            w = f"w{rng.randrange(3)}"
            p, pos = rng.choice(peers), rand_pos()
            cpu.add_subscription(w, p, pos)
            b.add_subscription(w, p, pos)
            if rng.random() < 0.3:
                w2, p2, pos2 = (f"w{rng.randrange(3)}",
                                rng.choice(peers), rand_pos())
                cpu.remove_subscription(w2, p2, pos2)
                b.remove_subscription(w2, p2, pos2)
        b.flush()
        b.wait_compaction()

    assert b.compactions >= 2, b.device_stats()
    assert b.compaction_failures == 0
    assert len(uploads) == baseline_uploads, (
        f"compaction re-uploaded the base: {uploads[baseline_uploads:]}"
    )

    # and the folded index still answers exactly like the oracle
    queries = [
        LocalQuery(f"w{rng.randrange(3)}", rand_pos(), rng.choice(peers))
        for _ in range(128)
    ]
    for c, t in zip(cpu.match_local_batch(queries),
                    b.match_local_batch(queries)):
        assert set(c) == set(t)


def test_sharded_reshard_on_imbalance_falls_back():
    """When the key-range boundaries drift past the imbalance bound
    (forced here via a tiny RESHARD_IMBALANCE), compaction must fall
    back to a full re-shard upload — and stay correct."""
    _require_devices(8)
    mesh = make_fanout_mesh(2, 4)
    rng = random.Random(17)
    cpu = CpuSpatialBackend(16)
    b = ShardedTpuSpatialBackend(16, mesh, compact_threshold=32)
    b.RESHARD_IMBALANCE = -1.0  # every compaction takes the fallback
    peers = [uuid.uuid4() for _ in range(32)]

    uploads = []
    real_upload = b._upload_base

    def counting_upload(*a, **kw):
        uploads.append(len(a[0]))
        return real_upload(*a, **kw)

    b._upload_base = counting_upload

    def rand_pos():
        return Vector3(
            rng.uniform(-200, 200), rng.uniform(-200, 200),
            rng.uniform(-200, 200),
        )

    for _ in range(3):
        for _ in range(100):
            w = f"w{rng.randrange(2)}"
            p, pos = rng.choice(peers), rand_pos()
            cpu.add_subscription(w, p, pos)
            b.add_subscription(w, p, pos)
        b.flush()
        b.wait_compaction()
    assert b.compactions >= 1
    assert b.compaction_failures == 0
    # the forced-imbalance bound must actually route compactions to the
    # re-shard upload (one initial install + >= 1 compaction fallback)
    assert len(uploads) >= 2, uploads

    queries = [
        LocalQuery(f"w{rng.randrange(2)}", rand_pos(), rng.choice(peers))
        for _ in range(64)
    ]
    for c, t in zip(cpu.match_local_batch(queries),
                    b.match_local_batch(queries)):
        assert set(c) == set(t)
