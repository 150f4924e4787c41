"""Jit-retrace tripwire for the batched fan-out engine.

The engine's latency budget assumes kernels compile once per capacity
tier, not per tick: every dynamic dimension (query batch, CSR slot
budget) is padded to a power-of-two tier precisely so steady traffic
reuses compiled variants. A change that breaks tiering (keying a jit on
the raw batch size, rebuilding a jit per tick, an unstable static arg)
turns every tick into a multi-second XLA compile — the regression class
behind BENCH_r05's unexplained 207-second depth-2 outlier. This suite
fails on any such change (budget knob: ``WQL_RETRACE_BUDGET``).
"""

import uuid

import numpy as np
import pytest

from worldql_server_tpu.protocol.types import Replication, Vector3
from worldql_server_tpu.spatial.backend import LocalQuery
from worldql_server_tpu.spatial.tpu_backend import TpuSpatialBackend
from worldql_server_tpu.utils import retrace

W = "world"


def build_backend(n_cubes=24, per_cube=6):
    b = TpuSpatialBackend(16, compact_threshold=64)
    cubes, peers = [], []
    pid = 0
    for c in range(n_cubes):
        for _ in range(per_cube):
            cubes.append([16 * (c + 1), 16, 16])
            peers.append(uuid.UUID(int=pid + 1))
            pid += 1
    b.bulk_add_subscriptions(W, peers, np.asarray(cubes, np.int64))
    b.flush()
    b.wait_compaction()
    return b, np.asarray(cubes, np.float64) - 0.5, peers


def tick(b, sub_pos, peers, m, seed):
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, len(sub_pos), m)
    queries = [
        LocalQuery(W, Vector3(*sub_pos[i]), peers[i], Replication.EXCEPT_SELF)
        for i in idx
    ]
    return b.match_local_batch(queries)


def test_hot_kernels_are_registered():
    families = retrace.GUARD.counts().keys()
    for family in (
        "tpu_backend.match_dense",
        "tpu_backend.match_run_csr",
        "tpu_backend.device_compact",
    ):
        assert family in families


def test_steady_state_ticks_stay_within_retrace_budget():
    """Varying batch sizes WITHIN one padded capacity tier must not add
    compiled variants once the tier is warm."""
    b, sub_pos, peers = build_backend()
    # warm the 64-query tier (and let the delivery-cap hint settle —
    # its growth/decay may legitimately select a second t_cap early on)
    for s in range(3):
        tick(b, sub_pos, peers, 50, seed=s)

    snap = retrace.GUARD.snapshot()
    for s, m in enumerate([33, 40, 47, 55, 63, 64, 36, 61]):
        got = tick(b, sub_pos, peers, m, seed=100 + s)
        assert len(got) == m
    # the tripwire: fails the suite on any over-budget family
    delta = retrace.GUARD.check(since=snap)
    assert sum(delta.values()) <= retrace.DEFAULT_BUDGET, delta


def test_new_capacity_tier_traces_are_counted():
    """Crossing a tier boundary legitimately compiles — and the guard
    must SEE it (a guard that always reads 0 protects nothing)."""
    b, sub_pos, peers = build_backend()
    tick(b, sub_pos, peers, 40, seed=1)   # 64-query tier
    snap = retrace.GUARD.snapshot()
    tick(b, sub_pos, peers, 100, seed=2)  # 128-query tier: new trace
    delta = retrace.GUARD.delta(snap)
    assert sum(delta.values()) >= 1, "tier crossing must register traces"
    with pytest.raises(retrace.RetraceBudgetExceeded):
        retrace.GUARD.check(0, since=snap)


def test_guard_check_reports_offending_family():
    guard = retrace.RetraceGuard()

    class FakeJit:
        def __init__(self, n):
            self._n = n

        def _cache_size(self):
            return self._n

    guard.register("fam.a", FakeJit(3))
    guard.register("fam.b", FakeJit(1))
    assert guard.counts() == {"fam.a": 3, "fam.b": 1}
    with pytest.raises(retrace.RetraceBudgetExceeded, match="fam.a"):
        guard.check({"fam.a": 2, "fam.b": 5})
    # per-family budgets: both within → returns counts
    assert guard.check(3) == {"fam.a": 3, "fam.b": 1}
