"""Ask the TPU's own compiler, without a TPU, whether the served
kernels build at the widths ``chip_smoke.py`` runs them.

The installed TPU compiler compiles for a chip that is described, not
attached (``jax.experimental.topologies``): a kernel Mosaic would refuse
— fast-memory limit, unaligned slice, a 64-bit type leaking in from the
global x64 mode — is refused here, at no chip time. Nothing runs, so
these cases say nothing about results or speed.

The topology is described INSIDE a fixture, never at import: loading
the TPU library is exclusive per process, and xdist workers all import
every test file. Keep every such test in this one file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from worldql_server_tpu.ops import knn_pallas, tick
from worldql_server_tpu.spatial import tpu_backend as tb

#: chip_smoke.py's index: 1M rows pad to the 2^20 tier, half as many
#: probe buckets (probe_buckets_for), 24-lane probe rows
BASE_CAP = 1 << 20
PROBE_BUCKETS = 1 << 19
#: the north star's query batch and the CSR slot tiers it reaches
#: against a Zipf crowd with the occupancy cap at 256
QUERY_CAP = 16_384
CSR_CAP = 1 << 19
PACK_BUCKET = 1 << 18


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the persistent compile cache off
    around this file's compiles: an executable compiled for a described
    device is written to the cache but cannot be read back without the
    chip, so every later run would warn and recompile."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _base_segment(spec):
    """The six device arrays of a 1M-row base segment, as
    ``TpuSpatialBackend._upload_base`` lays them out."""
    return (
        spec((BASE_CAP,), jnp.int64),          # sorted keys
        spec((BASE_CAP,), jnp.int64),          # second hash
        spec((BASE_CAP,), jnp.int32),          # peer ids
        spec((BASE_CAP,), jnp.int32),          # run remainders
        spec((PROBE_BUCKETS, 24), jnp.int32),  # probe table
        spec((1,), jnp.int32),                 # probe overflow flag
    )


def _queries(spec, m):
    return (spec((m,), jnp.int64), spec((m,), jnp.int64),
            spec((m,), jnp.int32), spec((m,), jnp.int8))


@pytest.fixture
def spec(one_chip):
    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def test_knn_kernel_compiles_for_the_chip(spec):
    n = 100_000
    compiled = knn_pallas._knn_jit.lower(
        spec((n,), jnp.int32), spec((n,), jnp.int32),
        spec((n, 3), jnp.float32), k=32, tile=512, interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_simulation_tick_takes_the_compiled_kernel(spec, monkeypatch):
    """``pallas=True`` alone does not mean Mosaic: ``knn_select`` picks
    interpret mode from ``jax.devices()``, which is this box's CPU. The
    choice is steered here, from the test — on the chip the same line
    picks the compiled kernel by itself."""
    n = 2_048   # 100K compiles for 94 s, 1M for minutes: by hand
    select = knn_pallas.knn_select
    monkeypatch.setattr(
        knn_pallas, "knn_select",
        lambda *a, **kw: select(*a, **{**kw, "interpret": False}),
    )
    state = tick.EntityState(
        position=spec((n, 3), jnp.float32),
        velocity=spec((n, 3), jnp.float32),
        world=spec((n,), jnp.int32),
        peer=spec((n,), jnp.int32),
    )
    compiled = jax.jit(
        tick.make_tick_fn(cube_size=16, k=32, pallas=True)
    ).lower(state).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_plane_tick_sorts_recipients_beside_the_kernel(spec, monkeypatch):
    """The entity plane's own tick (``canonical_tick_fn``): the op and
    a row sort of its ``[n, k]`` recipients, one program. 2 s here; by
    hand at 32,768 rows 46 s against the op's 44."""
    from worldql_server_tpu.entities.plane import canonical_tick_fn

    n = 2_048
    select = knn_pallas.knn_select
    monkeypatch.setattr(
        knn_pallas, "knn_select",
        lambda *a, **kw: select(*a, **{**kw, "interpret": False}),
    )
    state = tick.EntityState(
        position=spec((n, 3), jnp.float32),
        velocity=spec((n, 3), jnp.float32),
        world=spec((n,), jnp.int32),
        peer=spec((n,), jnp.int32),
    )
    compiled = jax.jit(
        canonical_tick_fn(cube_size=16, k=32, pallas=True)
    ).lower(state).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and text.count(" sort(") == 2


@pytest.mark.parametrize("m,t_cap", [(64, 1 << 15), (QUERY_CAP, CSR_CAP)])
def test_match_run_csr_compiles_at_the_1m_row_tier(spec, m, t_cap):
    tb._match_run_csr_kernel.lower(
        *_base_segment(spec), *_queries(spec, m), nseg=1, t_cap=t_cap,
    ).compile()


def test_pack_csr_compiles_at_the_16k_query_tier(spec):
    tb._pack_csr_kernel.lower(
        spec((QUERY_CAP, 1), jnp.int32), spec((CSR_CAP,), jnp.int32),
        bucket=PACK_BUCKET,
    ).compile()


def test_segment_sort_compiles_at_a_delta_tier(spec):
    """Live subscriptions land in the delta buffer, sorted on device.
    A small tier: the compiler's time for a sort grows steeply with
    its length (1 s here, 16 s at 2^14, 103 s at 2^16), and so does
    the 1M-row probe-table build a bulk load launches (82 s) — those
    are compiled by hand and recorded in CHANGES.md, not kept."""
    cap = 1 << 12
    tb._sort_segment_dev.lower(
        spec((cap,), jnp.int64), spec((cap,), jnp.int64),
        spec((cap,), jnp.int32), n_buckets=cap // 2,
    ).compile()


# region: the mesh programs of `worlds-64x10k-sharded` (ISSUE 32)

#: 640,000 rows over four key ranges: 160,000 a shard, the 2^18 tier;
#: ~26,400 cubes a shard; the fullest cube holds 256
MESH_SHARD_CAP = 1 << 18
MESH_PROBE_BUCKETS = tb.probe_buckets_for(26_400)
MESH_K = 256


@pytest.fixture(scope="module")
def mesh_backend(topo, one_chip):
    """The sharded backend on a {batch 1, space 4} mesh of the four
    DESCRIBED chips (``one_chip`` keeps the compile cache off)."""
    import numpy as np
    from jax.sharding import Mesh

    from worldql_server_tpu.parallel import ShardedTpuSpatialBackend

    devices = np.array(topo.devices[:4]).reshape(1, 4)
    return ShardedTpuSpatialBackend(16, Mesh(devices, ("batch", "space")))


def _mesh_base():
    s = jax.ShapeDtypeStruct
    return (
        s((4, MESH_SHARD_CAP), jnp.int64), s((4, MESH_SHARD_CAP), jnp.int64),
        s((4, MESH_SHARD_CAP), jnp.int32), s((4, MESH_SHARD_CAP), jnp.int32),
        s((4, MESH_PROBE_BUCKETS, 24), jnp.int32), s((4, 1), jnp.int32),
    )


@pytest.mark.parametrize("m,t_cap", [(32, 1 << 10), (1024, 1 << 18)])
def test_mesh_resolve_csr_compiles_for_four_chips(mesh_backend, m, t_cap):
    """A served tick's tier and a warm-up burst's: the program is named
    as the trace will print it and merges over the interconnect."""
    kernel = mesh_backend._make_kernel("csr", ("base",), (MESH_K,), t_cap)
    text = kernel.lower(*_mesh_base(), *_queries(jax.ShapeDtypeStruct, m)).compile().as_text()
    assert "jit_mesh_resolve_csr" in text and "all-reduce" in text


def test_mesh_resolve_dense_compiles_for_four_chips(mesh_backend):
    kernel = mesh_backend._make_kernel("dense", ("base",), (MESH_K,), None)
    text = kernel.lower(*_mesh_base(), *_queries(jax.ShapeDtypeStruct, 32)).compile().as_text()
    assert "jit_mesh_resolve_dense" in text and "all-reduce" in text


def test_mesh_repack_compiles_for_four_chips(mesh_backend):
    kernel = mesh_backend._pack_kernel(1 << 12, 1024, 1, 1 << 18)
    s = jax.ShapeDtypeStruct
    text = kernel.lower(s((1024, 1), jnp.int32),
                        s((1 << 18,), jnp.int32)).compile().as_text()
    assert "jit_mesh_repack" in text


# endregion
