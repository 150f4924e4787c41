"""JAX configuration shared by every accelerated module.

Cube labels are int64 (the reference's CubeArea is i64×3,
subscriptions/cube_area.rs:8-13) and the sort keys derived from them are
64-bit hashes, so the device path needs x64 enabled. TPU executes i64
compares/gathers as emulated pairs of i32 ops — cheap for this workload,
which is bandwidth-bound gathers, not arithmetic. No f64 ever reaches
the device: quantization runs host-side in numpy f64 (spatial/quantize).

Import this module before any ``import jax`` in accelerated code.
"""

from __future__ import annotations

import os

import jax

jax.config.update("jax_enable_x64", True)

# Persistent compilation cache: the large-tier device kernels (1M-row
# segment sorts, probe-table builds, match kernels) take tens of seconds
# each to compile for the TPU, which dominates a cold boot with a large
# index; every later process loads the serialized executables instead.
# One knob, the standard one: where JAX_COMPILATION_CACHE_DIR is set,
# jax reads it itself and nothing is set here. Otherwise the cache is
# the checkout's own .jax_cache — a fixed path, because the path is
# part of what a run's server, shard children and benches must share.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update(
        "jax_compilation_cache_dir",
        os.path.join(
            os.path.dirname(os.path.dirname(
                os.path.dirname(os.path.abspath(__file__)))),
            ".jax_cache",
        ),
    )
jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def on_tpu() -> bool:
    """The one reading of the default device that picks the compiled
    Pallas kernels over their XLA twins and over Pallas interpret mode
    (ops/tick.py, ops/knn_pallas.py, entities/plane.py): who asks here
    gets the same answer, so ``pallas=True`` in a log means Mosaic."""
    return jax.devices()[0].platform == "tpu"
