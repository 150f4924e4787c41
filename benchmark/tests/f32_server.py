"""The lower-precision control: the real server with a float32
quantizer on its served path. Every position is rounded to float32
before the program's own cube/key code sees it, the step from float64
that would tempt a later PR. Steered from here, not through an option
of the program:

    python -m benchmark.tests.f32_server <the server's own arguments>

`run.py --server-module benchmark.tests.f32_server` puts it in the
program's place; `correct` has to come out false.
"""

import numpy as np

from worldql_server_tpu.spatial import tpu_backend


def _through_f32(fn):
    def wrapped(world_ids, positions, *args, **kwargs):
        rounded = np.asarray(positions, np.float64).astype(np.float32)
        return fn(world_ids, rounded.astype(np.float64), *args, **kwargs)
    return wrapped


tpu_backend.encode_queries = _through_f32(tpu_backend.encode_queries)
tpu_backend.query_keys = _through_f32(tpu_backend.query_keys)

if __name__ == "__main__":
    import runpy

    runpy.run_module("worldql_server_tpu", run_name="__main__")
