"""The lower-precision control of the entity cells: the real server
with a bfloat16 position column. Every position a tick brings back from the device is rounded to
bfloat16 (8 bits of mantissa) before the plane takes it in, the
step below the float32 columns the configuration states. Positions are
multiples of 1/8 m up to 800 m: exact in float32, not in bfloat16.

    python -m benchmark.tests.bf16_server <the server's own arguments>
"""

import numpy as np

from worldql_server_tpu.entities import plane


def _bf16(a):
    a32 = np.ascontiguousarray(a, np.float32)
    bits = a32.view(np.uint32)
    rounded = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return rounded.view(np.float32)


_collect = plane.EntityPlane.collect_tick


def _collect_tick_bf16(self, handle):
    """The positions a tick brings back from the device, as a bfloat16
    column would hold them."""
    result = _collect(self, handle)
    if "pos" in result:
        result["pos"] = _bf16(np.asarray(result["pos"]))
    return result


plane.EntityPlane.collect_tick = _collect_tick_bf16

if __name__ == "__main__":
    import runpy

    runpy.run_module("worldql_server_tpu", run_name="__main__")
