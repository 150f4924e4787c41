"""The load generator's own clock: how late it sent (ms after due).
spec: {"kind": "generator", "reading": "late_ms", "percentile": 95}"""

import numpy as np


def read(spec: dict, ctx: dict):
    values = ctx["generator"].get(spec["reading"])
    if values is None or not len(values):
        return None
    return float(np.percentile(values, spec["percentile"]))
