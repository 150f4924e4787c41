"""A gauge as the scrape after the window shows it, scaled.
spec: {"kind": "gauge", "path": ["gauges", "precompile", "wall_ms"],
       "scale": 0.001}"""

from benchmark.sources._paths import lookup


def read(spec: dict, ctx: dict):
    v = lookup(ctx["after"], spec["path"])
    if not isinstance(v, (int, float)):
        return None
    return v * spec.get("scale", 1.0)
