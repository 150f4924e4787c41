"""A count's growth over the window, optionally per second or per tick.
spec: {"kind": "counter_delta", "path": ["counters", "tick.flushes"],
       "per": "second" | "tick" | absent}"""

from benchmark.sources._paths import delta


def read(spec: dict, ctx: dict):
    d = delta(ctx, spec["path"])
    if d is None:
        return None
    per = spec.get("per")
    if per == "second":
        return d / (ctx["window_unix"][1] - ctx["window_unix"][0])
    if per == "tick":
        ticks = delta(ctx, ["counters", "tick.flushes"])
        return d / ticks if ticks else None
    return d
