"""Mean of a latency histogram over the window: the delta of
mean x count between the two scrapes over the delta of count (the
histogram's own p50/p99 are bucket bounds, not a metric source).
spec: {"kind": "histogram_mean", "name": "tick.flush_ms"}"""

from benchmark.sources._paths import lookup


def read(spec: dict, ctx: dict):
    b = lookup(ctx["after"], ["latency", spec["name"]])
    if b is None:
        return None
    a = lookup(ctx["before"], ["latency", spec["name"]]) or {
        "count": 0, "mean_ms": 0.0}
    n = b["count"] - a["count"]
    if n <= 0:
        return None
    return (b["mean_ms"] * b["count"] - a["mean_ms"] * a["count"]) / n
