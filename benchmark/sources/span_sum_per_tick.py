"""Sum of the named spans' durations (ms) over the window, loose traces
(per-message spans outside a tick) included, divided by the ticks seen.
spec: {"kind": "span_sum_per_tick", "spans": ["zmq.recv", "codec.decode"]}"""


def read(spec: dict, ctx: dict):
    names = set(spec["spans"])
    lo, hi = ctx["window_unix"]
    ticks = ctx["window_ticks"]
    if not ticks:
        return None
    loose = [t for t in ctx["loose"] if lo <= t["start_unix_s"] < hi]
    total, found = 0.0, 0
    for t in [*ticks, *loose]:
        for s in t["spans"]:
            if s["name"] in names:
                total += s["dur_ms"]
                found += 1
    return total / len(ticks) if found else None
