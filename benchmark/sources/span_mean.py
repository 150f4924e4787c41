"""Mean duration (ms) of the spans of the given names over the window's
tick traces (GET /debug/ticks of a server started with --trace).
spec: {"kind": "span_mean", "spans": ["tick.dispatch"]}"""


def durations(ctx: dict, names) -> list:
    return [s["dur_ms"] for t in ctx["window_ticks"] for s in t["spans"]
            if s["name"] in names]


def read(spec: dict, ctx: dict):
    d = durations(ctx, set(spec["spans"]))
    return sum(d) / len(d) if d else None
