"""Device time (ms) of the operations or modules whose trace name
matches, per execution or per second of traced window.
spec: {"kind": "device_op_time", "line": "modules" | "ops",
       "match": "<regex>", "per": "call" | "second"}"""

import re


def matched(ctx: dict, line: str, pattern: str):
    """-> (ns, executions) summed over devices, averaged over them."""
    trace = ctx.get("trace")
    if not trace or not trace["devices"]:
        return None
    rx = re.compile(pattern)
    ns = count = 0
    for dev in trace["devices"].values():
        for name, (t, n) in dev[line].items():
            if rx.search(name):
                ns += t
                count += n
    n_dev = len(trace["devices"])
    return (ns / n_dev, count / n_dev) if count else None


def read(spec: dict, ctx: dict):
    got = matched(ctx, spec.get("line", "modules"), spec["match"])
    if got is None:
        return None
    ns, count = got
    if spec.get("per", "call") == "second":
        lo, hi = ctx["trace"]["window_ns"]
        return ns / 1e6 / ((hi - lo) / 1e9)
    return ns / 1e6 / count
