"""One count's growth over another's, scaled: a cost a message where
both are cumulative (ns taken / messages taken).
spec: {"kind": "counter_quotient", "num": [path], "den": [path],
       "scale": 0.001}"""

from benchmark.sources._paths import delta


def read(spec: dict, ctx: dict):
    num, den = delta(ctx, spec["num"]), delta(ctx, spec["den"])
    if num is None or den is None or den <= 0:
        return None         # no such counters, or nothing was counted
    return num / den * spec.get("scale", 1.0)
