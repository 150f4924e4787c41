"""Share (%) of one count's growth in the growth of several.
spec: {"kind": "counter_ratio", "part": [path], "whole": [[path], ...]}"""

from benchmark.sources._paths import delta


def read(spec: dict, ctx: dict):
    part = delta(ctx, spec["part"])
    whole = [delta(ctx, p) for p in spec["whole"]]
    if part is None or any(w is None for w in whole):
        return None         # the program has no such counters
    return 100.0 * part / sum(whole) if sum(whole) > 0 else 0.0
