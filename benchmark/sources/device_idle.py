"""Share (%) of the traced window in which no operation ran on the
device, averaged over the chips used.
spec: {"kind": "device_idle"}"""


def read(spec: dict, ctx: dict):
    trace = ctx.get("trace")
    if not trace or not trace["devices"]:
        return None
    lo, hi = trace["window_ns"]
    return 100.0 * (1.0 - trace["busy_ns"] / (hi - lo))
