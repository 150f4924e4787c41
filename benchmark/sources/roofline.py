"""A kernel family's share (%) of its roofline: the least time the chip
could take for one call (benchmark/roofline.py, from the call's shapes)
over the device time the trace shows for one call.
spec: {"kind": "roofline", "line": "modules", "match": "<regex>",
       "model": "cube_match", "shapes": "<a key of the run's shapes>"}
The shapes of a mean call come from the traffic the reference knows
(`ctx["shapes"]`), never from the program."""

from benchmark import roofline
from benchmark.sources.device_op_time import matched


def read(spec: dict, ctx: dict):
    got = matched(ctx, spec.get("line", "modules"), spec["match"])
    shapes = (ctx.get("shapes") or {}).get(spec["shapes"])
    if got is None or not shapes:
        return None
    ns, count = got
    work = roofline.MODELS[spec["model"]](**shapes)
    least, bound = roofline.least_seconds(work, ctx["device_kind"])
    per_call = ns / count / 1e9
    return (100.0 * least / per_call,
            f"{bound}-bound: least {least * 1e6:.3f} us a call of "
            f"{work['bytes']:.0f} B / {work['ops']:.0f} ops, the trace shows "
            f"{per_call * 1e6:.1f} us a call over {count:.0f} calls on "
            f"{ctx['device_kind']}")
