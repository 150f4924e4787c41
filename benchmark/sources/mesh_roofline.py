"""A mesh resolve call's share (%) of its roofline: the least time ONE
device of the mesh could take for its share of a mean call, over the
device time the trace shows for a call.
spec: {"kind": "mesh_roofline", "line": "modules",
       "match": "<regex: every program a call runs>",
       "calls": "<regex: the program that runs once a call>",
       "shapes": "<a key of the run's shapes>"}
The shapes of a mean call come from the traffic the reference knows
(`ctx["shapes"]`), never from the program; the mesh's shape is the
deployment's (`--mesh-batch`, `--mesh-space`) as the server's
`spatial_device.mesh` gauge states it. A program without that gauge, or
a trace without those programs, gives nothing to read."""

from benchmark import roofline
from benchmark.sources._paths import lookup
from benchmark.sources.device_op_time import matched


def mesh_resolve(queries: float, targets: float, n_batch: int,
                 n_space: int) -> dict:
    """What one device must move to resolve `queries` LocalMessages to
    `targets` subscribed rows through an index sharded over `n_space`
    key ranges, the queries over `n_batch`: its own share of the
    one-chip work through HBM (`roofline.cube_match` of queries /
    n_batch and targets / n_space: a cube's run lives on one space
    shard, so a device holds a 1/n_space of the matched rows), and over
    the interconnect the 4-byte peer id of every target, which must
    reach the device that assembles the result."""
    return {
        "hbm_bytes": roofline.cube_match(queries / n_batch,
                                         targets / n_space)["bytes"],
        "ici_bytes": 4.0 * targets,
    }


def least_seconds(work: dict, device_kind: str) -> tuple[float, str]:
    """-> (least time, which bound): the slower of the two links."""
    p = roofline.peaks(device_kind)
    t_hbm = work["hbm_bytes"] / p["hbm_bytes_per_s"]
    t_ici = work["ici_bytes"] / (p["ici_bits_per_s"] / 8.0)
    return (t_ici, "interconnect") if t_ici > t_hbm else (t_hbm, "memory")


def read(spec: dict, ctx: dict):
    line = spec.get("line", "modules")
    run, calls = matched(ctx, line, spec["match"]), matched(
        ctx, line, spec["calls"])
    shapes = (ctx.get("shapes") or {}).get(spec["shapes"])
    mesh = lookup(ctx["after"], ["gauges", "spatial_device", "mesh"])
    if run is None or calls is None or not shapes or not mesh:
        return None
    work = mesh_resolve(n_batch=int(mesh["batch"]), n_space=int(mesh["space"]),
                        **shapes)
    least, bound = least_seconds(work, ctx["device_kind"])
    # a call's time is that of every program it ran (the resolve, and
    # the repack where the collect asked for one) on one device
    per_call = run[0] / calls[1] / 1e9
    return (100.0 * least / per_call,
            f"{bound}-bound: least {least * 1e6:.3f} us a call of "
            f"{work['hbm_bytes']:.0f} B through HBM and "
            f"{work['ici_bytes']:.0f} B over ICI a device, the trace shows "
            f"{per_call * 1e6:.1f} us a call over {calls[1]:.0f} calls on a "
            f"{mesh['batch']}x{mesh['space']} mesh of {ctx['device_kind']}")
