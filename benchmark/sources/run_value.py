"""A number the harness itself took in the run.
spec: {"kind": "run_value", "key": "compiles_in_window"}"""


def read(spec: dict, ctx: dict):
    return ctx.get(spec["key"])
