"""Shared by the readers: a value inside a /metrics scrape."""


def lookup(snapshot: dict, path: list):
    node = snapshot
    for key in path:
        if not isinstance(node, dict) or key not in node:
            return None
        node = node[key]
    return node


def delta(ctx: dict, path: list):
    a, b = lookup(ctx["before"], path), lookup(ctx["after"], path)
    if a is None and b is not None:
        a = 0           # a counter appears with its first count
    if b is None:
        return None
    return b - a
