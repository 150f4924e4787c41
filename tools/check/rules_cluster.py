"""Cluster safety rule (ISSUE 14): cross-shard work is enqueue-and-drain.

The horizontal-serving design hides the inter-shard collective behind
the local device window: a shard's tick WRITES outbound frames onto
the peer rings (fire-and-forget ``try_write``) and DRAINS its inbound
rings between dispatch and collect — it never waits for another shard
to answer. One awaited inter-shard round trip inside a tick-path
function re-serializes the cluster: every shard's tick then runs at
the speed of its slowest peer plus a control-channel RTT, which is
exactly the TileLoom anti-pattern (collective in FRONT of compute
instead of behind it) this PR exists to avoid.

Two scopes:

* ``cluster/bus.py`` — the bus is the tick's data plane and must stay
  fully synchronous: ANY ``await``/``async def`` there is a violation
  (ring reads/writes are lock-free shared-memory operations; an async
  bus invites hidden waits).
* tick-path functions of ``engine/ticker.py`` and
  ``cluster/shard.py`` (flush/collect/drain/enqueue/deliver family):
  ``await`` of a call whose name smells like a remote round trip —
  ``recv``/``request``/``rpc``/``sock_recv``/``ctl``/``control``/
  ``round_trip`` in the dotted chain — fails lint. Control traffic
  belongs in the supervised control loop, off the tick path.

Suppress a deliberate case with ``# wql: allow(blocking-cross-shard)``.
"""

from __future__ import annotations

import ast
from typing import Iterator

from .core import FileContext, Rule, Violation, dotted_name, enclosing_functions

_BUS_SCOPED = ("cluster/bus.py",)
_TICK_SCOPED = ("engine/ticker.py", "cluster/shard.py")

#: function names forming the tick path in the scoped modules
_TICK_PATH = frozenset((
    "flush", "drain", "enqueue", "_dispatch_batch",
    "deliver_batch", "_deliver_batch_planed", "_deliver_batch_local",
    "send_frame", "try_write", "try_write_many",
))

#: dotted-chain tokens that mark an awaited call as a remote round trip
_ROUND_TRIP_TOKENS = (
    "recv", "request", "rpc", "sock_recv", "ctl", "control",
    "round_trip",
)


def _smells_remote(name: str | None) -> bool:
    if name is None:
        return False
    parts = name.lower().split(".")
    return any(
        tok in part for part in parts for tok in _ROUND_TRIP_TOKENS
    )


def _check_blocking_cross_shard(ctx: FileContext) -> Iterator[Violation]:
    bus_scope = ctx.relpath.endswith(_BUS_SCOPED)
    tick_scope = ctx.relpath.endswith(_TICK_SCOPED)
    if bus_scope:
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.Await, ast.AsyncFunctionDef,
                                 ast.AsyncFor, ast.AsyncWith)):
                yield from ctx.flag(
                    BLOCKING_CROSS_SHARD, node,
                    "await/async in the inter-shard bus — the tick's "
                    "data plane is synchronous shared-memory ring "
                    "work; waits belong to the control loop, never "
                    "the bus",
                )
        return
    if not tick_scope:
        return
    for func, _stack in enclosing_functions(ctx.tree):
        if func.name not in _TICK_PATH:
            continue
        for node in ast.walk(func):
            if not isinstance(node, ast.Await):
                continue
            call = node.value
            name = (
                dotted_name(call.func)
                if isinstance(call, ast.Call) else dotted_name(call)
            )
            if _smells_remote(name):
                yield from ctx.flag(
                    BLOCKING_CROSS_SHARD, node,
                    f"`await {name}(...)` inside tick-path "
                    f"`{func.name}` — an inter-shard round trip here "
                    "serializes every shard's tick behind its slowest "
                    "peer; cross-shard work must be enqueue-and-drain "
                    "(ring try_write + the cluster.drain leg)",
                )


BLOCKING_CROSS_SHARD = Rule(
    "blocking-cross-shard",
    "tick-path code must never await an inter-shard round trip; the "
    "bus stays synchronous — cross-shard work is enqueue-and-drain",
    _check_blocking_cross_shard,
)


# ---------------------------------------------------------------------
# untraced-forward (rule 20, ISSUE 15): cross-process hops carry the
# trace context
# ---------------------------------------------------------------------
#
# The cluster frame clock only works if EVERY hop threads the context:
# the router's forward stamps it as a framed prefix, and the bus's
# ring writes carry it in the frame header. One forwarding site that
# drops it silently punches a hole in cluster.e2e_ms and the
# router→home→remote trace chain — the frame still arrives, so
# nothing functional fails, which is exactly why a lint rule (not a
# test) has to guard it. Two scopes:
#
# * ``cluster/router.py`` — message-forwarding call sites (the
#   ``_forward`` helper and any ``send`` on a shard push socket) must
#   reference a trace-context argument (``ctx``/``trace``/``wrap``
#   in the argument expressions).
# * ``cluster/bus.py`` — ring ``try_write`` calls must thread the
#   context into the frame the same way.
#
# Deliberate context-free sends (the router's client-bound refusal
# hint) carry ``# wql: allow(untraced-forward)``.

_FORWARD_SCOPED = ("cluster/router.py",)
_RING_SCOPED = ("cluster/bus.py",)

#: identifier fragments that mark an argument as carrying the context
_CTX_TOKENS = ("ctx", "trace", "wrap")


def _mentions_ctx(call: ast.Call) -> bool:
    for sub in list(call.args) + [kw.value for kw in call.keywords]:
        for node in ast.walk(sub):
            name = None
            if isinstance(node, ast.Name):
                name = node.id
            elif isinstance(node, ast.Attribute):
                name = node.attr
            elif isinstance(node, ast.arg):
                name = node.arg
            if name is not None and any(
                tok in name.lower() for tok in _CTX_TOKENS
            ):
                return True
    return False


def _chain_mentions(node: ast.AST, token: str) -> bool:
    """True when any Name/Attribute in the (possibly subscripted)
    receiver chain contains ``token`` — ``self._push[shard].send``
    has no plain dotted name, but its chain mentions "push"."""
    for sub in ast.walk(node):
        name = (
            sub.id if isinstance(sub, ast.Name)
            else sub.attr if isinstance(sub, ast.Attribute) else None
        )
        if name is not None and token in name.lower():
            return True
    return False


def _check_untraced_forward(ctx: FileContext) -> Iterator[Violation]:
    router_scope = ctx.relpath.endswith(_FORWARD_SCOPED)
    ring_scope = ctx.relpath.endswith(_RING_SCOPED)
    if not (router_scope or ring_scope):
        return
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        leaf = (
            func.attr if isinstance(func, ast.Attribute)
            else func.id if isinstance(func, ast.Name) else None
        )
        if leaf is None:
            continue
        if router_scope:
            is_forward = leaf == "_forward"
            is_push_send = (
                leaf == "send"
                and isinstance(func, ast.Attribute)
                and _chain_mentions(func.value, "push")
            )
            if (is_forward or is_push_send) and not _mentions_ctx(node):
                yield from ctx.flag(
                    UNTRACED_FORWARD, node,
                    f"`{leaf}(...)` forwards a message to a shard "
                    "without threading the trace context — the frame "
                    "clock (cluster.e2e_ms) and the router→home→remote "
                    "trace chain silently lose this hop; pass the "
                    "(trace_id, t_ingress) ctx / tracectx.wrap the "
                    "payload",
                )
        if ring_scope and leaf == "try_write" and not _mentions_ctx(node):
            yield from ctx.flag(
                UNTRACED_FORWARD, node,
                "ring `try_write(...)` in the inter-shard bus without "
                "the trace context in the frame header — the remote "
                "shard can no longer close the router-ingress clock "
                "or stitch this frame; pack the ctx into the frame",
            )


UNTRACED_FORWARD = Rule(
    "untraced-forward",
    "router forwards and inter-shard ring writes must thread the "
    "cluster trace context — an untraced hop silently punches a hole "
    "in cluster.e2e_ms and the cross-process trace chain",
    _check_untraced_forward,
)

RULES = [BLOCKING_CROSS_SHARD, UNTRACED_FORWARD]
