"""Ingest-path hazard rules (unbounded growth, per-entity Python).

The overload plane (ISSUE 10) exists because one unbounded ``append``
on an ingest path is a memory-exhaustion vector under hostile offered
load: the tick queue, the entity pending buffer, and any transport-
side backlog all grow at wire speed while the event loop drains at
device speed. Every growth site on an ingest path must therefore sit
behind an admission decision (the ``OverloadGovernor``: a queue cap
with drop-oldest, a coalescing dict keyed by a bounded id space, a
token bucket) — or carry an auditable
``# wql: allow(unbounded-ingest)`` pragma explaining why it is
bounded some other way.

Scope: the modules that receive wire traffic (``engine/ticker.py``,
``engine/router.py``, ``entities/plane.py``, ``entities/ingest.py``,
``transports/zeromq.py``, ``transports/websocket.py``), and within
them only the ingest-path
functions (message arrival → enqueue). A function is exempt when it
visibly consults the admission plane — any reference whose dotted
path mentions the governor or one of its admission calls — because
the growth it performs is then governed by construction.
"""

from __future__ import annotations

import ast
from typing import Iterator

from .core import FileContext, Rule, Violation, dotted_name, walk_shallow

#: modules that take wire traffic (relpath suffixes)
_SCOPED = (
    "engine/ticker.py",
    "engine/router.py",
    "entities/plane.py",
    "entities/ingest.py",
    "transports/zeromq.py",
    "transports/websocket.py",
)

#: the ingest-path functions inside them (arrival → enqueue)
_INGEST_FUNCS = {
    "enqueue",
    "ingest",
    "hold",
    "handle_message",
    "_dispatch",
    "_entity_ingest",
    "_local_message",
    "_global_message",
    "_stage_update",
    "_recv_loop",
    "_process_inbound",
    "_decode_route",
    "_handle_connection",
    "_next_message",
}

#: container-growth calls that are unbounded unless admitted
_GROW_METHODS = {"append", "appendleft", "extend", "extendleft"}

#: names whose presence marks the function as admission-governed
_ADMIT_NAMES = {
    "admit",
    "local_queue_cap",
    "note_queue_depth",
    "note_drop_oldest",
    "coalesce_entities",
}


def _mentions_admission(func: ast.AST) -> bool:
    for node in ast.walk(func):
        if isinstance(node, ast.Attribute):
            if "governor" in node.attr or node.attr in _ADMIT_NAMES:
                return True
        elif isinstance(node, ast.Name):
            if "governor" in node.id or node.id in _ADMIT_NAMES:
                return True
    return False


def _check_unbounded_ingest(ctx: FileContext) -> Iterator[Violation]:
    if not ctx.relpath.endswith(_SCOPED):
        return
    funcs = [
        node for node in ast.walk(ctx.tree)
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
        and node.name in _INGEST_FUNCS
    ]
    for func in funcs:
        if _mentions_admission(func):
            continue
        for node in walk_shallow(func.body):
            if not (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _GROW_METHODS
            ):
                continue
            target = dotted_name(node.func.value) or "<container>"
            yield from ctx.flag(
                UNBOUNDED_INGEST,
                node,
                f"unbounded {target}.{node.func.attr}(...) on the "
                f"ingest path ({func.name}) with no admission "
                "decision — hostile offered load grows it at wire "
                "speed while the loop drains at device speed; gate "
                "it behind the overload governor (admit/"
                "local_queue_cap drop-oldest/coalesce) or justify "
                "the bound with # wql: allow(unbounded-ingest)",
            )


UNBOUNDED_INGEST = Rule(
    "unbounded-ingest",
    "ingest-path container growth without an admission decision "
    "(router/transport/entity arrival paths)",
    _check_unbounded_ingest,
)


# --------------------------------------------------------------------
# per-entity-python-ingest (ISSUE 11): the columnar wire→SoA path
# exists so entity-update ingest costs zero per-entity Python — one
# re-introduced `for ent in message.entities` loop puts the router back
# at ~1.3K updates/s against the 100K+ columnar budget. Any
# per-element iteration over an `.entities` list inside an ingest-path
# function must either BE the designated object-path fallback
# (pragma'd) or move to EntityPlane.ingest_columns.

#: modules on the entity ingest path (relpath suffixes)
_ENTITY_SCOPED = (
    "engine/router.py",
    "entities/plane.py",
    "entities/ingest.py",
    "transports/zeromq.py",
    "transports/websocket.py",
)

#: ingest-path functions (message arrival → staged columns)
_ENTITY_INGEST_FUNCS = _INGEST_FUNCS | {
    "ingest_columns",
    "process_batch",
    "_flush_run",
    "_admit",
    "_route_data",
    "_wire_slow_row",
}


def _iterates_entities(node: ast.AST) -> bool:
    """The iterable expression mentions an ``.entities`` attribute
    (covers ``message.entities``, ``enumerate(m.entities)``,
    ``zip(…, msg.entities)``, slices thereof)."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute) and sub.attr == "entities":
            return True
    return False


def _check_per_entity_ingest(ctx: FileContext) -> Iterator[Violation]:
    if not ctx.relpath.endswith(_ENTITY_SCOPED):
        return
    funcs = [
        node for node in ast.walk(ctx.tree)
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
        and node.name in _ENTITY_INGEST_FUNCS
    ]
    for func in funcs:
        for node in walk_shallow(func.body):
            iters = []
            if isinstance(node, (ast.For, ast.AsyncFor)):
                iters.append(node.iter)
            elif isinstance(node, (ast.ListComp, ast.SetComp,
                                   ast.DictComp, ast.GeneratorExp)):
                iters.extend(gen.iter for gen in node.generators)
            if not any(_iterates_entities(it) for it in iters):
                continue
            yield from ctx.flag(
                PER_ENTITY_PYTHON_INGEST,
                node,
                f"per-element Python iteration over an entities list "
                f"on the ingest path ({func.name}) — this is the "
                "~1.3K-updates/s regime the columnar wire→SoA path "
                "(EntityPlane.ingest_columns + wql_decode_entities) "
                "replaced; stage through the columns, or justify the "
                "object path with "
                "# wql: allow(per-entity-python-ingest)",
            )


PER_ENTITY_PYTHON_INGEST = Rule(
    "per-entity-python-ingest",
    "per-element Python loop over message entities in an ingest-path "
    "function (router/transport/entity arrival paths)",
    _check_per_entity_ingest,
)

# --------------------------------------------------------------------
# unguarded-handshake (ISSUE 12): handshakes are an admission class.
# A reconnect storm is the retry-storm/metastable-failure regime — the
# handshake path allocates per-peer state (connect-back sockets, map
# entries, session records, delivery shard slots) at wire speed, so
# any container growth or peer registration on it must sit behind the
# governor's handshake admission (``admit_handshake``: new connects
# shed before resumes, REJECT admits resumes via a token bucket) or
# carry an auditable ``# wql: allow(unguarded-handshake)`` pragma.

#: the transport handshake entry points (relpath suffixes → functions)
_HANDSHAKE_SCOPED = (
    "transports/zeromq.py",
    "transports/websocket.py",
)

_HANDSHAKE_FUNCS = {
    "_handle_handshake",
    "_handle_connection",
}

#: peer-registration calls: each allocates per-peer server state
_REGISTER_CALLS = {"insert", "rebind", "adopt", "mint"}

#: names whose presence marks the handshake path admission-guarded
_HS_ADMIT_NAMES = {"admit_handshake", "take_refusal_hint"}


def _mentions_handshake_admission(func: ast.AST) -> bool:
    for node in ast.walk(func):
        if isinstance(node, ast.Attribute):
            if "governor" in node.attr or node.attr in _HS_ADMIT_NAMES:
                return True
        elif isinstance(node, ast.Name):
            if "governor" in node.id or node.id in _HS_ADMIT_NAMES:
                return True
    return False


def _check_unguarded_handshake(ctx: FileContext) -> Iterator[Violation]:
    if not ctx.relpath.endswith(_HANDSHAKE_SCOPED):
        return
    funcs = [
        node for node in ast.walk(ctx.tree)
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
        and node.name in _HANDSHAKE_FUNCS
    ]
    for func in funcs:
        if _mentions_handshake_admission(func):
            continue
        for node in walk_shallow(func.body):
            what = None
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in (_GROW_METHODS | _REGISTER_CALLS)
            ):
                target = dotted_name(node.func.value) or "<object>"
                what = f"{target}.{node.func.attr}(...)"
            elif isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Subscript) for t in node.targets
            ):
                sub = next(
                    t for t in node.targets if isinstance(t, ast.Subscript)
                )
                target = dotted_name(sub.value) or "<container>"
                what = f"{target}[...] = …"
            if what is None:
                continue
            yield from ctx.flag(
                UNGUARDED_HANDSHAKE,
                node,
                f"handshake-path state growth {what} ({func.name}) "
                "with no admission reference — a reconnect storm "
                "allocates per-peer state at wire speed; gate the "
                "path behind governor.admit_handshake (new sheds "
                "before resume, REJECT admits resumes via token "
                "bucket) or justify with "
                "# wql: allow(unguarded-handshake)",
            )


UNGUARDED_HANDSHAKE = Rule(
    "unguarded-handshake",
    "handshake-path container growth or peer registration without a "
    "governor/admission reference (transport handshake entry points)",
    _check_unguarded_handshake,
)

RULES = [UNBOUNDED_INGEST, PER_ENTITY_PYTHON_INGEST, UNGUARDED_HANDSHAKE]
