"""JAX/TPU hazard rules for the tick path.

ASH (arXiv:2110.00511) and TPU-KNN (arXiv:2206.14286) both make the
same point about accelerator spatial indexes: the kernel is never the
bottleneck — silent host syncs and recompilation storms are. These
rules enforce that mechanically for this repo's hot modules:

* ``spatial/tpu_backend.py`` and ``parallel/sharded_backend.py`` — the
  per-tick dispatch/collect pipeline. Host syncs are legal only at the
  designated collect points, which carry ``# wql: allow(jax-host-sync)``
  pragmas so every device→host transfer on the tick path is auditable.
* ``ops/*`` — pure device kernels; a host sync anywhere is a bug.
"""

from __future__ import annotations

import ast
import re
from typing import Iterator

from .core import FileContext, Rule, Violation, dotted_name, walk_shallow

#: modules whose hot-path FUNCTIONS are checked for host syncs
_TICK_MODULES = ("spatial/tpu_backend.py", "parallel/sharded_backend.py")

#: the per-tick dispatch/collect pipeline — the functions a LocalMessage
#: batch flows through between the event loop and the device
_HOT_FUNCTIONS = {
    "dispatch_local_batch",
    "collect_local_batch",
    "match_local_batch",
    "match_arrays",
    "match_arrays_async",
    "_launch",
    "_dispatch",
    "_dispatch_csr",
    "_csr_effective_cap",
    "_prepare_queries",
    "_decode_csr",
    "_compact_fetch",
    "_decode_packed",
    "_dispatch_pack",
}

_SYNC_CALLS = {
    "np.asarray", "np.array", "numpy.asarray", "numpy.array",
    "jax.device_get",
}
_SYNC_METHODS = {"item", "tolist", "block_until_ready"}


def _is_tick_module(relpath: str) -> bool:
    return relpath.endswith(_TICK_MODULES)


def _is_ops_module(relpath: str) -> bool:
    return "/ops/" in relpath or relpath.startswith("ops/")


def _host_sync_reason(call: ast.Call) -> str | None:
    name = dotted_name(call.func)
    if name in _SYNC_CALLS:
        return f"`{name}(...)`"
    if (
        isinstance(call.func, ast.Attribute)
        and call.func.attr in _SYNC_METHODS
        and not call.args
        and not call.keywords
    ):
        return f"`.{call.func.attr}()`"
    if (
        isinstance(call.func, ast.Name)
        and call.func.id in ("int", "float", "bool")
        and len(call.args) == 1
        and not call.keywords
        and isinstance(call.args[0], ast.Name)
    ):
        return f"`{call.func.id}({call.args[0].id})`"
    return None


def _check_host_sync(ctx: FileContext) -> Iterator[Violation]:
    ops = _is_ops_module(ctx.relpath)
    if not ops and not _is_tick_module(ctx.relpath):
        return
    if ops:
        scopes: list[ast.AST] = [ctx.tree]
    else:
        scopes = [
            node for node in ast.walk(ctx.tree)
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
            and node.name in _HOT_FUNCTIONS
        ]
    seen: set[ast.AST] = set()
    for scope in scopes:
        for node in ast.walk(scope):
            if not isinstance(node, ast.Call) or node in seen:
                continue
            seen.add(node)
            reason = _host_sync_reason(node)
            if reason is not None:
                where = (
                    "a device kernel module" if ops
                    else f"tick-path function `{getattr(scope, 'name', '?')}`"
                )
                yield from ctx.flag(
                    HOST_SYNC,
                    node,
                    f"{reason} in {where} forces an implicit device→host "
                    "sync, serializing the dispatch pipeline; keep the "
                    "value on device, or mark the designated collect "
                    "point with `# wql: allow(jax-host-sync)`",
                )


#: host-fetch calls the full-fetch rule inspects (a subset of the
#: host-sync set: the ones that materialize a WHOLE array)
_FETCH_CALLS = {
    "np.asarray", "np.array", "numpy.asarray", "numpy.array",
    "jax.device_get",
}

#: identifiers that name cap-padded tick-path arrays in these modules
#: (the CSR flat result, dense [M, K] target tables) — fetching one
#: ships O(capacity) bytes, the exact regression ISSUE 3 removed
#: (BENCH_r05: fetch_ms.flat ≈ 956 ms of a ~1051 ms tick). The match
#: is heuristic by name, on either the fetched expression or the
#: assignment target; the unit repros in tests/test_check_rules.py are
#: the executable definition.
_FAT_NAMES = {"flat", "tgt", "targets", "dense", "flat_np", "result"}


def _check_full_fetch(ctx: FileContext) -> Iterator[Violation]:
    """Flag ``np.asarray(...)``/``jax.device_get(...)`` of a cap-padded
    device array in tick-path hot functions. Legal only at the
    designated overflow/fallback sites, which carry
    ``# wql: allow(full-fetch-on-tick)`` — keeping every O(capacity)
    device→host transfer on the tick path auditable (the compacted
    collect path ships O(actual fan-out) instead)."""
    if not _is_tick_module(ctx.relpath):
        return
    scopes = [
        node for node in ast.walk(ctx.tree)
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
        and node.name in _HOT_FUNCTIONS
    ]
    for scope in scopes:
        # `tgt = np.asarray(payload[1])[:m]` is a full fetch even
        # though the argument names nothing fat — assignment targets
        # give fetch calls their destination name
        assigned: dict[int, set[str]] = {}
        for node in ast.walk(scope):
            if isinstance(node, ast.Assign):
                names = {
                    t.id for t in node.targets if isinstance(t, ast.Name)
                }
                for sub in ast.walk(node.value):
                    if isinstance(sub, ast.Call):
                        assigned[id(sub)] = names
        for node in ast.walk(scope):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            if dotted_name(node.func) not in _FETCH_CALLS:
                continue
            arg_ids = set(assigned.get(id(node), set()))
            for sub in ast.walk(node.args[0]):
                if isinstance(sub, ast.Name):
                    arg_ids.add(sub.id)
                elif isinstance(sub, ast.Attribute):
                    arg_ids.add(sub.attr)
            hot = sorted(
                {name.lstrip("_") for name in arg_ids} & _FAT_NAMES
            )
            if hot:
                yield from ctx.flag(
                    FULL_FETCH,
                    node,
                    f"fetch of cap-padded device array ({', '.join(hot)}) "
                    "in a tick-path function ships O(capacity) bytes "
                    "D2H; pack it on device first (_compact_fetch) or "
                    "mark the deliberate overflow/fallback site with "
                    "`# wql: allow(full-fetch-on-tick)`",
                )


#: dispatch-path functions of spatial/*.py — between a tick's flush and
#: the device launch; per-element Python iteration over the query batch
#: here is the O(m) host-encode wall the staged columnar path exists to
#: kill (ISSUE 8 / BENCH_r05: dispatch p99 10 ms of a 14.5 ms engine
#: p99 was this loop)
_DISPATCH_FUNCS = {
    "dispatch_local_batch",
    "dispatch_staged_batch",
    "match_local_batch",
    "_dispatch_encoded",
    "_prepare_queries",
    # query-library dispatch leg (queries/expand.py + the backend's
    # kind branch): the mixed-kind expansion must stay vectorized —
    # a per-row loop here is the same host-encode wall. The FOLD side
    # (fold_collected) is collect-path per-result assembly, like the
    # radius path's list building, and deliberately not in this set.
    "expand_staged",
    "_dispatch_kind_batch",
}
#: parameter names that carry the per-tick query batch (`kinds` and
#: `params` are the staged kind/parameter COLUMNS — same cardinality,
#: same wall if iterated per element)
_QUERY_PARAMS = {"queries", "kinds", "params"}
#: call wrappers whose argument is still iterated per element
_ITER_WRAPPERS = {"enumerate", "zip", "reversed", "map", "iter"}


def _iterated_names(iter_node: ast.AST) -> set[str]:
    names: set[str] = set()
    if isinstance(iter_node, ast.Name):
        names.add(iter_node.id)
    elif (
        isinstance(iter_node, ast.Call)
        and dotted_name(iter_node.func) in _ITER_WRAPPERS
    ):
        for arg in iter_node.args:
            if isinstance(arg, ast.Name):
                names.add(arg.id)
    return names


def _check_per_query_loop(ctx: FileContext) -> Iterator[Violation]:
    """Flag per-element Python iteration over the query batch inside
    dispatch-path functions of ``spatial/*.py``: ``for q in queries``
    loops, comprehensions, and ``np.fromiter`` over per-object
    generator expressions. The CPU-backend reference path and the
    legacy object-list encode are the designated exceptions — they
    carry ``# wql: allow(per-query-python-loop)`` pragmas so every
    per-query loop on the dispatch path stays auditable."""
    if "spatial/" not in ctx.relpath and "queries/" not in ctx.relpath:
        return
    scopes = [
        node for node in ast.walk(ctx.tree)
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
        and node.name in _DISPATCH_FUNCS
    ]
    for scope in scopes:
        args = scope.args
        params = {
            a.arg
            for a in (*args.posonlyargs, *args.args, *args.kwonlyargs)
        } & _QUERY_PARAMS
        if not params:
            continue
        for node in ast.walk(scope):
            if isinstance(node, (ast.For, ast.AsyncFor)):
                hot = sorted(_iterated_names(node.iter) & params)
                if hot:
                    yield from ctx.flag(
                        PER_QUERY_LOOP,
                        node,
                        f"Python loop over query batch ({', '.join(hot)}) "
                        "in a dispatch-path function — O(m) host work "
                        "before the kernel launches; stage the batch as "
                        "columnar arrays at enqueue time "
                        "(engine/staging.py + dispatch_staged_batch), or "
                        "mark the designated CPU/fallback path with "
                        "`# wql: allow(per-query-python-loop)`",
                    )
            elif isinstance(
                node, (ast.ListComp, ast.SetComp, ast.GeneratorExp,
                       ast.DictComp)
            ):
                hot = sorted({
                    name
                    for gen in node.generators
                    for name in _iterated_names(gen.iter)
                } & params)
                if hot:
                    yield from ctx.flag(
                        PER_QUERY_LOOP,
                        node,
                        "per-object comprehension/generator over query "
                        f"batch ({', '.join(hot)}) in a dispatch-path "
                        "function (np.fromiter over a generator is still "
                        "a per-element Python loop); use the staged "
                        "columnar path, or mark the designated "
                        "CPU/fallback site with "
                        "`# wql: allow(per-query-python-loop)`",
                    )


#: wire-parameter shape of the query library: ``query.<name>`` requests
#: and ``query.<name>.result`` replies. A literal of this shape that
#: names no REGISTERED kind is a typo the router will silently route as
#: a plain radius match (parse_query_message returns None on unknown
#: parameters by design) — the query "works" and returns the wrong
#: geometry, which no exception will ever surface.
_QUERY_WIRE_RE = re.compile(r"query\.[a-z_.]+\Z")

_KNOWN_WIRES: set[str] | None = None
_KNOWN_WIRES_LOADED = False


def _known_query_wires() -> set[str] | None:
    """Registered wire names + their ``.result`` reply parameters,
    straight from the registry so the lint can never drift from the
    code. None (rule inert) when the package can't import — the lint
    must stay runnable from a checkout with a broken tree."""
    global _KNOWN_WIRES, _KNOWN_WIRES_LOADED
    if not _KNOWN_WIRES_LOADED:
        _KNOWN_WIRES_LOADED = True
        try:
            from worldql_server_tpu.queries.kinds import wire_names
        except Exception:
            _KNOWN_WIRES = None
        else:
            names = set(wire_names())
            _KNOWN_WIRES = names | {f"{n}.result" for n in names}
    return _KNOWN_WIRES


def _check_unregistered_kind(ctx: FileContext) -> Iterator[Violation]:
    hits = [
        node for node in ast.walk(ctx.tree)
        if isinstance(node, ast.Constant)
        and isinstance(node.value, str)
        and _QUERY_WIRE_RE.fullmatch(node.value)
    ]
    if not hits:
        return
    known = _known_query_wires()
    if known is None:
        return
    for node in hits:
        if node.value not in known:
            yield from ctx.flag(
                UNREGISTERED_KIND,
                node,
                f'"{node.value}" matches the query-library wire shape '
                "but names no registered kind — the router would parse "
                "it as a PLAIN RADIUS query and silently return the "
                "wrong geometry; register the kind in "
                "worldql_server_tpu/queries/kinds.py, fix the typo, or "
                "mark a deliberate negative-test literal with "
                "`# wql: allow(unregistered-query-kind)`",
            )


#: sim-tick hot functions of the entity plane (entities/plane.py): the
#: device dispatch/collect pair a simulation tick flows through —
#: including the delta-tick sub-dispatch legs. Frame assembly and
#: index churn (`apply`, `_build_frames`) are host delivery/index
#: work — O(fan-out)/O(churn) like the router — and deliberately NOT
#: in this set.
_SIM_TICK_FUNCS = {
    "dispatch_tick", "collect_tick",
    "_dispatch_tick_full", "_dispatch_tick_delta", "_predict_cubes",
}


def _is_entities_module(relpath: str) -> bool:
    return "/entities/" in relpath or relpath.startswith("entities/")


def _is_sim_ops_module(relpath: str) -> bool:
    return relpath.endswith("ops/tick.py")


def _is_bounded_iter(node: ast.AST) -> bool:
    """Iterables that cannot scale with the entity population: range()
    (static shift/window counts) and tuple/list/set literals (a fixed
    handful of arrays, e.g. a prefetch over three result buffers)."""
    if isinstance(node, (ast.Tuple, ast.List, ast.Set, ast.Constant)):
        return True
    if isinstance(node, ast.Call) and dotted_name(node.func) == "range":
        return True
    if (
        isinstance(node, ast.Call)
        and dotted_name(node.func) in _ITER_WRAPPERS
    ):
        return all(_is_bounded_iter(a) for a in node.args)
    return False


def _check_sim_tick(ctx: FileContext) -> Iterator[Violation]:
    """The entity-sim analog of jax-host-sync + per-query-python-loop:
    inside sim-tick hot functions (``dispatch_tick``/``collect_tick``
    in ``entities/`` and every function of ``ops/tick.py``), flag
    (a) implicit device→host syncs — legal only at the designated
    collect points, pragma'd ``# wql: allow(host-sync-in-sim-tick)`` —
    and (b) Python loops/comprehensions over anything that scales with
    the entity population (``range()`` windows and literal-tuple
    iterations are the bounded exceptions). One stray ``.item()`` or
    per-entity loop turns the one-kernel tick into an O(N) host crawl."""
    ops = _is_sim_ops_module(ctx.relpath)
    if not ops and not _is_entities_module(ctx.relpath):
        return
    if ops:
        scopes = [
            node for node in ast.walk(ctx.tree)
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]
    else:
        scopes = [
            node for node in ast.walk(ctx.tree)
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
            and node.name in _SIM_TICK_FUNCS
        ]
    for scope in scopes:
        for node in ast.walk(scope):
            if isinstance(node, ast.Call):
                reason = _host_sync_reason(node)
                if reason is not None:
                    yield from ctx.flag(
                        SIM_TICK_HAZARD,
                        node,
                        f"{reason} in sim-tick function "
                        f"`{scope.name}` forces an implicit "
                        "device→host sync mid-tick; keep the value on "
                        "device, or mark the designated collect point "
                        "with `# wql: allow(host-sync-in-sim-tick)`",
                    )
            elif isinstance(node, (ast.For, ast.AsyncFor)):
                if not _is_bounded_iter(node.iter):
                    yield from ctx.flag(
                        SIM_TICK_HAZARD,
                        node,
                        "Python loop over a population-sized iterable "
                        f"in sim-tick function `{scope.name}` — the "
                        "tick must stay one fused kernel over the SoA "
                        "columns; vectorize, move the work to "
                        "apply()/frame assembly, or mark a deliberate "
                        "bounded loop with "
                        "`# wql: allow(host-sync-in-sim-tick)`",
                    )
            elif isinstance(
                node, (ast.ListComp, ast.SetComp, ast.GeneratorExp,
                       ast.DictComp)
            ):
                if any(
                    not _is_bounded_iter(gen.iter)
                    for gen in node.generators
                ):
                    yield from ctx.flag(
                        SIM_TICK_HAZARD,
                        node,
                        "per-element comprehension/generator over a "
                        "population-sized iterable in sim-tick "
                        f"function `{scope.name}` — still a Python "
                        "loop; vectorize over the SoA columns or mark "
                        "a deliberate bounded site with "
                        "`# wql: allow(host-sync-in-sim-tick)`",
                    )


#: modules with BOTH a full-rebuild path and a delta path (ROADMAP 2);
#: tick-path calls into the full path must be designated fallbacks
_DELTA_MODULES = (
    "spatial/tpu_backend.py", "parallel/sharded_backend.py",
    "entities/plane.py",
)
#: the per-tick functions a flush/dispatch flows through in those
#: modules — where a stray full rebuild costs O(N) device work every
#: tick instead of the delta path's O(churn)
_DELTA_TICK_FUNCS = {
    "flush", "_sync_delta", "_dispatch_encoded",
    "dispatch_staged_batch", "dispatch_local_batch", "_dispatch_delta",
    "dispatch_tick",
}
#: full-hash-rebuild entry points: whole-segment device sorts/uploads
#: and the full-tier sim kernel leg — each has an O(churn) delta
#: sibling (tombstone scatter, chunk append, dirty-closure sub-tick)
_REBUILD_ENTRY_POINTS = {
    "_sort_delta", "_sort_segment_dev", "_device_compact",
    "_upload_stale_base", "_upload_base", "_rebuild_base_with",
    "_compact_sync", "_dispatch_tick_full", "_upload_state",
}


def _check_full_rebuild(ctx: FileContext) -> Iterator[Violation]:
    """Flag calls to a full-hash-rebuild entry point from tick-path
    functions of the delta-capable modules. A delta path exists for
    each (spatial/delta_ticks.py; the entity plane's dirty-closure
    sub-tick), so every remaining full rebuild on the tick path must
    be a DESIGNATED fallback site carrying
    ``# wql: allow(full-rebuild-on-tick)`` — keeping the O(N)-work
    escape hatches auditable exactly like the host-sync and
    full-fetch rules keep theirs."""
    if not ctx.relpath.endswith(_DELTA_MODULES):
        return
    scopes = [
        node for node in ast.walk(ctx.tree)
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
        and node.name in _DELTA_TICK_FUNCS
    ]
    for scope in scopes:
        for node in ast.walk(scope):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            attr = name.rsplit(".", 1)[-1] if name else None
            if attr in _REBUILD_ENTRY_POINTS:
                yield from ctx.flag(
                    FULL_REBUILD,
                    node,
                    f"call to full-hash-rebuild entry point `{attr}` "
                    f"in tick-path function `{scope.name}` — a delta "
                    "path exists (O(churn) scatter/sub-tick); route "
                    "the update incrementally, or mark the designated "
                    "fallback site with "
                    "`# wql: allow(full-rebuild-on-tick)`",
                )


def _is_jax_jit_ref(node: ast.AST) -> bool:
    return dotted_name(node) in ("jax.jit", "jit")


def _is_jit_call(call: ast.Call) -> bool:
    if _is_jax_jit_ref(call.func):
        return True
    # functools.partial(jax.jit, ...)
    return (
        dotted_name(call.func) in ("partial", "functools.partial")
        and bool(call.args)
        and _is_jax_jit_ref(call.args[0])
    )


def _check_jit_in_loop(ctx: FileContext) -> Iterator[Violation]:
    def visit(node: ast.AST, loop_depth: int) -> Iterator[Violation]:
        in_loop = loop_depth > 0
        if in_loop and isinstance(node, ast.Call) and _is_jit_call(node):
            yield from ctx.flag(
                JIT_IN_LOOP,
                node,
                "jax.jit called inside a loop — each iteration builds a "
                "fresh jitted callable with an empty compile cache (a "
                "retrace/recompile storm); hoist the jit out of the loop "
                "or cache the kernel by its static config, as the "
                "backends' `_kernels` dicts do",
            )
        if in_loop and isinstance(
            node, (ast.FunctionDef, ast.AsyncFunctionDef)
        ):
            for dec in node.decorator_list:
                if (
                    _is_jax_jit_ref(dec)
                    or (isinstance(dec, ast.Call) and _is_jit_call(dec))
                ):
                    yield from ctx.flag(
                        JIT_IN_LOOP,
                        dec,
                        "@jax.jit on a function defined inside a loop — "
                        "the closure (and its compile cache) is rebuilt "
                        "every iteration; define and jit it once outside",
                    )
        for child in ast.iter_child_nodes(node):
            yield from visit(
                child,
                loop_depth
                + isinstance(node, (ast.For, ast.AsyncFor, ast.While)),
            )

    yield from visit(ctx.tree, 0)


def _jit_static_names(dec: ast.AST) -> set[str] | None:
    """Static argnames if ``dec`` is a jit decorator, else None."""
    if _is_jax_jit_ref(dec):
        return set()
    if not isinstance(dec, ast.Call) or not _is_jit_call(dec):
        return None
    out: set[str] = set()
    for kw in dec.keywords:
        if kw.arg in ("static_argnames", "static_argnums"):
            value = kw.value
            elts = value.elts if isinstance(value, (ast.Tuple, ast.List)) else [value]
            for e in elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, str):
                    out.add(e.value)
    return out


def _check_traced_branch(ctx: FileContext) -> Iterator[Violation]:
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.FunctionDef):
            continue
        static: set[str] | None = None
        for dec in node.decorator_list:
            static = _jit_static_names(dec)
            if static is not None:
                break
        if static is None:
            continue
        args = node.args
        traced = {
            a.arg
            for a in (*args.posonlyargs, *args.args, *args.kwonlyargs)
        } - static
        if args.vararg is not None:
            traced.add(args.vararg.arg)
        for inner in walk_shallow(node.body):
            if not isinstance(inner, (ast.If, ast.While)):
                continue
            names = {
                n.id for n in ast.walk(inner.test) if isinstance(n, ast.Name)
            }
            hot = sorted(names & traced)
            if hot:
                yield from ctx.flag(
                    TRACED_BRANCH,
                    inner,
                    f"Python `{'if' if isinstance(inner, ast.If) else 'while'}` "
                    f"on traced argument(s) {', '.join(hot)} inside a "
                    "@jax.jit function — this raises TracerBoolConversionError "
                    "at trace time or silently bakes one branch into the "
                    "compiled kernel; use jnp.where/lax.cond, or move the "
                    "argument to static_argnames",
                )

    # jax.jit(fn) where fn's local def branches on a traced param is
    # covered at runtime by tracing itself; the decorator form is the
    # one that hides until the first odd-shaped tick.


HOST_SYNC = Rule(
    "jax-host-sync",
    "implicit device→host sync (np.asarray/.item()/int(x)) on the tick path",
    _check_host_sync,
)
JIT_IN_LOOP = Rule(
    "jax-jit-in-loop",
    "jax.jit built inside a loop — per-iteration recompile storm",
    _check_jit_in_loop,
)
TRACED_BRANCH = Rule(
    "jax-traced-branch",
    "Python if/while on a traced value inside a jitted function",
    _check_traced_branch,
)
FULL_FETCH = Rule(
    "full-fetch-on-tick",
    "D2H fetch of a cap-padded array on the tick path (O(capacity) "
    "bytes — use the on-device compaction, or pragma the fallback)",
    _check_full_fetch,
)
PER_QUERY_LOOP = Rule(
    "per-query-python-loop",
    "per-element Python iteration over the query batch in a "
    "dispatch-path function of spatial/*.py (the host-encode wall — "
    "stage columns at enqueue instead, or pragma the CPU/fallback path)",
    _check_per_query_loop,
)
SIM_TICK_HAZARD = Rule(
    "host-sync-in-sim-tick",
    "implicit host sync or per-entity Python loop in a sim-tick "
    "function (entities/ dispatch/collect, ops/tick.py — the tick "
    "must stay one fused kernel; pragma the designated collect points)",
    _check_sim_tick,
)
UNREGISTERED_KIND = Rule(
    "unregistered-query-kind",
    "query.<name> wire literal naming no registered kind — the router "
    "parses unknown parameters as plain radius queries, so a typo "
    "returns the wrong geometry without any error",
    _check_unregistered_kind,
)
FULL_REBUILD = Rule(
    "full-rebuild-on-tick",
    "full-hash-rebuild entry point called from a tick-path function "
    "where a delta path exists (O(N) device work per tick — use the "
    "incremental update, or pragma the designated fallback site)",
    _check_full_rebuild,
)

RULES = [HOST_SYNC, JIT_IN_LOOP, TRACED_BRANCH, FULL_FETCH,
         PER_QUERY_LOOP, UNREGISTERED_KIND, SIM_TICK_HAZARD,
         FULL_REBUILD]
