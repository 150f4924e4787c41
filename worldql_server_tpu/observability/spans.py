"""Lightweight span tracing: the causal substrate of the flight recorder.

The aggregate histograms in ``engine/metrics.py`` can say a tick was
slow; they can never say *why* — BENCH_r05's 207 s ``p99_ms_depth2``
outlier could only be explained structurally because no record of that
one tick survived. This module records per-tick, per-stage wall time
the way TPU-KNN (arXiv:2206.14286) accounts a device query pipeline:
every stage of every tick is a :class:`Span` inside a causally-linked
:class:`Trace`, cheap enough to leave on in production and (following
``utils/trace.py``'s one-branch-when-off discipline) near-free when
off — ``Tracer.begin``/``Tracer.span`` cost one attribute check and
return shared null singletons that swallow everything.

Thread-safety: the ticker's collect stage runs on a worker thread and
the WAL writer thread emits fsync spans, so ``Trace.add`` takes a small
lock and parent links ride a :mod:`contextvars` var (copied into
``asyncio.to_thread`` and ``create_task``, so spans opened on the
collect's worker thread or inside the delivery task still attach to
their tick's trace).

Two entry points:

* ``tracer.begin(name, **tags)`` — an explicit trace object for flows
  that cross task boundaries (the tick: dispatch on the loop, collect
  on a worker thread, delivery in a task of its own). The caller threads
  the ``Trace`` through and calls ``trace.span(...)`` / ``finish()``.
* ``tracer.span(name, **tags)`` — a context manager that attaches to
  the current trace if one is active, else records a single-span
  "loose" trace (per-message router handles, WAL fsyncs); finished
  root traces are handed to ``tracer.on_trace`` (the flight recorder).

Beside the trees, the tracer keeps what no ring can lose (ISSUE 24):
every span exit adds to a cumulative per-name total (``count``,
``wall_ms``), exported as the ``spans`` gauge. A span around an
``await`` gets its wall time there; the time it held the EVENT LOOP
is accounted by ``loop_time.LoopAccount`` (``tracer.loop``), which
span enter/exit tell who the innermost open span is. Beneath the wall
clock, a CPU clock (ISSUE 38): with ``tracer.cpu_clock`` set, a span of
an EXPLICIT trace (the tick) also reads its thread's CPU time, and
where that reading means something the totals say how much of the
span's wall its thread was on the CPU (``cpu_ms``) and how much it was
not (``off_cpu_ms``: the GIL, the kernel, other threads; a difference
of two clocks, so over few instances of a short span it can be a
little below 0). It means something for an instance that ran on a
thread of its own or entered and left inside ONE step of the event
loop (*clocked*: the account numbers its steps); a span around an
``await`` has other tasks' CPU inside it and adds to none of the
three. Loose traces and their children (a span a message) read no CPU
clock at all. While a
``jax.profiler`` capture runs (``tracer.annotate`` set by the
``ProfilerHook``) each span also opens a ``TraceAnnotation`` of its
name, so the program's spans sit on the profiler's host line, on the
profiler's clock.
"""

from __future__ import annotations

import contextvars
import threading
import time

#: (Trace, span id, span name) of the innermost open span, per context
_CURRENT: contextvars.ContextVar = contextvars.ContextVar(
    "wql_current_span", default=None
)


def current_cpu_clock():
    """The thread-CPU clock (ns) of the trace open in the calling
    context: the tracer's inside a span of an explicit trace, wherever
    the context rode to (``asyncio.to_thread``: a worker's bracket
    beneath ``tick.collect``); None outside any span, in a loose trace,
    or with the clock off."""
    cur = _CURRENT.get()
    return cur[0]._cpu_clock if cur is not None else None


class Span:
    """One completed (or open) stage: name + wall window + tags."""

    __slots__ = ("id", "parent", "name", "t0", "dur_ms", "cpu_ms", "tags",
                 "thread")

    def __init__(self, id, parent, name, t0, tags, thread):
        self.id = id
        self.parent = parent
        self.name = name
        self.t0 = t0           # perf_counter seconds
        self.dur_ms = 0.0
        #: its thread's CPU time, for a clocked instance (else None)
        self.cpu_ms = None
        self.tags = tags
        self.thread = thread

    def tag(self, **tags) -> None:
        """Late tags (values known only at stage end) — same surface
        as ``_NullSpan.tag`` so callers never branch on enablement."""
        self.tags.update(tags)

    def as_dict(self, perf_start: float) -> dict:
        out = {
            "id": self.id,
            "parent": self.parent,
            "name": self.name,
            "t0_ms": round((self.t0 - perf_start) * 1e3, 3),
            "dur_ms": round(self.dur_ms, 3),
            "tags": self.tags,
            "thread": self.thread,
        }
        if self.cpu_ms is not None:
            out["cpu_ms"] = round(self.cpu_ms, 3)
        return out


class Trace:
    """A finished-or-in-flight span tree (one tick, or one loose op)."""

    __slots__ = (
        "name", "tags", "wall_start", "mono_start_ns", "perf_start",
        "dur_ms", "spans", "_lock", "_next_id", "_on_finish", "_done",
        "_owner", "_clock", "_cpu_clock",
    )

    def __init__(self, name: str, on_finish=None, tracer=None, **tags):
        self.name = name
        self.tags = tags
        self.wall_start = time.time()
        # CLOCK_MONOTONIC, the clock of the frame stamps
        # (t_ingress_ns, the enqueue stamp) and of a load generator's
        # payload stamps: ring dumps lie on one axis with them
        self.mono_start_ns = time.monotonic_ns()
        self._clock = tracer.clock if tracer is not None else time.perf_counter
        #: the thread-CPU clock its spans read beside the wall clock:
        #: the tracer's for an explicit trace (``Tracer.begin`` sets
        #: it), None for a loose one
        self._cpu_clock = None
        self.perf_start = self._clock()
        self.dur_ms = 0.0
        self.spans: list[Span] = []
        self._lock = threading.Lock()
        self._next_id = 0
        self._on_finish = on_finish
        self._done = False
        #: the Tracer whose totals / loop account / annotation switch
        #: this trace's spans feed (None for a bare Trace in tests)
        self._owner = tracer

    def span(self, name: str, **tags) -> "_SpanCtx":
        """Open a child span in THIS trace (parented to the innermost
        open span of the calling context, or the trace root)."""
        return _SpanCtx(self, name, tags)

    def tag(self, **tags) -> None:
        self.tags.update(tags)

    def add(self, span: Span) -> None:
        with self._lock:
            self.spans.append(span)

    def _new_id(self) -> int:
        with self._lock:
            self._next_id += 1
            return self._next_id

    def finish(self) -> None:
        """Seal the trace (idempotent) and hand it to the sink."""
        if self._done:
            return
        self._done = True
        self.dur_ms = (self._clock() - self.perf_start) * 1e3
        if self._on_finish is not None:
            self._on_finish(self)

    def stage_ms(self) -> dict[str, float]:
        """Per-span-name wall-time totals — the breakdown a slow-tick
        dump leads with. Only TOP-LEVEL spans (parent is the trace
        root) are summed, so nested child spans don't double-count
        their parents' wall."""
        out: dict[str, float] = {}
        with self._lock:
            for s in self.spans:
                if s.parent is None:
                    out[s.name] = out.get(s.name, 0.0) + s.dur_ms
        return out

    def as_dict(self) -> dict:
        with self._lock:
            spans = [s.as_dict(self.perf_start) for s in self.spans]
        return {
            "name": self.name,
            "tags": self.tags,
            "start_unix_s": round(self.wall_start, 6),
            "start_mono_ns": self.mono_start_ns,
            "dur_ms": round(self.dur_ms, 3),
            "spans": spans,
        }


class _SpanCtx:
    """Context manager recording one span into a known trace; sets the
    parent-link context var for the duration so nested ``tracer.span``
    calls attach underneath."""

    __slots__ = ("_trace", "_name", "_tags", "_span", "_token", "_root",
                 "_annotation", "_step", "_cpu0")

    def __init__(self, trace: Trace, name: str, tags: dict, root=False):
        self._trace = trace
        self._name = name
        self._tags = tags
        self._span = None
        self._token = None
        self._root = root
        self._annotation = None
        # where it was entered (LoopAccount.span_entered) and, in a
        # trace that reads one, its thread's CPU clock there (0: none
        # was read: the span is not to be clocked)
        self._step = 0
        self._cpu0 = 0

    def __enter__(self):
        trace = self._trace
        name = self._name
        cur = _CURRENT.get()
        parent = cur[1] if cur is not None and cur[0] is trace else None
        owner = trace._owner
        if owner is not None:
            if owner.loop is not None:
                self._step = owner.loop.span_entered(name)
            if owner.annotate is not None:
                self._annotation = owner.annotate(name)
                self._annotation.__enter__()
        self._span = Span(
            trace._new_id(), parent, name, trace._clock(),
            self._tags, threading.current_thread().name,
        )
        self._token = _CURRENT.set((trace, self._span.id, name))
        if self._step and trace._cpu_clock is not None:
            # last: the span's own. (`or 1`: a thread that has used no
            # CPU yet reads 0, which here means "not read")
            self._cpu0 = trace._cpu_clock() or 1
        return self._span

    def __exit__(self, *exc) -> bool:
        span = self._span
        trace = self._trace
        owner = trace._owner
        if self._cpu0 and owner.loop.in_step(self._step):
            # first, and read only where it will be kept: entered and
            # left in one step of the loop, or on a thread of its own.
            # NOT cut to the span's wall: where the kernel keeps a
            # thread's CPU time by sampling (the chip hosts: 10 ms to
            # whoever runs at its tick, PERF.md) one instance reads 0
            # or 10 ms and only the SUM over many is the CPU time;
            # cutting the 10s would bias it
            span.cpu_ms = (trace._cpu_clock() - self._cpu0) / 1e6
        span.dur_ms = (trace._clock() - span.t0) * 1e3
        _CURRENT.reset(self._token)
        trace.add(span)
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
        if owner is not None:
            owner._total(span.name, span.dur_ms, span.cpu_ms)
            if owner.loop is not None:
                cur = _CURRENT.get()
                owner.loop.span_exited(
                    span.name, cur[2] if cur is not None else None
                )
        if self._root:
            trace.finish()
        return False


class _NullSpan:
    """Shared do-nothing span/context-manager for the disabled path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def tag(self, **tags) -> None:
        pass


class _NullTrace:
    """Shared do-nothing trace for the disabled path."""

    __slots__ = ()
    dur_ms = 0.0

    def span(self, name: str, **tags) -> _NullSpan:
        return NOOP_SPAN

    def tag(self, **tags) -> None:
        pass

    def finish(self) -> None:
        pass

    def stage_ms(self) -> dict:
        return {}

    def as_dict(self) -> dict:
        return {}


NOOP_SPAN = _NullSpan()
NULL_TRACE = _NullTrace()


class Tracer:
    """Per-server tracing switchboard. ``enabled`` is THE one branch
    the disabled hot path pays; ``on_trace`` receives every finished
    root trace (the flight recorder's ``record``)."""

    __slots__ = ("enabled", "on_trace", "loop", "annotate", "clock",
                 "cpu_clock", "_totals", "_totals_lock")

    def __init__(self, enabled: bool = False, on_trace=None,
                 clock=time.perf_counter, cpu_clock=None):
        self.enabled = enabled
        self.on_trace = on_trace
        #: the spans' wall clock, seconds (a test's fake one)
        self.clock = clock
        #: the CPU clock of the calling thread, ns, read by the spans
        #: of explicit traces (``time.thread_time_ns`` with the loop's
        #: account, set at server start); None: no span reads one
        self.cpu_clock = cpu_clock
        #: loop_time.LoopAccount while the server's event loop is
        #: accounted (tracing on, between server start and stop)
        self.loop = None
        #: ``jax.profiler.TraceAnnotation`` while a profiler capture
        #: is active (set and cleared by the ProfilerHook), else None
        self.annotate = None
        #: span name -> [count, wall ms, clocked instances, their wall
        #: ms, their CPU ms], every span since boot: the rings keep the
        #: last N trees, these lose none (spans close on the loop, the
        #: collect worker and the WAL writer thread)
        self._totals: dict[str, list] = {}
        self._totals_lock = threading.Lock()

    def begin(self, name: str, **tags):
        """Start an explicit trace (the tick root). Returns the shared
        null trace when disabled — callers never branch."""
        if not self.enabled:
            return NULL_TRACE
        trace = Trace(name, on_finish=self._emit, tracer=self, **tags)
        trace._cpu_clock = self.cpu_clock
        return trace

    def span(self, name: str, **tags):
        """A span in the current context's trace; with no trace active
        it becomes its own single-span loose trace (per-message router
        handles, WAL fsyncs from the writer thread)."""
        if not self.enabled:
            return NOOP_SPAN
        cur = _CURRENT.get()
        if cur is not None:
            return _SpanCtx(cur[0], name, tags)
        trace = Trace(name, on_finish=self._emit, tracer=self, **tags)
        return _SpanCtx(trace, name, tags, root=True)

    def _total(self, name: str, dur_ms: float, cpu_ms=None) -> None:
        with self._totals_lock:
            total = self._totals.get(name)
            if total is None:
                total = self._totals[name] = [0, 0.0, 0, 0.0, 0.0]
            total[0] += 1
            total[1] += dur_ms
            if cpu_ms is not None:
                total[2] += 1
                total[3] += dur_ms
                total[4] += cpu_ms

    def span_totals(self) -> dict:
        """The ``spans`` gauge: per span name ``count`` and ``wall_ms``
        since boot, and (with the loop accounted) the time the name
        held the event loop: ``loop_ms``, ``steps``, ``max_step_ms``.
        Loop time outside any span is under ``task:<task name>``. A
        name with clocked instances (module docstring) also has their
        count ``clocked``, their wall ``clocked_ms``, and its two
        parts: ``cpu_ms`` + ``off_cpu_ms`` = ``clocked_ms``."""
        out = {}
        with self._totals_lock:
            totals = [(name, *total) for name, total in self._totals.items()]
        for name, count, wall_ms, clocked, clocked_ms, cpu_ms in totals:
            row = out[name] = {"count": count, "wall_ms": round(wall_ms, 3)}
            if clocked:
                row["clocked"] = clocked
                row["clocked_ms"] = round(clocked_ms, 3)
                row["cpu_ms"] = round(cpu_ms, 3)
                row["off_cpu_ms"] = round(
                    row["clocked_ms"] - row["cpu_ms"], 3
                )
        if self.loop is not None:
            for name, held in self.loop.by_name().items():
                out.setdefault(name, {"count": 0, "wall_ms": 0.0}).update(
                    held
                )
        return out

    def _emit(self, trace: Trace) -> None:
        if self.on_trace is not None:
            try:
                self.on_trace(trace)
            except Exception:  # a broken sink must never break a tick
                import logging

                logging.getLogger(__name__).exception(
                    "trace sink failed for %r", trace.name
                )
