"""Who holds the event loop: on-loop time by span, rolled up by layer.

The server is ONE asyncio loop on one host core, shared by ingest,
dispatch, delivery and the HTTP surface. A span's wall time cannot say
who held that core: ``tick.deliver`` wraps an ``await``, and whatever
the loop runs meanwhile (the recv loop, a scrape) is inside its wall.
With tracing on, :class:`LoopAccount` accounts the loop's time itself:

* **Steps.** A task factory wraps every new task's coroutine so that
  each step (one ``send``/``throw``, the stretch between two
  suspensions) is timed. Inside a step the time goes to the INNERMOST
  OPEN SPAN of the task's context (``spans._CURRENT`` rides
  ``create_task``; span enter/exit move the charge as they happen), and
  outside any span to ``task:<task name>``. Per name: ``loop_ms``,
  ``steps`` (stretches charged) and ``max_step_ms`` (the longest one: a
  compile on the loop thread is the ``max_step_ms`` of ``tick.dispatch``).
* **Busy.** Independent of the attribution, the loop's total busy time
  is wall minus the time blocked in the selector. What the factory
  cannot see (plain callbacks, zmq's fd handlers, the loop's own
  bookkeeping, tasks older than the install) is ``busy - sum(names)``:
  it shows as ``unattributed`` rather than vanishing.

* **On the CPU.** Busy says the loop was not waiting in its selector;
  it cannot say the loop's thread was RUNNING. ``cpu_ms`` is that
  thread's CPU time since install (``time.thread_time_ns``, read by
  ``snapshot``, which runs on it: nothing is paid a step), and
  ``off_cpu_ms`` = ``busy_ms - cpu_ms`` is the busy time it was off
  the CPU: waiting for the GIL a worker thread holds, in the kernel,
  or preempted (less the CPU the selector itself takes, some us a
  ``select``: that is in ``cpu_ms`` and not in ``busy_ms``, so on a
  loop nobody makes wait the difference can be a little below 0). The
  steps carry a serial, so a span can tell whether it entered and left
  inside ONE step, where its own CPU reading means something
  (``spans.py``).

The identities that hold by construction:
``sum(layers) + unattributed = busy_ms <= wall_ms``;
``cpu_ms <= busy_ms (+ the selector's own CPU) <= wall_ms``.

Work on OTHER threads (``asyncio.to_thread``: the collect worker, the
WAL writer) is not loop time and is not charged; it does compete for
the GIL, which stretches the loop's steps and is inside their time:
``off_cpu_ms`` is where that shows.

With tracing off nothing here is constructed: no factory is installed
and no coroutine is wrapped.
"""

from __future__ import annotations

import asyncio
import collections.abc
import re
import threading
import time

from .spans import _CURRENT

#: the layers of ``loop_time`` (PERF.md section 3 names the metric that
#: reads each); ``other`` takes every name no row below claims
LAYERS = ("ingest", "dispatch", "collect", "deliver", "sim", "admin", "other")

#: span or ``task:`` name -> layer, first matching prefix wins. THE one
#: table: a new span is on the loop under ``other`` until a row is added.
#: ``ingest`` is a message's whole way in: recv, decode and the router's
#: per-message handling up to ``ticker.enqueue`` (``router.handle`` nests
#: in ``zmq.recv``); ``dispatch`` is the per-FLUSH half of router +
#: staging (the pump and ``tick.dispatch``).
LAYER_PREFIXES = (
    ("tick.sim.", "sim"),
    ("zmq.", "ingest"),
    ("ws.", "ingest"),
    ("codec.", "ingest"),
    ("router.", "ingest"),
    ("task:sup:zmq-recv", "ingest"),
    ("tick.dispatch", "dispatch"),
    ("cluster.drain", "dispatch"),
    ("task:sup:tick-batcher", "dispatch"),
    ("tick.collect", "collect"),
    ("device.", "collect"),
    ("tick.build_pairs", "deliver"),
    ("tick.deliver", "deliver"),
    ("deliver.", "deliver"),
    ("delivery.", "deliver"),
    ("task:PeerMap.", "deliver"),
    # the supervised housekeeping loops. (The HTTP surface is not here:
    # aiohttp starts its handler tasks eagerly itself, past any task
    # factory, so /metrics and /debug/* are in ``unattributed``)
    ("task:sup:", "admin"),
)

#: names kept at most; a name past it is charged to one overflow row
#: (task names are the only unbounded input, and they are normalised)
MAX_NAMES = 512
OVERFLOW = "task:(overflow)"

_DEFAULT_TASK_NAME = re.compile(r"Task-\d+$")


def layer_of(name: str) -> str:
    for prefix, layer in LAYER_PREFIXES:
        if name.startswith(prefix):
            return layer
    return "other"


class _TimedCoroutine(collections.abc.Coroutine):
    """A task's coroutine with every step timed. asyncio drives a task
    through ``send``/``throw`` only, so the wrapper is invisible to the
    coroutine inside; anything else asked of it (``cr_frame`` for a
    stack dump, ``__qualname__`` for a repr) is the inner one's."""

    __slots__ = ("_coro", "_account", "_label")

    def __init__(self, coro, account: "LoopAccount"):
        self._coro = coro
        self._account = account
        self._label = None      # made when first charged (see label)

    def send(self, value):
        return self._account._step(self, self._coro.send, value)

    def throw(self, *exc):
        return self._account._step(self, self._coro.throw, *exc)

    def close(self):
        return self._coro.close()

    def __await__(self):
        return self._coro.__await__()

    def __getattr__(self, name):
        return getattr(self._coro, name)

    def label(self) -> str:
        """``task:<name>``, for loop time outside any span (asked from
        inside a step of this task, and only then: a task that runs
        under a span of its maker's never needs one). A task nobody
        named is called after its coroutine (``Task-123`` would be a
        new name for every one of a tick's ``drain_peer`` tasks)."""
        if self._label is None:
            task = asyncio.current_task()
            name = task.get_name() if task is not None else ""
            if not name or _DEFAULT_TASK_NAME.match(name):
                coro = self._coro
                name = (getattr(coro, "__qualname__", None)
                        or type(coro).__name__)
            self._label = "task:" + name
        return self._label


class LoopAccount:
    """The accounting of one event loop (see the module docstring).
    ``install`` on the loop's own thread; everything a step touches is
    then that thread's alone, and ``snapshot`` (a scrape, also on the
    loop) reads between steps."""

    def __init__(self, clock=time.perf_counter_ns,
                 cpu_clock=time.thread_time_ns):
        #: the wall clock of the steps and the CPU clock of the calling
        #: thread, both ns (a test's fake ones)
        self._clock = clock
        self._cpu_clock = cpu_clock
        self._loop = None
        self._thread = 0
        #: name -> [loop ns, stretches charged, longest stretch ns]
        self._held: dict[str, list] = {}
        # the open stretch of the step that is running, if one is:
        # perf_counter_ns at its start (0 = no step running), the span
        # name it will be charged to (None = the task itself) and the
        # running task's coroutine
        self._mark = 0
        self._owner = None
        self._running = None
        # the steps' serial: that of the step running, on the loop's
        # thread (what span_entered hands a span, and in_step compares)
        self._serial = 0
        self._t_install = 0
        self._cpu_install = 0
        self._idle_ns = 0
        self._selector = None       # the loop's, while its select is timed
        self._prev_factory = None

    # region: install / uninstall (the loop's thread)

    def install(self) -> "LoopAccount":
        loop = self._loop = asyncio.get_running_loop()
        self._thread = threading.get_ident()
        self._prev_factory = loop.get_task_factory()
        loop.set_task_factory(self._task_factory)
        clock = self._clock
        self._t_install = clock()
        self._cpu_install = self._cpu_clock()
        selector = getattr(loop, "_selector", None)
        if selector is not None:
            # busy = wall - blocked in select: the one place a selector
            # loop waits. A loop without one (uvloop) reports no busy_ms
            self._selector = selector

            def timed_select(timeout=None, _select=selector.select):
                t0 = clock()
                try:
                    return _select(timeout)
                finally:
                    self._idle_ns += clock() - t0

            selector.select = timed_select
        return self

    def uninstall(self) -> None:
        loop, self._loop = self._loop, None
        if loop is None:
            return
        if loop.get_task_factory() == self._task_factory:
            loop.set_task_factory(self._prev_factory)
        if self._selector is not None:
            # the instance attribute goes: back to the class's method
            del self._selector.select
            self._selector = None

    def _task_factory(self, loop, coro, **kwargs):
        if self._prev_factory is not None:
            return self._prev_factory(
                loop, _TimedCoroutine(coro, self), **kwargs
            )
        return asyncio.Task(_TimedCoroutine(coro, self), loop=loop, **kwargs)

    # endregion

    # region: the step clock (hot: every task step, every span edge)

    def _charge(self, name: str | None, ns: int) -> None:
        if name is None:        # outside any span: the running task pays
            name = self._running.label()
        held = self._held.get(name)
        if held is None:
            if len(self._held) >= MAX_NAMES and name != OVERFLOW:
                return self._charge(OVERFLOW, ns)
            self._held[name] = [ns, 1, ns]
            return
        held[0] += ns
        held[1] += 1
        if ns > held[2]:
            held[2] = ns

    def _step(self, timed: _TimedCoroutine, resume, *args):
        # (steps do not nest: the factory makes no eager task)
        cur = _CURRENT.get()
        self._running = timed
        self._owner = cur[2] if cur is not None else None
        self._serial += 1
        self._mark = self._clock()
        try:
            return resume(*args)
        finally:
            self._charge(self._owner, self._clock() - self._mark)
            self._mark = 0
            self._owner = self._running = None

    def span_entered(self, name: str) -> int:
        """The charge moves to ``name`` (called by a span's enter; a
        no-op off the loop's thread and outside a timed step). Returns
        where the span stands, for ``in_step`` when it exits: the
        serial of the step that is running, -1 on any other thread, 0
        where nobody can tell (on the loop's thread outside a timed
        step, or no loop accounted)."""
        if threading.get_ident() != self._thread:
            return -1 if self._loop is not None else 0
        if not self._mark:
            return 0
        now = self._clock()
        self._charge(self._owner, now - self._mark)
        self._mark, self._owner = now, name
        return self._serial

    def in_step(self, entered: int) -> bool:
        """Whether the caller still stands where ``span_entered`` said:
        on a thread of its own, or in the same step of the loop. A span
        that does shared its thread's CPU clock with no other task."""
        if threading.get_ident() != self._thread:
            return entered == -1
        return entered > 0 and bool(self._mark) and entered == self._serial

    def span_exited(self, name: str, parent: str | None) -> None:
        """``name`` is charged up to now; the charge moves back to the
        span around it, or (None) to the task when there is none."""
        if self._mark and threading.get_ident() == self._thread:
            now = self._clock()
            self._charge(name, now - self._mark)
            self._mark, self._owner = now, parent

    # endregion

    # region: what /metrics shows

    def by_name(self) -> dict:
        return {
            name: {
                "loop_ms": round(ns / 1e6, 3), "steps": steps,
                "max_step_ms": round(longest / 1e6, 3),
            }
            for name, (ns, steps, longest) in list(self._held.items())
        }

    def snapshot(self) -> dict:
        """The ``loop_time`` gauge, all in ms since install: the layers,
        ``busy_ms``/``wall_ms``, ``unattributed`` (busy no step
        claimed) and ``rest`` = busy - ingest - dispatch - deliver, the
        one key a per-tick metric reads beside those three; ``cpu_ms``
        (the loop's thread on the CPU: the caller must BE that thread)
        and ``off_cpu_ms`` = busy - cpu. Not floored: both are read by
        their growth between two scrapes, and on a loop that waits for
        nobody the selector's own CPU (some us a ``select``: in
        ``cpu_ms``, outside ``busy_ms``) makes that growth negative."""
        layers = dict.fromkeys(LAYERS, 0)
        for name, (ns, _, _) in list(self._held.items()):
            layers[layer_of(name)] += ns
        out = {layer: round(ns / 1e6, 3) for layer, ns in layers.items()}
        wall_ns = self._clock() - self._t_install
        out["wall_ms"] = round(wall_ns / 1e6, 3)
        if self._selector is not None:
            busy_ns = wall_ns - self._idle_ns
            claimed = sum(layers.values())
            out["busy_ms"] = round(busy_ns / 1e6, 3)
            if threading.get_ident() == self._thread:
                cpu_ns = self._cpu_clock() - self._cpu_install
                out["cpu_ms"] = round(cpu_ns / 1e6, 3)
                out["off_cpu_ms"] = round((busy_ns - cpu_ns) / 1e6, 3)
            out["unattributed"] = round((busy_ns - claimed) / 1e6, 3)
            out["rest"] = round(
                (busy_ns - layers["ingest"] - layers["dispatch"]
                 - layers["deliver"]) / 1e6, 3
            )
        return out

    # endregion
