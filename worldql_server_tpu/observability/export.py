"""Trace export: Chrome-trace/Perfetto JSON + the jax.profiler hook.

``chrome_trace`` converts flight-recorder trace dicts into the Trace
Event Format every Chrome/Perfetto build loads (``chrome://tracing``,
https://ui.perfetto.dev): complete events (``ph: "X"``) with
microsecond epoch timestamps, one ``pid`` per process and one ``tid``
per recorded thread name (named via ``thread_name`` metadata events).
Served at ``GET /debug/ticks?format=chrome`` by the HTTP transport.

``ProfilerHook`` is the device-level escalation: when host-side spans
show the wall time disappearing INSIDE a dispatch/collect, a
``POST /debug/profile`` round captures a ``jax.profiler`` trace
(viewable in xprof/tensorboard) without restarting the server. jax is
imported lazily so the debug surface itself never forces device
bring-up. The capture runs WITHOUT jax's python tracer unless asked
(it records every python call: millions of events a second on the
event loop, and a ``stop_trace`` that stalls it for seconds); instead,
while a capture is active, every span of the server's ``Tracer`` opens
a ``jax.profiler.TraceAnnotation`` of its name, so the program's spans
sit on the profiler's host line, on the profiler's clock, beside the
device's operations.
"""

from __future__ import annotations

import logging
import threading
import time

from .spans import NOOP_SPAN

logger = logging.getLogger(__name__)


def chrome_trace(
    traces: list[dict],
    pid: int | None = None,
    process_name: str | None = None,
) -> dict:
    """Trace Event Format JSON for a list of ``Trace.as_dict()`` dicts.

    ``process_name`` labels the pid lane with a human-readable name
    (``process_name`` metadata event — "router", "shard-0", …) so a
    multi-process splice (``GET /debug/cluster``) reads as named
    process tracks instead of bare pids; thread lanes are named the
    same way (``thread_name``, e.g. ``delivery-worker-N``)."""
    import os

    if pid is None:
        pid = os.getpid()
    events: list[dict] = []
    tids: dict[str, int] = {}
    if process_name is not None:
        events.append({
            "name": "process_name",
            "ph": "M",
            "ts": 0,
            "pid": pid,
            "tid": 0,
            "args": {"name": process_name},
        })
    for trace in traces:
        base_us = trace.get("start_unix_s", 0.0) * 1e6
        for span in trace.get("spans", ()):
            thread = span.get("thread") or "main"
            tid = tids.setdefault(thread, len(tids) + 1)
            args = dict(span.get("tags") or {})
            args["trace"] = trace.get("name")
            args.update(trace.get("tags") or {})
            events.append({
                "name": span["name"],
                "cat": trace.get("name", "trace"),
                "ph": "X",
                "ts": round(base_us + span["t0_ms"] * 1e3, 3),
                "dur": round(span["dur_ms"] * 1e3, 3),
                "pid": pid,
                "tid": tid,
                "args": args,
            })
    for thread, tid in tids.items():
        events.append({
            "name": "thread_name",
            "ph": "M",
            "ts": 0,
            "pid": pid,
            "tid": tid,
            "args": {"name": thread},
        })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


class ProfilerHook:
    """Start/stop guard around ``jax.profiler`` for the HTTP hook.

    One capture at a time (jax itself enforces this); start/stop from
    the admin endpoint, state readable for ``GET``. Thread-safe — the
    aiohttp handlers run on the loop but tests poke it directly.
    """

    def __init__(self, tracer=None):
        self._lock = threading.Lock()
        #: the server's Tracer: its spans annotate the capture, and
        #: its wall clock times the stop
        self.tracer = tracer
        self._clock = tracer.clock if tracer is not None else time.perf_counter
        self.active_dir: str | None = None
        self.captures = 0
        self.last_stop_ms = 0.0

    def start(self, log_dir: str, python_tracer: bool = False) -> None:
        with self._lock:
            if self.active_dir is not None:
                raise RuntimeError(
                    f"profiler already capturing into {self.active_dir}"
                )
            import jax

            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 1 if python_tracer else 0
            jax.profiler.start_trace(log_dir, profiler_options=options)
            self.active_dir = log_dir
            if self.tracer is not None and self.tracer.enabled:
                self.tracer.annotate = jax.profiler.TraceAnnotation
            logger.info(
                "jax profiler capture started → %s (python tracer %s)",
                log_dir, "on" if python_tracer else "off",
            )

    def stop(self) -> str:
        with self._lock:
            if self.active_dir is None:
                raise RuntimeError("no profiler capture in flight")
            import jax

            span = NOOP_SPAN
            if self.tracer is not None:
                self.tracer.annotate = None
                span = self.tracer.span("profile.stop")
            t0 = self._clock()
            # spanned: what stopping cost the loop stays readable after
            # the fact as `spans["profile.stop"].wall_ms`
            with span:
                jax.profiler.stop_trace()
            self.last_stop_ms = (self._clock() - t0) * 1e3
            log_dir, self.active_dir = self.active_dir, None
            self.captures += 1
            logger.info(
                "jax profiler capture stopped → %s (stop_trace %.1f ms)",
                log_dir, self.last_stop_ms,
            )
            return log_dir

    def status(self) -> dict:
        return {
            "active_dir": self.active_dir, "captures": self.captures,
            # how long the last stop_trace held the caller (the event
            # loop, from the HTTP hook)
            "last_stop_ms": round(self.last_stop_ms, 3),
        }
