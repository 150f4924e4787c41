"""Tick flight recorder: span tracing, slow-tick dumps, loop health.

The diagnostic substrate under every perf PR (ISSUE 5): ``spans``
records per-stage wall time for every tick and message,
``flight_recorder`` keeps the last N tick traces and auto-dumps slow
ones, ``export`` renders Chrome-trace JSON for ``GET /debug/ticks``
and hosts the ``jax.profiler`` hook, ``loop_monitor`` separates a
blocked event loop from a slow device, ``device`` attributes jit
compiles/retraces and the per-tick encode/transfer/compute/fetch
split (ISSUE 7), ``loop_time`` accounts the one event loop's time by
span and layer, and the tracer keeps cumulative per-name span totals
(ISSUE 24).
"""

from .device import DeviceTelemetry
from .flight_recorder import FlightRecorder
from .loop_monitor import LoopMonitor
from .loop_time import LoopAccount
from .spans import NOOP_SPAN, NULL_TRACE, Trace, Tracer
from .export import ProfilerHook, chrome_trace

__all__ = [
    "DeviceTelemetry",
    "FlightRecorder",
    "LoopAccount",
    "LoopMonitor",
    "NOOP_SPAN",
    "NULL_TRACE",
    "ProfilerHook",
    "Trace",
    "Tracer",
    "chrome_trace",
]
