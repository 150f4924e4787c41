"""Device telemetry: compile/retrace events + per-tick timing split.

ROADMAP item 2 ("engine p99 < 5 ms *by measurement*") needs three
things no aggregate histogram provides: WHICH kernel recompiled and
when (a retrace mid-serving is tens of ms to seconds inside a 5 ms
budget — the failure mode the utils/retrace.py GUARD exists for),
WHERE a tick's wall went between host encode / transfer / device
compute / fetch, and how much device memory the index is pinning.
This module is the bridge between those device-side facts and the
PR 5 observability substrate:

* **Compile events** — a ``jax.monitoring`` duration listener counts
  every backend compile (``device.compiles`` counter,
  ``device.compile_ms_total`` counter of whole ms +
  ``device.compile_ms`` histogram). The listener is module-global and
  fans out to the live :class:`DeviceTelemetry` instances (jax's
  listener list is append-only — there is no unregister — so instances
  attach/detach from a shared set instead).
* **Retrace attribution** — :meth:`DeviceTelemetry.poll_retraces`
  diffs the retrace GUARD's per-family compiled-variant counts; any
  growth emits a ``device.retraces`` counter increment and a NAMED
  loose span (``device.retrace``) into the flight recorder, tagged
  with the kernel family, the capacity tier of the last dispatch (a
  tier first-hit is the expected trigger) and the compile wall drained
  from the listener since the last poll. The tick batcher polls once
  per collect, so a mid-serving retrace surfaces the same tick it
  happened.
* **Per-tick device split** — :meth:`on_tick` tags the tick root trace
  with the backend's ``last_device_timing`` (encode_ms / h2d_ms /
  compute_ms / d2h_ms / decode_ms, host-side brackets of the
  dispatch/collect instrumentation points — see
  spatial/tpu_backend.py) and feeds the
  ``device.{encode,h2d,compute,d2h,decode}_ms`` histograms, and
  ``device.mesh_fetch_ms`` / ``device.decode_off_cpu_ms`` on a tick
  whose timing carries that leg (parallel/sharded_backend.py; the
  decode's bracket with the tracer's CPU clock on).
* **Live buffer gauge** — :func:`live_device_bytes` sums live jax
  array footprints at scrape time (the ``device`` gauge), without ever
  importing jax on its own: a CPU-backend server that never loaded jax
  reports 0.
"""

from __future__ import annotations

import logging
import sys
import threading

from ..utils.retrace import GUARD

logger = logging.getLogger(__name__)

#: the backend-compile duration event jax emits once per XLA
#: compilation (jaxpr tracing / MLIR lowering emit their own events —
#: the backend compile is the expensive leg and the one-per-variant
#: signal the retrace accounting wants)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_active_lock = threading.Lock()
_active: set = set()
_listener_installed = False


def _dispatch_event(event: str, duration_secs: float, **_kw) -> None:
    if event != COMPILE_EVENT:
        return
    with _active_lock:
        sinks = list(_active)
    for tel in sinks:
        tel._on_compile(duration_secs)


def _ensure_listener() -> bool:
    """Register the module-global jax.monitoring listener once.
    Returns False when jax is unavailable (pure-CPU minimal builds) —
    telemetry then degrades to GUARD polling without compile walls."""
    global _listener_installed
    if _listener_installed:
        return True
    try:
        import jax.monitoring
    except Exception:
        return False
    jax.monitoring.register_event_duration_secs_listener(_dispatch_event)
    _listener_installed = True
    return True


def live_device_bytes() -> int:
    """Total bytes of live jax arrays RIGHT NOW (0 when jax was never
    imported — this probe must not force device bring-up). Pull-gauge
    cost only: evaluated per /metrics scrape, never on the tick path."""
    if "jax" not in sys.modules:
        return 0
    try:
        import jax

        return sum(
            int(getattr(a, "nbytes", 0) or 0) for a in jax.live_arrays()
        )
    except Exception:
        return 0


class DeviceTelemetry:
    """Per-server device telemetry hub (one per WorldQLServer)."""

    def __init__(self, metrics=None, tracer=None, backend=None):
        self.metrics = metrics
        self.tracer = tracer
        self.backend = backend
        self._lock = threading.Lock()
        self._pending_compile_ms = 0.0   # drained by the next poll
        self.compiles = 0
        self.compile_ms_total = 0.0
        self._compile_ms_counted = 0
        self.retraces = 0
        # baseline at construction: warmup compiles that happened
        # before telemetry existed are not "retraces"
        self._guard_last = GUARD.counts()

    # region: lifecycle

    def install(self) -> "DeviceTelemetry":
        with _active_lock:
            _active.add(self)
        if not _ensure_listener():
            logger.info(
                "jax.monitoring unavailable — compile walls will not be "
                "attributed (retrace counting still active)"
            )
        return self

    def uninstall(self) -> None:
        with _active_lock:
            _active.discard(self)

    # endregion

    # region: compile events (listener thread — may be any thread)

    def _on_compile(self, duration_secs: float) -> None:
        ms = duration_secs * 1e3
        with self._lock:
            self.compiles += 1
            self.compile_ms_total += ms
            self._pending_compile_ms += ms
            # whole ms not yet counted: the counter below stays within
            # 1 ms of the float total however many compiles there were
            whole = int(self.compile_ms_total) - self._compile_ms_counted
            self._compile_ms_counted += whole
        if self.metrics is not None:
            self.metrics.inc("device.compiles")
            # the seconds compiles held their caller (at first use of a
            # kernel variant: the event loop), as a counter a window's
            # delta can be taken of
            self.metrics.inc("device.compile_ms_total", whole)
            self.metrics.observe_ms("device.compile_ms", ms)

    def _drain_compile_ms(self) -> float:
        with self._lock:
            ms, self._pending_compile_ms = self._pending_compile_ms, 0.0
        return ms

    # endregion

    # region: retrace polling

    def poll_retraces(self) -> dict:
        """Diff the retrace GUARD since the last poll; every family
        that gained compiled variants emits a counter increment and a
        named loose span (flight-recorder visible). Returns the delta
        (tests pin it). Cost when nothing changed: one small dict
        compare — safe once per tick."""
        counts = GUARD.counts()
        last = self._guard_last
        delta = {
            family: grown
            for family, count in counts.items()
            if (grown := count - last.get(family, 0)) > 0
        }
        self._guard_last = counts
        if not delta:
            return delta
        compile_ms = self._drain_compile_ms()
        tier = dict(getattr(self.backend, "last_dispatch_tier", None) or {})
        for family, grown in delta.items():
            self.retraces += grown
            if self.metrics is not None:
                self.metrics.inc("device.retraces", grown)
            if self.tracer is not None and self.tracer.enabled:
                # a loose single-span trace: rides the flight
                # recorder's loose ring next to router handles/fsyncs
                with self.tracer.span(
                    "device.retrace", family=family, new_variants=grown,
                    compile_ms=round(compile_ms, 3), **tier,
                ):
                    pass
            logger.warning(
                "jit retrace: %s +%d variant(s) (compile %.1f ms, "
                "tier %s) — a hot-path kernel recompiled mid-serving",
                family, grown, compile_ms, tier or "?",
            )
        return delta

    # endregion

    # region: per-tick hook (called by TickBatcher._note_collect_stats)

    def on_tick(self, trace) -> None:
        timing = getattr(self.backend, "last_device_timing", None)
        if timing:
            trace.tag(device_timing={
                k: (round(v, 3) if isinstance(v, float) else v)
                for k, v in timing.items()
            })
            if self.metrics is not None:
                # mesh_fetch_ms: only the sharded backend's timing has
                # it, and only on a tick that fetched regions
                for leg in ("encode_ms", "h2d_ms", "compute_ms", "d2h_ms",
                            "decode_ms", "mesh_fetch_ms"):
                    value = timing.get(leg)
                    if isinstance(value, (int, float)):
                        self.metrics.observe_ms(
                            f"device.{leg}", max(float(value), 0.0)
                        )
                # only with the tracer's CPU clock on, on a tick that
                # decoded. A difference of two clocks, and NOT floored:
                # on a kernel that samples CPU time (the chip hosts,
                # 10 ms a tick) only the mean of many is the reading
                off_cpu = timing.get("decode_off_cpu_ms")
                if off_cpu is not None:
                    self.metrics.observe_ms(
                        "device.decode_off_cpu_ms", off_cpu
                    )
        self.poll_retraces()

    # endregion

    def stats(self) -> dict:
        """The ``device`` pull gauge: compile/retrace totals + the live
        device-buffer footprint."""
        return {
            "compiles": self.compiles,
            "retraces": self.retraces,
            "compile_ms_total": round(self.compile_ms_total, 3),
            "buffer_bytes": live_device_bytes(),
        }
