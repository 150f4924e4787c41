"""Event-loop lag probe + GC-pause accounting.

A blocked asyncio loop is indistinguishable from a slow device in
today's numbers: the ticker's wall timers run ON the loop, so a 300 ms
GC pause or a synchronous store commit shows up as a "slow tick" with
no further signature. This module gives both their own series:

* ``loop.lag_ms`` — a supervised probe sleeps ``interval`` and records
  how late it wakes. Lag is scheduling delay: anything hogging the
  loop (sync I/O, giant JSON dumps, GC) shows here even when no tick
  is in flight.
* ``gc.pause_ms`` — a ``gc.callbacks`` hook times every collection
  pass. CPython's collector runs inside whatever thread triggered it,
  which for this server is almost always the event loop — at any
  bytecode of it, inside ``Metrics``' own lock too. So the hook takes
  no lock: it notes the pause, and the lag probe observes the noted
  pauses into the series when it next wakes (``interval`` later).

``snapshot()`` feeds the slow-tick dump so every dump carries the
loop-health context alongside the span tree.
"""

from __future__ import annotations

import asyncio
import gc
import logging
import time

logger = logging.getLogger(__name__)


class LoopMonitor:
    def __init__(self, metrics=None, interval: float = 0.25):
        self.metrics = metrics
        self.interval = interval
        self.last_lag_ms = 0.0
        self.max_lag_ms = 0.0
        self.last_gc_pause_ms = 0.0
        self.max_gc_pause_ms = 0.0
        self.gc_passes = 0
        self._gc_t0: float | None = None
        # pauses the hook has timed and the probe has not yet observed
        self._gc_pauses: list[float] = []
        self._installed = False

    # region: GC hook

    def install(self) -> None:
        """Register the GC callback (idempotent)."""
        if not self._installed:
            gc.callbacks.append(self._gc_callback)
            self._installed = True

    def uninstall(self) -> None:
        if self._installed:
            try:
                gc.callbacks.remove(self._gc_callback)
            except ValueError:
                pass
            self._installed = False
            self._observe_gc()

    def _gc_callback(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
            return
        if self._gc_t0 is None:
            return
        pause_ms = (time.perf_counter() - self._gc_t0) * 1e3
        self._gc_t0 = None
        self.gc_passes += 1
        self.last_gc_pause_ms = pause_ms
        if pause_ms > self.max_gc_pause_ms:
            self.max_gc_pause_ms = pause_ms
        # NOT metrics.observe_ms: a pass that began while this thread
        # was inside Metrics (observe_ms allocates) would wait here for
        # the lock its own caller holds, and the loop never ran again
        # (PR 37: traced runs hung around a profiler capture's stop,
        # whose worker allocates enough to start pass after pass)
        if self.metrics is not None:
            self._gc_pauses.append(pause_ms)  # emptied every interval

    def _observe_gc(self) -> None:
        """The noted pauses into ``gc.pause_ms`` (the probe, each time
        it wakes, and ``uninstall``)."""
        pauses, self._gc_pauses = self._gc_pauses, []
        for pause_ms in pauses:
            self.metrics.observe_ms("gc.pause_ms", pause_ms)

    # endregion

    async def run(self) -> None:
        """The lag probe loop — run under the server's Supervisor so a
        crashed probe restarts instead of silently going dark."""
        interval = self.interval
        while True:
            t0 = time.perf_counter()
            await asyncio.sleep(interval)
            lag_ms = max((time.perf_counter() - t0 - interval) * 1e3, 0.0)
            self.last_lag_ms = lag_ms
            if lag_ms > self.max_lag_ms:
                self.max_lag_ms = lag_ms
            if self.metrics is not None:
                self.metrics.observe_ms("loop.lag_ms", lag_ms)
            self._observe_gc()

    def snapshot(self) -> dict:
        """Loop-health context for slow-tick dumps and the gauge."""
        return {
            "loop_lag_ms": round(self.last_lag_ms, 3),
            "loop_lag_max_ms": round(self.max_lag_ms, 3),
            "gc_last_pause_ms": round(self.last_gc_pause_ms, 3),
            "gc_max_pause_ms": round(self.max_gc_pause_ms, 3),
            "gc_passes": self.gc_passes,
            "gc_counts": gc.get_count(),
        }
