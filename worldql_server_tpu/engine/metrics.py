"""Metrics registry: counters, latency histograms, and gauges.

The reference's observability is log lines only — no counters, no
health endpoint (SURVEY §5 "Metrics/logging/observability: logging
only"). The rebuild's contract is structured per-tick timing and
engine state, exposed by ``GET /metrics`` (transports/http.py) and
importable for tests.

Thread-safe: counters were the first writers off the loop (the
resilience layer increments from the ticker's collect worker thread),
and since PR 3 histograms are too — ``tick.collect_ms`` is observed
from the collect worker, and PR 5's span/flight-recorder plumbing adds
the WAL writer thread. Lazy ``Histogram`` creation plus the bucket
list's read-modify-writes can lose updates across threads, so
``inc`` and ``observe_ms`` both take the registry lock. Histograms are
fixed log-spaced latency buckets — cheap, allocation-free, good enough
for p50/p99 estimates.
"""

from __future__ import annotations

import threading
import time
from collections import defaultdict
from contextlib import contextmanager
from typing import Callable

# Bucket upper bounds in milliseconds (log-spaced), +inf implicit.
# The ladder runs into the multi-MINUTE range on purpose: BENCH_r05
# recorded a 207,000 ms tick, and with a 2.5 s top bucket everything
# above it collapsed into +inf — exactly the outlier regime the
# flight recorder exists for. Anything past 250 s reports via the
# overflow bucket's max-observed estimate (see ``quantile``).
LATENCY_BUCKETS_MS = (
    0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0,
    1000.0, 2500.0, 5000.0, 10000.0, 25000.0, 50000.0, 100000.0,
    250000.0,
)


def _numeric(value) -> bool:
    """What the text exposition can carry (a bool is an int: not it)."""
    return isinstance(value, (int, float)) and not isinstance(value, bool)


class Histogram:
    __slots__ = ("buckets", "counts", "total", "sum_ms", "max_ms")

    def __init__(self, buckets=LATENCY_BUCKETS_MS):
        self.buckets = buckets
        self.counts = [0] * (len(buckets) + 1)
        self.total = 0
        self.sum_ms = 0.0
        self.max_ms = 0.0

    def observe_ms(self, value_ms: float) -> None:
        self.observe_ms_n(value_ms, 1)

    def observe_ms_n(self, value_ms: float, n: int) -> None:
        """``n`` identical observations in one bucket write — the
        batched-delivery paths close one wall clock for a whole tick's
        frames and must not pay a per-frame loop."""
        i = 0
        for i, bound in enumerate(self.buckets):  # noqa: B007
            if value_ms <= bound:
                break
        else:
            i = len(self.buckets)
        self.counts[i] += n
        self.total += n
        self.sum_ms += value_ms * n
        if value_ms > self.max_ms:
            self.max_ms = value_ms

    def merge_counts(self, counts, total: int, sum_ms: float,
                     max_ms: float) -> None:
        """Fold externally-accumulated bucket counts in (delivery
        workers push cumulative histograms over the control channel;
        the plane diffs consecutive packets and merges the deltas so
        the series stay monotone across worker restarts). Bucket
        bounds must match (delivery/worker.py BUCKETS_MS — pinned by
        test); a shorter/longer list folds positionally."""
        for i, c in enumerate(counts[: len(self.counts)]):
            self.counts[i] += c
        self.total += total
        self.sum_ms += sum_ms
        if max_ms > self.max_ms:
            self.max_ms = max_ms

    def quantile(self, q: float) -> float:
        """Upper-bound estimate of the q-quantile from bucket counts.
        Always finite: a rank landing in the overflow bucket reports
        the maximum observed value (a true upper bound) instead of the
        useless ``+inf`` the outlier regime used to collapse to."""
        if self.total == 0:
            return 0.0
        rank = q * self.total
        seen = 0
        for i, count in enumerate(self.counts):
            seen += count
            if seen >= rank:
                return (
                    self.buckets[i]
                    if i < len(self.buckets)
                    else self.max_ms
                )
        return self.max_ms

    def snapshot(self) -> dict:
        return {
            "count": self.total,
            "mean_ms": (self.sum_ms / self.total) if self.total else 0.0,
            "p50_ms": self.quantile(0.50),
            "p99_ms": self.quantile(0.99),
            "max_ms": self.max_ms,
        }


class Metrics:
    """Process-wide registry; one instance per server."""

    def __init__(self):
        self.started_at = time.time()
        self.counters: defaultdict[str, int] = defaultdict(int)
        self.histograms: dict[str, Histogram] = {}
        self._gauges: dict[str, Callable[[], object]] = {}
        self._lock = threading.Lock()

    def inc(self, name: str, by: int = 1) -> None:
        with self._lock:
            self.counters[name] += by

    def observe_ms(self, name: str, value_ms: float) -> None:
        """Thread-safe: observed from the event loop AND worker threads
        (tick.collect_ms from the collect worker, gc/wal series from
        their own threads). The lock covers BOTH the lazy Histogram
        creation (two racing creators would each keep half the
        observations) and the bucket increments (list writes are
        read-modify-write and can lose updates across threads)."""
        with self._lock:
            hist = self.histograms.get(name)
            if hist is None:
                hist = self.histograms[name] = Histogram()
            hist.observe_ms(value_ms)

    def observe_ms_n(self, name: str, value_ms: float, n: int) -> None:
        """``n`` identical observations under ONE lock acquisition —
        the frame clock closes a whole delivery batch at once (up to
        ``max_batch`` frames); per-frame ``observe_ms`` calls would
        put a 16K-iteration lock loop on the tick path."""
        if n <= 0:
            return
        with self._lock:
            hist = self.histograms.get(name)
            if hist is None:
                hist = self.histograms[name] = Histogram()
            hist.observe_ms_n(value_ms, n)

    def merge_histogram(self, name: str, counts, total: int,
                        sum_ms: float, max_ms: float) -> None:
        """Merge histogram DELTAS accumulated in another process (see
        ``Histogram.merge_counts``). Creating-on-first-merge means a
        worker's series appears in /metrics from its first stats
        packet even before it carried traffic."""
        with self._lock:
            hist = self.histograms.get(name)
            if hist is None:
                hist = self.histograms[name] = Histogram()
            hist.merge_counts(counts, total, sum_ms, max_ms)

    def export_histograms(self, prefixes: tuple[str, ...]) -> dict:
        """Raw cumulative bucket state of every histogram whose name
        starts with one of ``prefixes`` — the shard-side half of the
        cluster metrics federation: snapshots ride the ~1s control
        state packets and the router diffs consecutive packets into
        ``merge_histogram`` deltas (the delivery-worker idiom, now
        process-to-process). Copied under the lock so a concurrent
        observer can't tear a packet."""
        with self._lock:
            return {
                name: {
                    "counts": list(hist.counts),
                    "total": hist.total,
                    "sum_ms": hist.sum_ms,
                    "max_ms": hist.max_ms,
                }
                for name, hist in self.histograms.items()
                if name.startswith(prefixes)
            }

    @contextmanager
    def time_ms(self, name: str):
        """Histogram-timed block: ``with metrics.time_ms("x_ms"): ...``
        observes the block's wall time (including the error path — a
        failing store call still cost that latency)."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.observe_ms(name, (time.perf_counter() - t0) * 1e3)

    def gauge(self, name: str, fn: Callable[[], object]) -> None:
        """Register a pull-style gauge; evaluated at snapshot time."""
        self._gauges[name] = fn

    def set_gauge(self, name: str, value) -> None:
        """Push-style gauge: record the latest value directly. For
        writers with no stable object to pull from — the tick
        batcher's per-flush delivered bytes are a snapshot of a
        moment, not a live view."""
        self._gauges[name] = lambda v=value: v

    def gauge_value(self, name: str):
        """Evaluate ONE registered gauge by name (``None`` when absent
        or broken).  The SLO engine samples floor objectives through
        this instead of rendering the whole registry every tick."""
        fn = self._gauges.get(name)
        if fn is None:
            return None
        try:
            return fn()
        except Exception:  # a broken gauge must not kill slo-eval
            return None

    def _eval_gauges(self) -> dict:
        gauges = {}
        for name, fn in self._gauges.items():
            try:
                gauges[name] = fn()
            except Exception as exc:  # a broken gauge must not kill /metrics
                gauges[name] = f"error: {exc}"
        return gauges

    def snapshot(self) -> dict:
        gauges = self._eval_gauges()
        with self._lock:
            # copy under the lock: a worker thread lazily creating a
            # histogram mid-iteration would otherwise blow up the scrape
            counters = dict(self.counters)
            hists = list(self.histograms.items())
        return {
            "uptime_s": round(time.time() - self.started_at, 3),
            "counters": counters,
            "latency": {name: hist.snapshot() for name, hist in hists},
            "gauges": gauges,
        }

    def render_prometheus(self) -> str:
        """Prometheus text exposition (version 0.0.4) of the registry —
        what a scraper expects at GET /metrics. Counter/gauge names map
        dots to underscores under a ``wql_`` prefix; histograms emit
        the standard ``_bucket``/``_sum``/``_count`` series (bucket
        bounds in seconds, per convention); dict-valued gauges flatten
        one level, non-numeric leaves are skipped; a gauge whose every
        value is a dict is a table and renders as one ``name``-labelled
        series a column."""
        out: list[str] = []

        def name_of(raw: str) -> str:
            return "wql_" + raw.replace(".", "_").replace("-", "_")

        out.append("# TYPE wql_uptime_seconds gauge")
        out.append(
            f"wql_uptime_seconds {time.time() - self.started_at:.3f}"
        )
        with self._lock:
            counters = sorted(self.counters.items())
            hists = sorted(self.histograms.items())
        for raw, value in counters:
            n = name_of(raw) + "_total"  # Prometheus counter convention
            out.append(f"# TYPE {n} counter")
            out.append(f"{n} {value}")
        for raw, hist in hists:
            # registry names carry '_ms'; the export is in seconds, so
            # swap the unit suffix instead of stacking both
            n = name_of(raw.removesuffix("_ms")) + "_seconds"
            with self._lock:
                # consistent point-in-time copy: a worker observing
                # mid-render must not make +Inf's cumulative count
                # disagree with _count (scrapers reject that)
                counts = list(hist.counts)
                total, sum_ms = hist.total, hist.sum_ms
            out.append(f"# TYPE {n} histogram")
            acc = 0
            for bound, count in zip(hist.buckets, counts):
                acc += count
                out.append(f'{n}_bucket{{le="{bound / 1e3:g}"}} {acc}')
            out.append(f'{n}_bucket{{le="+Inf"}} {total}')
            out.append(f"{n}_sum {sum_ms / 1e3:.6f}")
            out.append(f"{n}_count {total}")
        for raw, value in sorted(self._eval_gauges().items()):
            if (
                isinstance(value, dict) and value
                and all(isinstance(row, dict) for row in value.values())
            ):
                # a table (``spans``: name -> {count, wall_ms, ...}):
                # one labelled series a column, so a column has ONE
                # ``# TYPE`` line however many rows there are
                columns: dict[str, list] = {}
                for row_name, row in value.items():
                    for column, v in row.items():
                        if _numeric(v):
                            columns.setdefault(column, []).append(
                                (row_name, v)
                            )
                for column, cells in sorted(columns.items()):
                    n = name_of(f"{raw}.{column}")
                    out.append(f"# TYPE {n} gauge")
                    for row_name, v in sorted(cells):
                        label = row_name.replace("\\", "\\\\").replace(
                            '"', '\\"'
                        )
                        out.append(f'{n}{{name="{label}"}} {v}')
                continue
            leaves = (
                {f"{raw}.{k}": v for k, v in value.items()}
                if isinstance(value, dict) else {raw: value}
            )
            for leaf, v in sorted(leaves.items()):
                if not _numeric(v):
                    continue
                n = name_of(leaf)
                out.append(f"# TYPE {n} gauge")
                out.append(f"{n} {v}")
        return "\n".join(out) + "\n"
