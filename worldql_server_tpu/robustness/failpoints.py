"""Named fault-injection failpoints.

Every crash-containment claim in this package is only as good as the
failures used to prove it, so this module gives each boundary we care
about a NAMED injection site (the catalog lives in the README): store
init/insert/delete, WAL append/fsync, the write-behind applier batch,
backend dispatch/collect, transport send, codec decode, the router
dispatch, and the long-lived loop bodies (ticker pump, ZMQ recv).

Design constraints, in order:

* **Near-zero overhead when off.** ``fire()``/``afire()`` are module
  functions whose first (and usually only) action is a truthiness
  check on the registry's point dict — one dict bool per call site,
  no string formatting, no lock. Production runs with no
  ``WQL_FAILPOINTS`` pay essentially nothing.
* **Deterministic under a seed.** Probabilistic points draw from one
  ``random.Random`` owned by the registry, so a seeded chaos run
  fires the same faults in the same order every time (modulo event
  scheduling, which the chaos suite's assertions are written to
  tolerate).
* **Accounted.** Each point counts ``hits`` (site reached while the
  point was armed) and ``fired`` (fault actually injected); the server
  exports ``fired`` per point as the ``failpoints`` metrics gauge, and
  the chaos suite asserts the registry and ``/metrics`` agree — no
  fault may ever be injected invisibly.

Spec syntax (env ``WQL_FAILPOINTS``, CLI ``--failpoints``, or the
optional HTTP admin endpoint)::

    name=error[:P][:xN] | name=delay:DUR[:P][:xN] | name=state:VALUE[:P][:xN]

comma-separated; ``P`` is a fire probability in (0, 1] (default 1),
``xN`` caps total fires at N, ``DUR`` is ``50ms``/``0.5s``/bare
milliseconds. ``state`` is a VALUE-injection action: it never raises
or sleeps — a subsystem that polls :func:`forced` reads the armed
value (fires counted like any other point). The overload governor's
``overload.force_state`` point uses it so chaos can drive every
state-machine transition deterministically. Example::

    WQL_FAILPOINTS=store.insert=error:0.2,wal.fsync=delay:5ms,backend.collect=error:1:x3

The registry is process-global on purpose: injection sites are plain
module-level calls with no object to thread a handle through, exactly
like the logging module. Tests reset it around themselves
(``reset()``).
"""

from __future__ import annotations

import asyncio
import logging
import random
import re
import time

logger = logging.getLogger(__name__)


class FailpointError(RuntimeError):
    """The injected fault: raised by an armed ``error`` failpoint."""

    def __init__(self, name: str):
        super().__init__(f"failpoint {name!r} fired")
        self.failpoint = name


class FailpointSpecError(ValueError):
    """A failpoint spec string failed to parse."""


_DUR_RE = re.compile(r"^(\d+(?:\.\d+)?)(ms|s|us)?$")


def _parse_duration_s(raw: str) -> float:
    m = _DUR_RE.match(raw)
    if not m:
        raise FailpointSpecError(f"bad delay duration {raw!r}")
    value = float(m.group(1))
    unit = m.group(2) or "ms"
    return value * {"us": 1e-6, "ms": 1e-3, "s": 1.0}[unit]


class _Point:
    __slots__ = ("name", "spec", "action", "delay_s", "prob", "max_fires",
                 "hits", "fired", "value")

    def __init__(self, name: str, spec: str):
        self.name = name
        self.spec = spec
        self.hits = 0
        self.fired = 0
        parts = spec.split(":")
        self.action = parts[0]
        self.delay_s = 0.0
        self.prob = 1.0
        self.max_fires: int | None = None
        self.value: str | None = None
        if self.action == "error":
            rest = parts[1:]
        elif self.action == "delay":
            if len(parts) < 2:
                raise FailpointSpecError(
                    f"{name}: delay needs a duration (delay:50ms)"
                )
            self.delay_s = _parse_duration_s(parts[1])
            rest = parts[2:]
        elif self.action == "state":
            if len(parts) < 2 or not parts[1]:
                raise FailpointSpecError(
                    f"{name}: state needs a value (state:shed_high)"
                )
            self.value = parts[1]
            rest = parts[2:]
        else:
            raise FailpointSpecError(
                f"{name}: unknown action {self.action!r} "
                "(expected error|delay|state)"
            )
        for tok in rest:
            if tok.startswith("x"):
                try:
                    self.max_fires = int(tok[1:])
                except ValueError:
                    raise FailpointSpecError(
                        f"{name}: bad fire cap {tok!r}"
                    ) from None
            else:
                try:
                    self.prob = float(tok)
                except ValueError:
                    raise FailpointSpecError(
                        f"{name}: bad probability {tok!r}"
                    ) from None
                if not 0.0 < self.prob <= 1.0:
                    raise FailpointSpecError(
                        f"{name}: probability must be in (0, 1]"
                    )


def parse_spec(spec: str) -> dict[str, _Point]:
    """Spec string → {name: point}; raises :class:`FailpointSpecError`
    on any malformed entry (config validation uses this without
    arming anything)."""
    points: dict[str, _Point] = {}
    for entry in (spec or "").split(","):
        entry = entry.strip()
        if not entry:
            continue
        name, sep, action = entry.partition("=")
        if not sep or not name.strip():
            raise FailpointSpecError(f"bad failpoint entry {entry!r}")
        name = name.strip()
        points[name] = _Point(name, action.strip())
    return points


class FailpointRegistry:
    """All armed failpoints plus their fire accounting."""

    def __init__(self, seed: int | None = None):
        self._points: dict[str, _Point] = {}
        self._rng = random.Random(seed)
        #: cumulative fired counts, kept across configure()/clear() so a
        #: chaos run can re-arm points without losing the audit trail
        self._fired_total: dict[str, int] = {}

    # region: configuration

    def configure(self, spec: str, *, seed: int | None = None) -> None:
        """Replace the armed set from a spec string (see module doc).
        An empty spec disarms everything."""
        points = parse_spec(spec)
        if seed is not None:
            self._rng = random.Random(seed)
        self._points = points
        if points:
            logger.warning(
                "failpoints armed: %s",
                ",".join(f"{p.name}={p.spec}" for p in points.values()),
            )

    def set(self, name: str, action: str) -> None:
        """Arm (or re-arm) one failpoint without touching the others."""
        # rebuild the dict so fire()'s lock-free read never sees a
        # half-updated mapping
        points = dict(self._points)
        points[name] = _Point(name, action)
        self._points = points

    def clear(self, name: str | None = None) -> None:
        """Disarm one failpoint, or all of them."""
        if name is None:
            self._points = {}
        else:
            points = dict(self._points)
            points.pop(name, None)
            self._points = points

    def seed(self, seed: int | None) -> None:
        self._rng = random.Random(seed)

    def reset(self) -> None:
        """Disarm everything AND zero the accounting (tests)."""
        self._points = {}
        self._fired_total = {}

    def active(self) -> bool:
        return bool(self._points)

    # endregion

    # region: firing

    def _should_fire(self, point: _Point) -> bool:
        point.hits += 1
        if point.max_fires is not None and point.fired >= point.max_fires:
            return False
        if point.prob < 1.0 and self._rng.random() >= point.prob:
            return False
        point.fired += 1
        self._fired_total[point.name] = (
            self._fired_total.get(point.name, 0) + 1
        )
        return True

    def fire(self, name: str) -> None:
        """Synchronous injection site. ``delay`` blocks the calling
        thread (worker-thread sites: WAL fsync); ``error`` raises
        :class:`FailpointError`; ``state`` is inert here (it only
        feeds :meth:`forced_value` polls)."""
        point = self._points.get(name)
        if point is None or not self._should_fire(point):
            return
        if point.action == "delay":
            time.sleep(point.delay_s)
            return
        if point.action == "state":
            return
        raise FailpointError(name)

    async def afire(self, name: str) -> None:
        """Async injection site: ``delay`` yields to the loop instead
        of blocking it."""
        point = self._points.get(name)
        if point is None or not self._should_fire(point):
            return
        if point.action == "delay":
            await asyncio.sleep(point.delay_s)
            return
        if point.action == "state":
            return
        raise FailpointError(name)

    def forced_value(self, name: str) -> str | None:
        """Value-injection poll: the armed ``state:<value>`` payload,
        or None (not armed / not a state point / prob-xN said no).
        Every returned value counts as a fire, so forced transitions
        stay visible in the failpoints audit gauge."""
        point = self._points.get(name)
        if point is None or point.action != "state":
            return None
        if not self._should_fire(point):
            return None
        return point.value

    # endregion

    # region: accounting

    def fired(self, name: str) -> int:
        return self._fired_total.get(name, 0)

    def note_remote_fires(self, deltas: dict) -> None:
        """Fold fire counts observed in ANOTHER process into the audit
        total. Delivery workers arm their own per-process registry
        (the spec rides the spawn args) and report cumulative fires
        over the control channel; the plane diffs consecutive packets
        and folds the deltas here, so the ``failpoints`` gauge audits
        the whole plane — a fault injected in a sender worker is never
        invisible to the parent's accounting."""
        for name, n in deltas.items():
            if isinstance(n, int) and n > 0:
                self._fired_total[name] = (
                    self._fired_total.get(name, 0) + n
                )

    def fired_counts(self) -> dict[str, int]:
        """{failpoint: total fires} — the ``failpoints`` metrics gauge.
        Includes disarmed points that fired earlier, so a chaos run's
        audit survives the verification phase disarming everything."""
        return dict(self._fired_total)

    def stats(self) -> dict:
        """Full per-point state for the admin endpoint."""
        out = {}
        for name, point in self._points.items():
            out[name] = {
                "spec": point.spec,
                "hits": point.hits,
                "fired": point.fired,
            }
        for name, fired in self._fired_total.items():
            if name not in out:
                out[name] = {"spec": None, "hits": None, "fired": fired}
        return out

    # endregion


#: process-global registry — injection sites are bare module calls
registry = FailpointRegistry()


def fire(name: str) -> None:
    """Hot-path sync injection site; no-ops in one dict-bool when no
    failpoint is armed."""
    if registry._points:
        registry.fire(name)


def armed(name: str) -> bool:
    """Whether ``fire(name)`` could do anything right now: a writer
    that cannot fire a point frame by frame (the native send pass)
    asks, and leaves the frames to the one that can."""
    return name in registry._points


async def afire(name: str) -> None:
    """Hot-path async injection site (loop-side boundaries)."""
    if registry._points:
        await registry.afire(name)


def forced(name: str) -> str | None:
    """Hot-path value-injection poll; one dict-bool when nothing is
    armed (the overload governor calls this every evaluation)."""
    if registry._points:
        return registry.forced_value(name)
    return None
