"""The observability registry — counters, gauges, spans, decision records.

One process-global registry backs every layer's instrumentation:

  * **Counters** are always-on integers (``bump``/``counter_value``) — cheap
    enough to live inside jitted function bodies, where an increment runs
    once per XLA *trace* and therefore counts compiles
    (``repro.sim.batch.trace_count``).
  * **Gauges** record last-written values (``set_gauge``) — device counts,
    mesh shapes, throughput figures.
  * **Spans** are wall-clock intervals on ``time.perf_counter``.
    :func:`span` is the hot-path form: when the registry is disabled (the
    default) it returns a shared no-op context manager — the ``enabled()``
    guard is the only cost.  :func:`timer` always measures (it exposes
    ``.dur`` for callers that *need* the number, e.g. a counter that must
    equal its span) but records the event only while enabled.  While
    enabled, a span also opens a ``jax.profiler.TraceAnnotation`` of its
    name (where ``jax`` is already imported), so it shows in the
    profiler's own host timeline.
  * **Decision records** (:class:`repro.obs.provenance.DecisionRecord`) are
    appended by allocators via :func:`record_decision` while enabled.

Two per-thread scopes carry spans across threads and processes:
:class:`tagged` adds arguments to every span a thread records (the
pipeline's ``call=<n>``), and :class:`collect` routes a thread's spans and
decision records into a buffer of their own, which a plan-build worker
returns to the parent for :func:`merge`.  ``perf_counter`` is
``CLOCK_MONOTONIC`` on Linux, one clock for every process of the host, so a
pool worker's spans lie on the parent's clock as they are.

Nothing here may change computation: the registry only observes.  Golden
schedule hashes must be bit-identical with the registry enabled or disabled
(``tests/test_obs.py`` pins this).
"""
from __future__ import annotations

import sys
import threading
import time
from typing import Any

__all__ = [
    "enabled", "enable", "disable", "capture", "reset",
    "bump", "counter_value", "set_counter", "counters",
    "set_gauge", "gauges",
    "span", "timer", "wall_events",
    "record_decision", "decision_records",
    "tagged", "tags", "collect", "merge", "Collected",
    "snapshot",
]


class _State:
    """Process-global mutable registry state."""

    __slots__ = ("enabled", "counters", "gauges", "events", "decisions")

    def __init__(self):
        self.enabled = False
        self.counters: dict[str, int] = {}
        self.gauges: dict[str, float] = {}
        self.events: list[dict] = []
        self.decisions: list = []


_STATE = _State()


class _Local(threading.local):
    """Per-thread recording scope: the tags every span of the thread
    carries, and the buffer its spans and decisions go to (``None``: the
    registry's own lists)."""

    tags: dict = {}
    sink: "Collected | None" = None


_LOCAL = _Local()


# ------------------------------------------------------------- enable/disable
def enabled() -> bool:
    """The zero-overhead guard: is the registry recording?"""
    return _STATE.enabled


def enable() -> None:
    """Start recording spans and decision records (counters/gauges are
    always on)."""
    _STATE.enabled = True


def disable() -> None:
    _STATE.enabled = False


class capture:
    """Context manager: enable the registry for a block, restoring the prior
    enabled state on exit.  ``reset=True`` (default) clears events and
    decision records on entry so the block observes only itself."""

    def __init__(self, reset: bool = True):
        self._reset = reset

    def __enter__(self):
        self._was = _STATE.enabled
        if self._reset:
            reset()
        _STATE.enabled = True
        return _STATE

    def __exit__(self, *exc):
        _STATE.enabled = self._was
        return False


def reset(counters: bool = False) -> None:
    """Clear recorded spans and decision records; with ``counters=True``
    also zero every counter and gauge."""
    _STATE.events.clear()
    _STATE.decisions.clear()
    if counters:
        _STATE.counters.clear()
        _STATE.gauges.clear()


# ------------------------------------------------------------------- counters
def bump(name: str, n: int = 1) -> None:
    """Increment a counter (always on — safe inside jitted bodies, where it
    runs once per trace)."""
    _STATE.counters[name] = _STATE.counters.get(name, 0) + n


def counter_value(name: str) -> int:
    return _STATE.counters.get(name, 0)


def set_counter(name: str, value: int) -> None:
    _STATE.counters[name] = int(value)


def counters() -> dict[str, int]:
    """Snapshot of all counters."""
    return dict(_STATE.counters)


def set_gauge(name: str, value: float) -> None:
    _STATE.gauges[name] = value


def gauges() -> dict[str, float]:
    return dict(_STATE.gauges)


# ---------------------------------------------------------------------- spans
class Span:
    """A measured wall-clock interval; records itself on exit when the
    registry is enabled.  ``.dur`` holds the measured seconds after exit.

    While the registry is enabled at entry, the span also opens a
    ``jax.profiler.TraceAnnotation`` of its name around the measured
    interval — only where ``jax`` is already imported, so observing never
    imports it."""

    __slots__ = ("name", "cat", "args", "t0", "dur", "_ann")

    def __init__(self, name: str, cat: str, args: dict[str, Any]):
        self.name, self.cat, self.args = name, cat, args
        self.t0 = 0.0
        self.dur = 0.0
        self._ann = None

    def __enter__(self) -> "Span":
        if _STATE.enabled and "jax.profiler" in sys.modules:
            self._ann = sys.modules["jax.profiler"].TraceAnnotation(self.name)
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.dur = time.perf_counter() - self.t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if _STATE.enabled:
            tags, sink = _LOCAL.tags, _LOCAL.sink
            (_STATE.events if sink is None else sink.events).append(
                {"name": self.name, "cat": self.cat, "ts": self.t0,
                 "dur": self.dur,
                 "args": {**tags, **self.args} if tags else self.args})
        return False

    def elapsed(self) -> float:
        """Seconds since entry — readable *inside* the block (``.dur`` is
        only final after exit)."""
        return time.perf_counter() - self.t0


class _NoopSpan:
    """Shared do-nothing span for the disabled hot path."""

    __slots__ = ()
    dur = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _NoopSpan()


def span(name: str, cat: str = "wall", **args):
    """Hot-path span: a no-op singleton while disabled (zero overhead), a
    recording :class:`Span` while enabled."""
    if not _STATE.enabled:
        return _NOOP
    return Span(name, cat, args)


def timer(name: str, cat: str = "wall", **args) -> Span:
    """Always-measuring span for harnesses that read ``.dur`` afterwards
    (benchmark phase timing); the event is recorded only while enabled."""
    return Span(name, cat, args)


def wall_events() -> list[dict]:
    """Recorded wall-clock span events (name/cat/ts/dur/args dicts, ts in
    ``time.perf_counter()`` seconds)."""
    return list(_STATE.events)


# ----------------------------------------------------------- decision records
def record_decision(rec) -> None:
    """Append a :class:`~repro.obs.provenance.DecisionRecord` while enabled.
    Callers should guard the record *construction* with :func:`enabled`."""
    if _STATE.enabled:
        sink = _LOCAL.sink
        (_STATE.decisions if sink is None else sink.decisions).append(rec)


def decision_records(scheduler: str | None = None) -> list:
    """Recorded decision records, optionally filtered by scheduler name."""
    if scheduler is None:
        return list(_STATE.decisions)
    return [r for r in _STATE.decisions if r.scheduler == scheduler]


# ------------------------------------------------- threads and pool workers
class tagged:
    """Context manager: every span this thread records inside the block
    carries ``tags`` among its arguments (a span's own arguments win).
    Nested blocks add to the enclosing tags."""

    def __init__(self, **tags):
        self._tags = tags

    def __enter__(self) -> dict:
        self._prev = _LOCAL.tags
        _LOCAL.tags = {**self._prev, **self._tags}
        return _LOCAL.tags

    def __exit__(self, *exc):
        _LOCAL.tags = self._prev
        return False


def tags() -> dict:
    """The tags of the calling thread's :class:`tagged` blocks."""
    return dict(_LOCAL.tags)


class Collected:
    """What a :class:`collect` block recorded: span events and decision
    records, in the order they were made."""

    __slots__ = ("events", "decisions")

    def __init__(self):
        self.events: list[dict] = []
        self.decisions: list = []


class collect:
    """Context manager for a worker's task: the registry records for the
    block, and this thread's spans (tagged with ``tags``) and decision
    records go to a :class:`Collected` buffer of their own instead of the
    registry's lists.  The worker returns the buffer with its result and
    the parent hands it to :func:`merge`.

    Enabling is process-wide: a process-pool worker runs one task at a
    time, and a thread worker runs only under a parent whose registry is
    already enabled, so restoring the prior state on exit is safe."""

    def __init__(self, **tags):
        self._tags = tags

    def __enter__(self) -> Collected:
        self._prev = (_STATE.enabled, _LOCAL.tags, _LOCAL.sink)
        _STATE.enabled = True
        _LOCAL.tags = {**_LOCAL.tags, **self._tags}
        _LOCAL.sink = Collected()
        return _LOCAL.sink

    def __exit__(self, *exc):
        _STATE.enabled, _LOCAL.tags, _LOCAL.sink = self._prev
        return False


def merge(rec: Collected) -> None:
    """Append a worker's :class:`Collected` spans and decision records to
    the calling thread's destination (the registry, or an enclosing
    :class:`collect` buffer), while enabled."""
    if _STATE.enabled:
        sink = _LOCAL.sink
        dest = _STATE if sink is None else sink
        dest.events.extend(rec.events)
        dest.decisions.extend(rec.decisions)


def snapshot() -> dict:
    """JSON-ready registry summary — the ``obs`` section of a
    ``repro.bench.v1`` document."""
    return {"enabled": _STATE.enabled,
            "counters": counters(),
            "gauges": gauges(),
            "spans": len(_STATE.events),
            "decisions": len(_STATE.decisions)}
