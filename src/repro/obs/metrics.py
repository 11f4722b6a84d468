"""Lightweight counter/gauge registry for engine telemetry.

One process-wide :class:`MetricsRegistry` (``default_registry()``)
collects operational metrics from the run loops, the distributed driver,
the product search and the benchmark harness — host-side only, so
attaching metrics never adds a device sync and never perturbs the
engine's computation.

The registry is deliberately tiny (no labels, no exporters): metric
names are dotted strings (``"engine.host_syncs"``), values are floats,
and a snapshot is a plain dict that the run report serializes.  Tests
that assert "telemetry does not change execution" diff two snapshots
(``snapshot()`` / ``Counter.value``) around a run.
"""
from __future__ import annotations

import threading
from typing import Dict, Optional


class Counter:
    """Monotonically increasing count (events, syncs, cache hits)."""

    __slots__ = ("name", "_value")

    def __init__(self, name: str):
        self.name = name
        self._value = 0.0

    def inc(self, n: float = 1.0) -> None:
        self._value += n

    @property
    def value(self) -> float:
        return self._value


class Gauge:
    """Last-written value (progress step count, pending work)."""

    __slots__ = ("name", "_value")

    def __init__(self, name: str):
        self.name = name
        self._value = 0.0

    def set(self, v: float) -> None:
        self._value = float(v)

    @property
    def value(self) -> float:
        return self._value


class MetricsRegistry:
    """Name -> metric map with get-or-create accessors.

    Thread-safe creation (benchmarks may time concurrently); observation
    itself is a plain float update — the engine hot path must not take a
    lock per superstep.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: Dict[str, Counter] = {}
        self._gauges: Dict[str, Gauge] = {}

    def counter(self, name: str) -> Counter:
        c = self._counters.get(name)
        if c is None:
            with self._lock:
                c = self._counters.setdefault(name, Counter(name))
        return c

    def gauge(self, name: str) -> Gauge:
        g = self._gauges.get(name)
        if g is None:
            with self._lock:
                g = self._gauges.setdefault(name, Gauge(name))
        return g

    def snapshot(self) -> Dict[str, object]:
        """Plain-dict view of every metric (JSON-serializable)."""
        return dict(
            counters={k: c.value for k, c in sorted(self._counters.items())},
            gauges={k: g.value for k, g in sorted(self._gauges.items())},
        )

    def reset(self) -> None:
        """Drop every metric (tests isolate runs with this)."""
        with self._lock:
            self._counters.clear()
            self._gauges.clear()


_DEFAULT: Optional[MetricsRegistry] = None
_DEFAULT_LOCK = threading.Lock()


def default_registry() -> MetricsRegistry:
    """The process-wide registry the run loops emit into."""
    global _DEFAULT
    if _DEFAULT is None:
        with _DEFAULT_LOCK:
            if _DEFAULT is None:
                _DEFAULT = MetricsRegistry()
    return _DEFAULT
