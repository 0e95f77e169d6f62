"""Low-overhead metrics primitives: counters, log-bucketed latency
histograms, and the registry that names them (DESIGN.md §9).

Design constraints, in priority order:

1. **Near-zero cost when disabled.**  Every mutating entry point checks
   one module-level flag and returns (a span is a shared no-op object,
   :mod:`.spans`).  Instrumented code never branches on telemetry state
   itself — it always calls the same handles, which are cheap either way.
2. **Cheap when enabled.**  A counter bump is one attribute add; a
   histogram observation is one log2 and two integer adds.  Handles are
   created once at import/module scope and cached by name, so the hot
   path never touches the registry dict.
3. **Lossless merge.**  Histograms store integer bucket counts plus
   exact count/sum/min/max, so ``merge`` is commutative, associative,
   and equal to having observed the concatenated samples into one
   histogram — shard-local histograms fold into a whole-engine view
   without approximation beyond the shared bucket geometry.

Naming convention: ``repro.<subsystem>.<verb>`` (see DESIGN.md §9.2).
Durations are recorded in nanoseconds and exported in microseconds.
"""

from __future__ import annotations

import math
import threading
from typing import Dict, List, Optional

# Histogram geometry: 4 buckets per octave (bucket i spans
# [2**(i/4), 2**((i+1)/4)) nanoseconds), 256 buckets total — 1 ns up to
# ~2 hours, with <=19% relative bucket width everywhere.
BUCKETS_PER_OCTAVE = 4
N_BUCKETS = 256
_LOG2_E4 = BUCKETS_PER_OCTAVE / math.log(2.0)


class _State:
    __slots__ = ("enabled",)

    def __init__(self) -> None:
        self.enabled = True


_state = _State()


def enabled() -> bool:
    """Is telemetry recording anything right now?"""
    return _state.enabled


def set_enabled(on: bool) -> bool:
    """Flip recording on/off globally; returns the previous state.

    Disabling must never change engine behaviour — only whether the
    registry accumulates.  (tests/test_telemetry.py proves enabled and
    disabled runs produce bit-identical store contents.)
    """
    prev = _state.enabled
    _state.enabled = bool(on)
    return prev


def bucket_index(ns: float) -> int:
    """Bucket holding a duration of ``ns`` nanoseconds (clamped)."""
    if ns < 1.0:
        return 0
    i = int(math.log(ns) * _LOG2_E4)
    return i if i < N_BUCKETS else N_BUCKETS - 1


def bucket_lo(i: int) -> float:
    """Inclusive lower edge of bucket ``i`` in nanoseconds."""
    return 2.0 ** (i / BUCKETS_PER_OCTAVE)


class Counter:
    """Monotonic event counter.  ``add`` is the only hot entry point."""

    __slots__ = ("name", "value")

    def __init__(self, name: str) -> None:
        self.name = name
        self.value = 0

    def add(self, n: int = 1) -> None:
        if _state.enabled:
            self.value += n

    def inc(self) -> None:
        self.add(1)

    def reset(self) -> None:
        self.value = 0


class Histogram:
    """Log-bucketed latency histogram over nanosecond durations.

    Buckets are global geometry (module constants), so any two
    histograms merge losslessly by adding bucket counts; count/sum are
    exact and min/max are exact extremes, making ``merge`` commutative
    and associative.
    """

    __slots__ = ("name", "count", "sum_ns", "min_ns", "max_ns", "buckets")

    def __init__(self, name: str) -> None:
        self.name = name
        self.count = 0
        self.sum_ns = 0
        self.min_ns = 0.0
        self.max_ns = 0.0
        self.buckets: List[int] = [0] * N_BUCKETS

    def observe(self, ns: float) -> None:
        """Record one duration (nanoseconds)."""
        if not _state.enabled:
            return
        if ns < 0:
            ns = 0
        if self.count == 0 or ns < self.min_ns:
            self.min_ns = ns
        if ns > self.max_ns:
            self.max_ns = ns
        self.count += 1
        self.sum_ns += int(ns)
        self.buckets[bucket_index(ns)] += 1

    def percentile(self, q: float) -> float:
        """q-quantile in nanoseconds (geometric bucket midpoint); 0.0 when
        empty — an unobserved histogram has no latency to report."""
        if self.count == 0:
            return 0.0
        want = q * self.count
        seen = 0
        for i, c in enumerate(self.buckets):
            seen += c
            if seen >= want and c:
                mid = 2.0 ** ((i + 0.5) / BUCKETS_PER_OCTAVE)
                # clamp to the exact extremes so tiny histograms don't
                # report a midpoint outside the observed range
                return min(max(mid, self.min_ns), self.max_ns)
        return self.max_ns  # pragma: no cover - count>0 guarantees a hit

    def merge(self, other: "Histogram") -> None:
        """Fold ``other`` in; lossless (see class docstring)."""
        if other.count == 0:
            return
        if self.count == 0 or other.min_ns < self.min_ns:
            self.min_ns = other.min_ns
        if other.max_ns > self.max_ns:
            self.max_ns = other.max_ns
        self.count += other.count
        self.sum_ns += other.sum_ns
        b, ob = self.buckets, other.buckets
        for i in range(N_BUCKETS):
            b[i] += ob[i]

    def total_seconds(self) -> float:
        return self.sum_ns / 1e9

    def summary(self) -> Dict[str, float]:
        """Exporter view: count plus total/percentiles in microseconds."""
        return {
            "count": self.count,
            "total_s": round(self.sum_ns / 1e9, 6),
            "p50_us": round(self.percentile(0.50) / 1e3, 3),
            "p95_us": round(self.percentile(0.95) / 1e3, 3),
            "p99_us": round(self.percentile(0.99) / 1e3, 3),
            "max_us": round(self.max_ns / 1e3, 3),
        }

    def reset(self) -> None:
        self.count = 0
        self.sum_ns = 0
        self.min_ns = 0.0
        self.max_ns = 0.0
        self.buckets = [0] * N_BUCKETS


class Registry:
    """Name -> metric map.  Handles are created once and cached by the
    instrumented modules, so lookups are off the hot path; creation is
    locked so concurrent first-touch is safe."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counters: Dict[str, Counter] = {}
        self._hists: Dict[str, Histogram] = {}

    def counter(self, name: str) -> Counter:
        c = self._counters.get(name)
        if c is None:
            with self._lock:
                c = self._counters.setdefault(name, Counter(name))
        return c

    def histogram(self, name: str) -> Histogram:
        h = self._hists.get(name)
        if h is None:
            with self._lock:
                h = self._hists.setdefault(name, Histogram(name))
        return h

    def counters(self) -> Dict[str, Counter]:
        return dict(self._counters)

    def histograms(self) -> Dict[str, Histogram]:
        return dict(self._hists)

    def hist_seconds(self, prefix: Optional[str] = None) -> Dict[str, float]:
        """name -> accumulated seconds for every histogram (phase math)."""
        return {
            n: h.total_seconds()
            for n, h in self._hists.items()
            if prefix is None or n.startswith(prefix)
        }

    def reset(self) -> None:
        """Zero every metric *in place* — cached handles stay valid."""
        for c in self._counters.values():
            c.reset()
        for h in self._hists.values():
            h.reset()


# The engine-wide default registry.  Per-object registries are possible
# (tests use them) but the engine instruments against this one: metric
# names are globally meaningful, like a process's /metrics page.
REGISTRY = Registry()
