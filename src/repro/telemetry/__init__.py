"""Unified telemetry layer (DESIGN.md §9): one engine-wide metrics
registry, timed spans that also land in a recording JAX profiler trace,
and snapshot exporters.

Quickstart::

    from repro import telemetry

    with telemetry.span("repro.db.get_many"):      # histogram + trace
        ...hot path...
    ROWS = telemetry.counter("repro.db.get_many.rows")  # cached handle
    ROWS.add(n)

    telemetry.snapshot()        # counters + histogram percentiles
    telemetry.to_prometheus()   # scrape-format text
    telemetry.set_enabled(False)  # near-zero-cost off switch

Metric names follow ``repro.<subsystem>.<verb>``; durations are stored
in nanoseconds and exported in microseconds.
"""

from .export import (
    PHASE_SOURCES,
    dumps,
    phase_breakdown,
    snapshot,
    to_prometheus,
)
from .metrics import (
    BUCKETS_PER_OCTAVE,
    N_BUCKETS,
    REGISTRY,
    Counter,
    Histogram,
    Registry,
    bucket_index,
    bucket_lo,
    enabled,
    set_enabled,
)
from .spans import Span, span


def counter(name: str) -> Counter:
    """Get-or-create a counter in the engine-wide registry."""
    return REGISTRY.counter(name)


def histogram(name: str) -> Histogram:
    return REGISTRY.histogram(name)


def reset() -> None:
    """Zero the engine-wide registry (handles stay valid)."""
    REGISTRY.reset()
