"""Timed regions: the program's one region timer, on the profiler's clock.

A span times one logical region (``with span("repro.db.get_many"):``) and
feeds its duration into the same-named registry histogram.  While a JAX
profiler trace is recording, the span also opens a
``jax.profiler.TraceAnnotation`` of the same name, so the trace's host
plane shows the program's regions on the device trace's clock.  With no
trace recording, that costs one flag check; with telemetry disabled, a
span is one shared no-op object.

The collector gets a span of its own: a ``gc.callbacks`` hook times every
CPython collection into ``repro.runtime.gc`` (and annotates it while a
trace records).  It does nothing while telemetry is disabled.
"""

from __future__ import annotations

import gc
import sys
import time
from typing import Any, Dict, Optional

from .metrics import REGISTRY, Histogram, Registry, _state

_annotation_cls: Any = None


def _annotation() -> Any:
    """``jax.profiler.TraceAnnotation`` while a profiler trace records,
    else None.  JAX is never imported from here: a process that has not
    loaded ``jax.profiler`` cannot be recording a trace."""
    global _annotation_cls
    cls = _annotation_cls
    if cls is None:
        # getattr, not import: a collection can fire while jax imports
        cls = _annotation_cls = getattr(sys.modules.get("jax.profiler"),
                                        "TraceAnnotation", None)
    return cls if cls is not None and cls.is_enabled() else None


class Span:
    """One active timed region."""

    __slots__ = ("hist", "_t0", "_ann")

    def __init__(self, hist: Histogram) -> None:
        self.hist = hist
        self._t0 = 0
        self._ann: Any = None

    def __enter__(self) -> "Span":
        ann = _annotation()
        if ann is not None:
            self._ann = ann(self.hist.name)
            self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc: Any) -> None:
        self.hist.observe(time.perf_counter_ns() - self._t0)
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None


class _NullSpan:
    """Shared do-nothing span handed out while telemetry is disabled."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc: Any) -> None:
        pass


_NULL = _NullSpan()


def span(name: str, registry: Optional[Registry] = None) -> Any:
    """Context manager timing one region into histogram ``name`` (and the
    profiler trace, while one records).  Returns a shared no-op when
    telemetry is disabled."""
    if not _state.enabled:
        return _NULL
    return Span((registry or REGISTRY).histogram(name))


class _GcSpan:
    """``gc.callbacks`` hook: one ``repro.runtime.gc`` span per collection,
    opened at the collector's ``start`` and closed at its ``stop``."""

    def __init__(self) -> None:
        self._open: Any = None

    def __call__(self, phase: str, info: Dict[str, int]) -> None:
        if phase == "start":
            self._open = span("repro.runtime.gc")
            self._open.__enter__()
        elif self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None


gc.callbacks.append(_GcSpan())
