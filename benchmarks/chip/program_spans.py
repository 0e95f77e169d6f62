"""The program's own spans in a traced run's profile, and the chip's idle
time put down to them.

``repro.telemetry.span`` opens a ``jax.profiler.TraceAnnotation`` of its
own name while a trace records, so a traced window's host plane holds the
program's ``repro.*`` regions on the device trace's clock.  ``reduce``
reads them from a profile: each span's seconds inside ``bench.window``,
and the first chip's idle time inside the window split by the innermost
``repro.*`` span open over it (``idle_by_span``; ``none`` where no program
span was open).

The per-layer readers of program spans (``metrics/*_share.py``) call
:func:`window_seconds`, which reads the context's ``spans``.  The harness
puts no ``spans`` in the context, so the first reader of a traced run
reads the profile that run left in ``harness.TRACE_DIR``, logs the idle
split on one stderr line (``trace idle by program span:``) and stores the
spans' seconds in the context for the other readers.  A reader reads
nothing where the program does not catalogue its span (a program older
than these spans) or the run was not traced.
"""

from __future__ import annotations

import json
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from benchmarks.chip import cells, harness, trace

PREFIX = "repro."
NONE = "none"
Span = Tuple[str, float, float]


def catalogued(*names: str) -> bool:
    """Does the program under test name every one of ``names``?"""
    from repro.telemetry.catalog import CATALOG

    return all(n in CATALOG for n in names)


def program_spans(profile) -> List[Span]:
    """The program's ``repro.*`` spans on every host thread."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events if e.name.startswith(PREFIX))
    return out


def idle_by_span(idle: Sequence[trace.Interval], spans: Sequence[Span]
                 ) -> Dict[str, float]:
    """Nanoseconds of ``idle`` (sorted, disjoint) under each innermost open
    span, by a sweep over the spans' ends; ``none`` where none is open.

    The innermost open span is the open one that started last (the outer
    one first where two start together): spans of one thread nest."""
    if not idle:
        return {}
    events = []  # (time, 0 = end before 1 = start, -end, index)
    for k, (_, s, e) in enumerate(spans):
        if e > s:
            events.append((s, 1, -e, k))
            events.append((e, 0, 0.0, k))
    events.sort()
    out: Dict[str, float] = {}
    open_: List[int] = []  # indices in start order
    i, t = 0, idle[0][0]

    def charge(a: float, b: float) -> None:
        nonlocal i
        while i < len(idle) and idle[i][1] <= a:
            i += 1
        j = i
        name = spans[open_[-1]][0] if open_ else NONE
        while j < len(idle) and idle[j][0] < b:
            d = min(b, idle[j][1]) - max(a, idle[j][0])
            if d > 0:
                out[name] = out.get(name, 0.0) + d
            j += 1

    for when, kind, _, k in events:
        if when > t:
            charge(t, when)
            t = when
        if kind:
            open_.append(k)
        else:
            open_.remove(k)
    charge(t, idle[-1][1])
    return out


def reduce(profile, window_span: str = "bench.window") -> Dict:
    """``seconds``: each program span's seconds inside the window (clipped
    to it); ``count``: how many of each; ``idle_s``: the first chip's
    idle seconds inside the window by the innermost program span open over
    them."""
    lo, hi = trace.window(trace.host_spans(profile), window_span)
    spans = program_spans(profile)
    seconds: Dict[str, float] = {}
    count: Dict[str, int] = {}
    for name, s, e in spans:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            seconds[name] = seconds.get(name, 0.0) + d / 1e9
            count[name] = count.get(name, 0) + 1
    ops = trace.device_ops(profile)
    idle: List[trace.Interval] = []
    if ops:
        first = sorted(ops)[0]
        idle = trace.gaps([(s, e) for _, s, e in ops[first]], lo, hi)
    inside = [(n, max(s, lo), min(e, hi)) for n, s, e in spans if e > lo and s < hi]
    by_span = idle_by_span(idle, inside)
    return {"seconds": seconds, "count": count,
            "idle_s": {n: v / 1e9 for n, v in
                       sorted(by_span.items(), key=lambda kv: -kv[1])}}


def traced_run(log_dir: Optional[str] = None) -> Dict:
    """:func:`reduce` of the profile a traced run left in ``log_dir``
    (default: the harness's), logged on stderr; empty where there is
    none."""
    log_dir = log_dir or str(cells.ROOT / harness.TRACE_DIR)
    try:
        path = trace.latest_xplane(log_dir)
    except FileNotFoundError:
        return {"seconds": {}, "count": {}, "idle_s": {}}
    out = reduce(trace.load(path))
    print("trace idle by program span: " + json.dumps(out["idle_s"]),
          file=sys.stderr, flush=True)
    print("trace program spans in window: "
          + json.dumps({n: [out["count"][n], s] for n, s in out["seconds"].items()}),
          file=sys.stderr, flush=True)
    return out


def window_seconds(ctx: Dict, *names: str) -> Optional[List[float]]:
    """Seconds inside the traced window of each of ``names``, 0 for a span
    that never opened; None in an untraced run or where the program does
    not catalogue a name."""
    if not ctx.get("trace") or not catalogued(*names):
        return None
    if "spans" not in ctx:
        ctx["spans"] = traced_run()["seconds"]
    return [ctx["spans"].get(n, 0.0) for n in names]


def window_share(ctx: Dict, name: str) -> Optional[float]:
    """Span ``name``'s seconds over the window, in percent."""
    got = window_seconds(ctx, name)
    return None if got is None else 100.0 * got[0] / ctx["window_s"]
