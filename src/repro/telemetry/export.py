"""Snapshot exporters: structured JSON and Prometheus-style text, plus
the per-phase wall-time breakdown the benchmarks emit (DESIGN.md §9.3).

The phase map answers "where does a transaction's wall time go" by
folding every timing histogram into six named phases.  Phases are
*leaf* regions (the instrumented code times the innermost kernel call,
not the enclosing verb), so their sums are disjoint and the residual —
``python_glue`` — is exactly the interpreter time between kernels: the
number the 7.5x OLTP gap hunt is about.
"""

from __future__ import annotations

import json
from typing import Dict, List, Optional, Tuple

from .metrics import REGISTRY, Registry, _state

# phase -> histogram-name prefixes whose total time it absorbs.  Every
# prefix is a leaf region; see the module docstring for why that makes
# the sums disjoint.
PHASE_SOURCES: Dict[str, Tuple[str, ...]] = {
    "encode": ("repro.core.encode",),
    "decode": ("repro.core.decode",),
    "jit_compile": ("repro.plan.compile", "repro.plan.pallas_pack",
                    "repro.exec.lower"),
    "fsync": ("repro.wal.fsync",),
    "fault_in": ("repro.residency.fault_in",),
    "spill": ("repro.residency.spill",),
}


def snapshot(
    registry: Optional[Registry] = None,
    prefix: Optional[Tuple[str, ...] | str] = None,
) -> Dict:
    """JSON-friendly view of the registry: counters and histogram
    summaries (count + total + p50/p95/p99/max in microseconds).

    ``prefix`` filters metric names — the per-subsystem ``stats()``
    sections use it so a store reports store/core/wal metrics, not the
    whole engine.
    """
    reg = registry or REGISTRY

    def keep(name: str) -> bool:
        return prefix is None or name.startswith(prefix)

    return {
        "enabled": _state.enabled,
        "counters": {
            n: c.value for n, c in sorted(reg.counters().items()) if keep(n)
        },
        "histograms": {
            n: h.summary()
            for n, h in sorted(reg.histograms().items())
            if keep(n) and h.count
        },
    }


def _prom_name(name: str) -> str:
    return name.replace(".", "_").replace("-", "_")


def to_prometheus(registry: Optional[Registry] = None) -> str:
    """Prometheus-style text exposition of the registry.

    Counters export as ``<name>_total``; histograms as a summary
    (quantile-labelled gauges plus ``_sum``/``_count``) — enough for a
    scrape-and-graph loop without pulling in a client library.
    """
    reg = registry or REGISTRY
    lines: List[str] = []
    for n, c in sorted(reg.counters().items()):
        pn = _prom_name(n)
        lines.append(f"# TYPE {pn}_total counter")
        lines.append(f"{pn}_total {c.value}")
    for n, h in sorted(reg.histograms().items()):
        if not h.count:
            continue
        pn = _prom_name(n) + "_us"
        lines.append(f"# TYPE {pn} summary")
        for q in (0.5, 0.95, 0.99):
            lines.append(f'{pn}{{quantile="{q}"}} {h.percentile(q) / 1e3:.3f}')
        lines.append(f"{pn}_sum {h.sum_ns / 1e3:.3f}")
        lines.append(f"{pn}_count {h.count}")
    return "\n".join(lines) + "\n"


def phase_breakdown(
    wall_s: float,
    registry: Optional[Registry] = None,
    since: Optional[Dict[str, float]] = None,
) -> Dict:
    """Fold the timing histograms into the six-phase wall-time breakdown.

    ``since`` is a prior ``Registry.hist_seconds()`` map; passing it
    diffs against that point so a bench can scope the breakdown to just
    its measured region without resetting the registry.  ``coverage`` is
    the measured (non-residual) fraction of wall time; the residual is
    reported as the ``python_glue`` phase.
    """
    reg = registry or REGISTRY
    sums = reg.hist_seconds()
    if since:
        sums = {n: v - since.get(n, 0.0) for n, v in sums.items()}
    phases: Dict[str, float] = {}
    for phase, prefixes in PHASE_SOURCES.items():
        phases[phase] = round(
            sum(v for n, v in sums.items() if n.startswith(prefixes)), 6
        )
    measured = sum(phases.values())
    wall_s = float(wall_s)
    glue = max(0.0, wall_s - measured)
    phases["python_glue"] = round(glue, 6)
    total = measured + glue
    return {
        "wall_s": round(wall_s, 6),
        "phases_s": phases,
        "phase_frac": {
            n: round(v / wall_s, 4) if wall_s > 0 else 0.0
            for n, v in phases.items()
        },
        # fraction of wall the phases sum to.  ~1.0 is healthy; far above
        # 1.0 means timers double-count (a leaf landed inside another
        # leaf); far below can't happen by construction (the residual is
        # python_glue) — so the CI gate checks coverage >= 0.9 AND the
        # kernel phases being separately present.
        "coverage": round(total / wall_s, 4) if wall_s > 0 else 0.0,
        # the directly-instrumented share of wall; 1 - measured_frac is
        # interpreter glue — the 7.5x-gap number (DESIGN.md §9.4)
        "measured_frac": round(measured / wall_s, 4) if wall_s > 0 else 0.0,
    }


def dumps(registry: Optional[Registry] = None, **kw) -> str:
    return json.dumps(snapshot(registry, **kw), indent=2, sort_keys=True)
