"""Telemetry layer (DESIGN.md §9): metric primitives, spans, exporters.

The load-bearing invariants: histogram merge is lossless (commutative,
associative, equal to observing the concatenated stream), spans land in a
recording profiler trace and cost nothing there otherwise, the decode's
stage spans stay inside its phase, ScanStats keeps its attribute
API while flowing deltas into shared registry counters without
double-counting on merge, and — most importantly — disabling telemetry
changes *nothing* about engine behaviour: an enabled and a disabled run
produce bit-identical store state.
"""

import dataclasses
import gc
import glob
import os
import pickle
import random

import numpy as np
import pytest

from repro import telemetry
from repro.core.blitzcrank import ColumnSpec, TableCodec
from repro.oltp.store import BlitzStore
from repro.scan.engine import ScanStats
from repro.telemetry import (
    N_BUCKETS,
    PHASE_SOURCES,
    Histogram,
    Registry,
    bucket_index,
    bucket_lo,
)
from repro.telemetry import spans as spans_mod
from repro.telemetry.catalog import CATALOG

# The spans and counters that split a decode and time row building, the
# group-by and the collector: none of them may fold into a phase.
STAGE_NAMES = (
    "repro.decode.prepare",
    "repro.decode.wait",
    "repro.decode.materialize",
    "repro.decode.kernel_rows",
    "repro.decode.kernel_rows_padded",
    "repro.db.aggregate",
    "repro.runtime.gc",
)


@pytest.fixture(autouse=True)
def _clean_registry():
    """Each test sees a zeroed global registry and enabled telemetry."""
    prev = telemetry.set_enabled(True)
    telemetry.reset()
    yield
    telemetry.set_enabled(prev)
    telemetry.reset()


# -- histogram geometry ---------------------------------------------------


def test_bucket_boundaries():
    assert bucket_index(0) == 0
    assert bucket_index(0.5) == 0
    assert bucket_index(1.0) == 0
    # a point safely inside bucket i lands in bucket i (buckets are a
    # factor 2**0.25 ~ 1.19 wide, so *1.1 stays inside)
    for i in range(0, 220, 7):
        inside = bucket_lo(i) * 1.1
        assert bucket_index(inside) == i
        assert bucket_lo(i) <= inside < bucket_lo(i + 1)
    # durations beyond the last edge clamp instead of overflowing
    assert bucket_index(1e30) == N_BUCKETS - 1


def test_histogram_observe_and_percentiles():
    h = Histogram("t")
    for ns in (100, 200, 300, 400, 1_000_000):
        h.observe(ns)
    assert h.count == 5
    assert h.sum_ns == 1_001_000
    assert h.min_ns == 100 and h.max_ns == 1_000_000
    # p50 lands near the middle observations, clamped to observed range
    assert 100 <= h.percentile(0.5) <= 400 * 1.2
    # the top quantile reports its bucket's midpoint: within one bucket
    # width (2**0.25 ~ 19%) of the true max, never above it
    assert 1_000_000 / 1.2 <= h.percentile(1.0) <= 1_000_000


def test_empty_histogram_percentiles_are_zero():
    h = Histogram("empty")
    for q in (0.0, 0.5, 0.95, 0.99, 1.0):
        assert h.percentile(q) == 0.0
    s = h.summary()
    assert s["count"] == 0 and s["p99_us"] == 0.0


def _hist_from(samples):
    h = Histogram("x")
    for s in samples:
        h.observe(s)
    return h


def _hist_eq(a, b):
    return (
        a.count == b.count
        and a.sum_ns == b.sum_ns
        and a.min_ns == b.min_ns
        and a.max_ns == b.max_ns
        and a.buckets == b.buckets
    )


def test_merge_is_lossless_commutative_associative():
    rng = random.Random(7)
    sa = [rng.randrange(1, 10**9) for _ in range(200)]
    sb = [rng.randrange(1, 10**6) for _ in range(50)]
    sc = [rng.randrange(10**3, 10**12) for _ in range(80)]

    # merge == observing the concatenated stream
    ab = _hist_from(sa)
    ab.merge(_hist_from(sb))
    assert _hist_eq(ab, _hist_from(sa + sb))

    # commutative
    ba = _hist_from(sb)
    ba.merge(_hist_from(sa))
    assert _hist_eq(ab, ba)

    # associative
    left = _hist_from(sa)
    left.merge(_hist_from(sb))
    left.merge(_hist_from(sc))
    bc = _hist_from(sb)
    bc.merge(_hist_from(sc))
    right = _hist_from(sa)
    right.merge(bc)
    assert _hist_eq(left, right)

    # merging an empty histogram is the identity
    before = _hist_from(sa)
    before.merge(Histogram("e"))
    assert _hist_eq(before, _hist_from(sa))


# -- spans and the profiler trace ------------------------------------------


def _host_events(log_dir):
    """``{name: (start_ns, end_ns)}`` of the ``repro.`` events on the host
    plane of the trace recorded under ``log_dir`` (last one of a name)."""
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    profile = ProfileData.from_file(path[0])
    out = {}
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("repro."):
                        out[e.name] = (e.start_ns, e.start_ns + e.duration_ns)
    return out


def test_span_nesting_depth_and_histogram(tmp_path):
    jax = pytest.importorskip("jax")
    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.span("repro.test.outer"):
            with telemetry.span("repro.test.inner"):
                gc.collect()
    finally:
        jax.profiler.stop_trace()
    ev = _host_events(str(tmp_path))
    (o0, o1), (i0, i1) = ev["repro.test.outer"], ev["repro.test.inner"]
    assert o0 <= i0 <= i1 <= o1  # the inner span sits inside the outer
    g0, g1 = ev["repro.runtime.gc"]  # the collector's span, inside both
    assert i0 <= g0 <= g1 <= i1
    assert telemetry.REGISTRY.histogram("repro.test.outer").count == 1
    assert telemetry.REGISTRY.histogram("repro.test.inner").count == 1


class _SpyAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``."""

    recording = False
    opened: list = []

    def __init__(self, name):
        self.name = name

    @classmethod
    def is_enabled(cls):
        return cls.recording

    def __enter__(self):
        self.opened.append(("enter", self.name))

    def __exit__(self, *exc):
        self.opened.append(("exit", self.name))


def test_span_annotates_only_while_a_trace_records(monkeypatch):
    monkeypatch.setattr(spans_mod, "_annotation_cls", _SpyAnnotation)
    monkeypatch.setattr(_SpyAnnotation, "opened", [])
    with telemetry.span("repro.test.quiet"):
        pass
    assert _SpyAnnotation.opened == []
    assert telemetry.REGISTRY.histogram("repro.test.quiet").count == 1
    monkeypatch.setattr(_SpyAnnotation, "recording", True)
    with telemetry.span("repro.test.loud"):
        pass
    assert _SpyAnnotation.opened == [("enter", "repro.test.loud"),
                                     ("exit", "repro.test.loud")]


def test_gc_span_times_each_collection():
    h = telemetry.REGISTRY.histogram("repro.runtime.gc")
    before = h.count
    gc.collect()
    assert h.count >= before + 1 and h.sum_ns > 0


def test_disabled_mode_is_inert(monkeypatch):
    monkeypatch.setattr(spans_mod, "_annotation_cls", _SpyAnnotation)
    monkeypatch.setattr(_SpyAnnotation, "opened", [])
    monkeypatch.setattr(_SpyAnnotation, "recording", True)
    c = telemetry.counter("repro.test.c")
    h = telemetry.histogram("repro.test.h")
    gc_h = telemetry.REGISTRY.histogram("repro.runtime.gc")
    prev = telemetry.set_enabled(False)
    try:
        c.add(5)
        h.observe(123)
        with telemetry.span("repro.test.h"):
            gc.collect()
        assert c.value == 0
        assert h.count == 0
        assert gc_h.count == 0  # the collector's hook records nothing either
        assert _SpyAnnotation.opened == []  # nor does anything reach a trace
    finally:
        telemetry.set_enabled(prev)


def test_stage_names_are_catalogued_and_fold_into_no_phase():
    prefixes = tuple(p for ps in PHASE_SOURCES.values() for p in ps)
    for name in STAGE_NAMES:
        assert name in CATALOG
        assert not name.startswith(prefixes), name


def test_pallas_decode_fills_its_stage_spans_and_row_counters():
    pytest.importorskip("jax")
    rng = np.random.default_rng(3)
    rows = [{"id": i, "qty": int(rng.integers(1, 100)),
             "amount": float(np.round(rng.uniform(0.01, 999.99), 2))}
            for i in range(600)]
    schema = [ColumnSpec("id", "int"), ColumnSpec("qty", "int"),
              ColumnSpec("amount", "float", precision=0.01)]
    plan = TableCodec.fit(rows, schema, sample=256).compile()
    assert plan.pallas_ok
    syms, ok = plan.encode_rows(rows)
    codes, offsets = plan.encode_batch(syms[ok])
    sel = np.arange(300)
    telemetry.reset()
    out = plan.decode_select(codes, offsets, sel, backend="pallas")
    np.testing.assert_array_equal(out, syms[ok][sel])
    reg = telemetry.REGISTRY
    prepare = reg.histogram("repro.decode.prepare")
    wait = reg.histogram("repro.decode.wait")
    decode = reg.histogram("repro.core.decode")
    assert prepare.count == wait.count == decode.count == 1
    assert prepare.sum_ns + wait.sum_ns <= decode.sum_ns
    assert reg.counter("repro.decode.kernel_rows").value == 300
    assert reg.counter("repro.decode.kernel_rows_padded").value == 512
    # the numpy path has no kernel: no stage spans, no kernel rows
    plan.decode_select(codes, offsets, sel, backend="numpy")
    assert prepare.count == 1 and decode.count == 2
    assert reg.counter("repro.decode.kernel_rows").value == 300


def test_materialize_span_covers_row_building():
    rows = [{"id": i, "qty": i % 7} for i in range(200)]
    plan = TableCodec.fit(rows, [ColumnSpec("id", "int"),
                                 ColumnSpec("qty", "int")]).compile()
    syms, ok = plan.encode_rows(rows)
    telemetry.reset()
    assert plan.decode_syms_to_rows(syms[:5]) == rows[:5]
    assert plan.decode_syms_to_rows(syms[:5], columns=["qty"]) == [
        {"qty": r["qty"]} for r in rows[:5]]
    assert telemetry.REGISTRY.histogram("repro.decode.materialize").count == 2


# -- ScanStats on shared registry counters --------------------------------


def test_scan_stats_attribute_api_and_registry():
    c = telemetry.counter("repro.scan.rows_decoded")
    s = ScanStats()
    assert s.rows_decoded == 0
    s.rows_decoded = 5
    assert s.rows_decoded == 5
    assert c.value == 5
    # overwriting flows the *delta*, so the registry nets to the final value
    s.rows_decoded = 3
    assert c.value == 3
    s2 = ScanStats(rows_decoded=4, blocks_total=2)
    assert c.value == 7


def test_scan_stats_merge_does_not_double_count():
    c = telemetry.counter("repro.scan.blocks_pruned")
    a = ScanStats(blocks_pruned=3)
    b = ScanStats(blocks_pruned=4)
    assert c.value == 7  # both scans registered their deltas when they ran
    a.merge(b)
    assert a.blocks_pruned == 7
    # merge is registry-neutral: folding per-shard stats into a table
    # total must not re-register work the shards already counted
    assert c.value == 7


def test_scan_stats_equality_and_repr():
    a = ScanStats(rows_decoded=2)
    b = ScanStats(rows_decoded=2)
    assert a == b
    assert "rows_decoded" in repr(a)


# -- exporters ------------------------------------------------------------


def test_snapshot_prefix_filter():
    # blitzlint: waive[BL002] -- scratch names probe registry prefix filtering; cataloguing them would defeat the test
    telemetry.counter("repro.db.x").add(1)
    # blitzlint: waive[BL002] -- scratch names probe registry prefix filtering; cataloguing them would defeat the test
    telemetry.counter("repro.wal.y").add(2)
    snap = telemetry.snapshot(prefix="repro.db.")
    assert "repro.db.x" in snap["counters"]
    assert "repro.wal.y" not in snap["counters"]
    snap2 = telemetry.snapshot(prefix=("repro.db.", "repro.wal."))
    assert {"repro.db.x", "repro.wal.y"} <= set(snap2["counters"])


def test_prometheus_exposition_format():
    telemetry.counter("repro.db.get_many.rows").add(3)
    telemetry.histogram("repro.db.get_many").observe(1500)
    text = telemetry.to_prometheus()
    assert "repro_db_get_many_rows_total 3" in text
    assert 'repro_db_get_many_us{quantile="0.5"}' in text
    assert "repro_db_get_many_us_count 1" in text


def test_phase_breakdown_folds_and_covers():
    reg = Registry()
    reg.histogram("repro.core.encode").observe(0.2e9)
    reg.histogram("repro.core.decode").observe(0.1e9)
    reg.histogram("repro.wal.fsync").observe(0.1e9)
    bd = telemetry.phase_breakdown(0.5, registry=reg)
    assert bd["phases_s"]["encode"] == pytest.approx(0.2)
    assert bd["phases_s"]["decode"] == pytest.approx(0.1)
    assert bd["phases_s"]["fsync"] == pytest.approx(0.1)
    assert bd["phases_s"]["python_glue"] == pytest.approx(0.1)
    assert bd["coverage"] == 1.0
    assert sum(bd["phases_s"].values()) == pytest.approx(0.5)

    # `since` scopes the fold to work done after the captured baseline
    base = reg.hist_seconds()
    reg.histogram("repro.core.encode").observe(0.3e9)
    bd2 = telemetry.phase_breakdown(0.4, registry=reg, since=base)
    assert bd2["phases_s"]["encode"] == pytest.approx(0.3)
    assert bd2["phases_s"]["python_glue"] == pytest.approx(0.1)


def test_registry_reset_keeps_handles_valid():
    c = telemetry.counter("repro.test.reset")
    c.add(9)
    telemetry.reset()
    assert c.value == 0
    c.add(2)
    assert telemetry.counter("repro.test.reset") is c
    assert c.value == 2


# -- disabled telemetry changes nothing about engine behaviour ------------

COLS = [
    ColumnSpec("w", "cat"),
    ColumnSpec("id", "int", growth=8.0),
    ColumnSpec("qty", "int"),
    ColumnSpec("amt", "float", precision=0.01),
]


def _drive_store(enabled: bool):
    prev = telemetry.set_enabled(enabled)
    try:
        rng = random.Random(1234)
        rows = [
            {
                "w": f"w{rng.randrange(6)}",
                "id": i,
                "qty": rng.randrange(1, 50),
                "amt": round(rng.uniform(0, 1000), 2),
            }
            for i in range(400)
        ]
        store = BlitzStore(COLS, rows, merge_min_bytes=1 << 10)
        store.insert_many(rows)
        for i in range(0, 400, 7):
            # stores take full rows; partial-update merging is Table's job
            store.update_many([i], [dict(rows[i], qty=99)])
        store.delete_many(list(range(0, 400, 13)))
        store.merge()
        live = [i for i in range(400) if i % 13]
        got = store.get_many(live[:100])
        return store.snapshot_state(), got
    finally:
        telemetry.set_enabled(prev)


def _neutralize_fit_timings(state):
    # FitStats carries wall-clock fit timings — run-dependent metadata,
    # not store contents.  Zero them so the comparison is about data.
    for codec in state["table"]["codecs"]:
        codec.stats = dataclasses.replace(
            codec.stats, structuring_s=0.0, generation_s=0.0
        )
    return state


def test_enabled_vs_disabled_bit_identical_state():
    state_on, got_on = _drive_store(True)
    state_off, got_off = _drive_store(False)
    assert got_on == got_off
    on = pickle.dumps(_neutralize_fit_timings(state_on))
    off = pickle.dumps(_neutralize_fit_timings(state_off))
    assert on == off
