"""The program's own spans in a traced run: the sweep that puts the chip's
idle time down to them, their reduction from a profile, and the per-layer
readers that read them, each with and without its source."""

import pytest

from benchmarks.chip import cells, program_spans

NEW_SHARES = ("decode_prepare_share", "decode_wait_share", "materialize_share",
              "gc_share")

HAND_MADE = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 12000000 duration_ps: 2000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "_delayed_decode_jit.1" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 18000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 10000000 }
    events { metadata_id: 4 offset_ps: 6000000 duration_ps: 2000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "repro.decode.prepare" } }
  event_metadata { key: 3 value { id: 3 name: "repro.db.aggregate" } }
  event_metadata { key: 4 value { id: 4 name: "repro.runtime.gc" } }
}
"""


@pytest.mark.parametrize("idle, spans, want", [
    # nested spans: the innermost one open takes the time
    ([(0, 100)], [("repro.a", 10, 90), ("repro.b", 20, 40)],
     {"none": 20, "repro.a": 60, "repro.b": 20}),
    # a gap with no span open at all
    ([(50, 70)], [("repro.a", 0, 40), ("repro.b", 80, 90)], {"none": 20}),
    # a gap that straddles two spans, and time between them
    ([(30, 70)], [("repro.a", 0, 40), ("repro.b", 60, 90)],
     {"repro.a": 10, "none": 20, "repro.b": 10}),
    # two gaps under one span; the span's busy stretch is not charged
    ([(0, 10), (20, 30)], [("repro.a", 0, 30)], {"repro.a": 20}),
    # spans that start together: the longer one is the outer one
    ([(0, 10)], [("repro.b", 0, 5), ("repro.a", 0, 10)],
     {"repro.b": 5, "repro.a": 5}),
    # an empty span changes nothing; no idle time reads nothing
    ([(0, 10)], [("repro.a", 5, 5)], {"none": 10}),
    ([], [("repro.a", 0, 10)], {}),
])
def test_idle_by_span_sweep(idle, spans, want):
    assert program_spans.idle_by_span(idle, spans) == pytest.approx(want)


def test_reduce_a_hand_made_trace():
    from jax.profiler import ProfileData

    out = program_spans.reduce(ProfileData.from_text_proto(HAND_MADE))
    # window [1, 19) us; each span clipped to it
    assert out["seconds"] == pytest.approx({"repro.decode.prepare": 3e-6,
                                            "repro.db.aggregate": 10e-6,
                                            "repro.runtime.gc": 2e-6})
    # chip busy [2, 3) and [12, 14) us, idle 15 us of the window
    assert out["idle_s"] == pytest.approx({"repro.decode.prepare": 2e-6,
                                           "none": 5e-6,
                                           "repro.db.aggregate": 6e-6,
                                           "repro.runtime.gc": 2e-6})
    assert list(out["idle_s"])[0] == "repro.db.aggregate"  # largest first
    assert out["count"] == {"repro.decode.prepare": 1, "repro.db.aggregate": 1,
                            "repro.runtime.gc": 1}


def test_traced_run_reads_a_recorded_cpu_trace(tmp_path, capsys):
    import jax

    from repro import telemetry

    prev = telemetry.set_enabled(True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with telemetry.span("repro.db.aggregate"):
                with telemetry.span("repro.scan.scan_table"):
                    pass
    finally:
        jax.profiler.stop_trace()
        telemetry.set_enabled(prev)
    out = program_spans.traced_run(str(tmp_path))
    assert set(out["seconds"]) == {"repro.db.aggregate", "repro.scan.scan_table"}
    assert out["seconds"]["repro.scan.scan_table"] <= out["seconds"]["repro.db.aggregate"]
    assert out["idle_s"] == {}  # no chip in a CPU trace
    assert "trace idle by program span: {}" in capsys.readouterr().err
    assert program_spans.traced_run(str(tmp_path / "none"))["seconds"] == {}


def test_readers_read_the_profile_once_a_run(monkeypatch):
    calls = []

    def traced_run():
        calls.append(1)
        return {"seconds": {"repro.runtime.gc": 1.0}}

    monkeypatch.setattr(program_spans, "traced_run", traced_run)
    ctx = _ctx()
    del ctx["spans"]
    assert cells.reader("gc_share.txn")(ctx) == pytest.approx(10.0)
    assert cells.reader("decode_wait_share.txn")(ctx) == 0.0
    assert calls == [1] and ctx["spans"] == {"repro.runtime.gc": 1.0}


def _ctx(**kw):
    ctx = {"window_s": 10.0, "trace": {"window_s": 10.0, "busy_s": 0.5},
           "counters": {"repro.decode.kernel_rows": 300,
                        "repro.decode.kernel_rows_padded": 512},
           "spans": {"repro.decode.prepare": 2.0, "repro.decode.wait": 0.5,
                     "repro.decode.materialize": 1.0, "repro.runtime.gc": 0.25,
                     "repro.db.aggregate": 4.0, "repro.scan.scan_table": 3.0}}
    ctx.update(kw)
    return ctx


@pytest.mark.parametrize("metric, want", [
    ("decode_prepare_share.txn", 20.0), ("decode_wait_share.scan", 5.0),
    ("materialize_share.scan", 10.0), ("gc_share.txn", 2.5),
    ("group_by_share.scan", 10.0), ("kernel_row_use.txn", 100 * 300 / 512),
])
def test_new_readers_read_their_source(metric, want):
    assert cells.reader(metric)(_ctx()) == pytest.approx(want)


@pytest.mark.parametrize("base", NEW_SHARES + ("group_by_share",))
def test_span_readers_read_nothing_without_a_trace_and_0_for_an_unopened_span(base):
    read = cells.reader(base)
    assert read(_ctx(trace=None)) is None
    assert read(_ctx(spans={})) == 0.0


def test_group_by_share_is_floored_at_zero():
    spans = {"repro.db.aggregate": 1.0, "repro.scan.scan_table": 1.5}
    assert cells.reader("group_by_share.scan")(_ctx(spans=spans)) == 0.0


def test_kernel_row_use_without_kernel_rows():
    assert cells.reader("kernel_row_use.scan")(_ctx(counters={})) == 0.0


@pytest.mark.parametrize("base", NEW_SHARES + ("group_by_share", "kernel_row_use"))
def test_new_readers_read_nothing_from_a_program_without_their_names(
        base, monkeypatch):
    """A program older than these spans and counters does not catalogue
    them: its traced runs leave the metrics out."""
    from repro.telemetry import catalog

    monkeypatch.setattr(catalog, "CATALOG", frozenset())
    assert cells.reader(base)(_ctx()) is None


def test_reduce_a_recorded_v5e_trace_without_program_spans():
    """A program without spans in the trace: all idle time is ``none``."""
    from benchmarks.chip import trace

    profile = trace.load(str(cells.ROOT / "tests" / "bench_chip" / "fixtures"
                             / "v5e_mix_tiny.xplane.pb"))
    tr = trace.reduce(profile, "bench.window", "delayed_decode")
    out = program_spans.reduce(profile)
    assert out["seconds"] == {} and out["count"] == {}
    assert list(out["idle_s"]) == ["none"]
    # the sweep covers exactly the first chip's idle time in the window
    assert out["idle_s"]["none"] == pytest.approx(tr["window_s"] - tr["busy_s"])
