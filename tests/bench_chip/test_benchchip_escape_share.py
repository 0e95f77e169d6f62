"""The ``escape_share`` reader: rows decoded on the escape path over all
rows decoded in the window."""

import pytest

from benchmarks.chip import cells

SCALAR = "repro.core.decode.scalar_rows"
PLANNED = "repro.core.decode.rows"


def _ctx(counters):
    return {"window_s": 10.0, "counters": counters}


@pytest.mark.parametrize("scalar, planned, want", [
    (30, 970, 3.0), (1, 0, 100.0), (0, 5000, 0.0), (0, 0, 0.0),
])
def test_escape_share_reads_its_counters(scalar, planned, want):
    ctx = _ctx({SCALAR: scalar, PLANNED: planned})
    assert cells.reader("escape_share.txn")(ctx) == pytest.approx(want)


def test_escape_share_without_scalar_rows_is_zero():
    assert cells.reader("escape_share.txn")(_ctx({PLANNED: 4096})) == 0.0
    assert cells.reader("escape_share.txn")(_ctx({})) == 0.0


def test_escape_share_reads_nothing_from_a_program_without_its_counters(
        monkeypatch):
    """A program older than the escape-row counter does not catalogue it:
    its traced runs leave the metric out."""
    from repro.telemetry import catalog

    monkeypatch.setattr(catalog, "CATALOG", catalog.CATALOG - {SCALAR})
    assert cells.reader("escape_share.txn")(_ctx({PLANNED: 10})) is None
