"""The ``device_gather_share`` reader: kernel rows gathered on the chip
from an arena mirror over all kernel rows in the window."""

import pytest

from benchmarks.chip import cells

GATHERED = "repro.decode.device_gather.rows"
ROWS = "repro.decode.kernel_rows"


def _ctx(counters):
    return {"window_s": 10.0, "counters": counters}


@pytest.mark.parametrize("gathered, rows, want", [
    (65536, 65536, 100.0), (300, 1200, 25.0), (0, 4096, 0.0), (0, 0, 0.0),
])
def test_device_gather_share_reads_its_counters(gathered, rows, want):
    ctx = _ctx({GATHERED: gathered, ROWS: rows})
    assert cells.reader("device_gather_share.txn")(ctx) == pytest.approx(want)


def test_device_gather_share_without_gathered_rows_is_zero():
    assert cells.reader("device_gather_share.txn")(_ctx({ROWS: 4096})) == 0.0
    assert cells.reader("device_gather_share.txn")(_ctx({})) == 0.0


def test_device_gather_share_reads_nothing_from_a_program_without_its_counter(
        monkeypatch):
    """A program older than the arena mirror does not catalogue the
    counter: its traced runs leave the metric out."""
    from repro.telemetry import catalog

    monkeypatch.setattr(catalog, "CATALOG", catalog.CATALOG - {GATHERED})
    assert cells.reader("device_gather_share.txn")(_ctx({ROWS: 10})) is None
