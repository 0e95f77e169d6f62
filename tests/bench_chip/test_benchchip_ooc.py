"""The larger-than-memory cell ``tpcc_w1_ooc25.mix`` at a test's size: a
sound run is correct and drives the cold tier, a fault-in that serves a
neighbour's codes fails the check, and the cold tier's three readers read
what they should."""

import time

import pytest

from benchchip_util import CPU_DEVICE
from benchmarks.chip import cells, fixture_trace, harness, reference
from repro.core.blitzcrank import CompressedTable

CELL = "tpcc_w1_ooc25.mix"
LIMIT_SHARE = 0.25  # the configuration's share of the loaded code bytes


def _tiny_config(cell, seed):
    """The cell cut to a test's size, its limit 25% of the code bytes of
    the same tiny population loaded with no limit."""
    config = fixture_trace.tiny(cell)
    store = dict(config["store"])
    store.pop("memory_limit")
    schemas = reference.load_schemas(config, cell.config_path.parent)
    pop = cell.module("populations", config["population"]).generate(config, seed)
    free = harness.build_store(dict(config, store=store), schemas, pop)
    config["store"]["memory_limit"] = int(LIMIT_SHARE * free.code_bytes)
    free.close()
    return config


def _run(monkeypatch, seed=7, seconds=0.5):
    """One tiny run; the result and the window's counter deltas."""
    cell = cells.Cell(CELL, cells.load_bench())
    config = _tiny_config(cell, seed)
    seen = {}
    check = harness.Run.check

    def keep(run):
        seen["run"] = run
        return check(run)

    monkeypatch.setattr(harness.Run, "check", keep)
    res = harness.run_cell(cell, seed, seconds, False, CPU_DEVICE,
                           time.perf_counter(), config=config)
    run = seen["run"]
    window = {n: v - run.c0.get(n, 0) for n, v in run.c1.items()}
    return res, window, run


def test_the_configuration_caps_the_database():
    cell = cells.Cell(CELL, cells.load_bench())
    store = cell.config["store"]
    assert store["memory_limit"] > 0
    assert cell.config["population"] == "tpcc" and cell.traffic["kind"] == "txn"
    assert "durability" in cell.config["reduced"]


def test_sound_ooc_mix_is_correct_and_drives_the_cold_tier(monkeypatch):
    res, window, run = _run(monkeypatch)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["checks"]["wrong"]["value"] == 0
    assert window.get("repro.residency.spill.blocks", 0) > 0
    assert window.get("repro.residency.fault_in.blocks", 0) > 0
    db = run.db
    stores = [s for t in db for s in t.shards]
    assert db.resident_code_bytes <= db.memory_limit + len(stores)


def test_a_fault_in_that_serves_a_neighbours_codes_is_not_correct(monkeypatch):
    fault_in = CompressedTable._fault_in

    def planted(self, blocks):
        rows = self._block2row[blocks].copy()
        fault_in(self, blocks)
        if rows.size >= 2:  # one row now reads the next one's codes
            self._row2block[rows[0]] = self._row2block[rows[1]]

    monkeypatch.setattr(CompressedTable, "_fault_in", planted)
    res, _, _ = _run(monkeypatch)
    assert not res["correct"]
    assert res["checks"]["wrong"]["value"] > 0 or res["failed"] > 0


def _ctx(spans, counters, trace=True):
    return {"window_s": 20.0, "spans": spans, "counters": counters,
            "trace": {"window_s": 20.0} if trace else None}


@pytest.mark.parametrize("metric, span", [
    ("fault_in_share.txn", "repro.residency.fault_in"),
    ("spill_share.txn", "repro.residency.spill"),
])
def test_cold_tier_span_shares_read_their_spans(metric, span):
    ctx = _ctx({span: 3.0, "repro.core.decode": 5.0}, {})
    assert cells.reader(metric)(ctx) == pytest.approx(15.0)
    assert cells.reader(metric)(_ctx({}, {})) == 0.0
    assert cells.reader(metric)(_ctx({span: 3.0}, {}, trace=False)) is None


@pytest.mark.parametrize("faults, planned, scalar, want", [
    (250, 900, 100, 25.0), (0, 4096, 0, 0.0), (0, 0, 0, 0.0), (7, 0, 7, 100.0),
])
def test_cold_read_share_reads_its_counters(faults, planned, scalar, want):
    ctx = _ctx({}, {"repro.residency.fault_in.blocks": faults,
                    "repro.core.decode.rows": planned,
                    "repro.core.decode.scalar_rows": scalar})
    assert cells.reader("cold_read_share.txn")(ctx) == pytest.approx(want)


@pytest.mark.parametrize("metric, name", [
    ("fault_in_share.txn", "repro.residency.fault_in"),
    ("spill_share.txn", "repro.residency.spill"),
    ("cold_read_share.txn", "repro.residency.fault_in.blocks"),
    ("cold_read_share.txn", "repro.core.decode.scalar_rows"),
])
def test_cold_tier_readers_read_nothing_from_a_program_without_their_names(
        monkeypatch, metric, name):
    from repro.telemetry import catalog

    monkeypatch.setattr(catalog, "CATALOG", catalog.CATALOG - {name})
    ctx = _ctx({name: 1.0}, {name: 10, "repro.core.decode.rows": 10})
    assert cells.reader(metric)(ctx) is None


def test_the_cell_lists_the_cold_tier_metrics_and_not_the_factor():
    cell = cells.Cell(CELL, cells.load_bench())
    names = {m["name"] for m in cell.per_layer}
    assert {"fault_in_share.txn", "spill_share.txn",
            "cold_read_share.txn"} <= names
    assert "compression_factor" not in names
    mix = {m["name"] for m in cells.Cell("tpcc_w1.mix", cells.load_bench()).per_layer}
    assert names == (mix - {"compression_factor"}) | {
        "fault_in_share.txn", "spill_share.txn", "cold_read_share.txn"}
    assert all(m["moves"] == "txn_per_s" for m in cell.per_layer)
