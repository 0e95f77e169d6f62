"""Scan engine (DESIGN.md §8): pushdown == decode-then-filter, bit-identically.

The pushdown scan (zone-map pruning + code-space predicate eval + selective
decode) must return exactly what the decode-everything reference returns —
through tombstones, the delta overlay, revived keys, mixed plan versions,
and a spilled cold tier, on both decode backends.  A seeded random soak
always runs; a hypothesis property deepens the same invariant where
hypothesis is installed (CI installs it; lean local containers may not).
"""

import numpy as np
import pytest

from repro.adaptive import refit_codec
from repro.core.blitzcrank import ColumnSpec
from repro.db import Database, TableSchema
from repro.oltp.store import BlitzStore
from repro.scan import Eq, In, Range, match_all

try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

COLS = [
    ColumnSpec("w", "cat"),
    ColumnSpec("id", "int", growth=8.0),
    ColumnSpec("qty", "int"),
    ColumnSpec("amt", "float", precision=0.01),
]
SCHEMA = TableSchema("t", COLS, "id")


def _row(i, rng):
    return {
        "w": f"w{int(rng.integers(0, 6))}",
        "id": int(i),
        "qty": int(rng.integers(1, 50)),
        "amt": round(float(rng.uniform(0.0, 1000.0)), 2),
    }


def _pred_pool(n):
    return [
        [],
        [Eq("w", "w2")],
        [In("w", ("w0", "w5"))],
        [Eq("w", "nope")],
        [Range("id", lo=int(n * 0.9))],
        [Range("qty", lo=10, hi=20)],
        [Range("amt", lo=250.0, hi=500.0)],
        [Range("id", lo=n // 2), Eq("w", "w1")],
        [Eq("qty", 7)],
        [In("id", (3, n // 2, n - 1, n * 10))],
    ]


def _build_table(n=600, n_shards=2, seed=0, memory_budget=None, churn=True):
    """A blitz table with mixed plan versions, overlay, tombstones, revives."""
    rng = np.random.default_rng(seed)
    rows = [_row(i, rng) for i in range(n)]
    db = Database(backend="blitzcrank", n_shards=n_shards)
    t = db.create_table(SCHEMA, sample_rows=rows[: n // 2], memory_budget=memory_budget)
    t.insert_many(rows[: n // 2])
    # Install a refit codec so later inserts encode under plan v1 while the
    # first half stays on v0 — the scan must handle both in one pass.
    for shard in t.shards:
        shard.install_codec(refit_codec(shard.codec, rows[: n // 4], ["amt"]))
    t.insert_many(rows[n // 2 :])
    deleted: set = set()
    if churn:
        for _ in range(3):
            ups = [int(k) for k in rng.choice(n, size=n // 8, replace=False)]
            upd = [k for k in ups if k not in deleted]
            t.update_many(
                upd,
                [
                    {
                        **t.get(k),
                        "qty": int(rng.integers(1, 50)),
                        "amt": round(float(rng.uniform(0.0, 1000.0)), 2),
                    }
                    for k in upd
                ],
            )
            dels = [
                int(k)
                for k in rng.choice(n, size=n // 10, replace=False)
                if int(k) not in deleted
            ]
            t.delete_many(dels)
            deleted.update(dels)
            revive = sorted(deleted)[: n // 20]
            t.insert_many([_row(k, rng) for k in revive])
            deleted.difference_update(revive)
        t.merge()
        # churn again after the merge so an unmerged overlay + fresh
        # tombstones shadow arena blocks during every scan below
        more = [
            int(k)
            for k in rng.choice(n, size=n // 10, replace=False)
            if int(k) not in deleted
        ]
        t.update_many(
            more, [{**t.get(k), "qty": int(rng.integers(1, 50))} for k in more]
        )
    return db, t


def _reference(t, preds, cols=None):
    out = {}
    for k, row in t.scan():
        if match_all(preds, row):
            out[k] = {c: row[c] for c in cols} if cols is not None else row
    return out


def _check_all_predicates(t, n, cols=None):
    for preds in _pred_pool(n):
        want = _reference(t, preds, cols)
        got = dict(t.scan_where(preds, columns=cols))
        ref = dict(t.scan_where(preds, columns=cols, pushdown=False))
        assert got == want, preds
        assert ref == want, preds


class TestPushdownEqualsReference:
    def test_seeded_soak(self):
        db, t = _build_table(n=600, n_shards=2, seed=1)
        _check_all_predicates(t, 600)
        _check_all_predicates(t, 600, cols=["id", "amt"])

    def test_single_shard(self):
        db, t = _build_table(n=300, n_shards=1, seed=2)
        _check_all_predicates(t, 300)

    def test_under_memory_budget(self):
        """Spilled cold tier: scans read extents through, results identical."""
        db, t = _build_table(n=900, n_shards=2, seed=3, memory_budget=1 << 12)
        res = t.shards[0].stats().get("residency")
        assert res is not None and res["spilled_bytes"] > 0
        _check_all_predicates(t, 900)

    def test_scan_does_not_promote(self):
        db, t = _build_table(n=900, n_shards=1, seed=4, memory_budget=1 << 12)

        def faults():
            return sum(s.stats()["residency"]["faults"] for s in t.shards)

        before = faults()
        hits, stats = t.scan_where([], with_stats=True)
        assert len(hits) == len(t)
        assert stats.spilled_reads > 0  # cold blocks were actually touched
        assert faults() == before  # ...but never faulted into the hot set

    @pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
    def test_property(self):
        @settings(
            max_examples=8, deadline=None, suppress_health_check=list(HealthCheck)
        )
        @given(seed=st.integers(0, 2**16), n_shards=st.integers(1, 3))
        def prop(seed, n_shards):
            db, t = _build_table(n=240, n_shards=n_shards, seed=seed)
            _check_all_predicates(t, 240)

        prop()


class TestBackendsBitIdentical:
    def test_numpy_vs_pallas(self):
        pytest.importorskip("jax")
        db, t = _build_table(n=400, n_shards=2, seed=5)
        for preds in _pred_pool(400):
            a = t.scan_where(preds, backend="numpy")
            b = t.scan_where(preds, backend="pallas")
            assert a == b, preds


class TestZoneMaps:
    def test_monotone_prune(self):
        """An insertion-ordered column prunes whole zone chunks untouched."""
        db, t = _build_table(n=2000, n_shards=1, seed=6, churn=False)
        hits, stats = t.scan_where([Range("id", lo=1800)], with_stats=True)
        assert {k for k, _ in hits} == set(range(1800, 2000))
        assert stats.blocks_pruned >= 1024
        assert stats.rows_decoded <= stats.blocks_total - stats.blocks_pruned

    def test_prune_never_drops_matches(self):
        db, t = _build_table(n=800, n_shards=2, seed=7)
        for lo, hi in [(0, 10), (700, 820), (400, 400), (-5, 3)]:
            preds = [Range("id", lo=lo, hi=hi)]
            assert dict(t.scan_where(preds)) == _reference(t, preds)

    def test_zone_survives_snapshot(self):
        """Zone maps persist through snapshot/restore (store level)."""
        rng = np.random.default_rng(8)
        rows = [_row(i, rng) for i in range(500)]
        store = BlitzStore(COLS, rows[:250])
        store.insert_many(rows)
        store.merge()
        before = store.scan_where([Range("id", lo=450)])
        clone = BlitzStore.from_state(COLS, store.snapshot_state())
        after = clone.scan_where([Range("id", lo=450)])
        assert before.ids == after.ids and before.rows == after.rows
        assert after.stats.blocks_pruned > 0


class TestCodeSpaceEval:
    def test_fast_paths_engaged(self):
        db, t = _build_table(n=800, n_shards=1, seed=9, churn=False)
        _, stats = t.scan_where([Eq("w", "w3")], with_stats=True)
        # either the slot-0 LUT or the prefix decode handled the fast
        # blocks; neither path materializes non-matching rows
        assert stats.blocks_lut + stats.rows_prefix_decoded > 0
        assert stats.rows_decoded < stats.blocks_total

    def test_impossible_literal(self):
        db, t = _build_table(n=400, n_shards=1, seed=10, churn=False)
        hits, stats = t.scan_where([Eq("w", "never-seen")], with_stats=True)
        assert hits == []
        # fast blocks are eliminated entirely in code space; only escaped
        # (slow) blocks still need their unconditional scalar decode
        assert stats.rows_decoded == stats.blocks_scalar
        assert stats.blocks_scalar < stats.blocks_total // 10


class TestAggregate:
    def test_matches_manual_groupby(self):
        db, t = _build_table(n=600, n_shards=2, seed=11)
        preds = [Range("qty", lo=5)]
        got = t.aggregate(
            preds,
            group_by=("w",),
            aggs={
                "n": ("count", None),
                "total": ("sum", "amt"),
                "mean": ("avg", "amt"),
                "lo": ("min", "qty"),
                "hi": ("max", "qty"),
            },
        )
        rows = [r for _, r in t.scan_where(preds)]
        want = {}
        for r in rows:
            g = want.setdefault((r["w"],), {"n": 0, "total": 0.0, "qs": []})
            g["n"] += 1
            g["total"] += r["amt"]
            g["qs"].append(r["qty"])
        assert set(got) == set(want)
        for g, w in want.items():
            assert got[g]["n"] == w["n"]
            assert got[g]["total"] == pytest.approx(w["total"])
            assert got[g]["mean"] == pytest.approx(w["total"] / w["n"])
            assert got[g]["lo"] == min(w["qs"])
            assert got[g]["hi"] == max(w["qs"])

    def test_aggregate_span_encloses_its_scans(self):
        from repro import telemetry

        db, t = _build_table(n=300, n_shards=2, seed=14, churn=False)
        prev = telemetry.set_enabled(True)
        telemetry.reset()
        try:
            t.aggregate([Range("qty", lo=5)], group_by=("w",))
            agg = telemetry.REGISTRY.histogram("repro.db.aggregate")
            scans = telemetry.REGISTRY.histogram("repro.scan.scan_table")
            assert agg.count == 1 and scans.count == 2  # one scan per shard
            assert scans.sum_ns <= agg.sum_ns
        finally:
            telemetry.set_enabled(prev)

    def test_database_query_routes(self):
        db, t = _build_table(n=300, n_shards=1, seed=12)
        preds = [Eq("w", "w1")]
        assert db.query("t", preds) == t.scan_where(preds)
        assert db.query("t", preds, aggs={"n": ("count", None)}) == t.aggregate(
            preds, aggs={"n": ("count", None)}
        )

    def test_unknown_column_raises(self):
        db, t = _build_table(n=200, n_shards=1, seed=13, churn=False)
        with pytest.raises(KeyError):
            t.scan_where([Eq("nope", 1)])
        with pytest.raises(KeyError):
            t.scan_where([], columns=["nope"])


class TestDurability:
    def test_extent_checkpoint_roundtrip(self, tmp_path):
        """Extent-mode checkpoints (offset, length references into a named
        spill file) reopen bit-identically; corrupting the spill file after
        the checkpoint forces the WAL full-history rebuild instead."""
        rng = np.random.default_rng(14)
        rows = [_row(i, rng) for i in range(1200)]
        spill = tmp_path / "t.spill"

        db = Database(
            backend="blitzcrank",
            durability=str(tmp_path / "root"),
            store_kwargs={"spill_path": str(spill)},
        )
        t = db.create_table(SCHEMA, sample_rows=rows[:200], memory_budget=1 << 12)
        t.insert_many(rows)
        upd = list(range(0, 1200, 7))
        t.update_many(upd, [{**t.get(k), "qty": 99} for k in upd])
        keys = list(range(1200))
        want = t.get_many(keys)
        assert t.shards[0].stats()["residency"]["spilled_bytes"] > 0
        db.close()  # final checkpoint references spill extents

        db2 = Database.open(str(tmp_path / "root"))
        assert db2["t"].get_many(keys) == want
        db2.close()

        # tear the spill file: recovery must fall back to WAL replay
        data = bytearray(spill.read_bytes())
        mid = len(data) // 2
        data[mid : mid + 64] = b"\xff" * 64
        spill.write_bytes(bytes(data))
        db3 = Database.open(str(tmp_path / "root"))
        assert db3["t"].get_many(keys) == want
        db3.close()
