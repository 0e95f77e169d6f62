"""Compiled execution engine tests (DESIGN.md §11, ISSUE 10).

Covers the vectorized key router's bit-identity against the scalar FNV
reference, the prepared-plan cache (hit/miss accounting, epoch-keyed
invalidation under adaptive refit vs merge, per-table isolation, schema
checks), replay bit-identity across decode backends and invalidations,
and the digit-cap string path (variable-length digit tokens round-trip
identically on the scalar and plan coders, padding drained).
"""

import numpy as np
import pytest

from repro.core import ColumnSpec
from repro.core.blitzcrank import TableCodec
from repro.db import Database, TableSchema, stable_key_hash
from repro.exec import PreparedOp, shard_keys, stable_key_hash_batch
from repro.exec.prepared import batch_bucket
from repro.oltp import tpcc

ORDERLINE = TableSchema(
    "orderline", tpcc.ORDERLINE_SCHEMA, ("ol_o_id", "ol_number"))


def _orderline_table(n_rows=300, n_shards=2, seed=0):
    db = Database(backend="blitzcrank", n_shards=n_shards)
    rows = tpcc.gen_orderline(n_rows, seed=seed)
    table = db.create_table(ORDERLINE, sample_rows=rows)
    table.insert_many(rows)
    return db, table, rows


class TestRouter:
    def test_int_keys_bit_identical(self):
        rng = np.random.default_rng(0)
        keys = [int(v) for v in rng.integers(-(1 << 61), 1 << 61, 500)]
        keys += [0, 1, -1, 255, 256, -256, (1 << 61) - 1, -(1 << 61) + 1]
        got = stable_key_hash_batch(keys, 1)
        want = np.array([stable_key_hash(k) for k in keys], np.uint64)
        assert (got == want).all()

    def test_composite_keys_bit_identical(self):
        rng = np.random.default_rng(1)
        keys = [(int(a), int(b), int(c)) for a, b, c in zip(
            rng.integers(0, 1 << 40, 300),
            rng.integers(-(1 << 20), 1 << 20, 300),
            rng.integers(0, 100, 300))]
        got = stable_key_hash_batch(keys, 3)
        want = np.array([stable_key_hash(k) for k in keys], np.uint64)
        assert (got == want).all()

    def test_non_int_parts_fall_back_identically(self):
        keys = [("TX", 1), ("CA", 2), ("NY", 3)]
        got = stable_key_hash_batch(keys, 2)
        want = np.array([stable_key_hash(k) for k in keys], np.uint64)
        assert (got == want).all()

    def test_magnitude_edge_falls_back_identically(self):
        keys = [1 << 62, -(1 << 62), (1 << 63) - 1, 5]
        got = stable_key_hash_batch(keys, 1)
        want = np.array([stable_key_hash(k) for k in keys], np.uint64)
        assert (got == want).all()

    def test_shard_keys_matches_scalar_route(self):
        rng = np.random.default_rng(2)
        keys = [(int(a), int(b)) for a, b in zip(
            rng.integers(0, 1 << 30, 200), rng.integers(0, 1 << 10, 200))]
        for n_shards in (1, 2, 5):
            got = shard_keys(keys, 2, n_shards)
            want = [stable_key_hash(k) % n_shards for k in keys]
            assert got.tolist() == want


class TestBatchBucket:
    def test_pow2_buckets_with_floor(self):
        assert batch_bucket(0) == 8
        assert batch_bucket(1) == 8
        assert batch_bucket(8) == 8
        assert batch_bucket(9) == 16
        assert batch_bucket(256) == 256
        assert batch_bucket(257) == 512


class TestPreparedCache:
    def test_hit_miss_accounting_per_bucket(self):
        _db, table, rows = _orderline_table()
        keys = [ORDERLINE.key_of(r) for r in rows]
        op = table.prepare("get")
        op.run(keys[:64])
        assert op.cache_info() == {"entries": 1, "hits": 0, "misses": 1}
        op.run(keys[:64])
        op.run(keys[:50])  # same pow2 bucket (64)
        assert op.cache_info()["hits"] == 2
        op.run(keys[:65])  # new bucket (128) -> one more lowering
        assert op.cache_info()["misses"] == 2

    def test_prepare_caches_handles_per_verb(self):
        _db, table, _rows = _orderline_table(n_rows=50)
        assert table.prepare("get") is table.prepare("get")
        assert table.prepare("get") is not table.prepare("insert")

    def test_schema_mismatch_raises(self):
        _db, table, _rows = _orderline_table(n_rows=50)
        other = TableSchema("other", [ColumnSpec("a", "int")], "a")
        with pytest.raises(ValueError, match="schema"):
            table.prepare("get", schema=other)
        # the table's own schema object is accepted
        assert table.prepare("get", schema=table.schema) is table.prepare("get")

    def test_unknown_verb_raises(self):
        _db, table, _rows = _orderline_table(n_rows=50)
        with pytest.raises(ValueError, match="verb"):
            table.prepare("upsert")

    def test_refit_invalidates_exactly_affected_entries(self):
        """An install_codec version bump on one table invalidates that
        table's prepared entries (by epoch mismatch) and no one else's."""
        _db_a, table_a, rows_a = _orderline_table(seed=3)
        _db_b, table_b, rows_b = _orderline_table(seed=4)
        keys_a = [ORDERLINE.key_of(r) for r in rows_a][:64]
        keys_b = [ORDERLINE.key_of(r) for r in rows_b][:64]
        op_a, op_b = table_a.prepare("get"), table_b.prepare("get")
        op_a.run(keys_a)
        op_b.run(keys_b)
        epoch_before = table_a.plan_epoch

        shard = table_a.shards[0]
        shard.install_codec(
            TableCodec.fit(rows_a, list(ORDERLINE.columns)))
        assert table_a.plan_epoch != epoch_before
        assert table_b.plan_epoch == (0,) * table_b.n_shards

        op_a.run(keys_a)  # epoch mismatch -> re-lower
        op_b.run(keys_b)  # untouched table -> still a hit
        assert op_a.cache_info()["misses"] == 2
        assert op_a.cache_info()["entries"] == 1  # replaced, not grown
        assert op_b.cache_info() == {"entries": 1, "hits": 1, "misses": 1}

    def test_merge_keeps_entries_valid(self):
        """Merges/rewrites that keep the plan leave the epoch unchanged,
        so lowered entries stay valid (no spurious re-lowering)."""
        _db, table, rows = _orderline_table()
        keys = [ORDERLINE.key_of(r) for r in rows][:64]
        op = table.prepare("get")
        op.run(keys)
        epoch = table.plan_epoch
        table.update_many(keys[:16], [dict(r, ol_quantity=int(r["ol_quantity"]) + 1)
                                      for r in rows[:16]])
        for shard in table.shards:
            shard.merge()
        assert table.plan_epoch == epoch
        op.run(keys)
        info = op.cache_info()
        assert info["misses"] == 1 and info["hits"] >= 1

    def test_explicit_invalidate_drops_entries(self):
        _db, table, rows = _orderline_table(n_rows=50)
        keys = [ORDERLINE.key_of(r) for r in rows]
        op = table.prepare("get")
        op.run(keys)
        assert op.cache_info()["entries"] == 1
        op.invalidate()
        assert op.cache_info()["entries"] == 0
        op.run(keys)
        assert op.cache_info()["misses"] == 2


class TestReplayIdentity:
    def test_backends_identical_across_invalidation(self):
        """Replayed reads stay bit-identical numpy-vs-pallas before and
        after a refit bump + migration invalidates the cached plans."""
        _db, table, rows = _orderline_table(n_rows=400)
        keys = [ORDERLINE.key_of(r) for r in rows]
        op = table.prepare("get")
        before_np = op.run(keys, backend="numpy")
        before_pl = op.run(keys, backend="pallas")
        assert before_np == before_pl

        for shard in table.shards:
            shard.install_codec(
                TableCodec.fit(rows, list(ORDERLINE.columns)))
            shard.migrate(limit=1 << 16, resident_only=False)
            shard.merge()
        after_np = op.run(keys, backend="numpy")
        after_pl = op.run(keys, backend="pallas")
        assert after_np == after_pl == before_np

    def test_prepared_matches_legacy_and_scalar_paths(self):
        _db, table, rows = _orderline_table(n_rows=200)
        keys = [ORDERLINE.key_of(r) for r in rows]
        prepared = table.prepare("get").run(keys)
        assert prepared == table.get_many(keys)
        assert prepared[:20] == [table.get(k) for k in keys[:20]]

    def test_session_shares_prepared_handles(self):
        db, table, rows = _orderline_table(n_rows=60)
        keys = [ORDERLINE.key_of(r) for r in rows]
        ses = db.session()
        assert ses.prepared("orderline", "get") is ses.prepared(
            "orderline", "get")
        assert ses.get("orderline", keys) == table.get_many(keys)

    def test_scalar_get_raises_on_missing(self):
        _db, table, _rows = _orderline_table(n_rows=30)
        with pytest.raises(KeyError):
            table.get((999999, 999999))


def _decode_by_template(codec, codes, offsets, tmpl, idx):
    """Rows ``idx`` decoded in order, each under the plan-family member
    that encoded it."""
    out = {}
    for t in np.unique(tmpl[idx]).tolist():
        sel = idx[tmpl[idx] == t]
        rows = codec.decompress_rows(codes, offsets, sel, template=t)
        out.update(zip(sel.tolist(), rows))
    return [out[int(i)] for i in idx]


class TestDigitCaps:
    """Variable-length digit tokens (street numbers) take the cap-padded
    digit path on both the scalar coder and the vectorized plan."""

    SCHEMA = [ColumnSpec("k", "int"), ColumnSpec("addr", "str")]

    @staticmethod
    def _rows(n=400, seed=5):
        rng = np.random.default_rng(seed)
        streets = ["Elm Grove", "Oak Lane", "Pine Road", "Birch Way"]
        return [{"k": i,
                 "addr": f"{int(rng.integers(1, 10 ** int(rng.integers(1, 5))))}"
                         f" {streets[int(rng.integers(0, len(streets)))]}"}
                for i in range(n)]

    def test_scalar_round_trip_all_widths(self):
        rows = self._rows()
        codec = TableCodec.fit(rows, self.SCHEMA)
        for r in rows[:80]:
            block = codec.compress_block([r])
            assert codec.decompress_block(block, 1) == [r]

    def test_plan_matches_scalar_stream_and_decode(self):
        rows = self._rows()
        codec = TableCodec.fit(rows, self.SCHEMA)
        plan = codec.compile()
        assert plan is not None
        codes, offsets, tmpl = codec.compress_rows(rows)
        fast = tmpl >= 0
        assert fast.mean() > 0.9  # digit caps keep 1-4 digit numbers fast
        idx = np.flatnonzero(fast)
        # plan batch decode == original rows (so == scalar stream decode)
        got = _decode_by_template(codec, codes, offsets, tmpl, idx)
        assert got == [rows[int(i)] for i in idx]
        # and the plan's codes for a conforming row match the scalar coder
        for i in map(int, idx[:40]):
            scalar_codes = codec.compress_block([rows[i]])
            assert (codes[offsets[i]:offsets[i + 1]] == scalar_codes).all()

    def test_minority_width_pads_and_drains(self):
        # one 1-digit number among 3-digit ones: encoded at the shared
        # cap with zero padding, which decode must drain exactly
        rows = [{"k": i, "addr": f"{100 + i} Elm Grove"} for i in range(60)]
        rows.append({"k": 60, "addr": "7 Elm Grove"})
        codec = TableCodec.fit(rows, self.SCHEMA)
        for r in (rows[0], rows[-1]):
            block = codec.compress_block([r])
            assert codec.decompress_block(block, 1) == [r]
        codes, offsets, tmpl = codec.compress_rows(rows)
        idx = np.flatnonzero(tmpl >= 0)
        got = _decode_by_template(codec, codes, offsets, tmpl, idx)
        assert got == [rows[int(i)] for i in idx]

    def test_over_cap_digits_escape_but_round_trip(self):
        rows = [{"k": i, "addr": f"{10 + i} Oak Lane"} for i in range(50)]
        codec = TableCodec.fit(rows, self.SCHEMA)
        huge = {"k": 99, "addr": "123456789012 Oak Lane"}  # over any cap
        block = codec.compress_block([huge])
        assert codec.decompress_block(block, 1) == [huge]
