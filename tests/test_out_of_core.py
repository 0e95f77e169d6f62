"""Out-of-core cold tier (DESIGN.md §6): spill/fault correctness.

Property-style tests: under an aggressively tiny ``memory_budget`` every
read must be bit-identical to a fully-resident reference store — through
random insert/update/delete interleavings, ``merge()``, ``rewrite()``,
``migrate_rows()``, and on both decode backends (numpy and pallas).
"""

import numpy as np
import pytest

from repro.core import CompressedTable, TableCodec
from repro.core.arena import FRAME_OVERHEAD, DiskArena
from repro.oltp import tpcc
from repro.oltp.store import BlitzStore, RamanStore, UncompressedStore

SCHEMA, GEN = tpcc.TABLES["orderline"]
TINY = 1 << 13  # 8 KiB: far below any population below, forces deep spill


def _rows_close(got, exp):
    """Row-list equality with float columns compared at model precision.

    The capped and reference stores merge on different cadences (the
    capped arena shrinks at rewrite), so at any instant one may serve a
    raw overlay value where the other serves the re-encoded (quantized)
    one.  Everything non-float must match exactly.
    """
    assert len(got) == len(exp)
    by_name = {c.name: c for c in SCHEMA}
    for g, e in zip(got, exp):
        if g is None or e is None:
            assert g is None and e is None
            continue
        for name, spec in by_name.items():
            if spec.kind == "float":
                assert abs(g[name] - e[name]) <= spec.precision + 1e-9, name
            else:
                assert g[name] == e[name], name


def _rand_row(rng, base):
    r = dict(base[int(rng.integers(0, len(base)))])
    r["ol_quantity"] = int(rng.integers(1, 60))
    r["ol_amount"] = round(float(rng.uniform(0.01, 12000.0)), 2)
    r["ol_o_id"] = int(rng.integers(0, 200))
    return r


def _baseline_makers():
    makers = {
        "silo": UncompressedStore,
        "raman": RamanStore,
    }
    try:
        import zstandard  # noqa: F401

        from repro.oltp.store import ZstdStore

        makers["zstd"] = ZstdStore
    except ImportError:
        pass
    return makers


class TestDiskArena:
    def test_write_read_roundtrip(self):
        arena = DiskArena()
        payloads = [bytes([i]) * (7 + i) for i in range(20)]
        offs = [arena.write(p) for p in payloads]
        for p, off in zip(payloads, offs):
            assert arena.read(off, len(p)) == p
        got = arena.read_many(offs, [len(p) for p in payloads])
        assert got == payloads

    def test_read_many_coalesces_adjacent(self):
        arena = DiskArena()
        seg = b"".join(bytes([i]) * 10 for i in range(8))
        base = arena.write(seg)
        offs = [base + 10 * i for i in range(8)]
        before = arena.reads
        got = arena.read_many(offs, [10] * 8)
        assert got == [bytes([i]) * 10 for i in range(8)]
        assert arena.reads == before + 1  # one pread for the whole range

    def test_compact_in_place(self):
        arena = DiskArena(page_bytes=64)
        payloads = [bytes([i]) * 33 for i in range(10)]
        offs = [arena.write(p) for p in payloads]
        for i in (0, 2, 4, 6, 8):
            arena.free(offs[i], len(payloads[i]))
        keep = [1, 3, 5, 7, 9]
        new_offs = arena.compact(
            [offs[i] for i in keep], [len(payloads[i]) for i in keep]
        )
        for i, off in zip(keep, new_offs):
            assert arena.read(off, len(payloads[i])) == payloads[i]
        assert arena.file_bytes < offs[-1] + 33

    def test_compact_interior_extents(self):
        # Spill segments hold many runs, so live extents have interior
        # (non-page-aligned) offsets; compaction must pack them densely
        # without the write cursor ever clobbering an unread extent.
        arena = DiskArena(page_bytes=4096)
        seg_a = b"A" * 10 + b"B" * 10 + b"C" * 10
        base_a = arena.write(seg_a)
        base_b = arena.write(b"D" * 10)  # page-aligned: offset 4096
        extents = [
            (base_a, 10, b"A" * 10),
            (base_a + 10, 10, b"B" * 10),
            (base_a + 20, 10, b"C" * 10),
            (base_b, 10, b"D" * 10),
        ]
        new_offs = arena.compact(
            [e[0] for e in extents], [e[1] for e in extents]
        )
        for (off, ln, want), new in zip(extents, new_offs):
            assert arena.read(new, ln) == want
        assert arena.file_bytes == 40  # packed dense, file truncated


class TestCompressedTableResidency:
    def _pair(self, n=1500, budget=TINY):
        rows = GEN(n, seed=3)
        codec = TableCodec.fit(rows[:500], SCHEMA)
        ref = CompressedTable(codec)
        ref.extend(rows)
        capped = CompressedTable(codec, memory_budget=budget)
        capped.extend(rows)
        return rows, ref, capped

    def test_reads_bit_identical_under_tiny_budget(self):
        _, ref, capped = self._pair()
        assert capped.spilled_bytes > 0
        rng = np.random.default_rng(0)
        idx = rng.integers(0, len(ref), 600).tolist()
        assert capped.get_many(idx) == ref.get_many(idx)
        for i in idx[:40]:  # scalar read-through path
            assert capped.get(i) == ref.get(i)

    def test_residency_tags_survive_rewrite(self):
        rows, ref, capped = self._pair()
        rng = np.random.default_rng(1)
        idx = rng.choice(len(rows), 200, replace=False).tolist()
        repl = [_rand_row(rng, rows) for _ in idx]
        ref.replace_many(idx, repl)
        capped.replace_many(idx, repl)
        dead = [int(i) for i in rng.choice(len(rows), 50, replace=False)]
        ref.delete_many(dead)
        capped.delete_many(dead)
        ref.rewrite()
        capped.rewrite()  # spilled blocks must carry tags through
        probe = rng.integers(0, len(rows), 500).tolist()
        assert capped.get_many(probe) == ref.get_many(probe)
        res = capped.residency()
        assert res["spilled_blocks"] > 0
        assert res["faults"] >= 0

    def test_budget_bounds_resident_codes(self):
        _, _, capped = self._pair()
        live_codes = capped.used - capped._dead_codes
        assert 2 * live_codes <= capped.memory_budget
        # nbytes means resident memory: the spilled payload is excluded
        assert capped.spilled_bytes > 0
        assert capped.residency()["resident_bytes"] == capped.nbytes


class TestBlitzStoreOutOfCore:
    def _ops(self, store, ref, rows, seed, n_ops=400):
        rng = np.random.default_rng(seed)
        model = {}
        ids = store.insert_many(rows)
        ref_ids = ref.insert_many(rows)
        assert list(ids) == list(ref_ids)
        for i, r in zip(ids, rows):
            model[i] = r
        for _ in range(n_ops):
            op = rng.random()
            live = [i for i in model if ref.is_live(i)]
            if op < 0.30 and live:
                i = int(live[int(rng.integers(0, len(live)))])
                r = _rand_row(rng, rows)
                store.update(i, r)
                ref.update(i, r)
                model[i] = r
            elif op < 0.38 and live:
                i = int(live[int(rng.integers(0, len(live)))])
                assert store.delete(i) == ref.delete(i)
            elif op < 0.50:
                fresh = [_rand_row(rng, rows) for _ in range(8)]
                a = store.insert_many(fresh)
                b = ref.insert_many(fresh)
                assert list(a) == list(b)
                for i, r in zip(a, fresh):
                    model[i] = r
            else:
                probe = rng.integers(0, len(store), 64).tolist()
                _rows_close(store.get_many(probe), ref.get_many(probe))

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_random_ops_match_resident_reference(self, seed):
        rows = GEN(1200, seed=5)
        ref = BlitzStore(SCHEMA, rows[:400], merge_min_bytes=1 << 10)
        capped = BlitzStore(
            SCHEMA,
            rows[:400],
            merge_min_bytes=1 << 10,
            memory_budget=TINY,
        )
        self._ops(capped, ref, rows, seed)
        capped.merge()
        ref.merge()
        every = list(range(len(ref)))
        _rows_close(capped.get_many(every), ref.get_many(every))
        # within one store the decode backends must be bit-identical,
        # spilled blocks included
        assert capped.get_many(every, backend="pallas") == capped.get_many(
            every, backend="numpy"
        )
        s = capped.stats()
        assert s["spilled_bytes"] > 0
        assert s["residency"]["faults"] > 0
        self._check_accounting(capped)

    @staticmethod
    def _check_accounting(store):
        """The incremental counters must equal ground truth recomputed
        from the block arrays (a sweep double-picking a victim, or a
        leaked disk extent, shows up here as drift)."""
        t = store.table
        nb = t.n_blocks
        lens = t.block_offsets[1:] - t.block_offsets[:-1]
        live_resident = int(lens[t._resident[:nb]].sum())
        # every resident block's run is live or dead; spilled runs are 0-len
        # after rewrite or counted dead before it
        assert t.used - t._dead_codes == live_resident - int(
            lens[t._resident[:nb] & (t._block2row[:nb] < 0)].sum()
        )
        spilled = ~t._resident[:nb]
        assert t._spilled_codes == int(t._disk_len[:nb][spilled].sum())
        # each spilled extent carries a CRC32 frame header on disk
        assert t._res.disk.live_bytes == 2 * t._spilled_codes + \
            FRAME_OVERHEAD * int(spilled.sum())

    def test_migrate_rows_under_budget(self):
        rows = GEN(1500, seed=9)
        sample = rows[:400]
        ref = BlitzStore(SCHEMA, sample)
        capped = BlitzStore(SCHEMA, sample, memory_budget=TINY)
        rng = np.random.default_rng(2)
        drifted = []
        for r in rows:
            r = dict(r)
            # quantities far outside the trained vocab escape the v0 plan
            r["ol_quantity"] = int(rng.integers(500, 600))
            drifted.append(r)
        ref.insert_many(drifted)
        capped.insert_many(drifted)
        from repro.adaptive import refit_codec

        new = refit_codec(ref.codec, drifted[:512], ["ol_quantity"])
        assert new.compile() is not None
        ref.install_codec(new)
        capped.install_codec(refit_codec(capped.codec, drifted[:512], ["ol_quantity"]))
        # resident-only migration must not fault the cold tier in
        faults_before = capped.table.residency()["faults"]
        capped.migrate(1 << 12, resident_only=True)
        assert capped.table.residency()["faults"] == faults_before
        ref.migrate(1 << 12)
        capped.migrate(1 << 12, resident_only=False)  # now drain the rest
        every = list(range(len(ref)))
        _rows_close(capped.get_many(every), ref.get_many(every))
        assert capped.get_many(every, backend="pallas") == capped.get_many(
            every, backend="numpy"
        )


class TestBaselineStoresOutOfCore:
    @pytest.mark.parametrize("name", sorted(_baseline_makers()))
    def test_reads_match_resident_reference(self, name):
        make = _baseline_makers()[name]
        rows = GEN(800, seed=7)
        ref = make(SCHEMA, rows[:300])
        capped = make(SCHEMA, rows[:300], memory_budget=1 << 12)
        ref.insert_many(rows)
        capped.insert_many(rows)
        rng = np.random.default_rng(4)
        for _ in range(60):
            probe = rng.integers(0, len(rows), 48).tolist()
            assert capped.get_many(probe) == ref.get_many(probe)
            i = int(rng.integers(0, len(rows)))
            if ref.is_live(i):
                r = _rand_row(rng, rows)
                ref.update(i, r)
                capped.update(i, r)
            j = int(rng.integers(0, len(rows)))
            assert capped.delete(j) == ref.delete(j)
        every = list(range(len(rows)))
        assert capped.get_many(every) == ref.get_many(every)
        s = capped.stats()
        assert s["spilled_bytes"] > 0
        assert s["residency"]["faults"] > 0
        assert s["nbytes"] < ref.stats()["nbytes"]
        # incremental accounting equals ground truth (no sweep double-picks,
        # no leaked disk extents)
        assert capped._resident_bytes == sum(
            len(r) for r in capped.rows if r
        )
        assert capped._spilled_payload == sum(
            ln for _, ln in capped._spilled.values()
        )
        assert capped._res.disk.live_bytes == (
            capped._spilled_payload + FRAME_OVERHEAD * len(capped._spilled)
        )


class TestDbTableBudget:
    def test_sharded_budget_split_reads_identical(self):
        from repro.db import Database

        pop = tpcc.generate_tpcc(
            n_warehouses=1,
            districts_per_wh=2,
            customers_per_district=60,
            n_items=100,
            orders_per_district=15,
            seed=11,
        )
        ref = Database(backend="blitzcrank", n_shards=2)
        capped = Database(backend="blitzcrank", n_shards=2,
                          memory_limit=2048 * len(tpcc.TPCC_TABLES))
        for db in (ref, capped):
            for tname, schema in tpcc.TPCC_TABLES.items():
                t = db.create_table(schema, sample_rows=pop[tname])
                t.insert_many(pop[tname])
        tpcc.run_tpcc_mix(ref, 120, seed=13)
        tpcc.run_tpcc_mix(capped, 120, seed=13)
        ref.merge_all()
        capped.merge_all()
        for tname in tpcc.TPCC_TABLES:
            keys = [k for k, _ in ref[tname].scan()]
            assert capped[tname].get_many(keys) == ref[tname].get_many(keys)
        s = capped.stats()
        assert s["spilled_bytes"] > 0
        assert s["residency"]["budget_bytes"] > 0
        # per-shard split: each shard carries half its table's share
        for shard in capped["order_line"].shards:
            assert shard.table.memory_budget == capped["order_line"].budget // 2


def _tpcc_db(pop, **kwargs):
    from repro.db import Database

    db = Database(backend="blitzcrank", **kwargs)
    for tname, schema in tpcc.TPCC_TABLES.items():
        t = db.create_table(schema, sample_rows=pop[tname])
        t.insert_many(pop[tname])
    return db


def _assert_within_limit(db):
    """The limit's invariant: the whole database's live resident code
    bytes are at most the limit plus each store's slack (and in fact at
    most one byte a store over it: the floor of an empty table's part)."""
    stores = [s for t in db for s in t.shards]
    slack = sum(s.table._res.slack_bytes for s in stores)
    assert db.resident_code_bytes <= db.memory_limit + slack
    assert db.resident_code_bytes <= db.memory_limit + len(stores)


def _new_lines(pop, o_base, n):
    """``n`` fresh order lines: loaded lines under order ids from
    ``o_base`` up."""
    out = []
    for j, r in enumerate(pop["order_line"][:n]):
        out.append(dict(r, ol_o_id=o_base + j // 10, ol_number=1 + j % 10))
    return out


class TestDatabaseMemoryLimit:
    """``Database(memory_limit=…)`` caps the whole database's resident
    code bytes, split among tables of unequal size by their bytes."""

    POP = dict(n_warehouses=1, districts_per_wh=2, customers_per_district=80,
               n_items=150, orders_per_district=40, seed=5)

    def _pair(self, n_shards=1, frac=0.25, **kwargs):
        pop = tpcc.generate_tpcc(**self.POP)
        ref = _tpcc_db(pop, n_shards=n_shards)
        limit = int(frac * ref.code_bytes)
        capped = _tpcc_db(pop, n_shards=n_shards, memory_limit=limit, **kwargs)
        return pop, ref, capped

    def test_limit_holds_as_one_table_grows_and_follows_bytes(self):
        pop, ref, capped = self._pair()
        _assert_within_limit(capped)
        assert capped.stats()["spilled_bytes"] > 0
        sizes = {t.name: t.code_bytes for t in capped}
        assert len(set(sizes.values())) == len(sizes)  # unequal tables
        before = {t.name: t.budget for t in capped}
        # the split follows the tables' bytes
        total = sum(sizes.values())
        for t in capped:
            assert abs(t.budget - capped.memory_limit * sizes[t.name] / total) <= 1
        ol, cust = capped["order_line"], capped["customer"]
        splits = 0
        for step in range(12):
            got = ol.insert_many(_new_lines(pop, 1000 + 100 * step, 150))
            ref["order_line"].insert_many(_new_lines(pop, 1000 + 100 * step, 150))
            assert len(got) == 150
            _assert_within_limit(capped)
            upd = [dict(r, c_balance=float(step)) for r in pop["customer"][:20]]
            keys = [tpcc.TPCC_TABLES["customer"].key_of(r) for r in upd]
            cust.update_many(keys, upd)
            ref["customer"].update_many(keys, upd)
            _assert_within_limit(capped)
            assert cust.get_many(keys) == ref["customer"].get_many(keys)
            _assert_within_limit(capped)
            splits = max(splits, sum(1 for t in capped if t.budget != before[t.name]))
        assert splits > 0
        # order_line grew the most: its share of the limit rose, the others fell
        assert ol.budget > before["order_line"]
        assert capped["stock"].budget < before["stock"]
        assert ol.code_bytes > sizes["order_line"]

    @pytest.mark.parametrize("backend", ["numpy", "pallas"])
    def test_reads_match_an_unlimited_database(self, backend):
        pop, ref, capped = self._pair(n_shards=2)
        tpcc.run_tpcc_mix(ref, 60, seed=3)
        tpcc.run_tpcc_mix(capped, 60, seed=3)
        _assert_within_limit(capped)
        for tname in ("customer", "stock", "order_line"):
            keys = [k for k, _ in ref[tname].scan()]
            assert (capped[tname].get_many(keys, backend=backend)
                    == ref[tname].get_many(keys, backend=backend))
            _assert_within_limit(capped)
        res = capped.stats()["residency"]
        assert res["faults"] > 0 and res["memory_limit"] == capped.memory_limit

    def test_a_table_budget_of_its_own_is_left_alone(self):
        from repro.db import Database, TableSchema

        pop = tpcc.generate_tpcc(**self.POP)
        db = Database(backend="blitzcrank", memory_limit=1 << 14)
        for tname, schema in tpcc.TPCC_TABLES.items():
            own = 3000 if tname == "item" else None
            db.create_table(schema, sample_rows=pop[tname],
                            memory_budget=own).insert_many(pop[tname])
        assert db["item"].budget == 3000
        assert sum(t.budget for t in db if t.name != "item") <= (1 << 14) - 3000 + 1
        _assert_within_limit(db)
        with pytest.raises(ValueError):
            db.create_table(TableSchema("item2", tpcc.ITEM_SCHEMA, "i_id"),
                            sample_rows=pop["item"], memory_budget=1 << 14)
        assert "item2" not in db

    def test_checkpoint_and_reopen_keep_the_limit(self, tmp_path):
        from repro.db import Database

        pop = tpcc.generate_tpcc(**self.POP)
        root = str(tmp_path / "db")
        db = Database(backend="blitzcrank", memory_limit=1 << 14, durability=root)
        for tname in ("customer", "order_line"):
            t = db.create_table(tpcc.TPCC_TABLES[tname], sample_rows=pop[tname])
            t.insert_many(pop[tname])
        keys = {n: [k for k, _ in db[n].scan()] for n in db.table_names}
        want = {n: db[n].get_many(k) for n, k in keys.items()}
        db.close()
        rdb = Database.open(root)
        assert rdb.memory_limit == 1 << 14
        _assert_within_limit(rdb)
        for n, k in keys.items():
            assert rdb[n].get_many(k) == want[n]
        rdb["order_line"].insert_many(_new_lines(pop, 5000, 120))
        _assert_within_limit(rdb)
        rdb.close()

    def test_a_budget_moves_on_a_live_store(self):
        rows = GEN(1200, seed=9)
        codec = TableCodec.fit(rows[:400], SCHEMA)
        ref = CompressedTable(codec)
        ref.extend(rows)
        t = CompressedTable(codec, memory_budget=1 << 16)
        t.extend(rows)
        assert t.resident_code_bytes <= 1 << 16
        t.set_memory_budget(1 << 12)  # lowered: spills down to it now
        assert t.memory_budget == 1 << 12
        assert t.resident_code_bytes <= 1 << 12
        assert t.code_bytes == ref.code_bytes
        every = list(range(len(rows)))
        assert t.get_many(every) == ref.get_many(every)
        t.set_memory_budget(1 << 20)  # raised: faults in only on demand
        assert t.resident_code_bytes <= 1 << 12
        t.get_many(every)
        assert t.spilled_bytes == 0
        with pytest.raises(ValueError):
            t.set_memory_budget(0)
        with pytest.raises(ValueError):
            t.set_memory_budget(1 << 12, spill_path="elsewhere")
