"""OLTP stores (paper §6/§7 setting)."""

import numpy as np
import pytest

from repro.oltp import tpcc
from repro.oltp.store import (BlitzStore, LRUFastPath, RamanStore,
                              ZstdStore)


def _check_store(store, rows, schema, n=30):
    rng = np.random.default_rng(0)
    for i in rng.integers(0, len(rows), n):
        got, exp = store.get(int(i)), rows[int(i)]
        for c in schema:
            if c.kind == "float":
                assert abs(got[c.name] - exp[c.name]) <= c.precision / 2 + 1e-9
            else:
                assert got[c.name] == exp[c.name], (c.name,)


class TestStores:
    @pytest.mark.parametrize("table", ["customer", "stock", "orderline"])
    def test_blitz_beats_baselines(self, table):
        schema, gen = tpcc.TABLES[table]
        rows = gen(1200)
        raw = tpcc.row_bytes(rows)
        classes = [RamanStore, BlitzStore]
        try:
            import zstandard  # noqa: F401
            classes.insert(0, ZstdStore)
        except ImportError:
            pass  # zstd baseline unavailable in this environment
        factors = {}
        for cls in classes:
            store = cls(schema, rows[:600])
            for r in rows:
                store.insert(r)
            _check_store(store, rows, schema)
            factors[store.name] = raw / store.nbytes
        if "zstd" in factors:
            assert factors["blitzcrank"] > factors["zstd"], factors
        assert factors["blitzcrank"] > 2.0

    def test_unseen_values_after_training(self):
        """Semantic models compress inserts with unseen values (paper §3)."""
        schema, gen = tpcc.TABLES["customer"]
        rows = gen(800)
        store = BlitzStore(schema, rows[:400])
        new = dict(rows[0])
        new.update(c_first="Zyxwv", c_balance=9.9e7, c_zip="00000",
                   c_street="1 Unobtainium Qz")
        i = store.insert(new)
        got = store.get(i)
        assert got["c_first"] == "Zyxwv" and got["c_zip"] == "00000"
        assert got["c_street"] == new["c_street"]

    def test_correlation_learns_hierarchy(self):
        schema, gen = tpcc.TABLES["customer"]
        rows = gen(2500)
        store = BlitzStore(schema, rows, correlation=True, sample=1500)
        parents = store.codec.stats.parents
        assert parents.get("c_city") == "c_state"
        assert parents.get("c_zip") == "c_city"
        for r in rows[:60]:
            store.insert(r)
        _check_store(store, rows[:60], schema, n=10)

    def test_lru_fastpath_zipf(self):
        schema, gen = tpcc.TABLES["orderline"]
        rows = gen(400)
        store = BlitzStore(schema, rows[:200])
        for r in rows:
            store.insert(r)
        fp = LRUFastPath(store, capacity=64)
        rng = np.random.default_rng(1)
        keys = (rng.zipf(1.3, 2000) - 1)
        keys = keys[keys < 400][:500]
        for i in keys:
            fp.read_modify_write(int(i), lambda r: r.update(ol_quantity=1))
        assert fp.hits / (fp.hits + fp.misses) > 0.3
