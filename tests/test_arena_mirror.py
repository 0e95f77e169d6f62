"""The arena's HBM mirror stays coherent with the host arena (DESIGN.md §2.8).

Every kernel read must return the newest acknowledged value, whatever
changed the arena since the last read: inserts, merges, deletes, rewrites,
spills and fault-ins, a restore.  Appends move the watermark; a rewrite or
a restore moves the generation and re-uploads; growth inside the mirror's
capacity compiles nothing.
"""

import numpy as np
import pytest

pytest.importorskip("jax")

from repro import telemetry  # noqa: E402
from repro.core import ColumnSpec, CompressedTable, TableCodec  # noqa: E402
from repro.core import mirror as mirror_mod  # noqa: E402
from repro.core.mirror import ArenaMirror, mirror_capacity  # noqa: E402
from repro.oltp.store import BlitzStore  # noqa: E402

SCHEMA = [ColumnSpec("id", "int"), ColumnSpec("qty", "int"),
          ColumnSpec("tag", "cat"),
          ColumnSpec("amount", "float", precision=0.01)]
TAGS = ["red", "green", "blue", "amber"]
TINY = 1 << 12  # bytes: far below the stores below, forces deep spill


def _row(rng, i):
    return {"id": i, "qty": int(rng.integers(1, 100)),
            "tag": TAGS[int(rng.integers(0, 4))],
            "amount": float(np.round(rng.uniform(0.01, 999.99), 2))}


def _rows(n, seed=0, first=0):
    rng = np.random.default_rng(seed)
    return [_row(rng, first + i) for i in range(n)]


def _same(got, want):
    """Rows equal, floats within their precision (p/2 off at most)."""
    assert len(got) == len(want)
    for g, w in zip(got, want):
        if w is None:
            assert g is None
            continue
        assert g is not None
        assert {k: g[k] for k in ("id", "qty", "tag")} == \
            {k: w[k] for k in ("id", "qty", "tag")}
        assert abs(g["amount"] - w["amount"]) <= 0.005 + 1e-9


def _read(store, model):
    """The kernel's reads against the model and the numpy decoder's.  The
    kernel reads last, so a store without a budget (whose reads append
    nothing) leaves its mirror synced."""
    idx = list(range(len(model)))
    want = store.get_many(idx, backend="numpy")
    got = store.get_many(idx, backend="pallas")
    _same(got, model)
    assert got == want


def _mirror(store):
    m = store.table._mirror
    assert m.synced == store.table.used
    assert m.generation == store.table._arena_gen
    return m


@pytest.mark.parametrize("budget", [None, TINY])
def test_reads_follow_every_write(budget):
    """insert, merge (replace_many), delete, rewrite, and under a budget
    spill and fault-in: each kernel read sees the newest values."""
    rows = _rows(400)
    store = BlitzStore(SCHEMA, rows, auto_merge=False, memory_budget=budget)
    store.insert_many(rows)
    model = [dict(r) for r in rows]
    _read(store, model)
    gen = store.table._arena_gen

    more = _rows(150, seed=1, first=400)
    store.insert_many(more)
    model += [dict(r) for r in more]
    _read(store, model)

    rng = np.random.default_rng(2)
    idx = sorted(set(rng.integers(0, len(model), 120).tolist()))
    new = [_row(rng, i) for i in idx]
    store.update_many(idx, new)
    store.merge()
    for i, r in zip(idx, new):
        model[i] = dict(r)
    _read(store, model)

    gone = idx[::5]
    store.delete_many(gone)
    store.merge()
    for i in gone:
        model[i] = None
    _read(store, model)

    if budget is None:
        assert _mirror(store).generation == gen  # appends only so far
    before = store.table.rewrites
    store.table.rewrite()
    assert store.table.rewrites == before + 1
    _read(store, model)
    assert store.table._arena_gen > gen
    if budget is None:
        assert _mirror(store).generation > gen
    else:
        res = store.table.residency()
        assert res["spills"] > 0 and res["faults"] > 0


def test_generation_moves_with_each_rewrite_and_reuploads():
    rows = _rows(300)
    store = BlitzStore(SCHEMA, rows, auto_merge=False)
    store.insert_many(rows)
    model = [dict(r) for r in rows]
    rebuilds = telemetry.counter("repro.decode.mirror.rebuilds")
    for k in range(3):
        _read(store, model)
        gen, n0 = store.table._arena_gen, rebuilds.value
        idx = list(range(k, 300, 7))
        new = _rows(len(idx), seed=10 + k)
        for i, r in zip(idx, new):
            r["id"] = i
            model[i] = dict(r)
        store.update_many(idx, new)
        store.merge()
        store.table.rewrite()
        assert store.table._arena_gen == gen + 1
        _read(store, model)
        assert rebuilds.value == n0 + 1
        assert _mirror(store).generation == gen + 1


def test_restore_from_snapshot_reads_through_a_fresh_mirror():
    rows = _rows(300)
    store = BlitzStore(SCHEMA, rows, auto_merge=False, memory_budget=TINY)
    store.insert_many(rows)
    model = [dict(r) for r in rows]
    _read(store, model)
    state = store.table.snapshot_state(embed_spilled=True)
    t = CompressedTable.from_state(state)
    assert t._arena_gen == 1 and t._mirror.generation == -1
    idx = list(range(len(model)))
    got = t.get_many(idx, backend="pallas")
    _same(got, model)
    assert t._mirror.generation == t._arena_gen


def test_growth_within_capacity_compiles_nothing():
    rows = _rows(300)
    t = CompressedTable(TableCodec.fit(rows, SCHEMA, sample=256))
    t.extend(rows)
    t.flush()
    miss = telemetry.counter("repro.plan.cache.pallas_miss")
    sel = np.arange(0, 300, 2)
    t.get_many(sel, backend="pallas")
    cap, n0 = t._mirror.capacity, miss.value
    t.extend(_rows(100, seed=3, first=300))
    assert t.used + 64 < cap  # still inside the mirror
    new = np.arange(250, 400)  # the batch's bucket is the first call's
    got = t.get_many(new, backend="pallas")
    assert t._mirror.capacity == cap and miss.value == n0
    assert got == t.get_many(new, backend="numpy")


def test_budgeted_mirror_is_sized_for_the_arena_its_budget_lets_stand():
    """A rewrite is forced once the arena passes (budget + slack) / 2 codes;
    the mirror holds that arena from its first build."""
    rows = _rows(300)
    store = BlitzStore(SCHEMA, rows, auto_merge=False, memory_budget=TINY)
    store.insert_many(rows)
    _read(store, [dict(r) for r in rows])
    res = store.table._res
    assert store.table._mirror.capacity >= (res.budget + res.slack_bytes) // 2


def test_capacity_is_pow2_sticky_and_doubles_past_need():
    assert mirror_capacity(1000, 0) == 2048
    assert mirror_capacity(1024, 0) == 2048
    assert mirror_capacity(1025, 0) == 4096
    assert mirror_capacity(900, 4096) == 4096   # a trimmed arena keeps it
    assert mirror_capacity(4097, 4096) == 16384


def test_chunked_writes_land_where_the_arena_has_them(monkeypatch):
    """Appends of every size, chunks that start before the watermark
    because they would run past the capacity, and a rebuild."""
    monkeypatch.setattr(mirror_mod, "CHUNK", 64)
    rng = np.random.default_rng(0)
    arena = rng.integers(0, 1 << 16, 5000).astype(np.uint16)
    m = ArenaMirror()
    uploaded = telemetry.counter("repro.decode.mirror.upload_bytes")
    for used in (1, 63, 64, 65, 200, 1000, 1003, 1500, 1500, 2000):
        u0 = uploaded.value
        codes = np.asarray(m.sync(arena, used, 0))
        np.testing.assert_array_equal(codes[:used], arena[:used])
        assert m.capacity >= used and m.synced == used
        assert (uploaded.value - u0) % (2 * min(64, m.capacity)) == 0
    arena2 = arena[::-1].copy()
    codes = np.asarray(m.sync(arena2, 700, 1))
    np.testing.assert_array_equal(codes[:700], arena2[:700])
    assert m.generation == 1
