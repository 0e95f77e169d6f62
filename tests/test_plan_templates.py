"""Plan families: a string column lowers to a few word templates, not one.

A string column whose rows come in several ``(word count, digit-or-word
kind at each position)`` shapes compiles to one plan per shape (plan.py
``compile_plan``).  Every member must emit the scalar coder's codes bit for
bit and decode them back on both backends; a row that a later member takes
is not an escape, and a row no member takes is charged exactly as the
modal plan alone would charge it.
"""

import numpy as np
import pytest

from repro.core import ColumnSpec, CompressedTable, TableCodec
from repro.core.plan import TablePlan, _lower, _StrPlan

SCHEMA = [
    ColumnSpec("k", "int"),
    ColumnSpec("city", "cat"),
    ColumnSpec("s", "str"),
    ColumnSpec("qty", "int"),
]
CITIES = ["Paris", "Rome", "Oslo", "Lima"]


def _three_word_counts(rng):
    """``<corp> grade X lot NNNNN`` with corporations of 1-3 words."""
    corp = ["Hooli", "Acme Corp", "Initech Inc Ltd"][
        int(rng.choice(3, p=[0.5, 0.3, 0.2]))]
    return f"{corp} grade {'ABCDEF'[int(rng.integers(0, 6))]} lot " \
           f"{int(rng.integers(0, 100000)):05d}"


def _same_count_two_kinds(rng):
    """``s_data``-like: two of its shapes share a word count of 7 and
    differ in where the digit runs sit."""
    two_words = rng.random() < 0.6
    corp = ("Acme Corp" if rng.random() < 0.5 else "Globex LLC") if two_words \
        else ("Hooli" if rng.random() < 0.5 else "Wonka")
    original = two_words and rng.random() < 0.3
    tail = "ORIGINAL" if original else f"bin {int(rng.integers(0, 10000)):04d}"
    return f"{corp} grade {'ABCDEF'[int(rng.integers(0, 6))]} lot " \
           f"{int(rng.integers(0, 100000)):05d} {tail}"


def _item_like(rng):
    """``i_data``-like: a name of one or two words, then a sku."""
    corp = "Acme Corp" if rng.random() < 0.7 else "Hooli"
    noun = ["Widget", "Gadget", "Lamp"][int(rng.integers(0, 3))]
    return f"{corp} sku {int(rng.integers(1000, 9999))} {noun} catalog"


# (shape generator, templates it makes)
SHAPES = {
    "three_word_counts": (_three_word_counts, 3),
    "same_count_two_kinds": (_same_count_two_kinds, 3),
    "item_like": (_item_like, 2),
}


def gen_rows(shape, n=900, seed=0):
    rng = np.random.default_rng(seed)
    make = SHAPES[shape][0]
    return [{"k": i, "city": CITIES[int(rng.integers(0, 4))], "s": make(rng),
             "qty": int(rng.integers(0, 50))} for i in range(n)]


def _fit(shape):
    rows = gen_rows(shape)
    codec = TableCodec.fit(rows, SCHEMA)
    plan = codec.compile()
    assert plan is not None
    return codec, plan, rows


def _shape_of(plan):
    cp, _ = plan.by_column["s"]
    return cp.W, tuple(mode is not None for mode in cp._modes)


def _modal_only(codec):
    """The plan a string column lowered to before families: its modal
    template alone, a family of one."""
    return TablePlan(codec, _lower(codec, {"s": _StrPlan(codec.models["s"])}, {}))


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_column_compiles_to_one_plan_per_template(shape):
    codec, plan, rows = _fit(shape)
    family = plan.family
    assert len(family) == SHAPES[shape][1]
    assert [p.template for p in family] == list(range(len(family)))
    assert all(p.family is family for p in family)
    shapes = [_shape_of(p) for p in family]
    assert len(set(shapes)) == len(shapes)
    # the first member is the modal plan, as before families
    assert shapes[0] == _shape_of(_modal_only(codec))
    assert family[0].S == _modal_only(codec).S
    # every fitted row takes exactly one member
    tmpl, parts = plan.encode_family(rows)
    assert (tmpl >= 0).all()
    assert sorted(parts) == list(range(len(family)))
    assert sum(p.shape[0] for p in parts.values()) == len(rows)


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_member_codes_equal_the_scalar_coder(shape):
    codec, plan, rows = _fit(shape)
    tmpl, parts = plan.encode_family(rows)
    for t, syms in parts.items():
        member = plan.family[t]
        codes, offsets = member.encode_batch(syms)
        for j, i in enumerate(np.nonzero(tmpl == t)[0][:60].tolist()):
            scalar = codec._scalar_compress([rows[i]])
            assert (codes[offsets[j]:offsets[j + 1]] == scalar).all()
    # the table's CSR arena is the scalar coder's, row for row
    codes, offsets, tmpl2 = codec.compress_rows(rows)
    assert (tmpl2 == tmpl).all()
    for i in range(0, len(rows), 7):
        scalar = codec._scalar_compress([rows[i]])
        assert (codes[offsets[i]:offsets[i + 1]] == scalar).all()


@pytest.mark.parametrize("backend", ["numpy", "pallas"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_member_decode_equals_scalar_decode(shape, backend):
    codec, plan, rows = _fit(shape)
    codes, offsets, tmpl = codec.compress_rows(rows)
    for t in range(len(plan.family)):
        idx = np.nonzero(tmpl == t)[0][:40]
        got = codec.decompress_rows(codes, offsets, idx, backend=backend,
                                    template=t)
        want = [codec.decompress_block(codes[offsets[i]:offsets[i + 1]], 1)[0]
                for i in idx.tolist()]
        assert got == want == [rows[i] for i in idx.tolist()]


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_family_shares_one_kernel_layout(shape):
    """Every member packs at the family's slot bits: one compiled kernel
    per row bucket serves the whole family."""
    _, plan, _ = _fit(shape)
    layouts = {(p.pallas_tables()[0].shape, p.pallas_tables()[1])
               for p in plan.family}
    assert len(layouts) == 1
    (_, m_bits), = layouts
    assert len(m_bits) == max(p.S for p in plan.family)


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_row_of_a_later_member_is_not_charged(shape):
    codec, plan, rows = _fit(shape)
    later = [r for r, t in zip(rows, plan.encode_family(rows)[0]) if t > 0]
    assert later
    plan = codec.compile(force=True)
    before = dict(plan.escape_counts)
    _, ok = plan.encode_rows(later)
    assert not ok.any()                      # the modal plan refuses them
    assert plan.escape_counts == before      # ... and charges nothing
    assert plan.rows_seen == len(later)
    assert all(plan.row_template(r) > 0 for r in later)
    assert plan.escape_counts == before
    table = CompressedTable(codec)
    table.extend(later[:5])
    table.append(later[5])                   # the scalar probe's path
    assert table.block_fast.all()
    assert (table.block_templates > 0).all()
    assert plan.escape_counts == before
    assert table.get_many(list(range(6))) == later[:6]


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_row_no_member_takes_is_charged_as_before(shape):
    codec, plan, rows = _fit(shape)
    later = [r for r, t in zip(rows, plan.encode_family(rows)[0]) if t > 0]
    misfits = [
        dict(rows[0], s="one two three four five six seven eight nine ten"),
        dict(rows[1], city="Kyoto"),                 # unseen category
        dict(later[0], city="Kyoto"),                # a later shape, unseen city
        dict(rows[2], s="zzzz grade A lot 00001"),   # a dictionary miss
    ]
    plan = codec.compile(force=True)
    modal = _modal_only(codec)
    _, ok = plan.encode_rows(misfits)
    _, ok_modal = modal.encode_rows(misfits)
    assert ok.tolist() == ok_modal.tolist() == [False] * 4
    assert plan.escape_counts == modal.escape_counts
    assert plan.escape_counts["city"] == 2
    scalar = codec.compile(force=True)
    assert [scalar.row_template(r) for r in misfits] == [-1] * 4
    assert scalar.escape_counts == modal.escape_counts
    codes, offsets, tmpl = codec.compress_rows(misfits)
    assert (tmpl == -1).all()
    table = CompressedTable(codec)
    table.extend(misfits)
    assert not table.block_fast.any()
    assert table.get_many(list(range(4))) == misfits


def test_one_template_schema_compiles_to_a_family_of_one():
    rng = np.random.default_rng(3)
    rows = [{"k": i, "city": CITIES[i % 4], "s": f"lot {int(rng.integers(0, 999)):03d} "
             f"{['red', 'blue'][i % 2]}", "qty": i % 7} for i in range(400)]
    codec = TableCodec.fit(rows, SCHEMA)
    plan = codec.compile()
    modal = _modal_only(codec)
    assert plan.family == [plan]
    assert plan.S == modal.S
    assert plan.coders == modal.coders
    assert plan.kernel_m_bits() is None
    from repro.kernels.ops import coder_m_bits
    assert plan.pallas_tables()[1] == tuple(coder_m_bits(c) for c in plan.coders)
    table = CompressedTable(codec)
    table.extend(rows)
    assert table.block_fast.all() and not table.block_templates.any()


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_templates_survive_rewrite_fault_in_and_snapshot(shape):
    codec, plan, rows = _fit(shape)
    table = CompressedTable(codec)
    table.extend(rows)
    tmpl = table.block_templates.copy()
    assert tmpl.max() > 0
    table.replace_many(list(range(0, 300, 3)), [rows[i] for i in range(0, 300, 3)])
    table.delete_many([1, 2])
    table.rewrite()
    want = [rows[0], None, None] + rows[3:]
    assert table.get_many(list(range(len(rows)))) == want
    restored = CompressedTable.from_state(table.snapshot_state())
    assert (restored.block_templates == table.block_templates).all()
    assert restored.get_many(list(range(len(rows)))) == want
    capped = CompressedTable(codec, memory_budget=2048)
    capped.extend(rows)
    assert capped.residency()["spilled_blocks"] > 0
    assert capped.get_many(list(range(len(rows)))) == rows


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_scan_groups_by_template(shape):
    from repro.scan.engine import scan_table
    from repro.scan.predicates import Eq, Range

    codec, _, rows = _fit(shape)
    table = CompressedTable(codec)
    table.extend(rows)
    preds = [Eq("city", "Rome"), Range("qty", 10, 30)]
    res = scan_table(table, preds)
    want = [i for i, r in enumerate(rows)
            if r["city"] == "Rome" and 10 <= r["qty"] <= 30]
    assert res.ids == want
    assert res.rows == [rows[i] for i in want]
    assert res.stats.versions == 1
