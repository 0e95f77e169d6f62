"""Delayed-decode kernel sweeps: pallas_call (interpret) vs the ref.py
oracle vs the numpy host codec (three-way agreement)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core.coders import DiscreteCoder, quantize_freqs  # noqa: E402
from repro.core.vectorized import encode_batch  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.kernels.delayed_decode import delayed_decode_runs  # noqa: E402


def _coder(rng, n):
    w = 1.0 / np.arange(1, n + 1) ** rng.uniform(0.4, 1.8)
    return DiscreteCoder(quantize_freqs(w * 1e7))


def _decode_csr(codes_csr, offsets, tables, mbits):
    """The kernel on a CSR batch: the codes on the device, each row's run
    by its offsets."""
    offsets = np.asarray(offsets)
    runs = np.stack([offsets[:-1], np.diff(offsets)]).astype(np.int32)
    return delayed_decode_runs(jnp.asarray(codes_csr.astype(np.uint16)),
                               jnp.asarray(runs), tables, mbits)


class TestAliasLookupInDelayedDecode:
    """The alias lookup (Algorithm 6) as the engine runs it: inside the
    delayed-decode kernel, one slot per row.  ``alias_decode_ref``, the
    lookup's oracle inside ``delayed_decode_ref``, is held to the host
    coder on the same grid."""

    @pytest.mark.parametrize("n_symbols", [1, 2, 37, 255, 1000])
    @pytest.mark.parametrize("n_tuples", [17, 1024, 4097])
    def test_sweep(self, n_symbols, n_tuples):
        rng = np.random.default_rng(n_symbols * 1000 + n_tuples)
        dc = _coder(rng, n_symbols)
        syms = rng.integers(0, dc.tables.n_symbols, (n_tuples, 1))
        codes_csr, offsets = encode_batch(syms, [dc])
        tables, mbits = ops.pack_slot_tables([dc])
        out = np.asarray(_decode_csr(codes_csr, offsets, tables, mbits))
        np.testing.assert_array_equal(out, syms)

        table, m = ref.pack_tables(dc)
        codes = rng.integers(0, 65536, n_tuples).astype(np.int32)
        got = ref.alias_decode_ref(jnp.asarray(codes), table, m)
        for g, want in zip(got, dc.inv_translate_batch(codes)):
            np.testing.assert_array_equal(np.asarray(g), want)


class TestDelayedDecodeKernel:
    @pytest.mark.parametrize("n_slots,n_tuples", [(1, 64), (5, 300), (24, 130)])
    def test_sweep(self, n_slots, n_tuples):
        rng = np.random.default_rng(n_slots * 7 + n_tuples)
        coders = [_coder(rng, int(rng.integers(2, 400)))
                  for _ in range(n_slots)]
        syms = np.stack([rng.integers(0, c.tables.n_symbols, n_tuples)
                         for c in coders], axis=1)
        codes_csr, offsets = encode_batch(syms, coders)
        dense = ops.dense_codes(codes_csr.astype(np.int64), offsets, n_slots)
        tables, mbits = ops.pack_slot_tables(coders)
        out_k = np.asarray(_decode_csr(codes_csr, offsets, tables, mbits))
        out_r = np.asarray(ref.delayed_decode_ref(jnp.asarray(dense), tables,
                                                  mbits))
        np.testing.assert_array_equal(out_r, syms)
        np.testing.assert_array_equal(out_k, syms)

    def test_skewed_distributions_stress_virtual_bits(self):
        """Highly skewed slots mark nearly every interval (max virtual use)."""
        w = np.ones(3)
        w[0] = 1e6  # one dominant symbol -> k ~ 2**16 -> constant marking
        coders = [DiscreteCoder(quantize_freqs(w)) for _ in range(30)]
        syms = np.zeros((50, 30), np.int64)
        syms[:, ::7] = 1
        codes_csr, offsets = encode_batch(syms, coders)
        tables, mbits = ops.pack_slot_tables(coders)
        out = np.asarray(_decode_csr(codes_csr, offsets, tables, mbits))
        np.testing.assert_array_equal(out, syms)


def _add_history_and_new_order(db, pop):
    """TPC-C's history and new_order tables (rev. 5.11 clause 1.3), which
    the program's TPC-C database leaves out: a loaded payment per
    customer (4.3.3.1) and the newest third of each district's orders
    undelivered."""
    from repro.core import ColumnSpec
    from repro.db import TableSchema

    rng = np.random.default_rng(1)
    history = [{
        "h_id": i + 1, "h_c_id": c["c_id"], "h_c_d_id": c["c_d_id"],
        "h_c_w_id": c["c_w_id"], "h_d_id": c["c_d_id"],
        "h_w_id": c["c_w_id"], "h_date": 19800,
        "h_amount": float(np.round(rng.uniform(1.0, 5000.0), 2)),
        "h_data": f"WH-{c['c_w_id']:03d}    DIST-{c['c_d_id']:02d}",
    } for i, c in enumerate(pop["customer"])]
    n_orders = max(o["o_id"] for o in pop["orders"])
    new_order = [{"no_o_id": o["o_id"], "no_d_id": o["o_d_id"],
                  "no_w_id": o["o_w_id"]}
                 for o in pop["orders"] if o["o_id"] > n_orders * 2 // 3]
    schemas = [
        (TableSchema("history", [
            ColumnSpec("h_id", "int", growth=8.0), ColumnSpec("h_c_id", "int"),
            ColumnSpec("h_c_d_id", "int"), ColumnSpec("h_c_w_id", "int"),
            ColumnSpec("h_d_id", "int"), ColumnSpec("h_w_id", "int"),
            ColumnSpec("h_date", "int", growth=0.01),
            ColumnSpec("h_amount", "float", precision=0.01, growth=2.0),
            ColumnSpec("h_data", "str")], "h_id"), history),
        (TableSchema("new_order", [
            ColumnSpec("no_o_id", "int", growth=8.0),
            ColumnSpec("no_d_id", "int"), ColumnSpec("no_w_id", "int")],
            ("no_w_id", "no_d_id", "no_o_id")), new_order),
    ]
    for schema, rows in schemas:
        db.create_table(schema, sample_rows=rows).insert_many(rows)


class TestDelayedDecodeTPUInterpret:
    """The compiled-kernel path (``interpret=False``) under Pallas's TPU
    interpreter, which models SMEM/VMEM and the kernel's scratch, on the
    slot plans of a fitted TPC-C database: bit-identical to numpy."""

    @pytest.fixture(scope="class")
    def tpcc_db(self):
        from repro.oltp import tpcc
        pop = tpcc.generate_tpcc(n_warehouses=1, districts_per_wh=10,
                                 customers_per_district=30, n_items=300,
                                 orders_per_district=30, seed=0)
        db = tpcc.build_tpcc_database(population=pop)[0]
        _add_history_and_new_order(db, pop)
        return db

    @pytest.mark.parametrize("name", ["customer", "district", "history",
                                      "item", "new_order", "order_line",
                                      "orders", "stock"])
    def test_matches_numpy(self, tpcc_db, name):
        from jax.experimental.pallas import tpu as pltpu
        from repro.kernels import delayed_decode as dd

        t = tpcc_db[name].shards[0].table
        fast = t._fast[:t.n_blocks]
        # every member of the plan family, each on the blocks it encoded,
        # at the family's shared kernel layout, gathered on the device from
        # the table's arena mirror
        for plan in t.codec.compile().family:
            rows = np.nonzero(fast & (t.block_templates == plan.template))[0][:256]
            assert rows.size
            want = plan.decode_select(t.arena[:t.used], t.block_offsets, rows,
                                      backend="numpy")
            sel = np.concatenate([rows, np.full(256 - rows.size, rows[-1])])
            starts, ends = t.block_offsets[sel], t.block_offsets[sel + 1]
            runs = np.stack([starts, ends - starts]).astype(np.int32)
            tables, m_bits = plan.pallas_tables()
            codes = t._device_arena()
            with pltpu.force_tpu_interpret_mode():
                got = dd._delayed_decode_jit(codes, jnp.asarray(runs), tables,
                                             m_bits, False)
            np.testing.assert_array_equal(
                np.asarray(got)[:rows.size, :plan.S], want)
