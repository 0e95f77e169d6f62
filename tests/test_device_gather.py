"""The decode kernel's input gathered on the device (DESIGN.md §2.8).

The host sends each requested row's start and length; the chip cuts the
runs out of codes already in HBM (a table's arena mirror, or a host CSR
uploaded in one copy) and builds the dense block the kernel reads.  The
block must equal the one the host used to build, and every decoded symbol
must equal the numpy decoder's (interpret mode on the CPU).
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro import telemetry  # noqa: E402
from repro.core import ColumnSpec, CompressedTable, TableCodec  # noqa: E402
from repro.core import vectorized  # noqa: E402
from repro.core.mirror import MirroredCodes, upload_codes  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.kernels.delayed_decode import BLOCK_T, gather_runs  # noqa: E402
from repro.scan import Range, scan_table  # noqa: E402

SCHEMA = [ColumnSpec("id", "int"), ColumnSpec("qty", "int"),
          ColumnSpec("amount", "float", precision=0.01)]


def _rows(n, seed=0):
    rng = np.random.default_rng(seed)
    return [{"id": i, "qty": int(rng.integers(1, 100)),
             "amount": float(np.round(rng.uniform(0.01, 999.99), 2))}
            for i in range(n)]


@pytest.fixture(scope="module")
def table():
    rows = _rows(700)
    t = CompressedTable(TableCodec.fit(rows, SCHEMA, sample=256))
    t.extend(rows)
    t.flush()
    assert t.codec.compile().pallas_ok and t.block_fast.all()
    return t


def _gather(codes, offsets, rows, n_slots):
    starts = offsets[rows]
    lens = offsets[rows + 1] - starts
    dev = upload_codes(codes)
    out = gather_runs(dev, jnp.asarray(starts, jnp.int32),
                      jnp.asarray(lens, jnp.int32), n_slots)
    return np.asarray(out)


@pytest.mark.parametrize("n_slots", [9, 130, 367])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_gather_equals_the_host_block(seed, n_slots):
    """Unequal runs, a zero-length run, runs longer than the slot count,
    runs across a page edge, and the arena's last row: the device block is
    the host's, bit for bit."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(1, n_slots + 5, 200)
    lens[[3, 50]] = 0
    offsets = np.concatenate([[0], np.cumsum(lens)])
    codes = rng.integers(0, 1 << 16, int(offsets[-1])).astype(np.uint16)
    rows = np.concatenate([rng.integers(0, 200, 300), [3, 50, 199]])
    want = ops.dense_codes(codes.astype(np.int64), offsets, n_slots)[rows]
    np.testing.assert_array_equal(_gather(codes, offsets, rows, n_slots), want)
    assert not want[-2].any() and not want[-3].any()


@pytest.mark.parametrize("n", [1, BLOCK_T, BLOCK_T + 44])
def test_decode_equals_numpy_at_every_bucket_edge(table, n):
    """1 row, exactly one tile, and a batch padded to the next pow2, each
    holding the arena's last row, through the host-CSR upload and through
    the table's mirror."""
    plan = table.codec.compile()
    codes, offsets = table.arena[:table.used], table.block_offsets
    rng = np.random.default_rng(n)
    sel = rng.choice(table.n_blocks, n, replace=False)
    sel[-1] = table.n_blocks - 1
    want = vectorized.decode_select(codes, offsets, plan.coders, sel, plan.lam)
    got = plan.decode_select(codes, offsets, sel, backend="pallas")
    np.testing.assert_array_equal(got, want)
    got = plan.decode_select(MirroredCodes(codes, table._device_arena), offsets,
                             sel, backend="pallas")
    np.testing.assert_array_equal(got, want)


def test_only_mirror_reads_count_as_device_gathers(table):
    plan = table.codec.compile()
    codes, offsets = table.arena[:table.used], table.block_offsets
    sel = np.arange(0, 300, 3)
    gathered = telemetry.counter("repro.decode.device_gather.rows")
    kernel = telemetry.counter("repro.decode.kernel_rows")
    g0, k0 = gathered.value, kernel.value
    plan.decode_select(codes, offsets, sel, backend="pallas")
    assert (gathered.value, kernel.value) == (g0, k0 + sel.size)
    plan.decode_select(MirroredCodes(codes, table._device_arena), offsets, sel,
                       backend="pallas")
    assert (gathered.value, kernel.value) == (g0 + sel.size, k0 + 2 * sel.size)


def _family_rows(n=900, seed=0):
    """A string column of three word counts: a plan family whose members
    have different slot counts."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        corp = ["Hooli", "Acme Corp", "Initech Inc Ltd"][
            int(rng.choice(3, p=[0.5, 0.3, 0.2]))]
        out.append({"k": i, "s": f"{corp} lot {int(rng.integers(0, 100000)):05d}",
                    "qty": int(rng.integers(0, 50))})
    return out


def test_member_narrower_than_the_family_kernel():
    rows = _family_rows()
    schema = [ColumnSpec("k", "int"), ColumnSpec("s", "str"),
              ColumnSpec("qty", "int")]
    t = CompressedTable(TableCodec.fit(rows, schema))
    t.extend(rows)
    t.flush()
    family = t.codec.compile().family
    assert len(family) >= 2
    narrow = [p for p in family if p.S < len(p.pallas_tables()[1])]
    assert narrow
    for plan in narrow:
        sel = np.nonzero(t.block_fast & (t.block_templates == plan.template))[0]
        assert sel.size
        want = plan.decode_select(t.arena[:t.used], t.block_offsets, sel)
        got = plan.decode_select(MirroredCodes(t.arena[:t.used], t._device_arena),
                                 t.block_offsets, sel, backend="pallas")
        np.testing.assert_array_equal(got, want)
    idx = np.arange(len(rows))
    assert t.get_many(idx, backend="pallas") == t.get_many(idx, backend="numpy")


def test_scan_compacted_csr_decodes_on_the_device(table):
    """The scan hands the kernel a compacted host CSR, not the arena: it is
    uploaded and gathered on the device, with the numpy scan's answer."""
    preds = [Range("qty", 10, 60)]
    gathered = telemetry.counter("repro.decode.device_gather.rows")
    kernel = telemetry.counter("repro.decode.kernel_rows")
    g0, k0 = gathered.value, kernel.value
    got = scan_table(table, preds, backend="pallas")
    want = scan_table(table, preds, backend="numpy")
    assert got.ids == want.ids and got.rows == want.rows and got.ids
    assert kernel.value > k0 and gathered.value == g0
