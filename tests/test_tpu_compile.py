"""The decode kernel compiles for a described v5e chip at TPC-C shapes.

Interpret-mode tests cannot see what the chip's compiler refuses: casts
Mosaic has no lowering for (f32 -> uint32) and kernels whose VMEM
footprint exceeds the scoped limit.  These tests hand the TPU compiler
the kernel's real argument shapes for every TPC-C table (slot counts and
per-slot bucket bits taken from a small fitted database, which already
holds the widest slots: the 2**16-bucket ``w_ytd`` and the 2**13/2**14
customer slots) at the 256-row bucket and one larger bucket.  Bucket
counts grow with the data, so the per-slot bucket bits of one warehouse
at TPC-C spec cardinalities (the ``chip_smoke.py`` database) are pinned
below and compiled too.  Nothing runs; a passing compile is not a chip
run.

The topology is described inside a fixture, never at import, so every
test worker collects the same tests and only the worker given this file
loads the TPU compiler.
"""

import os

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.kernels.delayed_decode import (  # noqa: E402
    LANES,
    N_FIELDS,
    _delayed_decode_jit,
    slot_layout,
)
from repro.oltp import tpcc  # noqa: E402

TABLES = sorted(tpcc.TPCC_TABLES)
BUCKETS = (256, 4096)

# Per-slot bucket bits (``plan.pallas_tables()[1]``) of one warehouse
# loaded at spec cardinalities with seed 0: 10 districts of 3,000
# customers, 100,000 items and stock rows, 3,000 orders per district.
SPEC_M_BITS = {
    "warehouse": (1, 1, 3, 1, 2, 4, 4, 4, 4, 1, 2, 1, 2, 1, 1, 1, 1, 10, 2,
                  16),
    "district": (1, 4, 4, 3, 1, 3, 4, 4, 4, 4, 1, 3, 1, 3, 3, 4, 4, 9, 2, 10,
                 15, 9, 7),
    "customer": (1, 4, 9, 3, 6, 3, 1, 5, 4, 4, 4, 4, 1, 5, 1, 5, 4, 6, 9, 3,
                 1, 2, 3, 2, 4, 4, 4, 4, 3, 2, 3, 2, 4, 4, 4, 4, 3, 2, 4, 4,
                 4, 4, 4, 10, 13, 10, 14, 9, 4, 3, 2, 5, 1, 5, 1, 5, 1, 5, 1,
                 5, 4, 4, 4, 4, 4),
    "item": (9, 8, 9, 5, 3, 1, 6, 1, 6, 1, 6, 9, 5, 3, 2, 5, 1, 5, 1, 5, 1,
             5, 4, 4, 4, 4, 4),
    "stock": (1, 9, 8, 9, 1, 9, 1, 9, 4, 3, 1, 2, 2, 2, 2, 2, 4, 4, 4, 2, 2,
              4, 4, 4, 2, 2, 4, 4, 4, 4, 4, 3, 1, 2, 2, 2, 2, 2, 4, 4, 4, 2,
              2, 4, 4, 4, 2, 2, 4, 4, 4, 4, 4, 3, 2, 5, 1, 5, 1, 5, 1, 5, 1,
              5),
    "orders": (1, 4, 9, 7, 9, 3, 9, 4, 4, 1),
    "order_line": (1, 4, 9, 7, 4, 9, 8, 1, 9, 6, 4, 9, 8, 3, 1, 2, 2, 2, 2,
                   2, 4, 4, 4, 2, 2, 4, 4, 4, 2, 2, 4, 4, 4, 4, 4),
}


@pytest.fixture(scope="module")
def m_bits_by_table():
    pop = tpcc.generate_tpcc(n_warehouses=1, districts_per_wh=10,
                             customers_per_district=30, n_items=300,
                             orders_per_district=30, seed=0)
    db, _ = tpcc.build_tpcc_database(population=pop)
    out = {}
    for name in TABLES:
        plan = db[name].shards[0].codec.compile()
        assert plan is not None and plan.pallas_ok, name
        out[name] = plan.pallas_tables()[1]
    return out


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A described-chip compile cannot be read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def test_widest_slots_are_present(m_bits_by_table):
    """The shapes below cover the slots that broke the old layout."""
    assert max(m_bits_by_table["warehouse"]) == 16
    assert max(m_bits_by_table["customer"]) >= 13
    assert len(m_bits_by_table["customer"]) >= 60
    assert len(m_bits_by_table["stock"]) >= 60


def _compile(m_bits, rows, one_chip):
    _, n_rows = slot_layout(m_bits)
    codes = jax.ShapeDtypeStruct((rows, len(m_bits)), jnp.int32,
                                 sharding=one_chip)
    tables = jax.ShapeDtypeStruct((n_rows, N_FIELDS * LANES), jnp.float32,
                                  sharding=one_chip)
    compiled = _delayed_decode_jit.lower(
        codes, tables, m_bits=m_bits, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows", BUCKETS)
@pytest.mark.parametrize("table", TABLES)
def test_decode_kernel_compiles_for_v5e(table, rows, m_bits_by_table, one_chip,
                                        no_persistent_cache):
    _compile(m_bits_by_table[table], rows, one_chip)


def test_spec_scale_shapes_cover_every_table(m_bits_by_table):
    """The pinned spec-scale plans are the small database's plans with
    wider buckets: one entry per table, the same widest slots."""
    assert sorted(SPEC_M_BITS) == TABLES
    assert max(SPEC_M_BITS["warehouse"]) == 16
    assert max(SPEC_M_BITS["customer"]) >= max(m_bits_by_table["customer"])
    for name in TABLES:
        assert abs(len(SPEC_M_BITS[name]) - len(m_bits_by_table[name])) <= 2


@pytest.mark.parametrize("rows", BUCKETS)
@pytest.mark.parametrize("table", TABLES)
def test_decode_kernel_compiles_for_v5e_at_spec_scale(table, rows, one_chip,
                                                      no_persistent_cache):
    _compile(SPEC_M_BITS[table], rows, one_chip)
