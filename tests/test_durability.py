"""Durability layer (DESIGN.md §7): WAL, checkpoint, recovery, faults.

Unit coverage for the pieces the crash-matrix harness composes: framed
WAL append/scan with torn-tail truncation and poisoning, atomic
checksummed checkpoints, checked spill reads (short reads and bit flips
become typed errors, never garbage), fd hygiene on ``drop_table``, and a
close/reopen bit-identity round trip through both recovery paths
(checkpoint + tail, and full from-zero replay).  A thin smoke slice of
the harness itself runs here so tier-1 catches a broken crash matrix
without CI's full sweep.
"""

import os

import pytest

from repro.core.arena import (ArenaReadError, DiskArena,
                              ExtentCorruptionError, SpillCorruptionError)
from repro.db import Database, TableSchema
from repro.durability import harness
from repro.durability.checkpoint import (checkpoint_path, load_checkpoint,
                                         write_checkpoint)
from repro.durability.io import DurableIO, FaultInjector, SimulatedCrash
from repro.durability.wal import WalPoisonedError, WriteAheadLog
from repro.oltp import tpcc
from repro.oltp.store import UncompressedStore

CUSTOMER = tpcc.TABLES["customer"][0]


def _customer_schema() -> TableSchema:
    return TableSchema("customer", CUSTOMER, "c_id")


# -- WAL ------------------------------------------------------------------

def test_wal_log_scan_roundtrip(tmp_path):
    path = str(tmp_path / "t.wal")
    wal = WriteAheadLog(path)
    wal.log("insert", [{"a": 1}])
    wal.log("delete", [7, 9])
    assert wal.lsn > 0
    got = [(op, payload) for _lsn, op, payload in wal.scan(0)]
    assert got == [("insert", [{"a": 1}]), ("delete", [7, 9])]
    # LSNs are byte offsets: scanning from the first record's end yields
    # only the second
    first_end = next(wal.scan(0))[0]
    assert [op for _l, op, _p in wal.scan(first_end)] == ["delete"]
    wal.close()


def test_wal_torn_tail_truncated_on_open(tmp_path):
    path = str(tmp_path / "t.wal")
    wal = WriteAheadLog(path)
    for i in range(3):
        wal.log("insert", [i])
    intact = wal.lsn
    wal.close()
    # a torn final record: garbage where a frame should start
    with open(path, "ab") as f:
        f.write(b"\x00garbage-torn-tail")
    wal2 = WriteAheadLog(path)
    assert wal2.truncated_bytes > 0
    assert wal2.lsn == intact
    assert [p for _l, _op, p in wal2.scan(0)] == [[0], [1], [2]]
    wal2.close()


def test_wal_poisons_after_failed_write(tmp_path):
    inj = FaultInjector(seed=1)
    inj.add_fault("pwrite", "enospc")
    wal = WriteAheadLog(str(tmp_path / "t.wal"), io=DurableIO(inj))
    with pytest.raises(OSError):
        wal.log("insert", [1])
    with pytest.raises(WalPoisonedError):
        wal.log("insert", [2])
    assert inj.fired == ["pwrite:enospc"]
    wal.close()


def test_wal_suspend_blocks_appends(tmp_path):
    wal = WriteAheadLog(str(tmp_path / "t.wal"))
    with wal.suspend():
        wal.log("insert", [1])
    assert wal.lsn == 0
    wal.close()


# -- checkpoint -----------------------------------------------------------

def test_checkpoint_roundtrip_and_corruption(tmp_path):
    root = str(tmp_path)
    state = {"tables": {"customer": {"wal_lsn": 123}}, "format": 1}
    size = write_checkpoint(root, state)
    assert size > 0
    assert load_checkpoint(root) == state
    # one flipped byte inside the payload -> CRC mismatch -> None
    path = checkpoint_path(root)
    buf = bytearray(open(path, "rb").read())
    buf[-1] ^= 0x40
    open(path, "wb").write(bytes(buf))
    assert load_checkpoint(root) is None


def test_checkpoint_replace_is_atomic(tmp_path):
    root = str(tmp_path)
    write_checkpoint(root, {"v": "old"})
    inj = FaultInjector(seed=0)
    inj.crash_at("checkpoint.mid")
    with pytest.raises(SimulatedCrash):
        write_checkpoint(root, {"v": "new"}, io=DurableIO(inj))
    # the crash tore the tmp file, not the live checkpoint
    assert load_checkpoint(root) == {"v": "old"}


# -- checked spill reads --------------------------------------------------

def test_arena_short_read_is_typed(tmp_path):
    arena = DiskArena(str(tmp_path / "spill.arena"))
    (off,) = arena.write_many([b"x" * 100])
    os.ftruncate(arena._fd, off + 10)
    with pytest.raises(ArenaReadError):
        arena.read(off, 100)
    with pytest.raises(ExtentCorruptionError):
        arena.read_checked(off, 100)
    arena.close()


def test_arena_bitflip_detected(tmp_path):
    arena = DiskArena(str(tmp_path / "spill.arena"))
    payloads = [bytes([i]) * 64 for i in range(4)]
    offs = arena.write_many(payloads)
    assert arena.read_many_checked(offs, [64] * 4) == payloads
    # flip one payload byte on disk: only that extent is reported bad
    byte = os.pread(arena._fd, 1, offs[2] + 20)
    os.pwrite(arena._fd, bytes([byte[0] ^ 0x01]), offs[2] + 20)
    with pytest.raises(ExtentCorruptionError) as ei:
        arena.read_many_checked(offs, [64] * 4)
    assert ei.value.indices == [2]
    arena.close()


def test_store_truncated_spill_never_serves_garbage(tmp_path):
    rows = tpcc.gen_customer(64)
    store = UncompressedStore(CUSTOMER, memory_budget=2048,
                              spill_path=str(tmp_path / "s.spill"))
    ids = store.insert_many(rows)
    assert store._spilled, "budget should have forced spills"
    os.ftruncate(store._res.disk._fd, 0)
    cold = sorted(store._spilled)[0]
    # no repair_fn installed (no WAL): typed error, never wrong rows
    with pytest.raises(SpillCorruptionError):
        store.get_many([ids[cold]])
    store.close(unlink=True)


# -- resource hygiene (satellite: close/unlink + fd leaks) ----------------

@pytest.mark.skipif(not os.path.exists("/proc/self/fd"),
                    reason="needs procfs to count open fds")
def test_drop_table_releases_files_and_fds(tmp_path):
    rows = tpcc.gen_customer(300)
    before = len(os.listdir("/proc/self/fd"))
    for i in range(3):
        root = str(tmp_path / f"db{i}")
        db = Database(backend="blitzcrank", memory_limit=4 * 1024,
                      durability=root)
        t = db.create_table(_customer_schema(), sample_rows=rows[:256])
        t.insert_many(rows[:256])
        assert os.path.exists(os.path.join(root, "customer.wal"))
        db.drop_table("customer")
        assert not os.path.exists(os.path.join(root, "customer.wal"))
        db.close()
    after = len(os.listdir("/proc/self/fd"))
    assert after <= before, f"leaked {after - before} fds"


def test_disk_arena_context_manager(tmp_path):
    path = str(tmp_path / "spill.arena")
    with DiskArena(path) as arena:
        arena.write_many([b"payload"])
        fd = arena._fd
    with pytest.raises(OSError):
        os.fstat(fd)  # closed on exit
    assert os.path.exists(path)


# -- recovery round trips -------------------------------------------------

def _populated_durable_db(root, rows):
    db = Database(backend="blitzcrank", memory_limit=4 * 1024,
                  durability=root)
    t = db.create_table(_customer_schema(), sample_rows=rows[:256])
    t.insert_many(rows[:256])
    upd = [dict(r, c_balance=float(i)) for i, r in enumerate(rows[:40])]
    t.update_many([r["c_id"] for r in upd], upd)
    t.delete_many(list(range(200, 220)))
    return db


def test_close_reopen_bit_identical(tmp_path):
    root = str(tmp_path / "db")
    rows = tpcc.gen_customer(300)
    db = _populated_durable_db(root, rows)
    keys = [k for k, _ in db["customer"].scan()]
    want = db["customer"].get_many(keys)
    db.close()  # checkpoint + close: recovery is checkpoint + empty tail

    rdb = Database.open(root)
    assert rdb["customer"].get_many(keys) == want
    for t in rdb:
        t.close()

    # corrupting the checkpoint degrades to full from-zero WAL replay,
    # with the same bit-identical answer
    os.unlink(checkpoint_path(root))
    rdb2 = Database.open(root)
    assert rdb2["customer"].get_many(keys) == want
    for t in rdb2:
        t.close()


def test_open_empty_root_is_fresh_durable_db(tmp_path):
    db = Database.open(str(tmp_path / "fresh"))
    assert db.durable and len(db) == 0
    t = db.create_table(_customer_schema(),
                        sample_rows=tpcc.gen_customer(64))
    t.insert_many(tpcc.gen_customer(64))
    db.close()


# -- harness smoke (full matrix runs in the CI recovery-matrix job) -------

@pytest.mark.parametrize("point,backend", [
    ("wal.before_flush", "blitzcrank"),   # in-flight batch is lost
    ("apply.before", "blitzcrank"),       # logged but never applied
    ("checkpoint.mid", "blitzcrank"),     # torn checkpoint tmp file
    ("spill.mid_write", "silo"),          # torn spill segment
])
def test_crash_scenario_smoke(point, backend):
    r = harness.run_crash_scenario(point, backend=backend, seed=0)
    assert r["crashed"], f"{point} never fired"
    assert r["ok"], r["errors"]


def test_corruption_scenarios_smoke():
    errs = harness._scenario_spill_bitflip(0)
    errs += harness._scenario_wal_torn_tail(0)
    assert not errs
