"""Deterministic crash-matrix + corruption harness (DESIGN.md §7).

Every named crash point in :data:`CRASH_POINTS` is exercised the same way:
build a durable database, run a deterministic batch stream with periodic
checkpoints, arm the point, let :class:`SimulatedCrash` "kill" the
process, reopen via :func:`~repro.durability.recovery.open_database`, and
compare every key against a fresh in-memory reference database that
applied exactly the batches the durability contract says must survive:

* ``wal.before_flush`` fires before the verb's record hits the log, so
  the in-flight batch is **lost** — recovery must show the prior state;
* every other point fires after the record was pwritten, so the batch is
  **durable** — recovery must show it applied (fsync_every=1, and a
  simulated kill does not lose the page cache).

Verification reads run on both decode backends (numpy, and pallas when
jax is importable), so recovery correctness is checked against the
compiled kernel path too, not just the interpreter.

:func:`run_corruption_scenarios` covers the non-crash faults: spill-page
bit flips (repaired from the WAL, never served), WAL torn tails, a
corrupt checkpoint (degrades to full replay), ENOSPC, and a failed fsync
(poisoned log).  Run ``python -m repro.durability.harness --smoke`` for
the CI subset.
"""

from __future__ import annotations

import argparse
import os
import shutil
import tempfile
from typing import Any, Dict, List, Optional, Tuple

from repro.db import Database, TableSchema
from repro.oltp import tpcc

from .config import DurabilityConfig
from .io import DurableIO, FaultInjector, SimulatedCrash
from .recovery import open_database
from .wal import WalPoisonedError

CRASH_POINTS = [
    "wal.before_flush",
    "wal.before_fsync",
    "wal.after_flush",
    "apply.before",
    "spill.mid_write",
    "checkpoint.before",
    "checkpoint.mid",
    "checkpoint.post",
]
# Crash here loses the in-flight batch; everywhere else it is durable.
BATCH_LOST = {"wal.before_flush"}

N_ROWS = 600
N_POP = 256  # initial population (doubles as the model-fit sample)


def _schema() -> TableSchema:
    return TableSchema("customer", tpcc.TABLES["customer"][0], "c_id")


def _batches(rows: List[Dict[str, Any]], schema: TableSchema,
             ) -> List[Tuple[str, Any]]:
    """A deterministic op stream over the tail rows: inserts of fresh
    keys, updates and deletes of populated ones."""
    out: List[Tuple[str, Any]] = []
    pop_keys = [schema.key_of(r) for r in rows[:N_POP]]
    nxt = N_POP
    for step in range(24):
        if step % 3 == 0 and nxt + 16 <= len(rows):
            out.append(("insert", rows[nxt:nxt + 16]))
            nxt += 16
        elif step % 3 == 1:
            # updates stay below 128: the delete batches drain [128, 192)
            lo = (step * 7) % 104
            ks = pop_keys[lo:lo + 24]
            out.append(("update",
                        [dict(rows[pop_keys.index(k)],
                              c_balance=1000.0 + step) for k in ks]))
        else:
            lo = 128 + (step * 3) % 64
            out.append(("delete", pop_keys[lo:lo + 4]))
    return out


def _apply(table, schema: TableSchema, op: str, payload: Any) -> None:
    if op == "insert":
        table.insert_many(payload)
    elif op == "update":
        table.update_many([schema.key_of(r) for r in payload], payload)
    else:
        table.delete_many(payload)


def _reference_state(backend: str, rows: List[Dict[str, Any]],
                     schema: TableSchema, n_batches: int,
                     store_kwargs: Optional[Dict[str, Any]],
                     memory_limit: Optional[int]) -> Database:
    """A fresh non-durable database that applied the expected prefix."""
    db = Database(backend=backend, store_kwargs=dict(store_kwargs or {}),
                  memory_limit=memory_limit)
    t = db.create_table(schema, sample_rows=rows[:N_POP])
    t.insert_many(rows[:N_POP])
    for op, payload in _batches(rows, schema)[:n_batches]:
        _apply(t, schema, op, payload)
    return db


def _compare(recovered: Database, reference: Database,
             schema: TableSchema, rows: List[Dict[str, Any]],
             backend: str) -> List[str]:
    """Bit-identity over every key, on every available decode backend."""
    keys = [schema.key_of(r) for r in rows]
    backends: List[Optional[str]] = [None]
    if backend == "blitzcrank":
        backends = ["numpy"]
        try:
            import jax  # noqa: F401
            backends.append("pallas")
        except ImportError:
            pass
    errs: List[str] = []
    for be in backends:
        got = recovered["customer"].get_many(keys, backend=be)
        want = reference["customer"].get_many(keys, backend=be)
        if got != want:
            bad = sum(1 for g, w in zip(got, want) if g != w)
            errs.append(f"backend={be}: {bad}/{len(keys)} rows differ")
    return errs


def run_crash_scenario(point: str, backend: str = "blitzcrank",
                       seed: int = 0, checkpoint_every: int = 7,
                       memory_limit: Optional[int] = None,
                       ) -> Dict[str, Any]:
    """Kill at ``point``, recover, verify.  Returns a result dict with
    ``ok`` (bit-identical), ``crashed`` (the point actually fired), and
    the batch counts."""
    if point not in CRASH_POINTS:
        raise ValueError(f"unknown crash point {point!r}")
    rows = tpcc.gen_customer(N_ROWS, seed=seed)
    schema = _schema()
    store_kwargs: Dict[str, Any] = {}
    if memory_limit is None:
        # tight enough that the 256-row population spills under either
        # representation (~8 KB compressed arena, ~10x that raw)
        memory_limit = {"blitzcrank": 4 * 1024, "silo": 24 * 1024}.get(
            backend)
    budget = memory_limit if backend in ("blitzcrank", "silo") else None
    inj = FaultInjector(seed)
    root = tempfile.mkdtemp(prefix="blitz-crash-")
    try:
        cfg = DurabilityConfig(root=root, fsync_every=1, io=DurableIO(inj))
        db = Database(backend=backend, store_kwargs=dict(store_kwargs),
                      memory_limit=budget, durability=cfg)
        t = db.create_table(schema, sample_rows=rows[:N_POP])
        t.insert_many(rows[:N_POP])
        db.checkpoint()
        inj.crash_at(point)  # armed only now: the load must complete
        applied = 0
        crashed = False
        in_checkpoint = False
        try:
            for b, (op, payload) in enumerate(_batches(rows, schema)):
                _apply(t, schema, op, payload)
                applied += 1
                if (b + 1) % checkpoint_every == 0:
                    in_checkpoint = True
                    db.checkpoint()
                    in_checkpoint = False
        except SimulatedCrash as e:
            assert e.point == point
            crashed = True
        result: Dict[str, Any] = {"point": point, "backend": backend,
                                  "crashed": crashed, "applied": applied}
        if not crashed:
            # the workload never reached this point (e.g. no spill under a
            # large budget) — report it so the matrix can fail loudly
            result["ok"] = False
            result["errors"] = ["crash point never fired"]
            return result
        # the process is "dead": recover from disk only
        n_expected = applied
        if not in_checkpoint and point not in BATCH_LOST:
            n_expected += 1
        recovered = open_database(root)
        reference = _reference_state(backend, rows, schema, n_expected,
                                     store_kwargs, budget)
        errs = _compare(recovered, reference, schema, rows, backend)
        recovered.close()
        result["ok"] = not errs
        result["errors"] = errs
        result["expected_batches"] = n_expected
        return result
    finally:
        shutil.rmtree(root, ignore_errors=True)


def run_crash_matrix(backends: Optional[List[str]] = None, seed: int = 0,
                     points: Optional[List[str]] = None,
                     verbose: bool = False) -> List[Dict[str, Any]]:
    backends = backends or ["blitzcrank", "silo"]
    points = points or CRASH_POINTS
    results = []
    for backend in backends:
        for point in points:
            if point == "spill.mid_write" and backend not in (
                    "blitzcrank", "silo"):
                continue
            r = run_crash_scenario(point, backend=backend, seed=seed)
            results.append(r)
            if verbose:
                status = "ok" if r["ok"] else f"FAIL {r['errors']}"
                print(f"  {backend:<10} {point:<22} {status}")
    return results


# -- non-crash fault scenarios -------------------------------------------

def run_corruption_scenarios(seed: int = 0,
                             verbose: bool = False) -> List[Dict[str, Any]]:
    """Checksum/fault coverage that doesn't fit the kill-reopen mold."""
    results = []
    for name, fn in [
        ("spill_bitflip_repair", _scenario_spill_bitflip),
        ("wal_torn_tail", _scenario_wal_torn_tail),
        ("checkpoint_corrupt_fallback", _scenario_checkpoint_corrupt),
        ("wal_enospc", _scenario_wal_enospc),
        ("fsync_eio_poisons", _scenario_fsync_eio),
    ]:
        errs = fn(seed)
        results.append({"scenario": name, "ok": not errs, "errors": errs})
        if verbose:
            print(f"  {name:<28} {'ok' if not errs else errs}")
    return results


def _durable_customer_db(
    root: str, rows: List[Dict[str, Any]], io: Optional[Any] = None
) -> Tuple[Database, Any, TableSchema]:
    schema = _schema()
    cfg = DurabilityConfig(root=root, fsync_every=1, io=io)
    db = Database(backend="blitzcrank", memory_limit=4 * 1024,
                  durability=cfg)
    t = db.create_table(schema, sample_rows=rows[:N_POP])
    t.insert_many(rows[:N_POP])
    return db, t, schema


def _scenario_spill_bitflip(seed: int) -> List[str]:
    """A flipped bit in a spilled extent is detected by its CRC, the rows
    rebuilt from the WAL, and reads stay bit-identical — never garbage."""
    import numpy as np

    rows = tpcc.gen_customer(N_ROWS, seed=seed)
    root = tempfile.mkdtemp(prefix="blitz-corrupt-")
    try:
        db, t, schema = _durable_customer_db(root, rows)
        keys = [schema.key_of(r) for r in rows[:N_POP]]
        want = t.get_many(keys)  # captured pre-corruption (faults all in)
        tbl = t.shards[0].table
        spilled = np.flatnonzero(~tbl._resident[: tbl.n_blocks])
        if spilled.size < 4:
            return ["budget never spilled — scenario is vacuous"]
        errs = []
        # flip one payload byte in each of 4 spilled extents, on disk
        arena_fd = tbl._res.disk._fd
        for b in spilled[:4].tolist():
            off = int(tbl._disk_off[b]) + 12  # past the frame header
            byte = os.pread(arena_fd, 1, off)
            os.pwrite(arena_fd, bytes([byte[0] ^ 0x40]), off)
        got = t.get_many(keys)
        if got != want:
            errs.append("reads after corruption are not bit-identical")
        repairs = sum(s.repairs for s in t.shards)
        if not repairs:
            errs.append("corruption was never detected/repaired")
        if not tbl._res.quarantined:
            errs.append("corrupt extents were not quarantined")
        db.close()
        return errs
    finally:
        shutil.rmtree(root, ignore_errors=True)


def _scenario_wal_torn_tail(seed: int) -> List[str]:
    """Garbage appended to the log (a torn final write) is truncated on
    reopen; every intact record replays."""
    rows = tpcc.gen_customer(N_ROWS, seed=seed)
    root = tempfile.mkdtemp(prefix="blitz-torn-")
    try:
        db, t, schema = _durable_customer_db(root, rows)
        keys = [schema.key_of(r) for r in rows[:N_POP]]
        want = t.get_many(keys)
        db["customer"]._wal.close()
        wal_path = os.path.join(root, "customer.wal")
        with open(wal_path, "ab") as f:
            f.write(b"\x00\x01torn-frame-garbage")
        ck = os.path.join(root, "checkpoint.bin")
        if os.path.exists(ck):  # force the replay path through the tail
            os.unlink(ck)
        db2 = open_database(root)
        errs = []
        if db2["customer"]._wal.truncated_bytes == 0:
            errs.append("torn tail was not truncated")
        if db2["customer"].get_many(keys) != want:
            errs.append("replay after torn tail lost records")
        db2.close()
        return errs
    finally:
        shutil.rmtree(root, ignore_errors=True)


def _scenario_checkpoint_corrupt(seed: int) -> List[str]:
    """A corrupt checkpoint is rejected by its CRC and recovery falls back
    to full WAL replay — same final state, just slower."""
    rows = tpcc.gen_customer(N_ROWS, seed=seed)
    root = tempfile.mkdtemp(prefix="blitz-ckpt-")
    try:
        db, t, schema = _durable_customer_db(root, rows)
        keys = [schema.key_of(r) for r in rows[:N_POP]]
        want = t.get_many(keys)
        db.close()  # writes a checkpoint
        ck = os.path.join(root, "checkpoint.bin")
        buf = bytearray(open(ck, "rb").read())
        buf[len(buf) // 2] ^= 0x40
        open(ck, "wb").write(bytes(buf))
        db2 = open_database(root)
        errs = []
        if db2["customer"].get_many(keys) != want:
            errs.append("full-replay fallback lost records")
        db2.close()
        return errs
    finally:
        shutil.rmtree(root, ignore_errors=True)


def _scenario_wal_enospc(seed: int) -> List[str]:
    """ENOSPC on a WAL write surfaces as an error on the verb, poisons
    the log, and recovery serves the pre-verb state."""
    rows = tpcc.gen_customer(N_ROWS, seed=seed)
    inj = FaultInjector(seed)
    root = tempfile.mkdtemp(prefix="blitz-enospc-")
    try:
        db, t, schema = _durable_customer_db(root, rows, io=DurableIO(inj))
        keys = [schema.key_of(r) for r in rows[:N_POP]]
        want = t.get_many(keys)
        inj.add_fault("pwrite", "enospc")
        errs = []
        try:
            t.update_many(keys[:4], [dict(rows[i], c_balance=1.0)
                                     for i in range(4)])
            errs.append("ENOSPC did not surface on the verb")
        except OSError:
            pass
        try:
            t.insert_many(rows[N_POP:N_POP + 4])
            errs.append("poisoned log accepted another append")
        except WalPoisonedError:
            pass
        db2 = open_database(root)
        if db2["customer"].get_many(keys) != want:
            errs.append("recovery after ENOSPC lost pre-verb state")
        db2.close()
        return errs
    finally:
        shutil.rmtree(root, ignore_errors=True)


def _scenario_fsync_eio(seed: int) -> List[str]:
    """A failed fsync leaves the durable tail unknowable: the log poisons
    itself (no later append may succeed), and recovery is allowed to
    surface the pwritten record — ambiguous ack, never silent loss."""
    rows = tpcc.gen_customer(N_ROWS, seed=seed)
    inj = FaultInjector(seed)
    root = tempfile.mkdtemp(prefix="blitz-fsync-")
    try:
        db, t, schema = _durable_customer_db(root, rows, io=DurableIO(inj))
        keys = [schema.key_of(r) for r in rows[:N_POP]]
        inj.add_fault("fsync", "eio")
        errs = []
        try:
            t.update_many(keys[:4], [dict(rows[i], c_balance=2.0)
                                     for i in range(4)])
            errs.append("fsync EIO did not surface")
        except OSError:
            pass
        if not db["customer"]._wal.poisoned:
            errs.append("log not poisoned after failed fsync")
        db2 = open_database(root)
        got = db2["customer"].get_many(keys[:4])
        # the record was pwritten before the fsync failed: recovery
        # applies it (the ambiguous-ack side of the contract)
        if any(r["c_balance"] != 2.0 for r in got):
            errs.append("pwritten record did not survive recovery")
        db2.close()
        return errs
    finally:
        shutil.rmtree(root, ignore_errors=True)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI subset: every crash point on blitzcrank + "
                         "silo, plus all corruption scenarios")
    ap.add_argument("--backend", action="append", default=None)
    ap.add_argument("--point", action="append", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    print("crash matrix:")
    results = run_crash_matrix(backends=args.backend, seed=args.seed,
                               points=args.point, verbose=True)
    print("corruption scenarios:")
    results += run_corruption_scenarios(seed=args.seed, verbose=True)
    bad = [r for r in results if not r["ok"]]
    print(f"{len(results) - len(bad)}/{len(results)} scenarios passed")
    return 1 if bad else 0


if __name__ == "__main__":
    raise SystemExit(main())
