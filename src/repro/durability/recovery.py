"""Crash recovery: checkpoint load + WAL tail replay (DESIGN.md §7).

:func:`open_database` rebuilds a durable :class:`~repro.db.Database` from
its on-disk root:

1. load the checkpoint (``None`` on missing/corrupt — full replay then);
2. restore every checkpointed table bit-identically from its snapshot
   (pickled codec versions, embedded spill payloads, pk directory) and
   replay only its WAL tail past the recorded LSN;
3. any ``*.wal`` the checkpoint doesn't know about is a table created
   after the last checkpoint: replay it from zero, starting with its
   ``create`` record (seeded model fits make the rebuild deterministic).

Replay drives the exact same batched verbs as live traffic, under
``wal.suspend()`` so nothing is re-logged.  Checkpoints are inhibited
until recovery completes — a mid-replay snapshot would pair a prefix
state with a full-tail LSN.
"""

from __future__ import annotations

import os
from typing import Any, Optional

from repro.core.arena import SpillCorruptionError
from repro.db.database import Database
from repro.db.table import Table

from .checkpoint import load_checkpoint
from .config import DurabilityConfig
from .wal import WriteAheadLog


def _replay(table: Table, wal: WriteAheadLog, from_lsn: int) -> int:
    """Re-apply every record past ``from_lsn``; returns records replayed."""
    n = 0
    key_of = table.schema.key_of
    with wal.suspend():
        for _lsn, op, payload in wal.scan(from_lsn):
            if op == "insert":
                table.insert_many(payload)
            elif op == "update":
                table.update_many([key_of(r) for r in payload], payload)
            elif op == "delete":
                table.delete_many(payload)
            elif op != "create":
                raise ValueError(f"{wal.path}: unknown WAL op {op!r}")
            n += 1
    return n


def _rebuild_from_log(db: Database, wal: WriteAheadLog) -> bool:
    """Rebuild one table from its WAL's full history, starting at the
    ``create`` record that heads every log.  Returns False when nothing
    durable ever reached the log (the create itself was lost)."""
    first = next(wal.scan(0), None)
    if first is None or first[1] != "create":
        wal.close()
        return False
    lsn, _op, meta = first
    kwargs = dict(meta["store_kwargs"])
    kwargs["spill_io"] = db._io
    table = Table(
        meta["schema"],
        backend=meta["backend"],
        n_shards=meta["n_shards"],
        sample_rows=meta["sample_rows"],
        store_kwargs=kwargs,
        memory_budget=meta["memory_budget"],
    )
    db.adopt_table(table, wal)
    _replay(table, wal, lsn)
    return True


def open_database(
    root: str,
    io: Optional[Any] = None,
    fsync_every: int = 1,
    checkpoint_every_ops: int = 0,
    checkpoint_on_maintenance: bool = True,
) -> Database:
    """Recover the durable database at ``root``.

    Safe on a fresh or empty root (returns an empty durable database) and
    idempotent: recovering twice yields the same state, because replay
    never appends to the log it reads.
    """
    cfg = DurabilityConfig(
        root=os.fspath(root),
        fsync_every=fsync_every,
        checkpoint_every_ops=checkpoint_every_ops,
        checkpoint_on_maintenance=checkpoint_on_maintenance,
        io=io,
    )
    ck = load_checkpoint(cfg.root)
    engine = (ck or {}).get("engine") or {}
    db = Database(
        backend=engine.get("backend") or "blitzcrank",
        n_shards=engine.get("n_shards", 1),
        store_kwargs=engine.get("store_kwargs") or {},
        memory_limit=engine.get("memory_limit"),
        durability=cfg,
    )
    with db.recovery_mode():
        if ck:
            for name, entry in ck["tables"].items():
                wal = WriteAheadLog(
                    os.path.join(cfg.root, f"{name}.wal"),
                    io=db._io,
                    fsync_every=fsync_every,
                )
                try:
                    table = Table.from_snapshot(entry["snapshot"], spill_io=db._io)
                    db.adopt_table(table, wal)
                    _replay(table, wal, entry["wal_lsn"])
                except SpillCorruptionError:
                    # An extent-mode checkpoint references spill-file
                    # ranges that a post-checkpoint disk compaction moved
                    # (or the crash tore).  The WAL keeps full history
                    # exactly for this: drop the snapshot and rebuild the
                    # table from its create record forward.
                    db.discard_table(name)
                    _rebuild_from_log(db, wal)
        for fn in sorted(os.listdir(cfg.root)):
            if not fn.endswith(".wal") or fn[:-4] in db:
                continue
            # a table created after the last checkpoint: nothing but its
            # log exists, so replay it from zero
            wal = WriteAheadLog(
                os.path.join(cfg.root, fn), io=db._io, fsync_every=fsync_every
            )
            _rebuild_from_log(db, wal)
    db.reset_checkpoint_clock()
    return db
