"""The `repro.db` catalog: named tables, one engine-wide view (DESIGN.md §5).

A :class:`Database` registers :class:`~repro.db.TableSchema` s and owns the
resulting :class:`~repro.db.Table` s.  It carries engine-wide defaults
(backend, shard count, store kwargs) that individual ``create_table`` calls
can override, and aggregates ``stats()`` / ``nbytes`` across every table
and shard — the number the paper's §6 "whole-database memory reduction"
claim is about, and the one ``benchmarks/bench_db_tpcc.py`` reports.

``memory_limit`` caps the resident compressed code bytes of the whole
database (DESIGN.md §6.2): the database splits it among its tables by
their live code bytes and re-splits as they grow or shrink.
"""

from __future__ import annotations

import contextlib
import os
from typing import TYPE_CHECKING, Any, Dict, Iterator, List, Optional, Sequence

if TYPE_CHECKING:
    from repro.durability.wal import WriteAheadLog

from repro import telemetry

from .schema import TableSchema
from .table import StoreFactory, Table

_C_SPLITS = telemetry.counter("repro.residency.split.count")

# A limited database re-splits its memory at the end of a write verb once
# some table's live code bytes moved by more than this share of what they
# were at the last split (a table that had none moves by any write).
SPLIT_DRIFT = 1 / 16


class Database:
    """Catalog of tables sharing engine-wide defaults.

    >>> db = Database(backend="blitzcrank", n_shards=4)
    >>> db.create_table(schema, sample_rows=rows)
    >>> db["customer"].get_many(keys)
    """

    def __init__(
        self,
        backend: str | StoreFactory = "blitzcrank",
        n_shards: int = 1,
        store_kwargs: Optional[Dict[str, Any]] = None,
        memory_limit: Optional[int] = None,
        durability: Optional[Any] = None,
    ):
        self.backend = backend
        self.n_shards = int(n_shards)
        self.store_kwargs = dict(store_kwargs or {})
        # Resident code bytes the whole database may hold (DESIGN.md §6.2).
        # Tables given their own ``memory_budget`` keep it and count
        # against the limit; the rest share what is left by their live
        # code bytes (split_limit), each table's part divided over its
        # shards.  ``_split_at`` holds each sharing table's code bytes at
        # the last split, against which a write's drift is measured.
        if memory_limit is not None and memory_limit <= 0:
            raise ValueError("memory_limit must be positive")
        if memory_limit is not None and "memory_budget" in self.store_kwargs:
            raise ValueError("memory_limit and a per-shard memory_budget exclude each other")
        self.memory_limit = int(memory_limit) if memory_limit is not None else None
        self._split_at: Dict[str, int] = {}
        self._tables: Dict[str, Table] = {}
        # Durability (DESIGN.md §7): a DurabilityConfig (or just its root
        # path) turns on one WAL per table + checkpoints; ``None`` keeps
        # the engine purely in-memory with zero overhead.
        self._dur = None
        self._io = None
        self._ops_since_ckpt = 0
        self._ckpt_requested = False
        self._recovering = False
        if durability is not None:
            from repro.durability.config import DurabilityConfig

            if not isinstance(durability, DurabilityConfig):
                durability = DurabilityConfig(root=os.fspath(durability))
            self._dur = durability
            os.makedirs(durability.root, exist_ok=True)
            self._io = durability.make_io()

    @property
    def durable(self) -> bool:
        return self._dur is not None

    # -- catalog ---------------------------------------------------------
    def create_table(
        self,
        schema: TableSchema,
        *,
        backend: str | StoreFactory | None = None,
        n_shards: Optional[int] = None,
        sample_rows: Optional[Sequence[Dict[str, Any]]] = None,
        store_kwargs: Optional[Dict[str, Any]] = None,
        memory_budget: Optional[int] = None,
    ) -> Table:
        """Register ``schema`` and build its table (engine defaults apply
        unless overridden).  Re-registering a name raises ``ValueError``."""
        if schema.name in self._tables:
            raise ValueError(f"table {schema.name!r} already registered")
        kwargs = dict(self.store_kwargs)
        kwargs.update(store_kwargs or {})
        if self.memory_limit is not None:
            if "memory_budget" in kwargs:
                raise ValueError(
                    "memory_limit and a per-shard memory_budget exclude each other")
            if (memory_budget is not None
                    and self._fixed_budgets() + memory_budget > self.memory_limit):
                raise ValueError(
                    f"table budgets {self._fixed_budgets() + memory_budget} exceed "
                    f"memory_limit {self.memory_limit}")
        if self._dur is not None:
            # fault injection (and crash points) must cover spill I/O too
            kwargs.setdefault("spill_io", self._io)
        table = Table(
            schema,
            backend=self.backend if backend is None else backend,
            n_shards=self.n_shards if n_shards is None else n_shards,
            sample_rows=sample_rows,
            store_kwargs=kwargs,
            memory_budget=memory_budget,
        )
        self._tables[schema.name] = table
        if self._dur is not None:
            self._attach_durability(table, sample_rows)
        self._join_limit(table)
        return table

    def drop_table(self, name: str) -> None:
        """Unregister a table, releasing its spill files and (durable) WAL;
        a durable drop checkpoints so recovery won't resurrect it."""
        if name not in self._tables:
            raise KeyError(f"no table {name!r}")
        table = self._tables.pop(name)
        table.close(unlink=True)
        self.split_limit()
        if self._dur is not None:
            self.checkpoint()

    def table(self, name: str) -> Table:
        try:
            return self._tables[name]
        except KeyError:
            raise KeyError(
                f"no table {name!r}; registered: {sorted(self._tables)}"
            ) from None

    def __getitem__(self, name: str) -> Table:
        return self.table(name)

    def __contains__(self, name: str) -> bool:
        return name in self._tables

    def __iter__(self) -> Iterator[Table]:
        return iter(self._tables.values())

    def __len__(self) -> int:
        return len(self._tables)

    @property
    def table_names(self) -> List[str]:
        return sorted(self._tables)

    @property
    def schemas(self) -> Dict[str, TableSchema]:
        return {n: t.schema for n, t in self._tables.items()}

    # -- compiled execution surface (DESIGN.md §11) ----------------------
    def session(self) -> Any:
        """Open an execution session: prepared-handle cache per
        (table, verb) plus batched verb conveniences —
        ``ses.insert("orders", rows)``, ``ses.get("customer", keys,
        backend="pallas")``.  Sessions are cheap; open one per worker or
        transaction loop.  See :class:`repro.exec.Session`."""
        from repro.exec.prepared import Session  # deferred: no cycle

        return Session(self)

    # -- analytics entry point (DESIGN.md §8) ----------------------------
    def query(
        self,
        table: str,
        predicates: Sequence[Any] = (),
        columns: Optional[Sequence[str]] = None,
        group_by: Sequence[str] = (),
        aggs: Optional[Dict[str, Any]] = None,
        pushdown: bool = True,
        backend: Optional[str] = None,
        with_stats: bool = False,
    ) -> Any:
        """One-stop OLAP entry point over a registered table.

        Without ``aggs`` this is a filtered projection —
        ``Table.scan_where`` returning ``(key, row)`` pairs.  With
        ``aggs`` (``{name: (op, column)}``, op in count/sum/avg/min/max)
        it runs the streaming group-by aggregation instead and returns
        ``{group key tuple: {name: value}}``.  ``pushdown=False`` forces
        the decode-everything reference path on every shard (the
        correctness oracle the scan tests diff against).  Both paths take
        the same ``backend=`` / ``with_stats=`` keywords and report the
        same ``ScanStats`` shape (DESIGN.md §8): ``with_stats=True``
        returns ``(result, stats)``.
        """
        t = self.table(table)
        if aggs is not None or group_by:
            return t.aggregate(
                predicates,
                group_by=group_by,
                aggs=aggs,
                pushdown=pushdown,
                backend=backend,
                with_stats=with_stats,
            )
        return t.scan_where(
            predicates,
            columns=columns,
            pushdown=pushdown,
            backend=backend,
            with_stats=with_stats,
        )

    # -- engine-wide maintenance -----------------------------------------
    def merge_all(self) -> None:
        """Fold every table's delta overlay back into its arenas."""
        for t in self._tables.values():
            t.merge()

    def migrate_all(self, limit_per_table: int = 1 << 12) -> int:
        return sum(t.migrate(limit_per_table) for t in self._tables.values())

    def maintenance_step(self) -> Dict[str, List[Dict[str, Any]]]:
        out = {n: t.maintenance_step() for n, t in self._tables.items()}
        self._note_ops(0)  # honor a checkpoint request from the steps
        return out

    # -- the memory limit (DESIGN.md §6.2) --------------------------------
    def _join_limit(self, table: Table) -> None:
        """Bring a new or adopted table under the limit: a table with its
        own budget counts against it, the others join the split."""
        if self.memory_limit is None:
            return
        if self._dur is None:
            table.on_ops(self._note_ops)
        self.split_limit()

    def _fixed_budgets(self) -> int:
        return sum(t.memory_budget for t in self._tables.values()
                   if t.memory_budget is not None)

    def split_limit(self) -> Dict[str, int]:
        """Re-split the limit now: each table without a budget of its own
        gets what the fixed budgets leave, times its share of those
        tables' live code bytes (evenly while none has any; at least one
        byte).  A table whose part fell spills down to it before this
        returns.  Returns ``{table: budget}``."""
        if self.memory_limit is None:
            return {}
        with telemetry.span("repro.residency.split"):
            sizes = {n: t.code_bytes for n, t in self._tables.items()
                     if t.memory_budget is None}
            free = self.memory_limit - self._fixed_budgets()
            total = sum(sizes.values())
            budgets = {n: max(1, free * b // total if total else free // len(sizes))
                       for n, b in sizes.items()}
            for n, b in budgets.items():
                self._tables[n].set_memory_budget(b)
            self._split_at = sizes
            _C_SPLITS.inc()
        return budgets

    def _maybe_split(self) -> None:
        """Re-split once some sharing table's code bytes drifted past
        :data:`SPLIT_DRIFT` of their size at the last split."""
        for n, t in self._tables.items():
            if t.memory_budget is not None:
                continue
            then = self._split_at.get(n)
            if then is None or abs(t.code_bytes - then) > SPLIT_DRIFT * then:
                self.split_limit()
                return

    # -- recovery entry points (DESIGN.md §7) -----------------------------
    # The recovery module drives engine-private catalog and checkpoint
    # state through these instead of reaching into ``_tables`` /
    # ``_recovering`` directly (blitzlint BL004).

    def adopt_table(self, table: Table, wal: "WriteAheadLog") -> None:
        """Register an externally rebuilt table and wire its durability
        hooks — the recovery-path counterpart of :meth:`create_table`."""
        self._tables[table.name] = table
        table.attach_wal(wal, io=self._io, on_ops=self._note_ops)
        table.on_shards_built(self._wire_maintenance)
        if table.shards:
            self._wire_maintenance(table)
        self._join_limit(table)

    def discard_table(self, name: str) -> None:
        """Drop ``name`` from the catalog without closing its files
        (recovery replaces a corrupt snapshot with a from-log rebuild)."""
        self._tables.pop(name, None)

    @contextlib.contextmanager
    def recovery_mode(self) -> Iterator[None]:
        """Inhibit checkpoints while replay re-drives the batched verbs —
        a mid-replay snapshot would pair a prefix state with a full-tail
        LSN."""
        self._recovering = True
        try:
            yield
        finally:
            self._recovering = False

    def reset_checkpoint_clock(self) -> None:
        """Zero the ops-since-checkpoint cadence after recovery: replayed
        traffic must not count toward the next checkpoint trigger."""
        self._ops_since_ckpt = 0
        self._ckpt_requested = False

    # -- durability (DESIGN.md §7) ---------------------------------------
    def _attach_durability(
        self, table: Table, sample_rows: Optional[Sequence[Dict[str, Any]]]
    ) -> None:
        from repro.durability.wal import WriteAheadLog

        wal = WriteAheadLog(
            os.path.join(self._dur.root, f"{table.name}.wal"),
            io=self._io,
            fsync_every=self._dur.fsync_every,
        )
        table.attach_wal(wal, io=self._io, on_ops=self._note_ops)
        table.on_shards_built(self._wire_maintenance)
        if table.shards:
            self._wire_maintenance(table)
        if wal.lsn == 0:
            # Fresh log: the catalog event heads it, so a from-zero replay
            # can rebuild the table (same sample => same seeded model fit
            # => bit-identical codecs).  On reopen the record is already
            # there (lsn > 0) and must not be duplicated.
            wal.log(
                "create",
                {
                    "schema": table.schema,
                    "backend": table.backend,
                    "n_shards": table.n_shards,
                    "store_kwargs": table.clean_store_kwargs(),
                    "memory_budget": table.memory_budget,
                    "sample_rows": (
                        [dict(r) for r in sample_rows] if sample_rows else None
                    ),
                },
            )

    def _wire_maintenance(self, table: Table) -> None:
        """A refit/migration step invalidates the checkpointed codec list;
        request a fresh checkpoint, taken at the *end* of the current verb
        (``_note_ops``), never mid-step."""
        if self._dur is None or not self._dur.checkpoint_on_maintenance:
            return

        def request(_result: Dict[str, Any]) -> None:
            self._ckpt_requested = True

        for shard in table.shards:
            maint = getattr(shard, "maintenance", None)
            if maint is not None:
                maint.on_step.append(request)

    def _note_ops(self, n: int) -> None:
        if self.memory_limit is not None:
            self._maybe_split()
        if self._dur is None or self._recovering:
            return
        self._ops_since_ckpt += int(n)
        every = self._dur.checkpoint_every_ops
        if self._ckpt_requested or (every > 0 and self._ops_since_ckpt >= every):
            self.checkpoint()

    def checkpoint(self) -> int:
        """Snapshot the whole catalog (atomic replace); returns byte size.

        Each table entry carries its WAL's current LSN, so recovery is
        checkpoint-load + replay of only the log tail past that offset."""
        if self._dur is None:
            raise RuntimeError("checkpoint() requires durability=")
        from repro.durability.checkpoint import write_checkpoint

        tables: Dict[str, Any] = {}
        for name, t in self._tables.items():
            tables[name] = {
                "snapshot": t.snapshot_state(),
                "wal_lsn": t._wal.lsn if t._wal is not None else 0,
            }
        state = {
            "format": 1,
            "engine": {
                "backend": (self.backend if isinstance(self.backend, str) else None),
                "n_shards": self.n_shards,
                "store_kwargs": {
                    k: v
                    for k, v in self.store_kwargs.items()
                    if k not in ("codec", "spill_io")
                },
                "memory_limit": self.memory_limit,
            },
            "tables": tables,
        }
        size = write_checkpoint(self._dur.root, state, io=self._io)
        self._ops_since_ckpt = 0
        self._ckpt_requested = False
        return size

    def close(self) -> None:
        """Checkpoint (durable) and release every table's files."""
        if self._dur is not None and self._tables:
            self.checkpoint()
        for t in self._tables.values():
            t.close()

    @classmethod
    def open(cls, root: str, **kwargs: Any) -> "Database":
        """Recover a durable database from its checkpoint + WAL tails."""
        from repro.durability.recovery import open_database

        return open_database(root, **kwargs)

    # -- aggregated accounting -------------------------------------------
    @property
    def nbytes(self) -> int:
        return sum(t.nbytes for t in self._tables.values())

    @property
    def n_live(self) -> int:
        return sum(t.n_live for t in self._tables.values())

    @property
    def code_bytes(self) -> int:
        """Live compressed code bytes of every table, resident or spilled."""
        return sum(t.code_bytes for t in self._tables.values())

    @property
    def resident_code_bytes(self) -> int:
        """Live compressed code bytes held in memory: what the limit caps."""
        return sum(t.resident_code_bytes for t in self._tables.values())

    def stats(self) -> Dict[str, Any]:
        per_table = {n: t.stats() for n, t in sorted(self._tables.items())}
        out = {
            "n_tables": len(self._tables),
            "n_live": self.n_live,
            "nbytes": self.nbytes,
            "store_bytes": sum(s["store_bytes"] for s in per_table.values()),
            "index_bytes": sum(s["index_bytes"] for s in per_table.values()),
            "model_bytes": sum(s["model_bytes"] for s in per_table.values()),
            "tables": per_table,
        }
        res = [s["residency"] for s in per_table.values() if "residency" in s]
        if res:
            # whole-database view of the cold tier: nbytes stays resident
            # memory, spilled bytes live on disk and are summed separately
            out["spilled_bytes"] = sum(r["spilled_bytes"] for r in res)
            out["residency"] = {
                "memory_limit": self.memory_limit,
                "budget_bytes": sum(r["budget_bytes"] for r in res),
                "spilled_bytes": out["spilled_bytes"],
                "spills": sum(r["spills"] for r in res),
                "faults": sum(r["faults"] for r in res),
                "fault_batches": sum(r["fault_batches"] for r in res),
                "disk_file_bytes": sum(r["disk_file_bytes"] for r in res),
            }
        # whole-engine view: the registry is global, so no prefix filter
        out["telemetry"] = telemetry.snapshot()
        return out
