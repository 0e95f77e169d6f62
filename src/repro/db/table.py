"""Hash-partitioned table: primary-key routing over RowStore shards.

A :class:`Table` owns N shards, each an independent
:class:`~repro.oltp.store.RowStore` (``BlitzStore`` by default — any
backend in ``STORE_KINDS`` or a user factory plugs in).  Rows are placed
by ``stable_key_hash(pk) % n_shards``; a directory maps each live primary
key to its ``(shard, local row id)`` slot.  The batched verbs group keys
per shard and issue **one** batched RowStore call per shard, so the
compiled Pallas ``decode_select`` fast path (DESIGN.md §2) is preserved:
a ``get_many`` over K keys costs at most ``n_shards`` vectorized decodes,
never K scalar ones.

Routing invariants (DESIGN.md §5):

* placement is a pure function of the key — the same key always routes to
  the same shard, across runs and processes;
* batched results come back in *request order*, exactly as an unsharded
  store would return them;
* local row ids are never reused (RowStore contract), so a delete + fresh
  insert of the same key occupies a new slot but the directory always
  points at the live one.

Key-level semantics mirror the RowStore protocol with keys in place of
dense ids: ``get_many`` returns ``None`` for unknown/deleted keys, scalar
``get`` raises ``KeyError``, ``update_many`` of a missing key raises
``KeyError``, ``insert_many`` of a live key raises ``ValueError``
(re-inserting a *deleted* key is allowed and revives it in a new slot).
"""

from __future__ import annotations

import inspect
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

from repro import telemetry
from repro.oltp.store import STORE_KINDS, RowStore
from .schema import Key, TableSchema, stable_key_hash

# Batched-verb telemetry (DESIGN.md §9): one span per Table verb call
# (the transaction hot path's outermost engine region) plus row and
# shard fan-out counters — `shard_calls / verb count` is the fan-out the
# 7.5x gap hunt watches.
_C_SHARD_CALLS = telemetry.counter("repro.db.shard_calls")
_C_INSERT_ROWS = telemetry.counter("repro.db.insert_many.rows")
_C_GET_ROWS = telemetry.counter("repro.db.get_many.rows")
_C_UPDATE_ROWS = telemetry.counter("repro.db.update_many.rows")
_C_DELETE_ROWS = telemetry.counter("repro.db.delete_many.rows")

# Per-entry directory charge: 8 B key hash + 8 B packed (shard, slot)
# pointer, the footprint of an open-addressed C hash index.  Key payload
# bytes are NOT charged: the primary-key columns are stored (compressed)
# in the rows themselves, and a hash index verifies the key against the
# decoded row rather than duplicating it.
INDEX_ENTRY_OVERHEAD = 16

StoreFactory = Callable[..., RowStore]


class Table:
    """One catalog table: schema + N hash-partitioned RowStore shards.

    Shards are built lazily on the first non-empty ``insert_many`` (that
    batch doubles as the model-fit sample) unless ``sample_rows`` is given,
    in which case they are built eagerly — the TPC-C loader passes its
    generated population so models are fit before any traffic.  All shards
    fit on the *same* sample: per-shard slices would give each shard a
    different model for the same column, which breaks nothing but wastes
    model bytes and makes shard stats incomparable.
    """

    def __init__(
        self,
        schema: TableSchema,
        backend: str | StoreFactory = "blitzcrank",
        n_shards: int = 1,
        sample_rows: Optional[Sequence[Dict[str, Any]]] = None,
        store_kwargs: Optional[Dict[str, Any]] = None,
        memory_budget: Optional[int] = None,
    ):
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        self.schema = schema
        self.name = schema.name
        self.n_shards = int(n_shards)
        self.backend = backend
        self.store_kwargs = dict(store_kwargs or {})
        # Out-of-core budget (DESIGN.md §6.2): ``memory_budget`` is the
        # table's own budget; a limited Database instead moves the table's
        # share of its limit in through set_memory_budget (``budget``).
        # Either is split evenly across the hash-partitioned shards —
        # placement is a uniform hash of the key, so each shard carries
        # ~1/N of the data and deserves ~1/N of the memory.  An explicit
        # per-shard ``memory_budget`` in store_kwargs wins over the split.
        self.memory_budget = int(memory_budget) if memory_budget is not None else None
        self.budget = self.memory_budget
        self._shards: List[RowStore] = []
        self._dir: Dict[Key, Tuple[int, int]] = {}
        self._prepared: Dict[str, Any] = {}
        # Durability hooks (DESIGN.md §7), wired by a durable Database via
        # attach_wal(): the WAL gets every batch verb *before* it applies,
        # _on_ops drives the checkpoint cadence at verb end, and _io
        # carries the apply.before crash point.
        self._wal = None
        self._io = None
        self._on_ops: Optional[Callable[[int], None]] = None
        self._on_shards_built: Optional[Callable[["Table"], None]] = None
        if sample_rows:
            self._build_shards(sample_rows)

    # -- shard lifecycle -------------------------------------------------
    def _build_shards(self, sample_rows: Sequence[Dict[str, Any]]) -> None:
        factory: StoreFactory
        if callable(self.backend):
            factory = self.backend
        else:
            try:
                factory = STORE_KINDS[self.backend]
            except KeyError:
                raise ValueError(
                    f"unknown backend {self.backend!r}; expected one of "
                    f"{sorted(STORE_KINDS)} or a factory"
                ) from None
        try:  # probe, don't catch build errors: those must propagate
            can_share = "codec" in inspect.signature(factory).parameters
        except (TypeError, ValueError):  # e.g. builtins without signatures
            can_share = False
        kwargs = dict(self.store_kwargs)
        if self.budget is not None:
            kwargs.setdefault("memory_budget", self._shard_budget())
        spill_base = kwargs.get("spill_path")
        for j in range(self.n_shards):
            if spill_base is not None and self.n_shards > 1:
                # each shard owns its spill file — one shared append-only
                # file under two arenas would interleave their extents
                kwargs["spill_path"] = f"{spill_base}.s{j}"
            shard = factory(self.schema, sample_rows, **kwargs)
            if (
                j == 0
                and self.n_shards > 1
                and can_share
                and "codec" not in kwargs
                and not kwargs.get("adaptive")
                and getattr(shard, "codec", None) is not None
            ):
                # Every shard fits on the same sample, so fit once and
                # share the codec (BlitzStore accepts a pre-fitted one):
                # N identical model sets would multiply both fit time and
                # model bytes by the shard count for nothing.  Shards
                # still version/refit independently from v0.  Not shared
                # under adaptive maintenance — each shard's drift monitor
                # owns its plan's escape window, and a shared plan would
                # let one shard's step reset every other shard's window.
                try:
                    kwargs["codec"] = shard.codec
                except Exception:
                    pass
            maint = getattr(shard, "maintenance", None)
            if maint is not None:
                maint.label = f"{self.name}/shard{j}"
            self._shards.append(shard)
        if self._wal is not None:
            self._install_repair_fns()
        if self._on_shards_built is not None:
            self._on_shards_built(self)

    def _shard_budget(self) -> int:
        return max(1, self.budget // self.n_shards)

    def set_memory_budget(self, budget: int) -> None:
        """Move the table's budget on a live table: each shard gets its
        even part, and a shard whose part fell spills down to it now."""
        self.budget = int(budget)
        for shard in self._shards:
            shard.set_memory_budget(self._shard_budget())

    def on_shards_built(
        self, callback: Optional[Callable[["Table"], None]]
    ) -> None:
        """Designated entry point for the owning engine to (re)wire the
        shards-built hook (maintenance wiring on build/rebuild).  Foreign
        writes to ``_on_shards_built`` are confined here (BL004)."""
        self._on_shards_built = callback

    @property
    def shards(self) -> List[RowStore]:
        return list(self._shards)

    def shard_of(self, key: Key) -> int:
        return stable_key_hash(key) % self.n_shards

    @property
    def plan_epoch(self) -> Tuple[int, ...]:
        """Per-shard plan versions — the epoch component of the
        prepared-op cache key (DESIGN.md §11).  A refit/migrate
        ``install_codec`` bumps a shard's version and so the epoch;
        merges that keep the plan leave it unchanged."""
        return tuple(getattr(s, "plan_epoch", 0) for s in self._shards)

    def prepare(self, verb: str, schema: Optional[TableSchema] = None) -> Any:
        """Prepared handle for a batched verb (DESIGN.md §11).

        ``verb`` is one of ``insert / get / update / delete``; the
        returned :class:`~repro.exec.PreparedOp` lowers the verb once per
        (plan epoch, batch bucket) and replays it via ``.run(...)``.
        ``schema``, when given, must be this table's schema (the arg
        exists so callers can assert the table they prepared against).
        """
        if schema is not None and schema is not self.schema:
            raise ValueError(
                f"table {self.name!r}: prepare() schema mismatch "
                f"(got {getattr(schema, 'name', schema)!r})"
            )
        op = self._prepared.get(verb)
        if op is None:
            from repro.exec.prepared import PreparedOp  # deferred: no cycle

            op = self._prepared[verb] = PreparedOp(self, verb)
        return op

    def _route(self, key: Key) -> Tuple[int, int]:
        """(shard, local id) of a live key, or raise KeyError."""
        try:
            return self._dir[key]
        except KeyError:
            raise KeyError(
                f"table {self.name!r}: no live row for key {key!r}"
            ) from None

    # -- batched verbs: compatibility shims over the prepared path -------
    # One execution path (DESIGN.md §11): each legacy verb routes through
    # ``prepare(verb).run(...)``, which resolves the lowered plan entry
    # and calls the matching ``_exec_*`` body below.

    def insert_many(self, rows: Sequence[Dict[str, Any]]) -> List[Key]:
        """Insert rows, returning their primary keys in request order.

        Raises ``ValueError`` on a key that is already live (in the table
        or earlier in the same batch) — TPC-C inserts are always fresh
        keys, and silent upsert would hide routing bugs.
        """
        return self.prepare("insert").run(rows)

    def get_many(
        self, keys: Sequence[Key], backend: Optional[str] = None
    ) -> List[Optional[Dict[str, Any]]]:
        """Batched point reads in request order; ``None`` for missing keys.

        ``backend`` forces the decode backend ("numpy"/"pallas"); every
        RowStore accepts it (non-blitz backends ignore it).
        """
        return self.prepare("get").run(keys, backend=backend)

    def update_many(self, keys: Sequence[Key], rows: Sequence[Dict[str, Any]]) -> None:
        """In-place updates (last write wins on duplicate keys); the primary
        key of each row must match its key — keys are immutable."""
        return self.prepare("update").run(keys, rows)

    def delete_many(self, keys: Sequence[Key]) -> int:
        """Delete live keys, returning how many were actually deleted
        (missing/repeated keys are no-ops, matching RowStore)."""
        return self.prepare("delete").run(keys)

    # -- verb bodies (one RowStore call per touched shard) ---------------
    def _exec_insert(
        self, rows: Sequence[Dict[str, Any]], keys: Sequence[Key], shards: Any
    ) -> List[Key]:
        """Apply a routed insert batch (keys/shards from the prepared op)."""
        with telemetry.span("repro.db.insert_many"):
            if not self._shards:
                self._build_shards(rows)
            batch_seen: set = set()
            per_shard: List[List[Dict[str, Any]]] = [[] for _ in self._shards]
            per_shard_keys: List[List[Key]] = [[] for _ in self._shards]
            for r, k, s in zip(rows, keys, shards):
                self.schema.validate_row(r)
                if k in self._dir or k in batch_seen:
                    raise ValueError(
                        f"table {self.name!r}: duplicate insert of key {k!r}"
                    )
                batch_seen.add(k)
                per_shard[s].append(r)
                per_shard_keys[s].append(k)
            self._log("insert", rows)
            for s, (grp, gkeys) in enumerate(zip(per_shard, per_shard_keys)):
                if not grp:
                    continue
                _C_SHARD_CALLS.inc()
                ids = self._shards[s].insert_many(grp)
                for i, k in zip(ids, gkeys):
                    self._dir[k] = (s, int(i))
            self._note_ops(len(rows))
            _C_INSERT_ROWS.add(len(rows))
        return list(keys)

    def _exec_get(
        self, keys: Sequence[Key], backend: Optional[str]
    ) -> List[Optional[Dict[str, Any]]]:
        out: List[Optional[Dict[str, Any]]] = [None] * len(keys)
        if not self._shards:
            return out
        with telemetry.span("repro.db.get_many"):
            per_shard_pos: List[List[int]] = [[] for _ in self._shards]
            per_shard_ids: List[List[int]] = [[] for _ in self._shards]
            for pos, k in enumerate(keys):
                slot = self._dir.get(k)
                if slot is None:
                    continue
                s, i = slot
                per_shard_pos[s].append(pos)
                per_shard_ids[s].append(i)
            for s, (poss, ids) in enumerate(zip(per_shard_pos, per_shard_ids)):
                if not ids:
                    continue
                _C_SHARD_CALLS.inc()
                got = self._shards[s].get_many(ids, backend=backend)
                for pos, row in zip(poss, got):
                    out[pos] = row
            _C_GET_ROWS.add(len(keys))
        return out

    def _exec_update(
        self, keys: Sequence[Key], rows: Sequence[Dict[str, Any]]
    ) -> None:
        with telemetry.span("repro.db.update_many"):
            merged: Dict[Key, Dict[str, Any]] = {}
            for k, r in zip(keys, rows):
                self.schema.validate_row(r)  # fail here, not in a later merge
                if self.schema.key_of(r) != k:
                    raise ValueError(
                        f"table {self.name!r}: update changes primary key "
                        f"{k!r} -> {self.schema.key_of(r)!r}"
                    )
                merged[k] = r
            per_shard_ids: List[List[int]] = [[] for _ in self._shards]
            per_shard_rows: List[List[Dict[str, Any]]] = [[] for _ in self._shards]
            for k, r in merged.items():
                s, i = self._route(k)
                per_shard_ids[s].append(i)
                per_shard_rows[s].append(r)
            self._log("update", list(merged.values()))
            for s, (ids, grp) in enumerate(zip(per_shard_ids, per_shard_rows)):
                if ids:
                    _C_SHARD_CALLS.inc()
                    self._shards[s].update_many(ids, grp)
            self._note_ops(len(merged))
            _C_UPDATE_ROWS.add(len(merged))

    def _exec_delete(self, keys: Sequence[Key]) -> int:
        with telemetry.span("repro.db.delete_many"):
            per_shard_ids: List[List[int]] = [[] for _ in self._shards]
            dropped: List[Key] = []
            for k in dict.fromkeys(keys):  # dedup, keep order
                slot = self._dir.get(k)
                if slot is None:
                    continue
                s, i = slot
                per_shard_ids[s].append(i)
                dropped.append(k)
            if dropped:
                self._log("delete", dropped)
            n = 0
            for s, ids in enumerate(per_shard_ids):
                if ids:
                    _C_SHARD_CALLS.inc()
                    n += self._shards[s].delete_many(ids)
            for k in dropped:
                del self._dir[k]
            self._note_ops(len(dropped))
            _C_DELETE_ROWS.add(len(dropped))
        return n

    # -- scalar wrappers -------------------------------------------------
    def insert(self, row: Dict[str, Any]) -> Key:
        return self.insert_many([row])[0]

    def get(self, key: Key) -> Dict[str, Any]:
        # One execution path: scalar reads replay the same prepared plan
        # as batched reads (missing keys keep the KeyError contract).
        row = self.get_many([key])[0]
        if row is None:
            raise KeyError(key)
        return row

    def update(self, key: Key, row: Dict[str, Any]) -> None:
        self.update_many([key], [row])

    def delete(self, key: Key) -> bool:
        return self.delete_many([key]) == 1

    def __contains__(self, key: Key) -> bool:
        return key in self._dir

    def scan(self, batch: int = 1024) -> Iterator[Tuple[Key, Dict[str, Any]]]:
        """Yield ``(key, row)`` for every live row, shard by shard, one
        batched ``get_many`` per chunk.

        Keys are recovered from the decoded rows themselves (the primary
        key lives in the row's columns), so no reverse id→key map is
        needed; the directory check skips stale slots of keys that were
        deleted and revived elsewhere.
        """
        key_of = self.schema.key_of
        for s, shard in enumerate(self._shards):
            span = len(shard)
            for lo in range(0, span, batch):
                ids = range(lo, min(lo + batch, span))
                for i, row in zip(ids, shard.get_many(ids)):
                    if row is None:  # tombstoned slot
                        continue
                    k = key_of(row)
                    if self._dir.get(k) == (s, i):
                        yield k, row

    # -- analytics scans (DESIGN.md §8) ----------------------------------
    def _shard_scan(
        self,
        predicates: Sequence[Any],
        columns: Optional[Sequence[str]],
        pushdown: bool,
        backend: Optional[str],
    ) -> Iterator[Tuple[int, Key, Dict[str, Any], Any]]:
        """Fan a filtered scan across shards, yielding live
        ``(shard, key, row, shard_stats)`` tuples.

        The shard-level projection is augmented with the primary-key
        columns so each hit can be checked against the directory — a slot
        whose key was deleted and revived elsewhere is stale and must be
        skipped (same rule as :meth:`scan`).  ``shard_stats`` is yielded
        once per shard (with the first row) for aggregation by callers.
        """
        key_of = self.schema.key_of
        need = columns
        if columns is not None:
            need = list(dict.fromkeys(list(columns) + list(self.schema.primary_key)))
        for s, shard in enumerate(self._shards):
            res = shard.scan_where(
                predicates, columns=need, pushdown=pushdown, backend=backend
            )
            first = res.stats
            for i, row in zip(res.ids, res.rows):
                k = key_of(row)
                if self._dir.get(k) == (s, i):
                    yield s, k, row, first
                    first = None
            if first is not None:  # no rows matched: still surface stats
                yield s, None, None, first

    def scan_where(
        self,
        predicates: Sequence[Any],
        columns: Optional[Sequence[str]] = None,
        pushdown: bool = True,
        backend: Optional[str] = None,
        with_stats: bool = False,
    ):
        """Filtered scan -> ``(key, projected row)`` pairs across shards.

        One :meth:`RowStore.scan_where` call per shard (predicate pushdown
        with zone-map pruning on blitz shards; ``pushdown=False`` forces
        the decode-everything reference).  Results carry exactly the
        requested ``columns`` and are merged into global primary-key
        order, so the pushdown and reference paths agree as *lists*, not
        merely as sets.  ``with_stats=True`` returns ``(hits, merged
        ScanStats)``.
        """
        from repro.scan import ScanStats

        hits: List[Tuple[Key, Dict[str, Any]]] = []
        total = ScanStats()
        cols = list(columns) if columns is not None else None
        for _s, k, row, st in self._shard_scan(predicates, cols, pushdown, backend):
            if st is not None:
                total.merge(st)
            if k is None:
                continue
            if cols is not None:
                row = {c: row[c] for c in cols}
            hits.append((k, row))
        # pk values are homogeneous within a table, so the sort is total;
        # per-shard results arrive id-ordered already, making this a
        # nearly-sorted merge for timsort.
        hits.sort(key=lambda kv: kv[0])
        total.rows_matched = len(hits)
        return (hits, total) if with_stats else hits

    def aggregate(
        self,
        predicates: Sequence[Any],
        group_by: Sequence[str] = (),
        aggs: Optional[Dict[str, Tuple[str, Optional[str]]]] = None,
        pushdown: bool = True,
        backend: Optional[str] = None,
        with_stats: bool = False,
    ) -> Any:
        """Filtered group-by aggregation: ``{group key: {name: value}}``.

        ``aggs`` maps output names to ``(op, column)`` with op one of
        ``count`` (column ignored, may be None), ``sum``, ``avg``, ``min``,
        ``max``.  Partial aggregates accumulate per shard as rows stream
        out of the pushdown scan — only the group table is materialized,
        never the matching row set — and merge trivially because every
        op is decomposable (avg is carried as sum+count until finalize).
        ``with_stats=True`` returns ``(groups, merged ScanStats)`` — the
        same stats shape :meth:`scan_where` reports (DESIGN.md §8).
        """
        with telemetry.span("repro.db.aggregate"):
            return self._aggregate(predicates, group_by, aggs, pushdown, backend,
                                   with_stats)

    def _aggregate(
        self,
        predicates: Sequence[Any],
        group_by: Sequence[str],
        aggs: Optional[Dict[str, Tuple[str, Optional[str]]]],
        pushdown: bool,
        backend: Optional[str],
        with_stats: bool,
    ) -> Any:
        from repro.scan import ScanStats

        aggs = dict(aggs or {"count": ("count", None)})
        group_by = list(group_by)
        need_cols = list(
            dict.fromkeys(group_by + [c for _, c in aggs.values() if c is not None])
        )
        total = ScanStats()
        matched = 0
        # state per group: [count, {name: accumulator}]
        groups: Dict[Tuple[Any, ...], List[Any]] = {}
        for _s, k, row, st in self._shard_scan(
            predicates, need_cols, pushdown, backend
        ):
            if st is not None:
                total.merge(st)
            if k is None:
                continue
            matched += 1
            g = tuple(row[c] for c in group_by)
            st = groups.get(g)
            if st is None:
                st = groups[g] = [0, {}]
            st[0] += 1
            acc = st[1]
            for name, (op, col) in aggs.items():
                if op == "count":
                    continue
                v = row[col]
                cur = acc.get(name)
                if op in ("sum", "avg"):
                    acc[name] = v if cur is None else cur + v
                elif op == "min":
                    acc[name] = v if cur is None or v < cur else cur
                elif op == "max":
                    acc[name] = v if cur is None or v > cur else cur
                else:
                    raise ValueError(f"unknown aggregate op {op!r}")
        out: Dict[Tuple[Any, ...], Dict[str, Any]] = {}
        for g, (n, acc) in groups.items():
            row_out: Dict[str, Any] = {}
            for name, (op, _col) in aggs.items():
                if op == "count":
                    row_out[name] = n
                elif op == "avg":
                    row_out[name] = acc[name] / n
                else:
                    row_out[name] = acc[name]
            out[g] = row_out
        if with_stats:
            total.rows_matched = matched
            return out, total
        return out

    # -- maintenance (DESIGN.md §3/§4, fanned across shards) -------------
    def merge(self) -> None:
        for shard in self._shards:
            if hasattr(shard, "merge"):
                shard.merge()

    def migrate(self, limit: int = 1 << 12) -> int:
        moved = 0
        for shard in self._shards:
            if hasattr(shard, "migrate"):
                moved += shard.migrate(limit)
        return moved

    def maintenance_step(self) -> List[Dict[str, Any]]:
        """Run one deterministic maintenance step on every adaptive shard."""
        out = []
        for shard in self._shards:
            maint = getattr(shard, "maintenance", None)
            if maint is not None:
                out.append(maint.step())
        return out

    # -- durability (DESIGN.md §7) ---------------------------------------
    def attach_wal(
        self,
        wal,
        io: Optional[Any] = None,
        on_ops: Optional[Callable[[int], None]] = None,
    ) -> None:
        """Wire this table to its redo log (one WAL per table).

        From here on every batch verb logs its logical record *before*
        touching any shard (log-before-apply), and ``on_ops`` fires with
        the row count at the end of each verb — never mid-apply, so a
        checkpoint can only observe verb boundaries."""
        self._wal = wal
        self._io = io
        self._on_ops = on_ops
        if self._shards:
            self._install_repair_fns()

    def on_ops(self, callback: Optional[Callable[[int], None]]) -> None:
        """Designated entry point for the owning engine to hear the row
        count at the end of every write verb (a limited database re-splits
        its memory there) without a WAL."""
        self._on_ops = callback

    def _log(self, op: str, payload: Any) -> None:
        if self._wal is not None:
            self._wal.log(op, payload)
            if self._io is not None:
                self._io.point("apply.before")

    def _note_ops(self, n: int) -> None:
        if self._on_ops is not None:
            self._on_ops(n)

    def _install_repair_fns(self) -> None:
        for j, shard in enumerate(self._shards):
            if hasattr(shard, "repair_fn"):
                shard.repair_fn = self._make_repair_fn(j)

    def _make_repair_fn(self, s: int) -> Callable:
        """Row rebuilder for shard ``s``: local row ids -> latest logical
        rows, reconstructed from the retained WAL history.

        A corrupt spilled extent names only local slot ids; the directory
        maps live slots back to primary keys, and one full WAL scan
        (insert/update set the key's latest row, delete clears it) yields
        each key's current value.  Slots no key points at — deleted, or
        revived elsewhere — resolve to ``None`` and get tombstoned by the
        caller.  Garbage is never served."""

        def repair(row_ids: Sequence[int]) -> List[Optional[Dict[str, Any]]]:
            wanted = {int(i) for i in row_ids}
            slot2key: Dict[int, Key] = {}
            for k, (sh, i) in self._dir.items():
                if sh == s and i in wanted:
                    slot2key[i] = k
            need = set(slot2key.values())
            latest: Dict[Key, Dict[str, Any]] = {}
            if need and self._wal is not None:
                key_of = self.schema.key_of
                for _lsn, op, payload in self._wal.scan(0):
                    if op in ("insert", "update"):
                        for r in payload:
                            k = key_of(r)
                            if k in need:
                                latest[k] = r
                    elif op == "delete":
                        for k in payload:
                            if k in need:
                                latest.pop(k, None)
            return [latest.get(slot2key.get(int(i))) for i in row_ids]

        return repair

    def close(self, unlink: bool = False) -> None:
        """Release shard spill files and the WAL; ``unlink=True`` deletes
        them (drop_table) instead of keeping them for reopen."""
        for shard in self._shards:
            if hasattr(shard, "close"):
                shard.close(unlink=unlink)
        if self._wal is not None:
            if unlink:
                self._wal.unlink()
            else:
                self._wal.close()

    def clean_store_kwargs(self) -> Dict[str, Any]:
        """store_kwargs safe to persist: live objects (a shared codec, an
        injected io) are reconstructed, never pickled."""
        return {
            k: v for k, v in self.store_kwargs.items() if k not in ("codec", "spill_io")
        }

    def snapshot_state(self) -> Dict[str, Any]:
        if not isinstance(self.backend, str):
            raise ValueError(
                f"table {self.name!r}: factory backends cannot be "
                f"checkpointed (pass a STORE_KINDS name)"
            )
        return {
            "schema": self.schema,
            "backend": self.backend,
            "n_shards": self.n_shards,
            "store_kwargs": self.clean_store_kwargs(),
            "memory_budget": self.memory_budget,
            "budget": self.budget,
            "dir": dict(self._dir),
            "shards": (
                [s.snapshot_state() for s in self._shards] if self._shards else None
            ),
        }

    @classmethod
    def from_snapshot(
        cls, state: Dict[str, Any], spill_io: Optional[Any] = None
    ) -> "Table":
        self = cls.__new__(cls)
        self.schema = state["schema"]
        self.name = self.schema.name
        self.n_shards = state["n_shards"]
        self.backend = state["backend"]
        self.store_kwargs = dict(state["store_kwargs"])
        if spill_io is not None:
            self.store_kwargs["spill_io"] = spill_io
        self.memory_budget = state["memory_budget"]
        self.budget = state.get("budget", self.memory_budget)
        self._dir = dict(state["dir"])
        self._shards = []
        self._prepared = {}
        self._wal = None
        self._io = None
        self._on_ops = None
        self._on_shards_built = None
        if state["shards"] is not None:
            store_cls = STORE_KINDS[self.backend]
            spill_base = self.store_kwargs.get("spill_path")
            for j, st in enumerate(state["shards"]):
                # same per-shard suffixing as _build_shards, so a durable
                # named spill file (extent-mode checkpoints) survives the
                # reopen instead of degrading to an anonymous temp file
                sp = spill_base
                if sp is not None and self.n_shards > 1:
                    sp = f"{spill_base}.s{j}"
                self._shards.append(
                    store_cls.from_state(
                        self.schema, st, spill_path=sp, spill_io=spill_io
                    )
                )
            for j, shard in enumerate(self._shards):
                maint = getattr(shard, "maintenance", None)
                if maint is not None:
                    maint.label = f"{self.name}/shard{j}"
        return self

    # -- accounting ------------------------------------------------------
    def __len__(self) -> int:
        return len(self._dir)

    @property
    def n_live(self) -> int:
        return len(self._dir)

    @property
    def index_bytes(self) -> int:
        return INDEX_ENTRY_OVERHEAD * len(self._dir)

    @property
    def code_bytes(self) -> int:
        """Every shard's live code bytes, resident or spilled."""
        return sum(s.code_bytes for s in self._shards)

    @property
    def resident_code_bytes(self) -> int:
        """Every shard's live code bytes held in memory."""
        return sum(s.resident_code_bytes for s in self._shards)

    @property
    def nbytes(self) -> int:
        """Total footprint: every shard's bytes plus the key directory."""
        return sum(s.nbytes for s in self._shards) + self.index_bytes

    @property
    def model_bytes(self) -> int:
        """Model bytes with cross-shard dedup: shards share their v0 fit
        (see :meth:`_build_shards`), so identical model objects count once."""
        seen: set = set()
        total = 0
        for s in self._shards:
            objs = getattr(s, "model_objects", None)
            if objs is None:
                total += getattr(s, "model_bytes", 0)
                continue
            for m in objs():
                if id(m) not in seen:
                    seen.add(id(m))
                    total += m.model_bytes()
        return total

    def stats(self) -> Dict[str, Any]:
        shard_stats = [s.stats() for s in self._shards]
        out: Dict[str, Any] = {
            "table": self.name,
            "backend": (
                self.backend
                if isinstance(self.backend, str)
                else getattr(self.backend, "__name__", "factory")
            ),
            "n_shards": self.n_shards,
            "n_live": self.n_live,
            "n_ids": sum(s["n_ids"] for s in shard_stats),
            "nbytes": self.nbytes,
            "store_bytes": sum(s["nbytes"] for s in shard_stats),
            "index_bytes": self.index_bytes,
            "model_bytes": self.model_bytes,
            "shards": shard_stats,
        }
        res = [s["residency"] for s in shard_stats if "residency" in s]
        if res:
            # nbytes/store_bytes above are *resident* memory; the on-disk
            # cold tier is aggregated separately (DESIGN.md §6).
            out["spilled_bytes"] = sum(s.get("spilled_bytes", 0) for s in shard_stats)
            out["residency"] = {
                "budget_bytes": sum(r["budget_bytes"] for r in res),
                "spilled_bytes": out["spilled_bytes"],
                "spills": sum(r["spills"] for r in res),
                "faults": sum(r["faults"] for r in res),
                "fault_batches": sum(r["fault_batches"] for r in res),
                "disk_file_bytes": sum(r["disk_file_bytes"] for r in res),
            }
        maint = [s["maintenance"] for s in shard_stats if "maintenance" in s]
        if maint:
            out["maintenance"] = {
                "refits": sum(m["refits"] for m in maint),
                "migrated_rows": sum(m["migrated_rows"] for m in maint),
                "steps": sum(m["steps"] for m in maint),
                "frozen_columns": sorted(
                    {c for m in maint for c in m["frozen_columns"]}
                ),
            }
        out["telemetry"] = telemetry.snapshot(prefix=("repro.db.", "repro.scan."))
        return out
