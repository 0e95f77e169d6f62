"""PreparedOp / Session: lower a table verb once, replay it many times.

A :class:`PreparedOp` is the plan/run split for ONE (table, verb) pair.
``run(...)`` looks up a lowered entry keyed by ``(plan epoch, batch
bucket, backend)``:

* **plan epoch** — the tuple of per-shard plan versions (a refit/migrate
  ``install_codec`` bumps a shard's version, changing the epoch and
  invalidating exactly that table's entries; merges that keep the plan
  leave the epoch unchanged, so their entries stay valid);
* **batch bucket** — the pow2-padded batch size, aligning the entry with
  the jit/trace cache of the Pallas decode kernel underneath;
* **backend** — the requested decode backend, because lowering for
  ``"pallas"`` additionally packs the slot tables of every member of
  the plan family.

A hit replays cached artifacts — warmed codec plans, the vectorized key
router, packed kernel tables — with no per-call re-lowering.  A miss
re-lowers under the ``repro.exec.lower`` histogram (folded into the
``jit_compile`` phase; the nested ``codec.compile()`` work keeps its own
``repro.plan.compile`` leaf timer and is excluded from the lower span to
preserve leaf-disjoint phase sums).

One execution path: the legacy ``Table.insert_many/get_many/...`` verbs
are shims over ``Table.prepare(verb).run(...)``, and :class:`Session`
(from ``Database.session()``) caches prepared handles across tables.
"""

from __future__ import annotations

from typing import TYPE_CHECKING, Any, Dict, List, Optional, Sequence, Tuple

from repro import telemetry

from .router import shard_keys

if TYPE_CHECKING:
    from repro.db.database import Database
    from repro.db.schema import Key
    from repro.db.table import Table

_C_HIT = telemetry.counter("repro.exec.plan.hit")
_C_MISS = telemetry.counter("repro.exec.plan.miss")
_C_REPLAY = telemetry.counter("repro.exec.replay")
_C_REPLAY_ROWS = telemetry.counter("repro.exec.replay.rows")

VERBS = ("insert", "get", "update", "delete")


def batch_bucket(n: int) -> int:
    """Pow2 batch-size bucket (floor 8) the lowered entry is keyed by.

    The Pallas decode underneath pads to the same pow2 but with a floor of
    its 256-row tile, so every bucket up to 256 shares one kernel compile
    (``TablePlan._decode_select_pallas``)."""
    return 1 << max(3, (max(1, n) - 1).bit_length())


class _Lowered:
    """One cache entry: routing constants for the replay path."""

    __slots__ = ("epoch", "n_parts", "n_shards")

    def __init__(self, epoch: Tuple[int, ...], n_parts: int, n_shards: int):
        self.epoch = epoch
        self.n_parts = n_parts
        self.n_shards = n_shards


class PreparedOp:
    """Prepared handle for one (table, verb); obtain via ``Table.prepare``.

    ``run(...)`` takes the verb's batched arguments — ``run(rows)`` for
    insert, ``run(keys, backend=...)`` for get, ``run(keys, rows)`` for
    update, ``run(keys)`` for delete — and returns exactly what the
    legacy verb returns.
    """

    def __init__(self, table: "Table", verb: str) -> None:
        if verb not in VERBS:
            raise ValueError(f"unknown verb {verb!r}; expected one of {VERBS}")
        self.table = table
        self.verb = verb
        # (bucket, backend) -> lowered entry; at most one entry per slot,
        # so an epoch change invalidates by replacement on next run.
        self._cache: Dict[Tuple[int, Optional[str]], _Lowered] = {}
        self.hits = 0
        self.misses = 0

    # -- plan ------------------------------------------------------------
    def _lowered(self, n: int, backend: Optional[str]) -> _Lowered:
        table = self.table
        epoch = table.plan_epoch
        slot = (batch_bucket(n), backend)
        low = self._cache.get(slot)
        if low is not None and low.epoch == epoch:
            self.hits += 1
            _C_HIT.inc()
            return low
        self.misses += 1
        _C_MISS.inc()
        # Warm each shard's compiled plan OUTSIDE the lower span: compile
        # time stays in its own repro.plan.compile leaf (jit_compile
        # phase) and is not double-counted.
        plans = []
        for shard in table.shards:
            codec = getattr(shard, "codec", None)
            if codec is not None:
                plans.append(codec.compile())
        with telemetry.span("repro.exec.lower"):
            if backend == "pallas":
                for plan in plans:
                    if plan is not None and plan.pallas_ok:
                        for member in plan.family:
                            member.pallas_tables()
            low = _Lowered(epoch, len(table.schema.primary_key), table.n_shards)
            self._cache[slot] = low
        return low

    def invalidate(self) -> None:
        """Drop every lowered entry (epoch checks make this automatic on
        version bumps; explicit invalidation is for tests/tooling)."""
        self._cache.clear()

    def cache_info(self) -> Dict[str, int]:
        return {
            "entries": len(self._cache),
            "hits": self.hits,
            "misses": self.misses,
        }

    # -- run -------------------------------------------------------------
    def run(self, *args: Any, backend: Optional[str] = None) -> Any:
        verb = self.verb
        table = self.table
        if verb == "insert":
            (rows,) = args
            rows = list(rows)
            if not rows:
                return []
            low = self._lowered(len(rows), None)
            _C_REPLAY.inc()
            _C_REPLAY_ROWS.add(len(rows))
            try:
                keys = table.schema.keys_of(rows)
            except KeyError:
                # Re-raise with the canonical "row missing column" message.
                for r in rows:
                    table.schema.validate_row(r)
                raise
            shards = shard_keys(keys, low.n_parts, low.n_shards)
            return table._exec_insert(rows, keys, shards)
        if verb == "get":
            (keys,) = args
            self._lowered(len(keys), backend)
            _C_REPLAY.inc()
            _C_REPLAY_ROWS.add(len(keys))
            return table._exec_get(keys, backend)
        if verb == "update":
            keys, rows = args
            self._lowered(len(keys), None)
            _C_REPLAY.inc()
            _C_REPLAY_ROWS.add(len(keys))
            return table._exec_update(keys, rows)
        keys = args[0]  # delete
        self._lowered(len(keys), None)
        _C_REPLAY.inc()
        _C_REPLAY_ROWS.add(len(keys))
        return table._exec_delete(keys)


class Session:
    """Execution surface over a :class:`~repro.db.Database`.

    Caches one prepared handle per (table, verb) so a transaction loop
    replays lowered plans without re-resolving tables or verbs:

    >>> ses = db.session()
    >>> ses.insert("orders", rows)
    >>> ses.get("customer", keys, backend="pallas")

    ``prepared(table, verb)`` exposes the underlying handles; ``query``
    passes through to the OLAP entry point unchanged.
    """

    def __init__(self, db: "Database") -> None:
        self._db = db
        self._ops: Dict[Tuple[str, str], PreparedOp] = {}

    def table(self, name: str) -> "Table":
        return self._db.table(name)

    def prepared(self, table: str, verb: str) -> PreparedOp:
        slot = (table, verb)
        op = self._ops.get(slot)
        if op is None:
            op = self._ops[slot] = self._db.table(table).prepare(verb)
        return op

    # -- batched verbs ----------------------------------------------------
    def insert(self, table: str, rows: Sequence[Dict[str, Any]]) -> List["Key"]:
        return self.prepared(table, "insert").run(rows)

    def get(
        self,
        table: str,
        keys: Sequence["Key"],
        backend: Optional[str] = None,
    ) -> List[Optional[Dict[str, Any]]]:
        return self.prepared(table, "get").run(keys, backend=backend)

    def update(
        self,
        table: str,
        keys: Sequence["Key"],
        rows: Sequence[Dict[str, Any]],
    ) -> None:
        return self.prepared(table, "update").run(keys, rows)

    def delete(self, table: str, keys: Sequence["Key"]) -> int:
        return self.prepared(table, "delete").run(keys)

    def query(self, table: str, *args: Any, **kwargs: Any) -> Any:
        return self._db.query(table, *args, **kwargs)
