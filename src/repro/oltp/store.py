"""In-memory row stores behind one batched-first protocol (paper §6.1/§7).

Every store implements the :class:`RowStore` protocol (DESIGN.md §3) —
``insert_many / get_many / update_many / delete_many / scan / stats()`` over
a dense primary-key id space, with scalar ``insert/get/update/delete`` kept
as thin wrappers — so every harness and benchmark drives one interface.
Compressors:

* ``BlitzStore``      — TableCodec (semantic models + delayed coding) over
                        the CSR code arena, with a bounded delta overlay and
                        Funke-style ``merge()`` compaction back into the arena
* ``ZstdStore``       — per-tuple zstd with a trained dictionary (the
                        paper's Zstandard baseline, §6 "training mode")
* ``RamanStore``      — per-column canonical Huffman, concatenated
                        variable-length tuples (static dictionary: unseen
                        values need an escape)
* ``UncompressedStore`` — Silo-style plain rows

Plus the §6.5 fast path: :class:`LRUFastPath`, an LRU write-back cache of
decompressed tuples that also speaks the protocol.

Deletion semantics (uniform across stores): ids are never reused;
``get_many`` returns ``None`` for tombstoned ids, scalar ``get`` raises
``KeyError``, updating a deleted row raises ``KeyError``, repeat deletes
are no-ops.
"""

from __future__ import annotations

import contextlib
import json
from collections import OrderedDict
from types import MappingProxyType
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from repro import sanitize, telemetry
from repro.adaptive import MaintenanceConfig, MaintenanceScheduler
from repro.core import ColumnSpec, TableCodec
from repro.core.arena import (
    ExtentCorruptionError, ResidencyManager, SpillCorruptionError, framed_len
)
from repro.core.blitzcrank import CompressedTable, _raw_row_bytes, column_specs
from repro.core.huffman import BitReader, BitWriter, HuffmanCode

# Per-entry charge of an uncompressed dict overlay / cache slot: 8 B key +
# 8 B table-slot pointer on top of the raw row bytes (DESIGN.md §3).
OVERLAY_ENTRY_OVERHEAD = 16
# A pending tombstone is one id in a hash set.
TOMBSTONE_BYTES = 8

# Telemetry handles (DESIGN.md §9): delta-merge and the byte-store's
# cold-tier spill/fault-in, which shares phase prefixes with the
# CompressedTable block paths.
_C_MERGES = telemetry.counter("repro.store.merge.events")
_C_OVERLAY_HITS = telemetry.counter("repro.store.overlay.hits")
_C_ROW_FAULTS = telemetry.counter("repro.residency.fault_in.rows.count")
_C_ROW_SPILLS = telemetry.counter("repro.residency.spill.rows.count")
_C_SPILL_BYTES = telemetry.counter("repro.residency.spill.bytes")
_C_FAULT_BYTES = telemetry.counter("repro.residency.fault_in.bytes")


class RowStore:
    """Unified batched-first storage protocol (DESIGN.md §3).

    Subclasses implement the batched methods; the scalar ``insert / get /
    update / delete`` are thin wrappers over them.  ``len(store)`` is the
    id span (including tombstones), ``n_live`` the live row count.
    ``schema`` may be a plain sequence of :class:`ColumnSpec` or any object
    with a ``.columns`` attribute (:class:`repro.db.TableSchema`).

    Return conventions and tombstone semantics (the protocol contract —
    every store and wrapper must match it bit for bit):

    * ``insert_many(rows) -> range`` — the dense ids assigned, in row
      order; ``insert(row) -> int`` is the single id.  Ids are assigned
      contiguously from the current span and **never reused**, even after
      deletion.
    * ``get_many(ids) -> list`` — one entry per requested id, in request
      order; tombstoned ids yield ``None`` (a read-side abort signal, not
      an error).  Scalar ``get(id)`` raises ``KeyError`` instead, and
      ``IndexError`` semantics for never-assigned ids follow the backing
      container.
    * ``update_many(ids, rows) -> None`` — in-place overwrite, duplicate
      ids deduplicated last-write-wins *before* hitting storage; updating
      a tombstoned id raises ``KeyError``.  ``update`` is the 1-element
      wrapper.
    * ``delete_many(ids) -> int`` — the number of rows that transitioned
      live→tombstoned (repeats and already-dead ids are no-ops, so the
      count is of *effective* deletes); ``delete(id) -> bool`` — whether
      this call performed the delete.  Both are idempotent.
    * ``scan() -> iterator of (id, row)`` — live rows only, id order.
    """

    name = "rowstore"

    def __init__(
        self, schema: Optional[Sequence[ColumnSpec]] = None
    ) -> None:
        self.schema = column_specs(schema) if schema is not None else None

    # -- batched protocol (override) -------------------------------------
    def insert_many(self, rows: Sequence[Dict[str, Any]]) -> range:
        raise NotImplementedError

    def get_many(
        self, indices: Sequence[int], backend: Optional[str] = None
    ) -> List[Optional[Dict[str, Any]]]:
        # ``backend`` selects the decode backend where one exists
        # (BlitzStore); every store accepts it so callers need no
        # isinstance checks (DESIGN.md §8 unified verb signatures).
        raise NotImplementedError

    def update_many(
        self, indices: Sequence[int], rows: Sequence[Dict[str, Any]]
    ) -> None:
        raise NotImplementedError

    def delete_many(self, indices: Sequence[int]) -> int:
        raise NotImplementedError

    def scan(
        self, start: int = 0, stop: Optional[int] = None, batch: int = 1024
    ) -> Iterator[Tuple[int, Dict[str, Any]]]:
        """Yield ``(id, row)`` for live rows in id order, a batch at a time."""
        n = len(self)
        stop = n if stop is None else min(stop, n)
        for lo in range(start, stop, batch):
            ids = range(lo, min(lo + batch, stop))
            # blitzlint: waive[BL001] -- scan generator yields per-row dicts; get_many batches the decode underneath
            for i, r in zip(ids, self.get_many(ids)):
                if r is not None:
                    yield i, r

    def scan_where(
        self,
        predicates: Sequence[Any],
        columns: Optional[Sequence[str]] = None,
        pushdown: bool = True,
        backend: Optional[str] = None,
    ) -> "Any":
        """Filtered scan -> :class:`repro.scan.ScanResult` (ids ascending).

        The base implementation is the decode-everything reference:
        decode every live row through :meth:`scan`, filter in value space,
        project.  Stores with a pushdown path override this;
        ``pushdown=False`` forces the reference everywhere (the
        comparability baseline in ``bench_htap``).
        """
        from repro.scan import ScanResult, ScanStats, match_all
        preds = list(predicates)
        ids: List[int] = []
        rows: List[Dict[str, Any]] = []
        stats = ScanStats()
        for i, r in self.scan():
            stats.rows_decoded += 1
            if match_all(preds, r):
                ids.append(i)
                rows.append(r if columns is None else {c: r[c] for c in columns})
        stats.rows_matched = len(ids)
        return ScanResult(ids, rows, stats)

    # Registry prefixes a store-level stats() view reports: encode/decode
    # kernels, plan cache, residency, delta merge — not db/scan/wal, which
    # belong to the table- and engine-level sections (DESIGN.md §9).
    TELEMETRY_PREFIXES = ("repro.core.", "repro.plan.",
                          "repro.residency.", "repro.store.")

    def stats(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "n_ids": len(self),
            "n_live": self.n_live,
            "n_deleted": len(self) - self.n_live,
            "nbytes": self.nbytes,
            "model_bytes": getattr(self, "model_bytes", 0),
            "telemetry": telemetry.snapshot(prefix=self.TELEMETRY_PREFIXES),
        }

    # -- scalar wrappers -------------------------------------------------
    def insert(self, row: Dict[str, Any]) -> int:
        return self.insert_many([row])[0]

    def get(self, i: int) -> Dict[str, Any]:
        r = self.get_many([int(i)])[0]
        if r is None:
            raise KeyError(f"row {int(i)} is deleted")
        return r

    def update(self, i: int, row: Dict[str, Any]) -> None:
        self.update_many([int(i)], [row])

    def delete(self, i: int) -> bool:
        """True when this call deleted a live row (already-dead: False)."""
        return self.delete_many([int(i)]) == 1

    # -- shared helpers --------------------------------------------------
    def is_live(self, i: int) -> bool:
        """True when id ``i`` exists and is not tombstoned (per-store state)."""
        raise NotImplementedError

    @property
    def n_live(self) -> int:
        raise NotImplementedError

    def __len__(self) -> int:
        raise NotImplementedError

    @property
    def nbytes(self) -> int:
        raise NotImplementedError

    @staticmethod
    def _dedup_last(
        indices: Sequence[int], rows: Sequence[Dict[str, Any]]
    ) -> Tuple[List[int], List[Dict[str, Any]]]:
        """Unique (id, row) pairs, last write wins (update_many contract)."""
        m: Dict[int, Dict[str, Any]] = {}
        # blitzlint: waive[BL001] -- last-write-wins dedup is one ordered pass over the update batch
        for i, r in zip(indices, rows):
            m[int(i)] = r
        return list(m.keys()), list(m.values())


class _BytesRowStore(RowStore):
    """Shared list-of-encoded-tuples plumbing for the baseline stores:
    one encoded payload per id, tombstones in a side set.

    ``memory_budget`` enables the same out-of-core cold tier the blitz
    store has (paper §6.4, DESIGN.md §6), at tuple granularity: when the
    resident payload bytes exceed the budget, a clock/second-chance sweep
    over per-row referenced bits spills cold payloads to a
    :class:`~repro.core.arena.DiskArena` (``rows[i] = None`` + an extent
    in ``_spilled``); reads fault them back in with one coalesced read per
    batch.  This is what makes "the uncompressed store at the same
    absolute budget" a fair baseline in ``bench_out_of_core``.
    """

    # Per spilled row: 8 B offset + 4 B length + clock bit, rounded up.
    SPILL_ENTRY_OVERHEAD = 13

    def __init__(
        self,
        schema: Sequence[ColumnSpec],
        memory_budget: Optional[int] = None,
        spill_path: Optional[str] = None,
        spill_io: Optional[Any] = None,
    ):
        super().__init__(schema)
        self.rows: List[Optional[bytes]] = []
        self._deleted: set = set()
        self._res: Optional[ResidencyManager] = None
        self._spilled: Dict[int, Tuple[int, int]] = {}  # id -> (off, len)
        self._ref = bytearray()  # clock bits; hand lives in the manager
        self._resident_bytes = 0
        self._spilled_payload = 0
        # Durability hook (DESIGN.md §7): rebuilds rows from the WAL when a
        # spilled extent fails its CRC check.  Installed by repro.db.Table
        # on durable databases; without it corruption propagates as
        # SpillCorruptionError (never as garbage rows).
        self.repair_fn: Optional[Callable] = None
        self.repairs = 0
        self._spill_path, self._spill_io = spill_path, spill_io
        if memory_budget is not None:
            self.set_memory_budget(memory_budget)

    def is_live(self, i: int) -> bool:
        i = int(i)
        return 0 <= i < len(self.rows) and i not in self._deleted

    @property
    def n_live(self) -> int:
        return len(self.rows) - len(self._deleted)

    def _encode_row(self, row: Dict[str, Any]) -> bytes:
        raise NotImplementedError

    def _decode_row(self, raw: bytes) -> Dict[str, Any]:
        raise NotImplementedError

    # -- cold tier -------------------------------------------------------
    @property
    def memory_budget(self) -> Optional[int]:
        return self._res.budget if self._res is not None else None

    def set_memory_budget(self, budget: int) -> None:
        """Install the cold tier, or move its budget on a live store (a
        lowered budget spills down to it here)."""
        if self._res is None:
            self._res = ResidencyManager(budget, self._spill_path, io=self._spill_io)
            self._ref = bytearray(b"\x01" * len(self.rows))
            self._resident_bytes = sum(len(r) for r in self.rows if r)
        else:
            self._res.budget = budget
        self._enforce_budget()

    @property
    def resident_code_bytes(self) -> int:
        """Live encoded bytes held in memory: what the budget bounds."""
        if self._res is None:
            return sum(len(r) for r in self.rows if r)
        return self._resident_bytes

    @property
    def code_bytes(self) -> int:
        """Live encoded bytes, resident or spilled."""
        return self.resident_code_bytes + self._spilled_payload

    def _append_payloads(self, payloads: List[bytes]) -> range:
        base = len(self.rows)
        self.rows.extend(payloads)
        if self._res is not None:
            self._ref.extend(b"\x01" * len(payloads))
            self._resident_bytes += sum(len(p) for p in payloads)
            self._enforce_budget()
        return range(base, len(self.rows))

    def _put_payload(self, i: int, payload: bytes) -> None:
        """Overwrite row ``i``'s payload, keeping residency accounting."""
        old = self.rows[i]
        if old is None:  # spilled: the old extent is simply dropped
            off, ln = self._spilled.pop(i)
            self._res.disk.free(off, framed_len(ln))
            self._spilled_payload -= ln
        elif self._res is not None:
            self._resident_bytes -= len(old)
        self.rows[i] = payload
        if self._res is not None:
            self._resident_bytes += len(payload)
            self._ref[i] = 1

    def _fetch_payloads(self, indices: Sequence[int]) -> List[Optional[bytes]]:
        """Payload per id (``None`` for tombstones), faulting spilled rows
        back in with one coalesced disk read for the whole batch."""
        dels, rows = self._deleted, self.rows
        out: List[Optional[bytes]] = [None] * len(indices)
        cold: List[int] = []
        for j, i in enumerate(indices):
            if i in dels:
                continue
            p = rows[i]
            if p is None:
                cold.append(i)
            else:
                out[j] = p
        if cold:
            with telemetry.span("repro.residency.fault_in.rows"):
                res = self._res
                ids = sorted(set(cold))
                for _attempt in range(3):
                    extents = [self._spilled[i] for i in ids]
                    try:
                        payloads = res.disk.read_many_checked(
                            [e[0] for e in extents], [e[1] for e in extents]
                        )
                        break
                    except ExtentCorruptionError as e:
                        # Quarantine the bad extents and rebuild their rows
                        # from the WAL (repair_fn); repaired rows come back
                        # resident, the rest retry the checked read.
                        bad = [ids[j] for j in e.indices]
                        res.quarantined += len(bad)
                        self._repair_rows(bad)
                        ids = [i for i in ids if i in self._spilled]
                        payloads = []
                else:
                    raise SpillCorruptionError(ids)
                # blitzlint: waive[BL001] -- crash-replay fault bookkeeping frees per-row extents on the cold repair path
                for i, p in zip(ids, payloads):
                    off, ln = self._spilled.pop(i)
                    rows[i] = p
                    res.disk.free(off, framed_len(ln))
                    self._resident_bytes += ln
                    self._spilled_payload -= ln
                    self._ref[i] = 1
                if ids:
                    res.faults += len(ids)
                    res.fault_batches += 1
                    _C_ROW_FAULTS.add(len(ids))
                    _C_FAULT_BYTES.add(sum(len(p) for p in payloads))
                for j, i in enumerate(indices):
                    if out[j] is None and i not in dels:
                        out[j] = rows[i]
                self._enforce_budget()
        if self._res is not None:
            for i in indices:
                if i not in dels:
                    self._ref[i] = 1
        return out

    def _enforce_budget(self) -> None:
        res = self._res
        if res is None:
            return
        if self._resident_bytes > res.budget:
            target = int(res.config.low_water * res.budget)
            rows, dels = self.rows, self._deleted

            def candidates(ids: np.ndarray) -> np.ndarray:
                # resident live payloads only (None=spilled, b""=deleted)
                return np.fromiter(
                    (bool(rows[i]) and i not in dels
                     for i in ids.tolist()),
                    dtype=bool, count=ids.size)

            def sizes(ids: np.ndarray) -> np.ndarray:
                return np.fromiter(
                    (len(rows[i]) for i in ids.tolist()), dtype=np.int64, count=ids.size
                )

            # a zero-copy numpy view over the bytearray of clock bits
            ref = np.frombuffer(self._ref, dtype=np.uint8)
            victims = res.sweep(
                len(rows), self._resident_bytes - target, candidates,
                sizes, lambda ids: ref[ids] != 0,
                lambda ids: ref.__setitem__(ids, 0))
            if victims.size:
                self._spill_rows(victims.tolist())
        # checked even when under budget: deletes/updates free extents
        # without spilling, and the file must still shrink
        if res.disk.needs_compact and self._spilled:
            ids = list(self._spilled)
            new_offs = res.disk.compact(
                [self._spilled[i][0] for i in ids],
                [framed_len(self._spilled[i][1]) for i in ids],
            )
            # blitzlint: waive[BL001] -- disk-compaction remap rewrites per-row extent directory entries (cold path)
            for i, off in zip(ids, new_offs):
                self._spilled[i] = (off, self._spilled[i][1])

    def _spill_rows(self, ids: List[int]) -> None:
        """One coalesced segment write (CRC32-framed extents) for the
        whole victim set."""
        with telemetry.span("repro.residency.spill.rows"):
            res = self._res
            payloads = [self.rows[i] for i in ids]
            offs = res.disk.write_many(payloads)
            # blitzlint: waive[BL001] -- per-row extent directory update after one coalesced segment write
            for i, off, p in zip(ids, offs, payloads):
                ln = len(p)
                self._spilled[i] = (off, ln)
                self.rows[i] = None
                self._resident_bytes -= ln
                self._spilled_payload += ln
            res.spills += len(ids)
            _C_ROW_SPILLS.add(len(ids))
            _C_SPILL_BYTES.add(sum(len(p) for p in payloads))

    def _repair_rows(self, ids: List[int]) -> None:
        """Rebuild corrupt spilled rows from the WAL via ``repair_fn``.

        Rebuilt rows are re-encoded resident (their corrupt extents are
        freed); ids the WAL cannot resolve to a live row are tombstoned —
        their latest logical state is "deleted", and garbage is never
        served.  Without a repair handler the corruption propagates."""
        if self.repair_fn is None:
            raise SpillCorruptionError(ids)
        fetched = self.repair_fn(list(ids))
        # blitzlint: waive[BL001] -- WAL-driven repair is the cold corruption path, not the OLTP fast path
        for i, row in zip(ids, fetched):
            if row is None:
                off, ln = self._spilled.pop(i)
                self._res.disk.free(off, framed_len(ln))
                self._spilled_payload -= ln
                self.rows[i] = b""
                self._ref[i] = 0
                self._deleted.add(i)
            else:
                self._put_payload(i, self._encode_row(row))
        self.repairs += len(ids)
        self._res.repaired_rows += len(ids)

    # -- batched protocol ------------------------------------------------
    def insert_many(self, rows: Sequence[Dict[str, Any]]) -> range:
        enc = self._encode_row
        return self._append_payloads([enc(r) for r in rows])

    def get_many(
        self, indices: Sequence[int], backend: Optional[str] = None
    ) -> List[Optional[Dict[str, Any]]]:
        idxs = [int(j) for j in indices]
        dec = self._decode_row
        if self._res is None:
            dels, rows = self._deleted, self.rows
            return [None if i in dels else dec(rows[i]) for i in idxs]
        return [None if p is None else dec(p) for p in self._fetch_payloads(idxs)]

    def update_many(
        self, indices: Sequence[int], rows: Sequence[Dict[str, Any]]
    ) -> None:
        idxs, rows = self._dedup_last(indices, rows)
        # blitzlint: waive[BL001] -- uncompressed silo baseline stores row dicts; per-row put is its contract
        for i, r in zip(idxs, rows):
            if not self.is_live(i):
                raise KeyError(f"row {i} is deleted")
            self._put_payload(i, self._encode_row(r))
        if self._res is not None:
            self._enforce_budget()

    def delete_many(self, indices: Sequence[int]) -> int:
        n = 0
        for i in {int(j) for j in indices}:
            if self.is_live(i):
                self._put_payload(i, b"")  # reclaim the tuple bytes
                self._deleted.add(i)
                n += 1
        if n and self._res is not None:
            self._enforce_budget()  # freed extents may warrant a compact
        return n

    def __len__(self) -> int:
        return len(self.rows)

    @property
    def nbytes(self) -> int:
        """Resident footprint: spilled payloads live on disk and are
        excluded; each spilled row is charged its extent-index entry."""
        if self._res is None:
            return (
                sum(len(r) for r in self.rows) + TOMBSTONE_BYTES * len(self._deleted)
            )
        return (self._resident_bytes
                + self.SPILL_ENTRY_OVERHEAD * len(self._spilled)
                + TOMBSTONE_BYTES * len(self._deleted))

    @property
    def spilled_bytes(self) -> int:
        return self._spilled_payload

    def stats(self) -> Dict[str, Any]:
        out = super().stats()
        if self.repairs:
            out["repairs"] = self.repairs
        if self._res is not None:
            out["spilled_bytes"] = self.spilled_bytes
            out["residency"] = {
                **self._res.stats(),
                "resident_bytes": self.nbytes,
                "spilled_bytes": self.spilled_bytes,
                "spilled_rows": len(self._spilled),
            }
        return out

    # -- durability (DESIGN.md §7) ---------------------------------------
    def close(self, unlink: bool = False) -> None:
        if self._res is not None:
            self._res.close(unlink=unlink)

    def _snapshot_model(self) -> Any:
        """Subclass hook: per-store model state (dict/codes) to pickle."""
        return None

    def _restore_model(self, state: Any) -> None:
        pass

    def snapshot_state(self) -> Dict[str, Any]:
        """Self-contained state: spilled payloads are read back
        (CRC-verified, repaired from the WAL on mismatch) and embedded."""
        st: Dict[str, Any] = {
            "model": self._snapshot_model(),
        }
        if self._res is not None:
            ids = sorted(self._spilled)
            for _attempt in range(3):
                extents = [self._spilled[i] for i in ids]
                try:
                    payloads = self._res.disk.read_many_checked(
                        [e[0] for e in extents], [e[1] for e in extents]
                    )
                    break
                except ExtentCorruptionError as e:
                    bad = [ids[j] for j in e.indices]
                    self._res.quarantined += len(bad)
                    self._repair_rows(bad)
                    ids = [i for i in ids if i in self._spilled]
                    payloads = []
            else:
                raise SpillCorruptionError(ids)
            st["residency"] = {
                "budget": self._res.budget,
                "config": self._res.config,
                "ref": bytes(self._ref),
                "spilled": dict(zip(ids, payloads)),
            }
        # after any repairs above so repaired rows snapshot resident
        st["rows"] = list(self.rows)
        st["deleted"] = sorted(self._deleted)
        return st

    @classmethod
    def from_state(
        cls,
        schema: Sequence[ColumnSpec],
        state: Dict[str, Any],
        spill_path: Optional[str] = None,
        spill_io: Optional[Any] = None,
    ) -> "_BytesRowStore":
        """Rebuild from :meth:`snapshot_state`; previously spilled rows are
        re-spilled into a fresh spill file, preserving the residency
        split."""
        self = cls.__new__(cls)
        RowStore.__init__(self, schema)
        self.rows = list(state["rows"])
        self._deleted = set(state["deleted"])
        self._res = None
        self._spilled = {}
        self._ref = bytearray()
        self._resident_bytes = 0
        self._spilled_payload = 0
        self.repair_fn = None
        self.repairs = 0
        self._spill_path, self._spill_io = spill_path, spill_io
        self._restore_model(state["model"])
        res_state = state.get("residency")
        if res_state is not None:
            self._res = ResidencyManager(
                res_state["budget"], spill_path, res_state.get("config"), io=spill_io
            )
            self._ref = bytearray(res_state["ref"])
            self._resident_bytes = sum(len(r) for r in self.rows if r is not None)
            sp = res_state["spilled"]
            ids = sorted(sp)
            if ids:
                offs = self._res.disk.write_many([sp[i] for i in ids])
                # blitzlint: waive[BL001] -- snapshot respill rebuilds the per-row extent directory on reopen (cold path)
                for i, off in zip(ids, offs):
                    ln = len(sp[i])
                    self._spilled[i] = (off, ln)
                    self._spilled_payload += ln
        return self


class UncompressedStore(_BytesRowStore):
    name = "silo"

    def __init__(
        self,
        schema: Sequence[ColumnSpec],
        rows_sample=None,
        memory_budget: Optional[int] = None,
        spill_path: Optional[str] = None,
        spill_io: Optional[Any] = None,
    ):
        super().__init__(
            schema,
            memory_budget=memory_budget,
            spill_path=spill_path,
            spill_io=spill_io,
        )

    def _encode_row(self, row: Dict[str, Any]) -> bytes:
        return json.dumps([row[c.name] for c in self.schema]).encode()

    def _decode_row(self, raw: bytes) -> Dict[str, Any]:
        return {c.name: v for c, v in zip(self.schema, json.loads(raw))}


class BlitzStore(RowStore):
    """TableCodec store: CSR code arena + bounded delta overlay (§2.5/§3).

    Cold rows live in a :class:`CompressedTable`; batched point reads
    (:meth:`get_many`) decode through ``decode_select`` with no per-tuple
    Python loop whenever the codec compiled.  Updates and deletes go to an
    uncompressed delta overlay / tombstone set consulted before the arena.
    The overlay is *bounded*: when it exceeds ``merge_frac`` of the arena
    code bytes (min ``merge_min_bytes``), :meth:`merge` re-encodes the dirty
    rows through the bulk ``encode_batch`` path back into the arena
    (``CompressedTable.replace_many``), applies tombstones, and rewrites the
    arena once dead bytes pass ``rewrite_frac`` — so a write-heavy run stays
    compressed instead of converging to raw size (DESIGN.md §3).

    ``adaptive`` (DESIGN.md §4) turns on model maintenance: a
    :class:`~repro.adaptive.MaintenanceScheduler` samples written rows into
    a reservoir and, every ``check_every`` writes, checks the plan's escape
    window, refits drifted column models into a new plan version
    (:meth:`install_codec`), and migrates stale escaped blocks — so a
    drifting workload holds its compression factor instead of degrading
    toward raw size.  Pass ``True`` for defaults or a ``MaintenanceConfig``;
    tests can drive ``store.maintenance.step()`` directly.
    """

    name = "blitzcrank"

    def __init__(
        self,
        schema: Sequence[ColumnSpec],
        rows_sample,
        correlation: bool = False,
        block_tuples: int = 1,
        sample: int = 1 << 15,
        use_pallas: bool | None = None,
        auto_merge: bool = True,
        merge_frac: float = 0.06,
        rewrite_frac: float = 0.12,
        merge_min_bytes: int = 1 << 16,
        adaptive: bool | MaintenanceConfig = False,
        codec: Optional[TableCodec] = None,
        memory_budget: Optional[int] = None,
        spill_path: Optional[str] = None,
        spill_io: Optional[Any] = None,
    ):
        super().__init__(schema)
        if codec is None:
            codec = TableCodec.fit(
                rows_sample,
                self.schema,
                correlation=correlation,
                sample=sample,
                block_tuples=block_tuples,
            )
        else:
            # A pre-fitted codec (shared across a repro.db Table's shards:
            # same sample => same models, fit once, count model bytes once)
            block_tuples = codec.block_tuples
        # memory_budget (paper §6.4, DESIGN.md §6) bounds the *compressed
        # arena's* live resident bytes; the bounded delta overlay rides on
        # top and is folded back by merge() as before.
        self.table = CompressedTable(codec, use_pallas=use_pallas,
                                     memory_budget=memory_budget,
                                     spill_path=spill_path,
                                     spill_io=spill_io)
        self._spill_path, self._spill_io = spill_path, spill_io
        self.block_tuples = block_tuples
        # Durability hook, same contract as _BytesRowStore.repair_fn.
        self.repair_fn: Optional[Callable] = None
        self.repairs = 0
        self.auto_merge = bool(auto_merge) and block_tuples == 1
        self.merge_frac = merge_frac
        self.rewrite_frac = rewrite_frac
        self.merge_min_bytes = merge_min_bytes
        self._overlay: Dict[int, Dict] = {}
        self._overlay_bytes = 0
        self._tombstones: set = set()
        self.merges = 0
        self.maintenance: MaintenanceScheduler | None = None
        if adaptive and block_tuples == 1:
            cfg = (adaptive if isinstance(adaptive, MaintenanceConfig) else None)
            self.maintenance = MaintenanceScheduler(self, cfg)

    # -- codec versions (DESIGN.md §4) -----------------------------------
    @property
    def codec(self) -> TableCodec:
        """The current (newest) codec; older versions live in the table."""
        return self.table.codec

    @property
    def n_versions(self) -> int:
        return self.table.n_versions

    def install_codec(self, codec: TableCodec) -> int:
        """Install a refit codec as the new plan version (writes use it)."""
        return self.table.install_codec(codec)

    @property
    def plan_epoch(self) -> int:
        """Plan-version counter for the prepared-op cache (DESIGN.md §11):
        bumps on ``install_codec`` (adaptive refit / migrate), stays put
        across merges/rewrites that keep the plan."""
        return self.table.current_version

    # -- cold tier (DESIGN.md §6) ----------------------------------------
    @property
    def memory_budget(self) -> Optional[int]:
        return self.table.memory_budget

    def set_memory_budget(self, budget: int) -> None:
        """Install the arena's cold tier, or move its budget on a live
        store (a lowered budget spills down to it here)."""
        if self.table.memory_budget is None:
            self.table.set_memory_budget(budget, spill_path=self._spill_path,
                                         spill_io=self._spill_io)
        else:
            self.table.set_memory_budget(budget)

    @property
    def resident_code_bytes(self) -> int:
        """Live arena code bytes in memory (the overlay rides on top)."""
        return self.table.resident_code_bytes

    @property
    def code_bytes(self) -> int:
        """Live arena code bytes, resident or spilled."""
        return self.table.code_bytes

    def migrate(self, limit: int = 1 << 12, resident_only: bool = True) -> int:
        """Re-encode up to ``limit`` stale escaped rows under the newest
        plan (dirty overlay rows migrate through :meth:`merge` instead).
        Under a memory budget, ``resident_only`` keeps maintenance from
        faulting cold blocks in — background work must not thrash the
        hot set (DESIGN.md §6)."""
        return self.table.migrate_rows(limit, resident_only=resident_only)

    @property
    def n(self) -> int:
        return len(self.table)

    def __len__(self) -> int:
        return len(self.table)

    @property
    def n_live(self) -> int:
        return self.table.n_live - len(self._tombstones)

    def is_live(self, i: int) -> bool:
        i = int(i)
        if i in self._overlay:
            return True
        if i in self._tombstones:
            return False
        return self.table.is_live(i)

    # -- batched protocol ------------------------------------------------
    def insert_many(self, rows: Sequence[Dict[str, Any]]) -> range:
        base = len(self.table)
        self.table.extend(rows)
        if self.maintenance is not None:
            self.maintenance.observe_writes(rows)
            self.maintenance.maybe_step()
        return range(base, len(self.table))

    def get_many(
        self, indices: Sequence[int], backend: str | None = None
    ) -> List[Optional[Dict[str, Any]]]:
        idxs = [int(i) for i in indices]  # materialize: may be an iterator
        for _attempt in range(3):
            try:
                rows = self.table.get_many(idxs, backend=backend)
                break
            except SpillCorruptionError as e:
                self._repair(e)
        else:
            rows = self.table.get_many(idxs, backend=backend)
        if self._overlay or self._tombstones:
            ov, ts = self._overlay, self._tombstones
            rows = [None if i in ts
                    else (dict(ov[i]) if i in ov else r)
                    for i, r in zip(idxs, rows)]
            _C_OVERLAY_HITS.add(sum(1 for i in idxs if i in ov))
        return rows

    def update_many(
        self, indices: Sequence[int], rows: Sequence[Dict[str, Any]]
    ) -> None:
        idxs, rows = self._dedup_last(indices, rows)
        # blitzlint: waive[BL001] -- per-key overlay payload update; the batch was deduped just above
        for i, r in zip(idxs, rows):
            if not self.is_live(i):
                raise KeyError(f"row {i} is deleted")
            old = self._overlay.get(i)
            if old is not None:
                self._overlay_bytes -= _raw_row_bytes(old) + OVERLAY_ENTRY_OVERHEAD
            r = dict(r)
            self._overlay[i] = r
            self._overlay_bytes += _raw_row_bytes(r) + OVERLAY_ENTRY_OVERHEAD
        self._maybe_merge()
        if self.maintenance is not None:
            self.maintenance.observe_writes(rows)
            self.maintenance.maybe_step()

    def scan_where(
        self,
        predicates: Sequence[Any],
        columns: Optional[Sequence[str]] = None,
        pushdown: bool = True,
        backend: str | None = None,
    ) -> "Any":
        """Predicate-pushdown scan over the code arena (DESIGN.md §8).

        The arena scan (``repro.scan.scan_table``) evaluates predicates in
        code space with zone-map pruning and decodes only survivors,
        reading cold blocks through without promoting them.  Arena hits
        shadowed by the delta overlay or store-level tombstones are
        dropped and the overlay is re-filtered in value space, so the
        result matches the reference scan exactly at any merge state.
        ``pushdown=False`` falls back to the decode-everything baseline.
        """
        if not pushdown:
            return super().scan_where(
                predicates, columns=columns, pushdown=False, backend=backend
            )
        from repro.scan import ScanResult, match_all, scan_table
        preds = list(predicates)
        for _attempt in range(3):
            try:
                res = scan_table(self.table, preds, columns=columns, backend=backend)
                break
            except SpillCorruptionError as e:
                self._repair(e)
        else:
            res = scan_table(self.table, preds, columns=columns, backend=backend)
        if not self._overlay and not self._tombstones:
            return res
        ov, ts = self._overlay, self._tombstones
        proj = (columns if columns is not None else list(self.table.codec.order))
        merged: List[Tuple[int, Dict[str, Any]]] = [
            (i, r) for i, r in zip(res.ids, res.rows) if i not in ts and i not in ov
        ]
        for i, r in ov.items():
            if match_all(preds, r):
                merged.append((int(i), {c: r[c] for c in proj}))
        merged.sort(key=lambda h: h[0])
        res.stats.rows_matched = len(merged)
        return ScanResult([h[0] for h in merged], [h[1] for h in merged], res.stats)

    def delete_many(self, indices: Sequence[int]) -> int:
        if self.block_tuples != 1:
            raise ValueError("delete_many requires block_tuples == 1")
        n = 0
        for i in {int(j) for j in indices}:
            if not self.is_live(i):
                continue
            old = self._overlay.pop(i, None)
            if old is not None:
                self._overlay_bytes -= _raw_row_bytes(old) + OVERLAY_ENTRY_OVERHEAD
            self._tombstones.add(i)
            n += 1
        self._maybe_merge()
        return n

    # -- delta-merge compaction (DESIGN.md §3) ---------------------------
    def _maybe_merge(self) -> None:
        if not self.auto_merge:
            return
        delta = (self._overlay_bytes + TOMBSTONE_BYTES * len(self._tombstones))
        if delta > max(self.merge_min_bytes, self.merge_frac * 2 * self.table.used):
            self.merge()

    def merge(self) -> Dict[str, Any]:
        """Fold the delta overlay + tombstones back into the code arena.

        Dirty rows are re-encoded through the bulk ``compress_rows`` path
        (one vectorized ``encode_batch`` for conforming rows) and their old
        runs tombstoned; the arena is rewritten once dead bytes exceed
        ``rewrite_frac`` of the code bytes.  Returns :meth:`stats`.
        """
        if self.block_tuples != 1:
            raise ValueError("merge requires block_tuples == 1")
        with telemetry.span("repro.store.merge"):
            if sanitize.ENABLED:
                sanitize.check_overlay(
                    self._overlay, self._tombstones, where="BlitzStore.merge"
                )
            if self._tombstones:
                self.table.delete_many(sorted(self._tombstones))
                self._tombstones.clear()
            if self._overlay:
                idxs = sorted(self._overlay)
                self.table.replace_many(idxs, [self._overlay[i] for i in idxs])
                self._overlay.clear()
                self._overlay_bytes = 0
            self.merges += 1
            _C_MERGES.inc()
            if self.table.dead_bytes > max(
                self.merge_min_bytes, self.rewrite_frac * 2 * self.table.used
            ):
                self.table.rewrite()
        return self.stats()

    # -- durability (DESIGN.md §7) ---------------------------------------
    def _repair(self, err: SpillCorruptionError) -> None:
        """Rebuild rows whose spilled blocks failed their CRC check.

        ``replace_many`` retires the corrupt blocks *without* reading them
        and re-encodes the WAL-reconstructed rows under the newest plan;
        ids the WAL resolves to "deleted" are tombstoned.  Escape
        accounting is paused — repair traffic is not workload drift."""
        if self.repair_fn is None:
            raise err
        ids = list(err.row_ids)
        fetched = self.repair_fn(ids)
        alive = [(i, r) for i, r in zip(ids, fetched) if r is not None]
        dead = [i for i, r in zip(ids, fetched) if r is None]
        plan = self.table.codec.compile()
        ctx = (plan.pause_escape_accounting() if plan is not None
               else contextlib.nullcontext())
        with ctx:
            if alive:
                self.table.replace_many([i for i, _ in alive], [r for _, r in alive])
            if dead:
                self.table.delete_many(dead)
        self.repairs += len(ids)
        self.table.note_repaired_rows(len(ids))

    def close(self, unlink: bool = False) -> None:
        self.table.close(unlink=unlink)

    def snapshot_state(self) -> Dict[str, Any]:
        for _attempt in range(3):
            try:
                table_state = self.table.snapshot_state()
                break
            except SpillCorruptionError as e:
                self._repair(e)
        else:
            table_state = self.table.snapshot_state()
        return {
            "table": table_state,
            "overlay": {int(i): dict(r)
                        for i, r in self._overlay.items()},
            "overlay_bytes": self._overlay_bytes,
            "tombstones": sorted(self._tombstones),
            "merges": self.merges,
            "flags": {
                "auto_merge": self.auto_merge,
                "merge_frac": self.merge_frac,
                "rewrite_frac": self.rewrite_frac,
                "merge_min_bytes": self.merge_min_bytes,
                "block_tuples": self.block_tuples,
            },
            "maintenance": (self.maintenance.snapshot_state()
                            if self.maintenance is not None else None),
        }

    @classmethod
    def from_state(
        cls,
        schema: Sequence[ColumnSpec],
        state: Dict[str, Any],
        spill_path: Optional[str] = None,
        spill_io: Optional[Any] = None,
    ) -> "BlitzStore":
        self = cls.__new__(cls)
        RowStore.__init__(self, schema)
        self.table = CompressedTable.from_state(
            state["table"], spill_path=spill_path, spill_io=spill_io
        )
        self._spill_path, self._spill_io = spill_path, spill_io
        flags = state["flags"]
        self.block_tuples = flags["block_tuples"]
        self.auto_merge = flags["auto_merge"]
        self.merge_frac = flags["merge_frac"]
        self.rewrite_frac = flags["rewrite_frac"]
        self.merge_min_bytes = flags["merge_min_bytes"]
        self._overlay = {int(i): dict(r) for i, r in state["overlay"].items()}
        self._overlay_bytes = state["overlay_bytes"]
        self._tombstones = set(state["tombstones"])
        self.merges = state["merges"]
        self.repair_fn = None
        self.repairs = 0
        self.maintenance = None
        if state.get("maintenance") is not None:
            self.maintenance = MaintenanceScheduler.from_state(
                self, state["maintenance"]
            )
        return self

    # -- accounting ------------------------------------------------------
    @property
    def nbytes(self) -> int:
        """Total footprint: arena (incl. dead bytes) + overlay + tombstones.

        Overlay entries are charged at raw row bytes plus
        ``OVERLAY_ENTRY_OVERHEAD`` (dict key + slot pointer) so compression
        factors are not overstated mid-merge; ``stats()`` reports the
        overlay separately from the arena.
        """
        return (self.table.nbytes + self._overlay_bytes
                + TOMBSTONE_BYTES * len(self._tombstones))

    def model_objects(self) -> List[Any]:
        """Every model object across codec versions (repro.db.Table dedups
        these by identity across shards sharing a fit)."""
        out: List[Any] = []
        for v in range(self.table.n_versions):
            out.extend(self.table.codec_at(v).models.values())
        return out

    @property
    def model_bytes(self) -> int:
        # Codec versions share unchanged model objects; count each once.
        seen: set = set()
        total = 0
        for m in self.model_objects():
            if id(m) not in seen:
                seen.add(id(m))
                total += m.model_bytes()
        return total

    def stats(self) -> Dict[str, Any]:
        t = self.table
        plans = [t.codec_at(v).compile() for v in range(t.n_versions)]
        plan = plans[-1]
        # Cumulative escapes aggregate over every plan version's lifetime;
        # the window counters (drift signal, DESIGN.md §4) are the current
        # plan's open window only.
        escapes: Dict[str, int] = {}
        for p in plans:
            if p is not None:
                for k, v in p.escape_counts.items():
                    escapes[k] = escapes.get(k, 0) + v
        n_blocks = t.n_blocks
        out = {
            "name": self.name,
            "n_ids": len(t),
            "n_live": self.n_live,
            "n_deleted": len(t) - self.n_live,
            "nbytes": self.nbytes,
            "arena_bytes": t.nbytes,
            "dead_bytes": t.dead_bytes,
            "overlay_bytes": self._overlay_bytes,
            "overlay_rows": len(self._overlay),
            "tombstones": len(self._tombstones),
            "merges": self.merges,
            "rewrites": t.rewrites,
            "model_bytes": self.model_bytes,
            "fast_fraction": (float(t.block_fast.mean())
                              if n_blocks else 0.0),
            # §5 dynamic value sets: cumulative per-column model misses ...
            "escapes": escapes,
            # ... and the current drift window (resets on refit/dismissal).
            "escapes_window": (dict(plan.window_escapes)
                               if plan is not None else {}),
            "window_rows": plan.window_rows if plan is not None else 0,
            "plan_versions": t.n_versions,
            "version_rows": t.version_rows(),
            "migrated_rows": t.migrated_rows,
            "plan_fallback": (None if plan is not None
                              else self.codec.plan_fallback_reason),
        }
        out["telemetry"] = telemetry.snapshot(prefix=self.TELEMETRY_PREFIXES)
        if self.repairs:
            out["repairs"] = self.repairs
        if t.memory_budget is not None:
            # nbytes above is *resident* memory (how the paper counts the
            # budget); the on-disk cold tier is reported separately.
            out["spilled_bytes"] = t.spilled_bytes
            out["residency"] = t.residency()
        if self.maintenance is not None:
            out["maintenance"] = self.maintenance.stats()
        return out


class ZstdStore(_BytesRowStore):
    name = "zstd"

    def __init__(
        self,
        schema: Sequence[ColumnSpec],
        rows_sample,
        dict_kb: int = 110,
        level: int = 3,
        memory_budget: Optional[int] = None,
        spill_path: Optional[str] = None,
        spill_io: Optional[Any] = None,
    ):
        super().__init__(
            schema,
            memory_budget=memory_budget,
            spill_path=spill_path,
            spill_io=spill_io,
        )
        import zstandard as zstd
        self.level = level
        samples = [
            json.dumps([r[c.name] for c in self.schema]).encode() for r in rows_sample
        ]
        try:
            dict_data = zstd.train_dictionary(dict_kb * 1024, samples)
            self._set_dict(dict_data.as_bytes())
        except Exception:  # tiny sample sets cannot train a dictionary
            self._set_dict(None)

    def _set_dict(self, dict_bytes: Optional[bytes]) -> None:
        import zstandard as zstd
        if dict_bytes is not None:
            dict_data = zstd.ZstdCompressionDict(dict_bytes)
            self._dict = dict_data
            self.cctx = zstd.ZstdCompressor(level=self.level, dict_data=dict_data)
            self.dctx = zstd.ZstdDecompressor(dict_data=dict_data)
            self.dict_bytes = len(dict_bytes)
        else:
            self._dict = None
            self.cctx = zstd.ZstdCompressor(level=self.level)
            self.dctx = zstd.ZstdDecompressor()
            self.dict_bytes = 0

    def _snapshot_model(self) -> Any:
        return {
            "level": self.level,
            "dict": (self._dict.as_bytes() if self._dict is not None else None),
        }

    def _restore_model(self, state: Any) -> None:
        self.level = state["level"]
        self._set_dict(state["dict"])

    def _encode_row(self, row: Dict[str, Any]) -> bytes:
        raw = json.dumps([row[c.name] for c in self.schema]).encode()
        return self.cctx.compress(raw)

    def _decode_row(self, raw: bytes) -> Dict[str, Any]:
        vals = json.loads(self.dctx.decompress(raw))
        return {c.name: v for c, v in zip(self.schema, vals)}

    def insert_many(self, rows: Sequence[Dict[str, Any]]) -> range:
        """Bulk insert through ``multi_compress_to_buffer`` when available:
        one C call over all payloads, amortizing context setup."""
        schema = self.schema
        payloads = [json.dumps([r[c.name] for c in schema]).encode() for r in rows]
        frames = None
        if len(payloads) > 1 and hasattr(self.cctx, "multi_compress_to_buffer"):
            try:
                segs = self.cctx.multi_compress_to_buffer(payloads)
                frames = [segs[i].tobytes() for i in range(len(segs))]
            except Exception:
                frames = None
        if frames is None:
            comp = self.cctx.compress
            frames = [comp(p) for p in payloads]
        return self._append_payloads(frames)

    def get_many(
        self, indices: Sequence[int], backend: Optional[str] = None
    ) -> List[Optional[Dict[str, Any]]]:
        """Batched point gets: one ``multi_decompress_to_buffer`` C call for
        the whole batch when the library supports it."""
        idxs = [int(i) for i in indices]
        dels = self._deleted
        live = [j for j, i in enumerate(idxs) if i not in dels]
        out: List[Optional[Dict[str, Any]]] = [None] * len(idxs)
        if self._res is None:
            frames = [self.rows[idxs[j]] for j in live]
        else:
            fetched = self._fetch_payloads(idxs)
            frames = [fetched[j] for j in live]
        raws = None
        if len(frames) > 1 and hasattr(self.dctx, "multi_decompress_to_buffer"):
            try:
                segs = self.dctx.multi_decompress_to_buffer(frames)
                raws = [segs[i].tobytes() for i in range(len(segs))]
            except Exception:
                raws = None
        if raws is None:
            dec = self.dctx.decompress
            raws = [dec(f) for f in frames]
        schema = self.schema
        for j, raw in zip(live, raws):
            vals = json.loads(raw)
            out[j] = {c.name: v for c, v in zip(schema, vals)}
        return out

    @property
    def model_bytes(self) -> int:
        return self.dict_bytes


class RamanStore(_BytesRowStore):
    """Per-column Huffman over value ids (static dictionary baseline §6).

    Values unseen at train time go through a length-prefixed byte escape.
    Numeric columns are coded on their value dictionary too (Raman & Swart
    treat fields as symbols); tuples are concatenated variable-length codes.
    """

    name = "raman"

    def __init__(
        self,
        schema: Sequence[ColumnSpec],
        rows_sample,
        memory_budget: Optional[int] = None,
        spill_path: Optional[str] = None,
        spill_io: Optional[Any] = None,
    ):
        super().__init__(
            schema,
            memory_budget=memory_budget,
            spill_path=spill_path,
            spill_io=spill_io,
        )
        self.columns = {}
        for c in self.schema:
            vals = [r[c.name] for r in rows_sample]
            uniq: Dict[Any, int] = {}
            counts: List[float] = []
            # blitzlint: waive[BL001] -- Raman fit-time frequency estimation over the sample, not the op path
            for v in vals:
                j = uniq.setdefault(v, len(uniq))
                if j == len(counts):
                    counts.append(0.0)
                counts[j] += 1
            # reserve an escape symbol
            uniq["\x00<esc>"] = len(uniq)
            counts.append(max(1.0, 0.01 * len(vals)))
            self.columns[c.name] = (
                uniq, list(uniq.keys()), HuffmanCode(np.asarray(counts))
            )
        # hoisted per-column (name, value->id, esc_id, id->value, code)
        self._cols = [(c.name, *self.columns[c.name],
                       self.columns[c.name][0]["\x00<esc>"])
                      for c in self.schema]

    def _snapshot_model(self) -> Any:
        return {"columns": self.columns}

    def _restore_model(self, state: Any) -> None:
        self.columns = state["columns"]
        self._cols = [(c.name, *self.columns[c.name],
                       self.columns[c.name][0]["\x00<esc>"])
                      for c in self.schema]

    def _encode_row(self, row: Dict[str, Any]) -> bytes:
        bw = BitWriter()
        for name, uniq, _, hc, esc in self._cols:
            v = row[name]
            j = uniq.get(v)
            if j is None:
                hc.encode(esc, bw)
                payload = json.dumps(v).encode()
                bw.write(len(payload), 16)
                for byte in payload:
                    bw.write(byte, 8)
            else:
                hc.encode(j, bw)
        return bw.getvalue()[0]

    def _decode_row(self, raw: bytes) -> Dict[str, Any]:
        br = BitReader(raw)
        out = {}
        for name, _, keys, hc, esc in self._cols:
            j = hc.decode(br)
            if j == esc:
                ln = br.peek(16)
                br.skip(16)
                data = bytearray()
                for _ in range(ln):
                    data.append(br.peek(8))
                    br.skip(8)
                out[name] = json.loads(bytes(data))
            else:
                out[name] = keys[j]
        return out

    @property
    def model_bytes(self) -> int:
        total = 0
        for name, (uniq, keys, hc) in self.columns.items():
            total += sum(len(str(k)) + 10 for k in keys)
        return total


class LRUFastPath(RowStore):
    """§6.5 write-back cache of decompressed tuples above any RowStore.

    Speaks the full protocol: reads are served from the cache when hot and
    batch-fetched through the store's ``get_many`` otherwise; updates are
    buffered dirty in the cache and written back to the underlying store
    (``update_many``) on eviction and on :meth:`sync`, so
    ``read_modify_write`` never loses data once the cache fills.
    """

    name = "lru"

    def __init__(self, store: "UncompressedStore", capacity: int) -> None:
        super().__init__(getattr(store, "schema", None))
        self.store = store
        self.capacity = capacity
        self.cache: OrderedDict[int, Dict] = OrderedDict()
        self.dirty: set = set()
        self.hits = 0
        self.misses = 0
        self.writebacks = 0

    def _writeback(self, i: int, row: Dict[str, Any]) -> None:
        self.dirty.discard(i)
        self.writebacks += 1
        self.store.update(i, row)

    def _evict(self) -> None:
        while len(self.cache) > self.capacity:
            i, row = self.cache.popitem(last=False)
            if i in self.dirty:
                self._writeback(i, row)

    def read_modify_write(self, i: int, update_fn) -> None:
        row = self.cache.get(i)
        if row is not None:
            self.hits += 1
            self.cache.move_to_end(i)
        else:
            self.misses += 1
            row = self.store.get(i)
            self.cache[i] = row
        # Apply the update and mark dirty BEFORE evicting: with a full (or
        # zero-capacity) cache the evicted row may be this one, and the
        # write-back must carry the new value.
        update_fn(row)
        self.dirty.add(i)
        self._evict()

    def get(self, i: int) -> Dict[str, Any]:
        row = self.cache.get(i)
        if row is not None:
            self.hits += 1
            self.cache.move_to_end(i)
            return dict(row)  # a copy: callers must not alias the cache
        self.misses += 1
        return self.store.get(i)

    def sync(self) -> None:
        """Flush all dirty cached rows back in one ``update_many`` call."""
        idxs = [i for i in self.dirty if i in self.cache]
        if idxs:
            self.store.update_many(idxs, [self.cache[i] for i in idxs])
            self.writebacks += len(idxs)
        self.dirty.clear()

    # -- batched protocol ------------------------------------------------
    def insert_many(self, rows: Sequence[Dict[str, Any]]) -> range:
        return self.store.insert_many(rows)

    def get_many(
        self, indices: Sequence[int], backend: Optional[str] = None
    ) -> List[Optional[Dict[str, Any]]]:
        idxs = [int(i) for i in indices]
        out: List[Optional[Dict[str, Any]]] = [None] * len(idxs)
        miss_pos: List[int] = []
        cache = self.cache
        for j, i in enumerate(idxs):
            row = cache.get(i)
            if row is not None:
                self.hits += 1
                cache.move_to_end(i)
                out[j] = dict(row)  # copies: callers must not alias the cache
            else:
                miss_pos.append(j)
        if miss_pos:
            self.misses += len(miss_pos)
            fetched = self.store.get_many(
                [idxs[j] for j in miss_pos], backend=backend
            )
            for j, row in zip(miss_pos, fetched):
                if row is None:
                    continue  # tombstone: never cached
                i = idxs[j]
                if i in cache:  # duplicate miss within this batch
                    row = cache[i]
                else:
                    cache[i] = row
                out[j] = dict(row)
            self._evict()
        return out

    def update_many(
        self, indices: Sequence[int], rows: Sequence[Dict[str, Any]]
    ) -> None:
        idxs, rows = self._dedup_last(indices, rows)
        # blitzlint: waive[BL001] -- baseline row-cache update maintains per-key recency (not the hot store)
        for i, r in zip(idxs, rows):
            if not self.is_live(i):
                raise KeyError(f"row {i} is deleted")
            self.cache[i] = dict(r)
            self.cache.move_to_end(i)
            self.dirty.add(i)
        self._evict()

    def delete_many(self, indices: Sequence[int]) -> int:
        idxs = {int(i) for i in indices}
        for i in idxs:
            self.cache.pop(i, None)
            self.dirty.discard(i)
        return self.store.delete_many(idxs)

    def scan(
        self, start: int = 0, stop: Optional[int] = None, batch: int = 1024
    ) -> Iterator[Tuple[int, Dict[str, Any]]]:
        self.sync()  # the underlying store must see dirty rows
        return self.store.scan(start, stop, batch)

    def scan_where(
        self,
        predicates: Sequence[Any],
        columns: Optional[Sequence[str]] = None,
        pushdown: bool = True,
        backend: Optional[str] = None,
    ) -> "Any":
        self.sync()  # the underlying store must see dirty rows
        return self.store.scan_where(
            predicates, columns=columns, pushdown=pushdown, backend=backend
        )

    def is_live(self, i: int) -> bool:
        return int(i) in self.cache or self.store.is_live(i)

    def __len__(self) -> int:
        return len(self.store)

    @property
    def n_live(self) -> int:
        return self.store.n_live

    @property
    def nbytes(self) -> int:
        return self.store.nbytes + sum(
            _raw_row_bytes(r) + OVERLAY_ENTRY_OVERHEAD for r in self.cache.values()
        )

    def stats(self) -> Dict[str, Any]:
        s = dict(self.store.stats())
        s.update(nbytes=self.nbytes,  # include the cached rows (§3.4)
                 cache_rows=len(self.cache), cache_hits=self.hits,
                 cache_misses=self.misses, writebacks=self.writebacks)
        return s


STORE_KINDS = MappingProxyType({
    "silo": UncompressedStore,
    "blitzcrank": BlitzStore,
    "zstd": ZstdStore,
    "raman": RamanStore,
})
