"""Per-table redo WAL: CRC32-framed records, group flush, torn-tail scan.

One :class:`WriteAheadLog` per table, fed by the table's batch verbs
(DESIGN.md §7).  Records are *logical* redo — ``("insert", rows)``,
``("update", rows)``, ``("delete", keys)``, plus a ``("create", meta)``
header — so replay goes through exactly the same batched code paths as
live traffic and reproduces bit-identical state (model fits are seeded,
shard routing is a pure hash).

Framing is ``[magic u32][len u32][crc32 u32][pickle body]``.  The log is
append-only and never truncated by a checkpoint — a checkpoint records the
LSN (byte offset) replay should start from, and the retained prefix is
what lets runtime corruption repair rebuild *any* row's latest value by a
full scan.  On open, a torn tail (short frame, bad magic, CRC mismatch)
is detected and the file truncated back to the last valid record.

A failed append or fsync leaves the on-disk tail unknowable, so the log
*poisons* itself: every later append raises :class:`WalPoisonedError`
until the database is closed and recovered — the same contract real
engines adopted after fsync-gate.
"""

from __future__ import annotations

import contextlib
import os
import pickle
import struct
import zlib
from typing import Any, Iterator, Optional, Tuple

from repro import sanitize, telemetry
from repro.core.arena import OS_IO

RECORD_MAGIC = 0x57414C31  # "WAL1"
RECORD_HEADER = struct.Struct("<III")

_C_RECORDS = telemetry.counter("repro.wal.records")
_C_BYTES = telemetry.counter("repro.wal.bytes")
_C_FSYNCS = telemetry.counter("repro.wal.fsyncs")


class WalError(RuntimeError):
    pass


class WalPoisonedError(WalError):
    """The log hit an append/fsync failure; close and recover the DB."""


class WriteAheadLog:
    def __init__(self, path: str, io: Optional[Any] = None,
                 fsync_every: int = 1):
        self.path = path
        self.io = io if io is not None else OS_IO
        self.fsync_every = max(0, int(fsync_every))
        self._fd = os.open(path, os.O_RDWR | os.O_CREAT, 0o644)
        self.closed = False
        self.poisoned = False
        self.suspended = False
        self._pending: list = []
        self._flushes = 0
        self.records = 0
        self.truncated_bytes = 0
        self._tail = self._recover_tail()

    # -- open-time torn-tail scan ----------------------------------------
    def _recover_tail(self) -> int:
        end = 0
        for end, _op, _payload in self.scan(0):
            pass
        size = os.fstat(self._fd).st_size
        if end < size:
            self.truncated_bytes = size - end
            os.ftruncate(self._fd, end)
        return end

    # -- append path ------------------------------------------------------
    @property
    def lsn(self) -> int:
        """Byte offset of the durable tail; doubles as the log's LSN."""
        return self._tail

    def append(self, op: str, payload: Any) -> None:
        """Stage one logical record (framed, not yet written)."""
        if self.poisoned:
            raise WalPoisonedError(f"{self.path}: log is poisoned")
        if self.suspended:
            return
        body = pickle.dumps((op, payload), protocol=4)
        self._pending.append(
            RECORD_HEADER.pack(RECORD_MAGIC, len(body), zlib.crc32(body))
        )
        self._pending.append(body)

    def flush(self) -> None:
        """Group-write staged records; fsync on the configured cadence."""
        if self.poisoned:
            raise WalPoisonedError(f"{self.path}: log is poisoned")
        if not self._pending:
            return
        buf = b"".join(self._pending)
        self.io.point("wal.before_flush")
        try:
            with telemetry.span("repro.wal.append"):
                self.io.pwrite(self._fd, buf, self._tail)
            self._flushes += 1
            if self.fsync_every and self._flushes % self.fsync_every == 0:
                self.io.point("wal.before_fsync")
                with telemetry.span("repro.wal.fsync"):
                    self.io.fsync(self._fd)
                _C_FSYNCS.inc()
        except OSError:
            self.poisoned = True
            raise
        self._pending.clear()
        self._tail += len(buf)
        _C_BYTES.add(len(buf))
        if sanitize.ENABLED:
            # The LSN is the durable byte tail: after a group write it must
            # equal the physical file length (shorter = torn/lost write).
            sanitize.check_wal_lsn(
                self._tail, os.fstat(self._fd).st_size, where=self.path
            )
        self.io.point("wal.after_flush")

    def log(self, op: str, payload: Any) -> None:
        """Append + flush one record: the per-batch-verb group commit."""
        if self.suspended:
            return
        self.append(op, payload)
        self.flush()
        self.records += 1
        _C_RECORDS.inc()

    @contextlib.contextmanager
    def suspend(self) -> Iterator["WriteAheadLog"]:
        """No-op appends inside the block (used during recovery replay)."""
        prev = self.suspended
        self.suspended = True
        try:
            yield self
        finally:
            self.suspended = prev

    # -- scan / replay ----------------------------------------------------
    def scan(self, from_lsn: int = 0) -> Iterator[Tuple[int, str, Any]]:
        """Yield ``(end_lsn, op, payload)`` per valid record.

        Stops at the first torn or corrupt frame — everything before it
        is intact (CRC-verified), everything after is unreachable.
        """
        size = os.fstat(self._fd).st_size
        pos = int(from_lsn)
        while pos + RECORD_HEADER.size <= size:
            head = os.pread(self._fd, RECORD_HEADER.size, pos)
            if len(head) < RECORD_HEADER.size:
                return
            magic, ln, crc = RECORD_HEADER.unpack(head)
            body_at = pos + RECORD_HEADER.size
            if magic != RECORD_MAGIC or body_at + ln > size:
                return
            body = os.pread(self._fd, ln, body_at)
            if len(body) != ln or zlib.crc32(body) != crc:
                return
            pos = body_at + ln
            op, payload = pickle.loads(body)
            yield pos, op, payload

    # -- lifecycle --------------------------------------------------------
    def close(self) -> None:
        if self.closed:
            return
        self.closed = True
        try:
            os.close(self._fd)
        except OSError:
            pass

    def unlink(self) -> None:
        self.close()
        try:
            os.unlink(self.path)
        except FileNotFoundError:
            pass

    def __del__(self) -> None:  # pragma: no cover - GC timing dependent
        self.close()
