"""Out-of-core cold tier (paper §6.4): a paged on-disk arena + clock policy.

The paper's final claim is that for data sets larger than physical memory,
Blitzcrank "helps the database sustain a high throughput for more
transactions before the I/O overhead dominates".  This module provides the
two pieces the stores need to reproduce that experiment:

* :class:`DiskArena` — an append-only, page-aligned spill file holding the
  compressed code runs of cold blocks.  Extents are byte-addressed
  ``(offset, length)`` pairs owned by the caller; freed extents are
  accounted and reclaimed by an in-place ascending compaction
  (:meth:`compact`), so the file never grows without bound.  Victim runs
  are always written in arena byte order (ascending in-memory offset), so
  blocks that were adjacent in the memory arena stay adjacent on disk and
  a fault over a contiguous range coalesces into one read.

* :class:`ResidencyManager` — the policy half: a memory budget, a
  clock/second-chance hand over per-block referenced bits, and the
  spill/fault counters surfaced through ``stats()``.  The sweep itself is
  driven by the owning store (it owns the per-block arrays); the manager
  decides *how much* to free and records what happened.

The residency lifecycle of a block (DESIGN.md §6)::

    resident --(clock finds ref=0)--> spilled --(get_many miss)--> faulted
       ^                                 |                            |
       +--------- rewrite() keeps tags --+----------- promoted ------+

Hot-path invariant: a fault costs one (coalesced) disk read plus one
vectorized batch decode — never per-row work.
"""

from __future__ import annotations

import dataclasses
import os
import struct
import tempfile
import zlib
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

# Spill segments are aligned to this many bytes so compaction and
# sequential fault-in behave like page I/O rather than byte soup.
PAGE_BYTES = 4096

# Checksummed extent frame (DESIGN.md §7): every spill payload written via
# the framed API carries a 12-byte header — magic, payload length, CRC32 —
# verified on fault-in.  A mismatch means the extent is quarantined and the
# rows rebuilt from the WAL, never decoded.
FRAME_MAGIC = 0x53504731  # "SPG1"
FRAME_HEADER = struct.Struct("<III")
FRAME_OVERHEAD = FRAME_HEADER.size


def framed_len(payload_len: int) -> int:
    """On-disk length of a framed extent holding ``payload_len`` bytes."""
    return FRAME_OVERHEAD + int(payload_len)


def read_extents(
    path: str, offsets: Sequence[int], payload_lens: Sequence[int]
) -> List[Optional[bytes]]:
    """Verify-and-read framed extents straight from a spill file path.

    Read-only and stateless (no :class:`DiskArena`): checkpoint restore
    uses it to source extent-referenced payloads from a durable spill file
    *before* a fresh arena — possibly at the same path, which would
    truncate it — is opened.  Returns ``None`` for any extent that is
    missing, short, or fails its magic/length/CRC check; the caller maps
    those back to rows for WAL repair.
    """
    offsets = [int(o) for o in offsets]
    lens = [int(ln) for ln in payload_lens]
    try:
        f = open(path, "rb")
    except OSError:
        return [None] * len(offsets)
    out: List[Optional[bytes]] = []
    with f:
        fd = f.fileno()
        for off, ln in zip(offsets, lens):
            fln = framed_len(ln)
            try:
                raw = os.pread(fd, fln, off)
            except OSError:
                out.append(None)
                continue
            if len(raw) != fln:
                out.append(None)
                continue
            magic, n, crc = FRAME_HEADER.unpack_from(raw)
            body = raw[FRAME_OVERHEAD:]
            ok = (magic == FRAME_MAGIC and n == len(body) and zlib.crc32(body) == crc)
            out.append(body if ok else None)
    return out


class ArenaError(RuntimeError):
    """Base class for spill-file I/O failures."""


class ArenaReadError(ArenaError):
    """A ``pread`` returned fewer bytes than the extent length.

    Before this check a truncated spill file silently fed short (garbage)
    payloads back into the decode path — the checksum layer now converts
    this into quarantine + WAL rebuild instead of wrong answers.
    """

    def __init__(self, offset: int, wanted: int, got: int) -> None:
        super().__init__(
            f"short spill read at offset {offset}: wanted {wanted} bytes, " f"got {got}"
        )
        self.offset = int(offset)
        self.wanted = int(wanted)
        self.got = int(got)


class ExtentCorruptionError(ArenaError):
    """One or more framed extents failed their magic/length/CRC check.

    ``indices`` are positions into the ``read_many_checked`` request, so
    the caller can map them back to blocks/rows and quarantine precisely.
    """

    def __init__(self, indices: Sequence[int]) -> None:
        super().__init__(
            f"{len(list(indices))} corrupt spill extent(s): "
            f"{sorted(int(i) for i in indices)[:8]}"
        )
        self.indices = [int(i) for i in indices]


class SpillCorruptionError(ArenaError):
    """Store-level view of extent corruption: the affected row ids.

    Raised by stores *before* any state mutation so a durability layer can
    rebuild the rows from WAL replay and retry the read; without a repair
    handler it propagates (corrupt data is never returned to the caller).
    """

    def __init__(self, row_ids: Sequence[int]) -> None:
        super().__init__(f"spill corruption affecting {len(list(row_ids))} row(s)")
        self.row_ids = sorted(int(i) for i in row_ids)


class _OsIO:
    """Default I/O provider: direct os calls, crash points are no-ops.

    The durability layer substitutes a fault-injecting implementation with
    the same four methods; core code never imports ``repro.durability``.
    """

    def pwrite(self, fd: int, data: bytes, offset: int) -> int:
        return os.pwrite(fd, data, offset)

    def pread(self, fd: int, length: int, offset: int) -> bytes:
        return os.pread(fd, length, offset)

    def fsync(self, fd: int) -> None:
        os.fsync(fd)

    def point(self, name: str) -> None:
        pass


OS_IO = _OsIO()


class DiskArena:
    """Append-only spill file with free-extent accounting and compaction.

    ``path=None`` (the default) uses an anonymous temp file that the OS
    reclaims when the arena is closed or the process exits — spill data
    never outlives the store that wrote it.  All offsets and lengths are
    in bytes.
    """

    def __init__(
        self,
        path: Optional[str] = None,
        page_bytes: int = PAGE_BYTES,
        io: Optional[Any] = None,
    ):
        if page_bytes <= 0:
            raise ValueError("page_bytes must be positive")
        self.page_bytes = int(page_bytes)
        self.path = path
        self.io = io if io is not None else OS_IO
        if path is None:
            self._file = tempfile.TemporaryFile(prefix="blitz-spill-")
        else:
            self._file = open(path, "w+b")
        self._fd = self._file.fileno()
        self.closed = False
        self._tail = 0  # next unallocated byte (page-aligned per segment)
        self._live = 0  # live payload bytes
        self._freed = 0  # dead payload bytes awaiting compaction
        self.writes = 0
        self.reads = 0
        self.bytes_written = 0
        self.bytes_read = 0
        self.compactions = 0

    # -- allocation ------------------------------------------------------
    def write(self, payload: bytes) -> int:
        """Append one segment, returning its byte offset.

        Segments start page-aligned; interior layout (many block runs per
        segment) is the caller's business.
        """
        off = -self._tail % self.page_bytes + self._tail
        n = len(payload)
        self.io.pwrite(self._fd, payload, off)
        self._tail = off + n
        self._live += n
        self.writes += 1
        self.bytes_written += n
        return off

    def write_many(self, payloads: Sequence[bytes]) -> List[int]:
        """Append payloads as CRC32-framed extents in one segment.

        Returns each frame's byte offset (pointing at its header).  The
        segment is written in two halves around the ``spill.mid_write``
        crash point so a simulated kill can land inside the write; the
        callers' metadata only references the new extents after this
        returns, so a torn segment is dead weight, not corruption.
        """
        frames: List[bytes] = []
        for p in payloads:
            frames.append(FRAME_HEADER.pack(FRAME_MAGIC, len(p), zlib.crc32(p)))
            frames.append(p)
        buf = b"".join(frames)
        off = -self._tail % self.page_bytes + self._tail
        half = len(buf) // 2
        self.io.pwrite(self._fd, buf[:half], off)
        self.io.point("spill.mid_write")
        self.io.pwrite(self._fd, buf[half:], off + half)
        self._tail = off + len(buf)
        self._live += len(buf)
        self.writes += 1
        self.bytes_written += len(buf)
        offs, pos = [], off
        for p in payloads:
            offs.append(pos)
            pos += FRAME_OVERHEAD + len(p)
        return offs

    def free(self, offset: int, length: int) -> None:
        """Mark ``length`` bytes at ``offset`` dead (reclaimed at compact)."""
        self._live -= int(length)
        self._freed += int(length)

    # -- reads -----------------------------------------------------------
    def read(self, offset: int, length: int) -> bytes:
        self.reads += 1
        self.bytes_read += int(length)
        buf = self.io.pread(self._fd, int(length), int(offset))
        if len(buf) != int(length):
            raise ArenaReadError(int(offset), int(length), len(buf))
        return buf

    def read_checked(self, offset: int, payload_len: int) -> bytes:
        """Read and verify one framed extent, returning its payload."""
        return self.read_many_checked([offset], [payload_len])[0]

    def read_many_checked(
        self, offsets: Sequence[int], payload_lens: Sequence[int]
    ) -> List[bytes]:
        """Batched framed-extent reads with magic/length/CRC verification.

        ``payload_lens`` are payload byte counts (the frame overhead is
        added here).  Adjacent frames coalesce into one I/O exactly like
        :meth:`read_many`.  Any extent failing verification — short read,
        bad magic, length mismatch, CRC mismatch — raises
        :class:`ExtentCorruptionError` carrying the request indices of
        every bad extent; no partial result is returned.
        """
        framed = [framed_len(ln) for ln in payload_lens]
        try:
            raws: List[Optional[bytes]] = list(self.read_many(offsets, framed))
        except ArenaReadError:
            # A coalesced read hit a hole/truncation: retry per-extent so
            # only the genuinely bad extents are quarantined.
            raws = []
            for off, fln in zip(offsets, framed):
                try:
                    raws.append(self.read(off, fln))
                except ArenaReadError:
                    raws.append(None)
        out: List[bytes] = []
        bad: List[int] = []
        for j, raw in enumerate(raws):
            payload: Optional[bytes] = None
            if raw is not None and len(raw) == framed[j]:
                magic, ln, crc = FRAME_HEADER.unpack_from(raw)
                body = raw[FRAME_OVERHEAD:]
                if (
                    magic == FRAME_MAGIC and ln == len(body) and zlib.crc32(body) == crc
                ):
                    payload = body
            if payload is None:
                bad.append(j)
                payload = b""
            out.append(payload)
        if bad:
            raise ExtentCorruptionError(bad)
        return out

    def read_many(self, offsets: Sequence[int], lengths: Sequence[int]) -> List[bytes]:
        """Batched extent reads, coalescing adjacent extents into one I/O.

        Returns payloads in request order.  Extents written in arena byte
        order by one spill sweep are adjacent on disk, so faulting a range
        of once-neighboring blocks costs one ``pread``, not N.
        """
        offs = np.asarray(list(offsets), dtype=np.int64)
        lens = np.asarray(list(lengths), dtype=np.int64)
        n = offs.size
        out: List[Optional[bytes]] = [None] * n
        if not n:
            return []
        order = np.argsort(offs, kind="stable")
        j = 0
        while j < n:
            # grow a contiguous disk range [start, end)
            k = j
            start = int(offs[order[j]])
            end = start + int(lens[order[j]])
            while k + 1 < n and int(offs[order[k + 1]]) == end:
                k += 1
                end += int(lens[order[k]])
            buf = self.read(start, end - start)
            pos = 0
            for m in range(j, k + 1):
                nxt = pos + int(lens[order[m]])
                out[int(order[m])] = buf[pos:nxt]
                pos = nxt
            j = k + 1
        return out  # type: ignore[return-value]

    # -- compaction ------------------------------------------------------
    @property
    def needs_compact(self) -> bool:
        return self._freed > max(1 << 20, self._live)

    def compact(self, offsets: Sequence[int], lengths: Sequence[int]) -> List[int]:
        """Rewrite the live extents densely from byte 0, in place.

        Extents are moved in ascending offset order and packed with NO
        page alignment: the write cursor is then always <= the sum of the
        already-moved extents' lengths, which is <= the current extent's
        old offset — it can never overtake an unread live extent, so the
        move is safe without a second file.  (Aligning here would break
        that invariant and overwrite live data.)  Returns the new offsets
        in request order and truncates the file.
        """
        offs = np.asarray(list(offsets), dtype=np.int64)
        lens = np.asarray(list(lengths), dtype=np.int64)
        order = np.argsort(offs, kind="stable")
        new_offs = [0] * offs.size
        cursor = 0
        for m in order:
            off, ln = int(offs[m]), int(lens[m])
            if cursor != off:
                self.io.pwrite(self._fd, self.io.pread(self._fd, ln, off), cursor)
            new_offs[int(m)] = cursor
            cursor += ln
        self._file.truncate(cursor)
        self._tail = cursor
        self._live = int(lens.sum())
        self._freed = 0
        self.compactions += 1
        return new_offs

    # -- accounting ------------------------------------------------------
    @property
    def live_bytes(self) -> int:
        return self._live

    @property
    def file_bytes(self) -> int:
        """Allocated file span (live + dead + alignment padding)."""
        return self._tail

    def fsync(self) -> None:
        self.io.fsync(self._fd)

    def close(self) -> None:
        if self.closed:
            return
        self.closed = True
        try:
            self._file.close()
        except Exception:
            pass

    def unlink(self) -> None:
        """Close and remove a named spill file (no-op for temp arenas)."""
        self.close()
        if self.path is not None:
            try:
                os.unlink(self.path)
            except FileNotFoundError:
                pass

    def __enter__(self) -> "DiskArena":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self) -> None:  # pragma: no cover - GC timing dependent
        self.close()


@dataclasses.dataclass
class ResidencyConfig:
    """Policy knobs for the cold tier (DESIGN.md §6)."""

    # Spill down to this fraction of the budget once over it, so every
    # insert batch doesn't trigger a sweep (hysteresis).
    low_water: float = 0.9
    # Physical arenas hold dead/spilled residue until rewrite(); force a
    # compaction once the physical footprint passes budget + slack.
    slack_frac: float = 0.25
    slack_min_bytes: int = 1 << 16
    # Clock sweep chunk: candidates examined per vectorized step.
    sweep_chunk: int = 2048


class ResidencyManager:
    """Budget + clock state + counters for one store's cold tier.

    The owning store keeps the per-block arrays (referenced bits, disk
    offsets, residency flags) because they must grow and be permuted with
    its other per-block metadata; the manager owns the budget arithmetic,
    the clock hand, the spill file, and the observability counters.
    """

    def __init__(
        self,
        budget_bytes: int,
        spill_path: Optional[str] = None,
        config: Optional[ResidencyConfig] = None,
        io: Optional[Any] = None,
    ):
        self.budget = budget_bytes
        self.config = config or ResidencyConfig()
        self.disk = DiskArena(spill_path, io=io)
        self.hand = 0
        self.spills = 0  # blocks spilled
        self.spill_sweeps = 0
        self.faults = 0  # blocks faulted back in
        self.fault_batches = 0
        self.scalar_faults = 0  # read-through scalar block reads
        self.quarantined = 0  # extents that failed their CRC check
        self.repaired_rows = 0  # rows rebuilt from WAL after corruption

    def close(self, unlink: bool = False) -> None:
        if unlink:
            self.disk.unlink()
        else:
            self.disk.close()

    # -- budget arithmetic ----------------------------------------------
    @property
    def budget(self) -> int:
        """Resident live code bytes the owning store may hold."""
        return self._budget

    @budget.setter
    def budget(self, budget_bytes: int) -> None:
        # Movable on a live store (a database limit re-split); the owner
        # spills down to a lowered budget at its next enforcement.
        if budget_bytes <= 0:
            raise ValueError("memory_budget must be positive")
        self._budget = int(budget_bytes)

    @property
    def budget_codes(self) -> int:
        """The budget expressed in uint16 code units."""
        return self.budget // 2

    @property
    def target_codes(self) -> int:
        return int(self.config.low_water * self.budget_codes)

    @property
    def slack_bytes(self) -> int:
        return max(
            self.config.slack_min_bytes,
            int(self.config.slack_frac * self.budget),
        )

    @property
    def rewrite_codes(self) -> int:
        """Arena codes, live or not, past which a store with dead residue
        is rewritten: the budget plus the slack, in code units."""
        return (self.budget + self.slack_bytes) // 2

    # -- the clock/second-chance sweep (shared by every store) -----------
    def sweep(
        self,
        n_items: int,
        need: int,
        candidates: Callable[[np.ndarray], np.ndarray],
        sizes: Callable[[np.ndarray], np.ndarray],
        ref_get: Callable[[np.ndarray], np.ndarray],
        ref_clear: Callable[[np.ndarray], None],
    ) -> np.ndarray:
        """Pick victims worth >= ``need`` size units via two clock passes.

        Items are ids in ``[0, n_items)``; the callbacks are vectorized
        over id arrays: ``candidates(ids) -> bool mask`` (spillable now),
        ``sizes(ids) -> int64 sizes``, ``ref_get(ids) -> bool mask`` and
        ``ref_clear(ids)`` over the caller-owned referenced bits.  A
        referenced candidate gets its bit cleared and one more chance;
        pass two takes it.  Items picked in an earlier chunk are excluded
        when the hand wraps — a victim is chosen at most once per sweep
        (the caller marks them spilled only after the sweep returns).
        Advances :attr:`hand`; returns the victim ids in pick order.
        """
        if n_items <= 0 or need <= 0:
            return np.zeros(0, dtype=np.int64)
        self.spill_sweeps += 1
        chunk = self.config.sweep_chunk
        picked = np.zeros(n_items, dtype=bool)
        victims = []
        freed = 0
        hand = self.hand % n_items
        scanned = 0
        limit = 2 * n_items + chunk  # two full passes: clear refs, take
        while freed < need and scanned < limit:
            ids = np.arange(hand, min(hand + chunk, n_items), dtype=np.int64)
            hand = int(ids[-1] + 1) % n_items
            scanned += ids.size
            cand = candidates(ids) & ~picked[ids]
            refd = cand & ref_get(ids)
            ref_clear(ids[refd])
            pick = cand & ~refd
            if pick.any():
                pids = ids[pick]
                csum = np.cumsum(sizes(pids))
                k = min(int(np.searchsorted(csum, need - freed)) + 1, pids.size)
                picked[pids[:k]] = True
                victims.append(pids[:k])
                freed += int(csum[k - 1])
        self.hand = hand
        if victims:
            return np.concatenate(victims)
        return np.zeros(0, dtype=np.int64)

    def stats(self) -> Dict[str, Any]:
        return {
            "budget_bytes": self.budget,
            "spills": self.spills,
            "spill_sweeps": self.spill_sweeps,
            "faults": self.faults,
            "fault_batches": self.fault_batches,
            "scalar_faults": self.scalar_faults,
            "quarantined": self.quarantined,
            "repaired_rows": self.repaired_rows,
            "disk_live_bytes": self.disk.live_bytes,
            "disk_file_bytes": self.disk.file_bytes,
            "disk_reads": self.disk.reads,
            "disk_writes": self.disk.writes,
            "disk_compactions": self.disk.compactions,
        }
