"""Columnar scan over the compressed CSR code arena (DESIGN.md §8).

``scan_table`` evaluates a predicate conjunction against a
:class:`~repro.core.blitzcrank.CompressedTable` without materializing
non-matching rows:

1. **Zone prune** — numeric predicates test chunked min/max zone maps
   (raw-value bounds widened by the plan's quantization slack) and drop
   whole blocks before any code is touched.
2. **Code-space eval** — per plan version, predicates lower to category-id
   sets and quantized-step intervals.  A single categorical predicate on
   slot 0 evaluates straight off the raw arena through the coder's LUT;
   anything else decodes only the slot *prefix* the predicates name.
   Spilled blocks are read through (CRC-checked) without promotion, so an
   OLAP scan never evicts the OLTP hot set.
3. **Materialize survivors** — matching rows gather into one compact CSR
   and decode with ONE ``decode_select`` per plan version and plan-family
   member, reconstructing only the projected columns.

Slow blocks, non-lowerable versions, and pending rows fall back to
decode-then-filter with the same value-space matchers, so results are
bit-identical to the reference scan by construction.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro import telemetry
from repro.core.arena import ExtentCorruptionError, SpillCorruptionError
from repro.core.plan import (
    decode_select_prefix,
    lower_cat_ids,
    lower_cat_range_ids,
    lower_num_interval,
    num_q_of_syms,
    quantize_slack,
    scan_lowering,
    slot0_match_lut,
)

from .predicates import Eq, In, Predicate, Range, match_all

# Lowering outcomes for one (version, predicate-set) pair.
_FALLBACK = "fallback"  # can't lower every predicate: decode + filter
_IMPOSSIBLE = "impossible"  # no conforming row can match: skip fast blocks


class ScanStats:
    """Observability for one scan (accumulated across shards by callers).

    Backed by the shared telemetry registry (DESIGN.md §9): every field
    write flows its *delta* into the ``repro.scan.<field>`` counter, so
    the registry carries engine-wide scan totals while each instance
    keeps its per-scan view.  :meth:`merge` folds another instance's
    local values in WITHOUT touching the registry — the merged-in scan
    already registered its deltas when they happened, so cross-shard
    aggregation can never double-count globally (the old
    dataclass-``merge`` duplication risk).  The attribute API (reads,
    ``+=``, plain assignment) is unchanged; fields are thin properties.
    """

    _FIELDS = (
        "blocks_total",  # live candidate blocks before pruning
        "blocks_pruned",  # dropped by zone maps alone
        "blocks_lut",  # evaluated via the slot-0 LUT gather
        "rows_prefix_decoded",  # rows through the slot-prefix decode
        "blocks_fallback",  # full decode + value filter (no lowering)
        "blocks_scalar",  # slow blocks: per-block scalar decode
        "spilled_reads",  # cold blocks read through (not promoted)
        "rows_decoded",  # rows fully materialized
        "rows_matched",
        "versions",  # plan versions seen among fast blocks
    )
    __slots__ = ("_v",)

    def __init__(self, **fields: int) -> None:
        object.__setattr__(self, "_v", dict.fromkeys(self._FIELDS, 0))
        for name, value in fields.items():
            setattr(self, name, value)  # through the property: registers

    def merge(self, other: "ScanStats") -> None:
        """Fold ``other``'s local values in; registry-neutral (see class
        docstring)."""
        v, ov = self._v, other._v
        for f in self._FIELDS:
            v[f] += ov[f]

    def __repr__(self) -> str:  # dataclass-style, for test/debug output
        body = ", ".join(f"{f}={self._v[f]}" for f in self._FIELDS)
        return f"ScanStats({body})"

    def __eq__(self, other: object) -> bool:
        return isinstance(other, ScanStats) and self._v == other._v


def _scan_stat_property(name: str) -> property:
    # blitzlint: waive[BL002] -- repro.scan.<field> names are enumerated in the catalog and pinned by test_blitzlint
    counter = telemetry.counter(f"repro.scan.{name}")

    def _get(self: ScanStats) -> int:
        return self._v[name]

    def _set(self: ScanStats, value: int) -> None:
        delta = value - self._v[name]
        self._v[name] = value
        if delta:
            counter.add(delta)

    return property(_get, _set)


for _f in ScanStats._FIELDS:
    setattr(ScanStats, _f, _scan_stat_property(_f))
del _f


@dataclasses.dataclass
class ScanResult:
    ids: List[int]  # logical row ids, ascending
    rows: List[Dict[str, Any]]  # projected rows, parallel to ids
    stats: ScanStats


def _zone_bounds(
    pred: Predicate,
) -> Optional[Tuple[Optional[float], Optional[float]]]:
    """Value-space interval implied by ``pred``, or None (not prunable)."""
    try:
        if isinstance(pred, Eq):
            v = float(pred.value)
            return (v, v)
        if isinstance(pred, In):
            if not pred.values:
                return None
            vs = [float(v) for v in pred.values]
            return (min(vs), max(vs))
        if isinstance(pred, Range):
            lo = None if pred.lo is None else float(pred.lo)
            hi = None if pred.hi is None else float(pred.hi)
            if lo is None and hi is None:
                return None
            return (lo, hi)
    except (TypeError, ValueError):
        return None
    return None


def _column_slack(table, column: str) -> Optional[float]:
    """Worst-case |decoded - raw| for ``column`` across every plan version
    the table has ever encoded under; None disables pruning (a model with
    unbounded reconstruction error, or an unknown column)."""
    worst = 0.0
    for codec in table._codecs:
        m = codec.models.get(column)
        if m is None:
            return None
        s = quantize_slack(m)
        if s is None:
            return None
        worst = max(worst, s)
    return worst


def _lower_preds(plan: Any, preds: Sequence[Predicate]) -> Any:
    """Lower the conjunction into code-space forms for one plan version.

    Returns a list of lowered predicate tuples, ``_FALLBACK`` when any
    predicate has no code-space form under this plan, or ``_IMPOSSIBLE``
    when a lowered predicate provably matches no conforming (fast) row.
    """
    lowered = []
    for p in preds:
        ent = scan_lowering(plan, p.column)
        if ent is None:
            return _FALLBACK
        kind, cp, off = ent
        if kind == "cat":
            if isinstance(p, Eq):
                ids = lower_cat_ids(cp, [p.value])
            elif isinstance(p, In):
                ids = lower_cat_ids(cp, p.values)
            else:  # Range over a categorical vocabulary (small-int columns)
                ids = lower_cat_range_ids(cp, p.lo, p.hi)
                if ids is None:
                    return _FALLBACK
            if not ids.size:
                return _IMPOSSIBLE
            lowered.append(("cat", cp, off, ids))
        else:  # numeric two-level model: value intervals -> step intervals
            m = cp.m
            if isinstance(p, Range):
                try:
                    lo = None if p.lo is None else float(p.lo)
                    hi = None if p.hi is None else float(p.hi)
                except (TypeError, ValueError):
                    return _FALLBACK
                iv = lower_num_interval(m, lo, hi)
                if iv is None:
                    return _IMPOSSIBLE
                lowered.append(("numrange", cp, off, iv[0], iv[1]))
            else:
                values = [p.value] if isinstance(p, Eq) else list(p.values)
                qs: set = set()
                # blitzlint: waive[BL001] -- loops over predicate literals (a handful of constants), not table rows
                for v in values:
                    try:
                        fv = float(v)
                    except (TypeError, ValueError):
                        continue  # non-numeric literal can't match fast rows
                    iv = lower_num_interval(m, fv, fv)
                    if iv is not None:
                        qs.update(range(iv[0], iv[1] + 1))
                if not qs:
                    return _IMPOSSIBLE
                lowered.append(
                    ("numset", cp, off, np.asarray(sorted(qs), dtype=np.int64))
                )
    return lowered


def _read_spilled(table, blocks: np.ndarray, cache: Dict[int, np.ndarray]) -> None:
    """CRC-checked read-through of spilled ``blocks`` into ``cache``
    (block id -> uint16 codes) WITHOUT promoting them: the scan must not
    evict the transactional hot set or perturb the clock."""
    need = [int(b) for b in blocks if int(b) not in cache]
    if not need:
        return
    res = table._res
    offs = table._disk_off[need]
    lens = table._disk_len[need]
    try:
        payloads = res.disk.read_many_checked(offs, 2 * lens)
    except ExtentCorruptionError as e:
        bad = np.asarray(need, dtype=np.int64)[np.asarray(e.indices, dtype=np.int64)]
        table.note_quarantined_rows(len(e.indices))
        raise SpillCorruptionError(table._block2row[bad].tolist()) from e
    for b, p in zip(need, payloads):
        cache[b] = np.frombuffer(p, dtype=np.uint16)


def _compact_csr(
    table, blocks: np.ndarray, cache: Dict[int, np.ndarray]
) -> Tuple[np.ndarray, np.ndarray]:
    """Gather the code runs of ``blocks`` (resident from the arena, spilled
    from ``cache``) into one compact CSR ``(codes, offsets)``."""
    if table._res is not None:
        resident = table._resident[blocks]
    else:
        resident = np.ones(blocks.size, dtype=bool)
    lens = np.where(
        resident,
        table._offsets[blocks + 1] - table._offsets[blocks],
        (table._disk_len[blocks] if table._res is not None else 0),
    )
    offsets = np.zeros(blocks.size + 1, dtype=np.int64)
    np.cumsum(lens, out=offsets[1:])
    codes = np.empty(int(offsets[-1]), dtype=np.uint16)
    # Bulk-gather the resident runs with one fancy index; spilled runs
    # copy from the read-through cache.
    rb = blocks[resident]
    if rb.size:
        starts = table._offsets[rb]
        rlens = lens[resident]
        dst = offsets[:-1][resident]
        total = int(rlens.sum())
        within = np.arange(total) - np.repeat(np.cumsum(rlens) - rlens, rlens)
        codes[np.repeat(dst, rlens) + within] = table.arena[
            np.repeat(starts, rlens) + within
        ]
    for j in np.nonzero(~resident)[0]:
        b = int(blocks[j])
        codes[offsets[j] : offsets[j + 1]] = cache[b]
    return codes, offsets


def _eval_lowered(
    table,
    plan,
    lowered,
    blocks: np.ndarray,
    cache: Dict[int, np.ndarray],
    stats: ScanStats,
) -> np.ndarray:
    """bool mask over ``blocks``: does the (single-tuple) block's row match
    every lowered predicate?  Evaluates on raw codes (slot-0 LUT) or a
    decoded slot prefix — never materializes a row."""
    if not lowered:
        return np.ones(blocks.size, dtype=bool)
    if table._res is not None:
        resident = table._resident[blocks]
    else:
        resident = np.ones(blocks.size, dtype=bool)
    spilled = blocks[~resident]
    if spilled.size:
        _read_spilled(table, spilled, cache)
        stats.spilled_reads += int(spilled.size)

    # Fast path: one categorical predicate on the first physical slot
    # compares raw stream codes through the coder's LUT — zero decode.
    if len(lowered) == 1 and lowered[0][0] == "cat" and lowered[0][2] == 0:
        lut = slot0_match_lut(plan.coders[0], lowered[0][3])
        if lut is not None:
            mask = np.zeros(blocks.size, dtype=bool)
            rb = blocks[resident]
            if rb.size:
                mask[resident] = lut[table.arena[table._offsets[rb]]]
            for j in np.nonzero(~resident)[0]:
                mask[j] = lut[cache[int(blocks[j])][0]]
            stats.blocks_lut += int(blocks.size)
            return mask

    # General path: decode just the slot prefix the predicates reach.
    upto = max(off + cp.n_slots for _, cp, off, *_ in lowered)
    syms = np.empty((blocks.size, upto), dtype=np.int64)
    rb = blocks[resident]
    if rb.size:
        syms[resident] = decode_select_prefix(
            plan, table.arena[: table.used], table.block_offsets, rb, upto
        )
    if spilled.size:
        codes, offsets = _compact_csr(table, spilled, cache)
        syms[~resident] = decode_select_prefix(
            plan, codes, offsets, np.arange(spilled.size), upto
        )
    stats.rows_prefix_decoded += int(blocks.size)
    mask = np.ones(blocks.size, dtype=bool)
    for ent in lowered:
        if ent[0] == "cat":
            _, cp, off, ids = ent
            mask &= np.isin(syms[:, off], ids)
        elif ent[0] == "numrange":
            _, cp, off, qlo, qhi = ent
            q = num_q_of_syms(cp, syms[:, off:])
            mask &= (q >= qlo) & (q <= qhi)
        else:  # numset
            _, cp, off, qs = ent
            q = num_q_of_syms(cp, syms[:, off:])
            mask &= np.isin(q, qs)
    return mask


def scan_table(
    table,
    predicates: Sequence[Predicate],
    columns: Optional[Sequence[str]] = None,
    backend: Optional[str] = None,
) -> ScanResult:
    """Predicate-pushdown scan of one :class:`CompressedTable`.

    Returns matching ``(logical row id, projected row)`` pairs in
    ascending id order — bit-identical to decoding every live row and
    filtering in value space.  Read-only: never flushes pending rows,
    faults in cold blocks, or advances the clock.
    """
    with telemetry.span("repro.scan.scan_table"):
        return _scan_table(table, predicates, columns, backend)


def _scan_table(
    table,
    predicates: Sequence[Predicate],
    columns: Optional[Sequence[str]],
    backend: Optional[str],
) -> ScanResult:
    table.sanitize_boundary("scan_table")
    preds = list(predicates)
    stats = ScanStats()
    order = list(table.codec.order)
    known = set(order)
    for p in preds:
        if p.column not in known:
            raise KeyError(f"unknown predicate column: {p.column!r}")
    proj = order if columns is None else list(columns)
    unknown = set(proj) - known
    if unknown:
        raise KeyError(f"unknown columns: {sorted(unknown)}")
    pred_cols = [p.column for p in preds]
    hits: List[Tuple[int, Dict[str, Any]]] = []

    def _value_filtered(rid: int, row: Dict[str, Any]) -> None:
        if match_all(preds, row):
            hits.append((rid, {c: row[c] for c in proj}))

    if table.codec.block_tuples != 1:
        # Multi-tuple blocks: no indirection layer, decode-and-filter.
        rid = 0
        for b in range(table.n_blocks):
            rows = table.get_block(b)
            stats.blocks_scalar += 1
            stats.rows_decoded += len(rows)
            # blitzlint: waive[BL001] -- overlay rows are per-key Python dicts (delta layer contract)
            for r in rows:
                _value_filtered(rid, r)
                rid += 1
        # blitzlint: waive[BL001] -- pending tail rows are uncompressed dicts awaiting the next block flush
        for i, r in enumerate(table._pending):
            _value_filtered(table._rows_stored + i, r)
        stats.rows_matched = len(hits)
        return ScanResult([h[0] for h in hits], [h[1] for h in hits], stats)

    nrows = table._rows_stored
    live = np.nonzero(table._row2block[:nrows] >= 0)[0]
    blks = table._row2block[live]
    stats.blocks_total = int(live.size)

    # -- phase 1: zone-map pruning (value space, version independent) ----
    if live.size:
        keep = np.ones(live.size, dtype=bool)
        for p in preds:
            bounds = _zone_bounds(p)
            if bounds is None:
                continue
            slack = _column_slack(table, p.column)
            if slack is None:
                continue
            m = table.zone_block_mask(p.column, bounds[0], bounds[1], slack=slack)
            if m is not None:
                keep &= m[blks]
        stats.blocks_pruned = int(live.size - np.count_nonzero(keep))
        live, blks = live[keep], blks[keep]

    # -- phase 2+3: per-version code-space eval, then one decode each ----
    cache: Dict[int, np.ndarray] = {}
    if live.size:
        fastm = table._fast[blks]
        # one group per (plan version, plan-family member) of fast blocks
        route = table._plan_ver[blks].astype(np.int64) << 8 | table._tmpl[blks]
        scalar = ~fastm  # slow blocks always decode under their own codec
        versions = set()
        for g in np.unique(route[fastm]).tolist():
            sel = fastm & (route == g)
            ids_v, blks_v = live[sel], blks[sel]
            codec_v = table._codecs[g >> 8]
            plan = codec_v.compile()
            if plan is not None:
                plan = plan.family[g & 0xFF]
            lowered = _lower_preds(plan, preds) if plan is not None else _FALLBACK
            if lowered is _IMPOSSIBLE:
                continue  # fast => conforming => provably no match
            if g >> 8 not in versions:
                versions.add(g >> 8)
                stats.versions += 1
            if lowered is _FALLBACK:
                survivors = np.arange(ids_v.size)
                stats.blocks_fallback += int(ids_v.size)
                need_cols = [c for c in order if c in set(proj) | set(pred_cols)]
            else:
                mask = _eval_lowered(table, plan, lowered, blks_v, cache, stats)
                survivors = np.nonzero(mask)[0]
                need_cols = proj
            if not survivors.size:
                continue
            if plan is None:  # uncompiled version: scalar decode per block
                for j in survivors.tolist():
                    stats.blocks_scalar += 1
                    stats.rows_decoded += 1
                    _value_filtered(int(ids_v[j]), table.get_block(int(blks_v[j]))[0])
                continue
            sblks = blks_v[survivors]
            if table._res is not None:
                sp = sblks[~table._resident[sblks]]
                if sp.size:
                    pre = len(cache)
                    _read_spilled(table, sp, cache)
                    stats.spilled_reads += len(cache) - pre
            codes, offsets = _compact_csr(table, sblks, cache)
            syms = plan.decode_select(
                codes,
                offsets,
                np.arange(sblks.size),
                backend=table._resolve_backend(backend, sblks.size, codec_v),
            )
            rows = plan.decode_syms_to_rows(syms, columns=need_cols)
            stats.rows_decoded += len(rows)
            if lowered is _FALLBACK:
                # blitzlint: waive[BL001] -- residual value filter evaluates on decoded row dicts (no code-space form)
                for rid, row in zip(ids_v[survivors].tolist(), rows):
                    _value_filtered(rid, row)
            else:
                # blitzlint: waive[BL001] -- residual value filter evaluates on decoded row dicts (no code-space form)
                for rid, row in zip(ids_v[survivors].tolist(), rows):
                    hits.append((rid, {c: row[c] for c in proj}))
        for j in np.nonzero(scalar)[0].tolist():
            stats.blocks_scalar += 1
            stats.rows_decoded += 1
            _value_filtered(int(live[j]), table.get_block(int(blks[j]))[0])

    # blitzlint: waive[BL001] -- pending tail is a per-row dict list by design; scans must see it
    for i, r in enumerate(table._pending):
        # Pending rows are value-filtered in place: the read path must not
        # flush (scan is concurrent with the transaction mix).
        _value_filtered(nrows + i, r)

    hits.sort(key=lambda h: h[0])
    stats.rows_matched = len(hits)
    return ScanResult([h[0] for h in hits], [h[1] for h in hits], stats)
