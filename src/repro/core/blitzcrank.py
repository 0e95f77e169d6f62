"""The Blitzcrank facade (§3): Semantic Learner + Attribute Encoder + Tuple
Encoder wired together for relational rows.

``TableCodec.fit`` is the Semantic Learner: (1) structure-learn a column
ordering + conditional models on a random sample, (2) scan the full data to
fit accurate per-column semantic models.  ``compress_block`` /
``decompress_block`` are the Attribute Encoder (value <-> intervals) feeding
the Tuple Encoder (delayed coding).  ``CompressedTable`` is the in-memory
store with per-block random access (default granularity: 1 tuple, §6.4).
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from typing import TYPE_CHECKING, Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro import device, sanitize, telemetry

if TYPE_CHECKING:
    from .plan import TablePlan

from . import delayed
from .casts import checked_asarray, checked_astype
from .arena import (
    FRAME_OVERHEAD,
    ArenaReadError,
    ExtentCorruptionError,
    ResidencyConfig,
    ResidencyManager,
    SpillCorruptionError,
    framed_len,
    read_extents,
)
from .delayed import BlockDecoder
from .mirror import ArenaMirror, MirroredCodes
from .models import (
    BlockEncoder,
    CategoricalModel,
    ConditionalCategoricalModel,
    NumericModel,
    StringModel,
    TimeSeriesModel,
)
from .structure import discretize_column, learn_order

# Telemetry handles (DESIGN.md §9).  The scalar encode/decode and
# spill/fault-in spans are leaf phases of the wall-time breakdown;
# plan-cache hit/miss and maintenance verbs are counters the gap hunt reads.
_C_PLAN_HIT = telemetry.counter("repro.plan.cache.hit")
_C_PLAN_MISS = telemetry.counter("repro.plan.cache.miss")
_C_SPILL_BLOCKS = telemetry.counter("repro.residency.spill.blocks")
_C_FAULT_BLOCKS = telemetry.counter("repro.residency.fault_in.blocks")
_C_SPILL_BYTES = telemetry.counter("repro.residency.spill.bytes")
_C_FAULT_BYTES = telemetry.counter("repro.residency.fault_in.bytes")
_C_MIGRATED = telemetry.counter("repro.store.migrate.rows")
_C_PALLAS_DOWNGRADE = telemetry.counter("repro.plan.pallas_downgrade")
_C_SCALAR_ROWS = telemetry.counter("repro.core.decode.scalar_rows")


@dataclasses.dataclass
class ColumnSpec:
    name: str
    kind: str                    # 'cat' | 'int' | 'float' | 'str' | 'ts'
    precision: float = 1.0       # for 'float' (absolute precision p, §4.2)
    buckets: int = 512           # level-1 bucket budget T
    # Headroom for append-mostly columns (order ids, ytd counters,
    # balances): fraction of the observed value span added to each end of
    # the fitted numeric range, so values that grow past the load-time
    # population keep conforming instead of escaping on every insert.
    # growth > 0 also pins an 'int' column to the numeric (range) model —
    # a growing key must never specialize to a closed categorical vocab.
    growth: float = 0.0


def column_specs(schema: Any) -> List[ColumnSpec]:
    """Normalize a schema argument to a list of :class:`ColumnSpec`.

    Accepts either a plain sequence of specs or a schema object exposing
    ``.columns`` (e.g. :class:`repro.db.TableSchema`), so the codec and
    every :class:`~repro.oltp.store.RowStore` take both interchangeably —
    the `db` engine layer hands its declarative schemas straight down.
    """
    cols = getattr(schema, "columns", schema)
    cols = list(cols)
    for c in cols:
        if not isinstance(c, ColumnSpec):
            raise TypeError(f"expected ColumnSpec, got {type(c).__name__}")
    return cols


@dataclasses.dataclass
class FitStats:
    structuring_s: float = 0.0
    generation_s: float = 0.0
    sample_rows: int = 0
    order: Tuple[str, ...] = ()
    parents: Dict[str, Optional[str]] = dataclasses.field(default_factory=dict)


def fit_column_model(
    spec: ColumnSpec,
    rows: Sequence[Dict[str, Any]],
    parent: Optional[str] = None,
    block_tuples: int = 1,
    extra_values: Optional[Sequence[Any]] = None,
    extra_pairs: Optional[Sequence[Tuple[Any, Any]]] = None,
) -> Any:
    """Fit one column's semantic model (Semantic Learner step 2, per column).

    Shared by :meth:`TableCodec.fit` and the adaptive per-column refitter
    (``repro.adaptive.refit``): both must produce models under identical
    rules or a refit would silently change plan-ability.  ``extra_values``
    augments the training column (each value once) — the refitter passes the
    outgoing model's vocabulary / range endpoints there so every value the
    old model encoded stays conforming under the new one.  For conditional
    columns ``extra_pairs`` additionally preserves the per-parent child
    vocabularies (the encode-side conformance check is per parent group,
    so marginal coverage alone is not enough).
    """
    col = [r[spec.name] for r in rows]
    if extra_values:
        col = col + list(extra_values)
    if spec.growth > 0.0 and spec.kind in ("int", "float", "ts") and col:
        # Synthetic range endpoints widen the fitted range by
        # ``growth * max(span, magnitude)`` on each side: two extra values
        # cost two near-empty buckets, not a distribution shift.  Basing
        # the pad on magnitude too keeps constant columns (a ytd counter
        # loaded at one value) from getting a degenerate zero-width pad.
        lo, hi = float(min(col)), float(max(col))
        unit = spec.precision if spec.kind != "int" else 1.0
        pad = spec.growth * max(hi - lo, abs(hi), abs(lo), unit)
        if spec.kind == "int":
            col = col + [int(lo - pad) - 1, int(hi + pad) + 1]
        else:
            col = col + [lo - pad, hi + pad]
    # growth>0 numeric columns never specialize to a conditional (closed)
    # vocabulary either — same reasoning as the categorical pin below
    if parent is not None and (spec.kind in ("cat", "str")
                               or (spec.kind == "int"
                                   and spec.growth <= 0.0)):
        pairs = [(r[parent], r[spec.name]) for r in rows]
        if extra_pairs:
            pairs = pairs + list(extra_pairs)
        if extra_values:
            # A fresh sentinel parent keeps the extras out of every real
            # conditional group while still feeding the marginal fallback.
            sentinel = object()
            pairs = pairs + [(sentinel, v) for v in extra_values]
        return ConditionalCategoricalModel(pairs, parent)
    if spec.kind == "cat":
        return CategoricalModel(col)
    if spec.kind == "int":
        # small-cardinality ints behave better as categorical — unless the
        # schema declares growth: a growing key needs an open-ended range
        card = len(set(col[:4096]))
        if spec.growth <= 0.0 and card <= 256 and len(set(col)) <= 4096:
            return CategoricalModel(col)
        return NumericModel(col, precision=1, T=spec.buckets, integer=True)
    if spec.kind == "float":
        return NumericModel(col, precision=spec.precision, T=spec.buckets)
    if spec.kind == "ts":
        return TimeSeriesModel(col, precision=spec.precision, T=spec.buckets)
    if spec.kind == "str":
        return StringModel(col, block_tuples=block_tuples)
    raise ValueError(f"unknown column kind {spec.kind}")


class TableCodec:
    """Compresses/decompresses rows (dicts or tuples in schema order)."""

    def __init__(
        self,
        schema: Sequence[ColumnSpec],
        models: Dict[str, Any],
        order: List[str],
        stats: FitStats,
        block_tuples: int = 1,
        lam: int = delayed.LAMBDA_DEFAULT,
    ):
        self.schema = column_specs(schema)
        self.by_name = {c.name: c for c in self.schema}
        self.models = models
        self.order = order
        self.stats = stats
        self.block_tuples = block_tuples
        self.lam = lam
        self._plan = None
        self._plan_reason: Optional[str] = None
        self._plan_tried = False

    # ------------------------------------------------------------------
    @classmethod
    def fit(
        cls,
        rows: Sequence[Dict[str, Any]],
        schema: Sequence[ColumnSpec],
        correlation: bool = False,
        sample: int = 1 << 15,
        block_tuples: int = 1,
        seed: int = 0,
        lam: int = delayed.LAMBDA_DEFAULT,
    ) -> "TableCodec":
        schema = column_specs(schema)
        rng = np.random.default_rng(seed)
        n = len(rows)
        stats = FitStats()
        idx = rng.choice(n, size=min(sample, n), replace=False)
        sample_rows = [rows[i] for i in idx]
        stats.sample_rows = len(sample_rows)

        # ---- Semantic Learner step 1: structure learning on the sample ----
        # blitzlint: waive[BL007] -- fit wall time is FitStats data returned to the caller, not a telemetry series
        t0 = time.perf_counter()
        order = [c.name for c in schema]
        parents: Dict[str, Optional[str]] = {c.name: None for c in schema}
        if correlation:
            disc: Dict[str, List] = {}
            for c in schema:
                col = [r[c.name] for r in sample_rows]
                d = discretize_column(col, c.kind)
                if d is not None and c.kind in ("cat", "int", "str"):
                    disc[c.name] = d
            if disc:
                sub_order, sub_parents = learn_order(disc, len(sample_rows))
                rest = [c.name for c in schema if c.name not in disc]
                order = sub_order + rest
                parents.update(sub_parents)
        # blitzlint: waive[BL007] -- fit wall time is FitStats data returned to the caller, not a telemetry series
        stats.structuring_s = time.perf_counter() - t0
        stats.order = tuple(order)
        stats.parents = dict(parents)

        # ---- Semantic Learner step 2: model generation on the full scan ----
        # blitzlint: waive[BL007] -- fit wall time is FitStats data returned to the caller, not a telemetry series
        t0 = time.perf_counter()
        models: Dict[str, Any] = {}
        for c in schema:
            models[c.name] = fit_column_model(
                c, rows, parents.get(c.name), block_tuples
            )
        # blitzlint: waive[BL007] -- fit wall time is FitStats data returned to the caller, not a telemetry series
        stats.generation_s = time.perf_counter() - t0
        return cls(schema, models, order, stats, block_tuples, lam)

    # ------------------------------------------------------------------
    # Compiled fast path (DESIGN.md §2): lower the fitted models to a
    # static slot plan once, then batch-encode/decode through the
    # vectorized codec (and the Pallas kernel for plain-table plans).
    # ------------------------------------------------------------------
    def compile(self, force: bool = False) -> Optional["TablePlan"]:
        """Return the compiled :class:`~repro.core.plan.TablePlan` or None.

        Compilation is attempted once and cached; on fallback the reason is
        recorded in :attr:`plan_fallback_reason`.
        """
        if not self._plan_tried or force:
            self._plan_tried = True
            _C_PLAN_MISS.inc()
            with telemetry.span("repro.plan.compile"):
                from .plan import PlanFallback, compile_plan
                try:
                    self._plan = compile_plan(self)
                    self._plan_reason = None
                except PlanFallback as e:
                    self._plan = None
                    self._plan_reason = str(e)
        else:
            _C_PLAN_HIT.inc()
        return self._plan

    @property
    def plan_fallback_reason(self) -> Optional[str]:
        self.compile()
        return self._plan_reason

    # -- pickling (durability checkpoints, DESIGN.md §7) ----------------
    def __getstate__(self) -> Dict[str, Any]:
        """Drop the compiled plan: it holds prebuilt decode tables that are
        pure functions of the models, so a restored codec recompiles to an
        identical plan (escape counters are snapshotted separately by
        :meth:`CompressedTable.snapshot_state`)."""
        state = dict(self.__dict__)
        state["_plan"] = None
        state["_plan_reason"] = None
        state["_plan_tried"] = False
        return state

    def __setstate__(self, state: Dict[str, Any]) -> None:
        self.__dict__.update(state)

    # ------------------------------------------------------------------
    def _reset_block_state(self) -> None:
        for m in self.models.values():
            if hasattr(m, "reset_block"):
                m.reset_block()

    def _scalar_compress(self, rows: Sequence[Dict[str, Any]]) -> np.ndarray:
        with telemetry.span("repro.core.encode.scalar_block"):
            self._reset_block_state()
            enc = BlockEncoder()
            # blitzlint: waive[BL001] -- scalar encode chains each model on the previous column value (sequential by design)
            for r in rows:
                ctx: Dict[str, Any] = {}
                for name in self.order:
                    self.models[name].encode_value(r[name], enc, ctx)
                    ctx[name] = r[name]
            codes = delayed.encode_block(enc.slots, self.lam)
        return checked_asarray(codes, np.uint16, where="scalar_compress codes")

    def compress_block(self, rows: Sequence[Dict[str, Any]]) -> np.ndarray:
        """Compress a block of rows into a uint16 code array.

        The compiled plan emits bit-identical codes for conforming
        single-tuple blocks (verified in tests), so the scalar path is used
        here unconditionally — for one row its Python loop beats the fixed
        overhead of a 1-row numpy batch.  Bulk compression goes through
        :meth:`compress_rows`, which amortizes ``encode_batch`` over N rows.
        """
        return self._scalar_compress(rows)

    def compress_rows(
        self, rows: Sequence[Dict[str, Any]]
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Batch-compress rows at single-tuple granularity.

        Returns ``(codes uint16, offsets int64[N+1], template int8[N])`` — a
        CSR arena where row ``r`` owns ``codes[offsets[r]:offsets[r+1]]``,
        and the plan-family member that encoded each row (-1: it escaped).
        The rows each member takes go through one vectorized
        ``encode_batch`` call; the rest are scalar-encoded one block each
        (identical stream format).  Requires ``block_tuples == 1``.
        """
        if self.block_tuples != 1:
            raise ValueError("compress_rows requires block_tuples == 1")
        n = len(rows)
        tmpl = np.full(n, -1, np.int8)
        if n == 0:
            return np.zeros(0, np.uint16), np.zeros(1, np.int64), tmpl
        plan = self.compile()
        # (row positions, codes, offsets) of each group of rows
        groups: List[Tuple[np.ndarray, np.ndarray, np.ndarray]] = []
        if plan is not None:
            t_rows, parts = plan.encode_family(rows)
            tmpl = t_rows.astype(np.int8)
            for t, syms in sorted(parts.items()):
                fcodes, foff = plan.family[t].encode_batch(syms)
                if syms.shape[0] == n:
                    # One member took every row: its batch CSR is already
                    # the arena layout — skip the interleave entirely.
                    codes = checked_astype(
                        fcodes, np.uint16, where="compress_rows codes"
                    )
                    return codes, np.asarray(foff, np.int64), tmpl
                groups.append((np.nonzero(tmpl == t)[0], fcodes,
                               np.asarray(foff, np.int64)))
        esc = np.nonzero(tmpl < 0)[0]
        if esc.size:
            # the escape path: rows no plan member takes, one block each
            chunks = [self._scalar_compress([rows[i]]) for i in esc.tolist()]
            eoff = np.zeros(esc.size + 1, np.int64)
            np.cumsum([c.size for c in chunks], out=eoff[1:])
            groups.append((esc, np.concatenate(chunks), eoff))
        lens = np.zeros(n, np.int64)
        for pos, _, off in groups:
            lens[pos] = np.diff(off)
        offsets = np.zeros(n + 1, np.int64)
        np.cumsum(lens, out=offsets[1:])
        codes = np.empty(int(offsets[-1]), np.uint16)
        for pos, c, off in groups:
            # element k of a group's run j lands at offsets[pos[j]] + k - off[j]
            codes[np.repeat(offsets[pos] - off[:-1], np.diff(off))
                  + np.arange(off[-1])] = checked_astype(
                c, np.uint16, where="compress_rows codes")
        return codes, offsets, tmpl

    def decompress_rows(
        self,
        codes: np.ndarray,
        offsets: np.ndarray,
        indices: Sequence[int],
        backend: str = "numpy",
        template: int = 0,
        device_codes: Optional[Callable[[], Any]] = None,
    ) -> List[Dict[str, Any]]:
        """Batch random-access decode from a CSR arena (compiled codecs only).

        Every indexed row must have been encoded by plan-family member
        ``template`` (its codes follow that member's fixed slot layout).
        ``backend`` is ``"numpy"`` or ``"pallas"`` (compiled on a TPU,
        interpret mode on CPU, verified against numpy); ``device_codes``
        hands the kernel path the same codes already in HBM
        (:meth:`TablePlan.decode_select`).
        """
        plan = self.compile()
        if plan is None:
            raise RuntimeError(f"codec did not compile: {self._plan_reason}")
        plan = plan.family[template]
        codes = checked_asarray(codes, np.uint16, where="decompress_rows codes")
        if device_codes is not None:
            codes = MirroredCodes(codes, device_codes)
        syms = plan.decode_select(
            codes,
            np.asarray(offsets, np.int64),
            np.asarray(indices, np.int64),
            backend=backend,
        )
        return plan.decode_syms_to_rows(syms)

    def decompress_block(self, codes: np.ndarray, n_rows: int) -> List[Dict[str, Any]]:
        with telemetry.span("repro.core.decode.scalar_block"):
            _C_SCALAR_ROWS.add(n_rows)
            self._reset_block_state()
            dec = BlockDecoder(
                codes.tolist() if isinstance(codes, np.ndarray) else codes, self.lam
            )
            out = []
            for _ in range(n_rows):
                ctx: Dict[str, Any] = {}
                for name in self.order:
                    ctx[name] = self.models[name].decode_value(dec, ctx)
                out.append(ctx)
        return out

    # ------------------------------------------------------------------
    def model_bytes(self) -> int:
        return sum(m.model_bytes() for m in self.models.values())

    def est_row_bits(self, row: Dict[str, Any]) -> float:
        return sum(self.models[n].est_bits(row[n]) for n in self.order
                   if hasattr(self.models[n], "est_bits"))


def _read_spill_extents(
    path: str, extents: Dict[int, Tuple[int, int]], block2row: np.ndarray
) -> Dict[int, bytes]:
    """Read extent-referenced spill payloads for an extent-mode checkpoint
    (see :meth:`CompressedTable.snapshot_state`).  Must run *before* any
    :class:`ResidencyManager` re-opens (and truncates) the spill path.
    CRC or length mismatches surface as :class:`SpillCorruptionError`
    carrying the affected row ids for WAL-backed repair."""
    blocks = sorted(int(b) for b in extents)
    offs = [extents[b][0] for b in blocks]
    lens = [2 * extents[b][1] for b in blocks]
    payloads = read_extents(path, offs, lens)
    bad = [b for b, p in zip(blocks, payloads) if p is None]
    if bad:
        b2r = np.asarray(block2row, dtype=np.int64)
        raise SpillCorruptionError([int(b2r[b]) for b in bad])
    return {b: p for b, p in zip(blocks, payloads)}


def _raw_row_bytes(row: Dict[str, Any]) -> int:
    """Silo-style uncompressed footprint of one row (for honest accounting)."""
    total = 0
    for v in row.values():
        if isinstance(v, str):
            total += len(v.encode()) + 1
        elif isinstance(v, bytes):
            total += len(v) + 1
        else:
            total += 8
    return total


class CompressedTable:
    """In-memory compressed row store with per-block random access (§6.1).

    Tuples are grouped into blocks of ``codec.block_tuples`` (default 1);
    blocks live in one growing uint16 code arena addressed by a CSR offset
    array ``(codes uint16[], offsets int64[n_blocks+1])`` — the storage
    layout Blitzcrank sits above in Silo, and exactly the layout the batched
    decoder (``vectorized`` / Pallas ``delayed_decode``) consumes.

    When the codec compiled (``codec.compile()``), blocks whose rows conform
    to the slot plan are flagged *fast*; :meth:`get_many` decodes fast rows
    with one ``decode_select`` call (no per-tuple Python loop) and falls back
    to scalar block decode for the rest.  ``use_pallas`` selects the kernel
    backend for large fast batches: ``None`` auto-detects (kernel only on a
    TPU), ``True`` forces it (interpret mode on CPU), ``False`` disables it.
    """

    PALLAS_MIN_ROWS = 4096  # auto mode: below this, numpy always wins
    ZONE_CHUNK = 256        # physical blocks per zone-map extent

    def __init__(
        self,
        codec: TableCodec,
        capacity_hint: int = 1 << 16,
        use_pallas: Optional[bool] = None,
        memory_budget: Optional[int] = None,
        spill_path: Optional[str] = None,
        residency: Optional[ResidencyConfig] = None,
        spill_io: Optional[Any] = None,
    ):
        # Versioned codecs (DESIGN.md §4): writes always encode under the
        # newest codec; every block carries the version it was encoded with
        # so older blocks stay readable after a refit installs a new codec.
        self._codecs: List[TableCodec] = [codec]
        self._plan_ver = np.zeros(1023, dtype=np.uint16)
        # Per-block plan-family member of a fast block (DESIGN.md §2.3):
        # which template's slot layout its codes follow.
        self._tmpl = np.zeros(1023, dtype=np.uint8)
        self.use_pallas = use_pallas
        self.arena = np.zeros(capacity_hint, dtype=np.uint16)
        self.used = 0
        # HBM mirror of arena[:used] for the kernel decode (DESIGN.md §2.8),
        # tied to the arena's generation: a rewrite or restore that
        # replaces the arena bumps it, and the next decode re-uploads.
        self._mirror = ArenaMirror()
        self._arena_gen = 0
        self.n_blocks = 0
        self._offsets = np.zeros(1024, dtype=np.int64)
        self._fast = np.zeros(1023, dtype=bool)
        self.block_rows: List[int] = []
        self._rows_stored = 0
        self._pending: List[Dict[str, Any]] = []
        # Mutation support (DESIGN.md §3), single-tuple granularity only:
        # logical row id -> physical block, -1 = tombstone.  Replaced and
        # deleted runs stay in the arena as dead bytes until rewrite().
        self._row2block = np.full(1024, -1, dtype=np.int64)
        self._dead_codes = 0
        self._n_deleted = 0
        self.rewrites = 0
        self.migrated_rows = 0
        # Out-of-core cold tier (DESIGN.md §6): when a memory budget is
        # set, cold blocks spill their code runs to a DiskArena and fault
        # back in on access.  The per-block arrays below only exist while
        # a ResidencyManager is installed.
        # Zone maps (DESIGN.md §8): raw-value min/max per *chunk* of
        # ZONE_CHUNK consecutive physical blocks, over the numeric schema
        # columns.  The scan engine prunes chunks whose bounds exclude a
        # range predicate before any decode or disk read.  Bounds are
        # conservative supersets: they only widen between rewrites (a
        # rewrite renumbers blocks and rebuilds them as chunk unions), so
        # pruning is always safe; NaN poisons a chunk (never pruned).
        self._zone_cols: List[str] = [c.name for c in codec.schema
                                      if c.kind in ("int", "float", "ts")]
        self._zcol_idx = {c: j for j, c in enumerate(self._zone_cols)}
        self._zmin = np.full((0, len(self._zone_cols)), np.inf)
        self._zmax = np.full((0, len(self._zone_cols)), -np.inf)
        self._res: Optional[ResidencyManager] = None
        self._resident: Optional[np.ndarray] = None   # bool[cap]
        self._disk_off: Optional[np.ndarray] = None   # int64[cap], bytes
        self._disk_len: Optional[np.ndarray] = None   # int64[cap], codes
        self._ref: Optional[np.ndarray] = None        # uint8[cap], clock bit
        self._block2row: Optional[np.ndarray] = None  # int64[cap], -1=orphan
        self._spilled_codes = 0
        self._in_enforce = False
        if memory_budget is not None:
            self.set_memory_budget(
                memory_budget,
                spill_path=spill_path,
                config=residency,
                spill_io=spill_io,
            )

    # -- codec versions (DESIGN.md §4) -----------------------------------
    @property
    def codec(self) -> TableCodec:
        """The newest installed codec — all writes encode under it."""
        return self._codecs[-1]

    @property
    def current_version(self) -> int:
        return len(self._codecs) - 1

    @property
    def n_versions(self) -> int:
        return len(self._codecs)

    def codec_at(self, version: int) -> TableCodec:
        return self._codecs[version]

    def install_codec(self, codec: TableCodec) -> int:
        """Install a refit codec as the new current version.

        Pending rows are flushed first (they were probed against the old
        plan); existing blocks keep their version tag and remain decodable
        forever — migration to the new plan is opportunistic
        (:meth:`migrate_rows`, merge re-encodes), never stop-the-world.
        """
        if codec.block_tuples != self.codec.block_tuples:
            raise ValueError("install_codec: block_tuples mismatch")
        if codec.order != self.codec.order:
            raise ValueError("install_codec: column order mismatch")
        if len(self._codecs) >= 0xFFFF:  # the uint16 tag must never wrap
            raise ValueError("install_codec: plan version limit reached")
        self.flush()
        self._codecs.append(codec)
        return self.current_version

    @property
    def block_versions(self) -> np.ndarray:
        """Per-block plan-version tag ``uint16[n_blocks]``."""
        return self._plan_ver[:self.n_blocks]

    def version_rows(self) -> Dict[int, int]:
        """Live-row counts keyed by the plan version of their block."""
        live = self._row2block[:self._rows_stored]
        live = live[live >= 0]
        vers, counts = np.unique(self._plan_ver[live], return_counts=True)
        return {int(v): int(c) for v, c in zip(vers, counts)}

    def migrate_rows(self, limit: int = 1 << 12, resident_only: bool = True) -> int:
        """Re-encode up to ``limit`` stale rows under the newest plan.

        Candidates are live rows whose block is tagged with an older version
        AND flagged slow — they escaped their own plan, so the refit that
        superseded it is the first realistic chance to encode them fast
        (plus reclaim their oversized escape runs at the next rewrite).
        Old *fast* blocks are left alone: their codes are already tight and
        every installed version stays decodable.  Under a memory budget,
        ``resident_only`` (the default) keeps maintenance off the cold
        tier: faulting spilled blocks in just to re-encode them would
        evict the workload's hot set — cache thrash for a background
        chore.  Spilled stale blocks migrate when the workload itself
        faults them.  Returns rows migrated.
        """
        self._require_mutable("migrate_rows")
        if limit <= 0 or self.current_version == 0:
            return 0
        self.flush()
        r2b = self._row2block[:self._rows_stored]
        live = r2b >= 0
        blks = r2b[live]
        stale = (self._plan_ver[blks] < self.current_version) & ~self._fast[blks]
        if resident_only and self._res is not None:
            stale &= self._resident[blks]
        rows_idx = np.nonzero(live)[0][stale][:limit]
        if not rows_idx.size:
            return 0
        rows = self.get_many(rows_idx.tolist())
        # Maintenance re-encodes must not feed the drift monitor: these
        # rows already escaped once; recounting them would make migration
        # traffic look like fresh workload drift.
        plan = self.codec.compile()
        ctx = (plan.pause_escape_accounting() if plan is not None
               else contextlib.nullcontext())
        with ctx:
            self.replace_many(rows_idx, rows)
        self.migrated_rows += int(rows_idx.size)
        _C_MIGRATED.add(int(rows_idx.size))
        return int(rows_idx.size)

    # -- out-of-core residency (DESIGN.md §6) ----------------------------
    @property
    def memory_budget(self) -> Optional[int]:
        return self._res.budget if self._res is not None else None

    @property
    def spilled_bytes(self) -> int:
        """Compressed payload bytes currently living on disk (not memory)."""
        return 2 * self._spilled_codes

    @property
    def resident_code_bytes(self) -> int:
        """Live code bytes held in memory: what the budget bounds."""
        return 2 * (self.used - self._dead_codes)

    @property
    def code_bytes(self) -> int:
        """Live code bytes, resident or spilled: the table's data size."""
        return 2 * (self.used - self._dead_codes + self._spilled_codes)

    def set_memory_budget(
        self,
        budget: int,
        spill_path: Optional[str] = None,
        config: Optional[ResidencyConfig] = None,
        spill_io: Optional[Any] = None,
    ) -> None:
        """Bound live resident code bytes, installing the cold tier on the
        first call and moving its budget on later ones.

        Single-tuple granularity only (the spill unit is the block and
        fault-in re-points rows at freshly appended blocks, which needs
        the mutation machinery).  Can be enabled at any point in the
        table's life; existing blocks start resident-and-referenced and
        the first enforcement sweeps them against the budget.  On a table
        that has a cold tier only the budget moves (the spill file and
        policy stay), and a lowered budget spills down to it here.
        """
        self._require_mutable("set_memory_budget")
        if self._res is not None:
            if spill_path is not None or config is not None or spill_io is not None:
                raise ValueError("the cold tier is installed: only its budget moves")
            self._res.budget = budget
            self._enforce_budget()
            return
        self.flush()
        self._res = ResidencyManager(budget, spill_path, config, io=spill_io)
        cap = self._offsets.size - 1
        self._resident = np.ones(cap, dtype=bool)
        self._disk_off = np.full(cap, -1, dtype=np.int64)
        self._disk_len = np.zeros(cap, dtype=np.int64)
        self._ref = np.ones(cap, dtype=np.uint8)
        self._block2row = np.full(cap, -1, dtype=np.int64)
        live = np.nonzero(self._row2block[:self._rows_stored] >= 0)[0]
        self._block2row[self._row2block[live]] = live
        self._spilled_codes = 0
        self._enforce_budget()

    def sanitize_boundary(self, where: str) -> None:
        """``REPRO_SANITIZE=1`` boundary assertions (DESIGN.md §10): CSR
        offset monotonicity, plan-version tag validity, residency
        accounting vs ground truth, and zone-map well-formedness.  A
        no-op (one falsy branch) when the sanitizer is off."""
        if not sanitize.ENABLED:
            return
        nb = self.n_blocks
        sanitize.check_csr_offsets(self._offsets[:nb + 1], self.used, where=where)
        sanitize.check_plan_versions(
            self._plan_ver[:nb], len(self._codecs), where=where
        )
        if self._res is not None:
            res_mask = self._resident[:nb]
            actual = int(self._disk_len[:nb][~res_mask].sum())
            sanitize.check_residency(
                self._spilled_codes,
                actual,
                res_mask,
                self._disk_off[:nb],
                where=where,
            )
        if self._zone_cols:
            sanitize.check_zone_maps(self._zmin, self._zmax, where=where)

    def note_repaired_rows(self, n: int) -> None:
        """Designated entry point for repair drivers (WAL-backed stores) to
        record ``n`` quarantined rows rebuilt from the log.  Foreign writes
        to residency counters are confined to these note_* methods (BL004)."""
        if self._res is not None:
            self._res.repaired_rows += int(n)

    def note_quarantined_rows(self, n: int) -> None:
        """Record ``n`` rows quarantined by a failed checked spill read
        (scan engine / fault-in paths)."""
        if self._res is not None:
            self._res.quarantined += int(n)

    def _init_new_blocks(self, first: int, n: int, rows: Optional[np.ndarray]) -> None:
        """Fresh blocks are resident and referenced (recently written)."""
        if self._res is None:
            return
        self._resident[first:first + n] = True
        self._disk_off[first:first + n] = -1
        self._disk_len[first:first + n] = 0
        self._ref[first:first + n] = 1
        self._block2row[first:first + n] = -1 if rows is None else rows

    def _enforce_budget(self) -> None:
        """Spill cold blocks until live resident codes fit the budget, then
        physically reclaim the arena once residue outgrows the slack."""
        res = self._res
        if res is None or self._in_enforce:
            return
        self._in_enforce = True
        try:
            if self.used - self._dead_codes > res.budget_codes:
                self._spill_until(res.target_codes)
            # Spilled/dead residue stays in the memory arena until a
            # rewrite; force one when physical footprint passes the slack.
            if self._dead_codes and self.used > res.rewrite_codes:
                self.rewrite()
            self._maybe_compact_disk()
        finally:
            self._in_enforce = False

    def _spill_until(self, target_codes: int) -> None:
        """Spill cold blocks via the shared clock sweep: victims are live
        resident blocks whose referenced bit is clear (DESIGN.md §6)."""
        res = self._res
        need = (self.used - self._dead_codes) - target_codes

        def candidates(ids: np.ndarray) -> np.ndarray:
            lens = self._offsets[ids + 1] - self._offsets[ids]
            rows = self._block2row[ids]
            cand = self._resident[ids] & (lens > 0) & (rows >= 0)
            if cand.any():
                ok = np.zeros_like(cand)
                ok[cand] = self._row2block[rows[cand]] == ids[cand]
                cand = ok
            return cand

        victims = res.sweep(
            self.n_blocks, need, candidates,
            lambda ids: self._offsets[ids + 1] - self._offsets[ids],
            lambda ids: self._ref[ids] != 0,
            lambda ids: self._ref.__setitem__(ids, 0))
        if victims.size:
            self._spill_blocks(victims)

    def _spill_blocks(self, blocks: np.ndarray) -> None:
        """Write the victims' code runs to disk in arena byte order (one
        coalesced segment write of CRC32-framed extents) and mark them
        non-resident.  Their in-memory runs become dead bytes until the
        next rewrite."""
        with telemetry.span("repro.residency.spill"):
            res = self._res
            order = np.argsort(self._offsets[blocks], kind="stable")
            blocks = blocks[order]
            starts = self._offsets[blocks]
            lens = self._offsets[blocks + 1] - starts
            total = int(lens.sum())
            payloads = [
                self.arena[int(s):int(s) + int(ln)].tobytes() for s, ln in zip(starts, lens)
            ]
            offs = res.disk.write_many(payloads)
            self._disk_off[blocks] = np.asarray(offs, dtype=np.int64)
            self._disk_len[blocks] = lens
            self._resident[blocks] = False
            self._dead_codes += total
            self._spilled_codes += total
            res.spills += int(blocks.size)
            _C_SPILL_BLOCKS.add(int(blocks.size))
            _C_SPILL_BYTES.add(2 * total)
        self.sanitize_boundary("spill_blocks")

    def _fault_in(self, blocks: np.ndarray) -> None:
        """Promote spilled blocks: one coalesced disk read, then append the
        runs back into the memory arena as fresh physical blocks carrying
        their fast/version tags, and re-point their rows.  The batched
        decode path then serves them exactly like always-resident blocks —
        a miss costs one read plus one vectorized decode, never per-row
        work."""
        with telemetry.span("repro.residency.fault_in"):
            res = self._res
            lens = self._disk_len[blocks].copy()
            offs_old = self._disk_off[blocks].copy()
            try:
                payloads = res.disk.read_many_checked(offs_old, 2 * lens)
            except ExtentCorruptionError as e:
                # No state was mutated: surface the affected row ids so a
                # durability layer can rebuild them from the WAL and retry.
                bad = blocks[np.asarray(e.indices, dtype=np.int64)]
                res.quarantined += len(e.indices)
                raise SpillCorruptionError(self._block2row[bad].tolist()) from e
            total = int(lens.sum())
            buf = np.empty(total, dtype=np.uint16)
            pos = 0
            for j in range(blocks.size):
                ln = int(lens[j])
                buf[pos:pos + ln] = np.frombuffer(payloads[j], dtype=np.uint16)
                pos += ln
            n = int(blocks.size)
            base = self.used
            self._append_codes(buf)
            self._grow_index(n)
            first = self.n_blocks
            self._offsets[first + 1:first + 1 + n] = base + np.cumsum(lens)
            self._fast[first:first + n] = self._fast[blocks]
            self._plan_ver[first:first + n] = self._plan_ver[blocks]
            self._tmpl[first:first + n] = self._tmpl[blocks]
            self._zone_union(first, blocks)
            rows = self._block2row[blocks]
            self._init_new_blocks(first, n, rows)
            self.n_blocks += n
            self.block_rows.extend([1] * n)
            self._row2block[rows] = np.arange(first, first + n)
            # the old slots are orphans now; their disk extents are freed
            self._block2row[blocks] = -1
            self._resident[blocks] = True
            self._disk_off[blocks] = -1
            self._disk_len[blocks] = 0
            for o, ln in zip(offs_old.tolist(), lens.tolist()):
                res.disk.free(o, framed_len(2 * ln))
            self._spilled_codes -= total
            res.faults += n
            res.fault_batches += 1
            _C_FAULT_BLOCKS.add(n)
            _C_FAULT_BYTES.add(2 * total)
        self.sanitize_boundary("fault_in")

    def _maybe_compact_disk(self) -> None:
        res = self._res
        if res is None or not res.disk.needs_compact:
            return
        spilled = np.nonzero(~self._resident[:self.n_blocks])[0]
        new_offs = res.disk.compact(
            self._disk_off[spilled], 2 * self._disk_len[spilled] + FRAME_OVERHEAD
        )
        self._disk_off[spilled] = np.asarray(new_offs, dtype=np.int64)

    def residency(self) -> Dict[str, Any]:
        """Cold-tier observability: budget, resident/spilled split, faults."""
        if self._res is None:
            return {}
        out = self._res.stats()
        out.update(
            resident_bytes=self.nbytes,
            spilled_bytes=self.spilled_bytes,
            spilled_blocks=int((~self._resident[:self.n_blocks]).sum()),
        )
        return out

    # -- storage helpers -------------------------------------------------
    def _append_codes(self, codes: np.ndarray) -> None:
        need = self.used + codes.size
        if need > self.arena.size:
            new = np.zeros(max(need, 2 * self.arena.size), dtype=np.uint16)
            new[:self.used] = self.arena[:self.used]
            self.arena = new
        self.arena[self.used:need] = codes
        self.used = need

    def _device_arena(self) -> Any:
        """The arena's HBM mirror, synced to ``arena[:used]`` of this
        generation.

        Under a memory budget the arena breathes between rewrites, up to
        the size that forces one (:attr:`ResidencyManager.rewrite_codes`);
        the mirror is sized for that arena, so it does not step (and
        recompile every decode bucket) each time the residue grows past a
        fresh rewrite's.
        """
        most = 0 if self._res is None else self._res.rewrite_codes
        return self._mirror.sync(self.arena, self.used, self._arena_gen, most)

    def _grow_index(self, n_new: int) -> None:
        need = self.n_blocks + n_new + 1
        if need > self._offsets.size:
            cap = max(need, 2 * self._offsets.size)
            off = np.zeros(cap, dtype=np.int64)
            off[:self.n_blocks + 1] = self._offsets[:self.n_blocks + 1]
            self._offsets = off
            fast = np.zeros(cap - 1, dtype=bool)
            fast[:self.n_blocks] = self._fast[:self.n_blocks]
            self._fast = fast
            ver = np.zeros(cap - 1, dtype=np.uint16)
            ver[:self.n_blocks] = self._plan_ver[:self.n_blocks]
            self._plan_ver = ver
            tmpl = np.zeros(cap - 1, dtype=np.uint8)
            tmpl[:self.n_blocks] = self._tmpl[:self.n_blocks]
            self._tmpl = tmpl
            if self._res is not None:
                nb = self.n_blocks
                resident = np.ones(cap - 1, dtype=bool)
                resident[:nb] = self._resident[:nb]
                doff = np.full(cap - 1, -1, dtype=np.int64)
                doff[:nb] = self._disk_off[:nb]
                dlen = np.zeros(cap - 1, dtype=np.int64)
                dlen[:nb] = self._disk_len[:nb]
                ref = np.zeros(cap - 1, dtype=np.uint8)
                ref[:nb] = self._ref[:nb]
                b2r = np.full(cap - 1, -1, dtype=np.int64)
                b2r[:nb] = self._block2row[:nb]
                self._resident, self._disk_off, self._disk_len = resident, doff, dlen
                self._ref, self._block2row = ref, b2r

    def _grow_rows(self, n_new: int) -> None:
        need = self._rows_stored + n_new
        if need > self._row2block.size:
            cap = max(need, 2 * self._row2block.size)
            r2b = np.full(cap, -1, dtype=np.int64)
            r2b[:self._rows_stored] = self._row2block[:self._rows_stored]
            self._row2block = r2b

    # -- zone maps (DESIGN.md §8) ----------------------------------------
    def _zone_chunks(self, n_blocks: int) -> int:
        return -(-int(n_blocks) // self.ZONE_CHUNK)

    def _zone_ensure(self, n_chunks: int) -> None:
        if n_chunks > self._zmin.shape[0]:
            cap = max(n_chunks, 2 * self._zmin.shape[0], 8)
            zc = len(self._zone_cols)
            zmin = np.full((cap, zc), np.inf)
            zmax = np.full((cap, zc), -np.inf)
            zmin[:self._zmin.shape[0]] = self._zmin
            zmax[:self._zmax.shape[0]] = self._zmax
            self._zmin, self._zmax = zmin, zmax

    def _zone_values(self, rows: Sequence[Dict[str, Any]]) -> np.ndarray:
        """``float64[n, Z]`` raw zone-column values; non-numeric or
        non-finite entries become NaN (poisoning their chunk)."""
        n = len(rows)
        vals = np.full((n, len(self._zone_cols)), np.nan)
        for j, c in enumerate(self._zone_cols):
            col = [r.get(c) for r in rows]
            try:
                v = np.asarray(col, dtype=np.float64)
                if v.shape != (n,):
                    raise ValueError("ragged zone column")
            except (TypeError, ValueError):
                v = np.full(n, np.nan)
                for i, x in enumerate(col):
                    try:
                        v[i] = float(x)
                    except (TypeError, ValueError):
                        pass
            vals[:, j] = np.where(np.isfinite(v), v, np.nan)
        return vals

    def _zone_widen(self, blocks: np.ndarray, rows: Sequence[Dict[str, Any]]) -> None:
        """Widen chunk bounds with the raw values of ``rows``, one entry
        per row landing in the matching ``blocks`` id (ids may repeat for
        multi-row blocks).  Raw values bound decoded values for escapes
        exactly and for quantized values within the model's slack, which
        the pruning test re-adds — so the maps are valid for fast AND
        slow blocks."""
        if not self._zone_cols or not len(rows):
            return
        blocks = np.asarray(blocks, dtype=np.int64)
        self._zone_ensure(self._zone_chunks(int(blocks.max()) + 1))
        chunks = blocks // self.ZONE_CHUNK
        vals = self._zone_values(rows)
        np.minimum.at(self._zmin, chunks, vals)
        np.maximum.at(self._zmax, chunks, vals)

    def _zone_union(self, first: int, old_blocks: np.ndarray) -> None:
        """Blocks ``[first, first+n)`` now carry the rows of ``old_blocks``
        (fault-in promotion): union the old chunks' bounds into the new
        chunks — conservative, and tight when the rows dominated their old
        chunk."""
        if not self._zone_cols or not old_blocks.size:
            return
        n = int(old_blocks.size)
        self._zone_ensure(self._zone_chunks(first + n))
        nc = (first + np.arange(n, dtype=np.int64)) // self.ZONE_CHUNK
        oc = np.asarray(old_blocks, np.int64) // self.ZONE_CHUNK
        np.minimum.at(self._zmin, nc, self._zmin[oc])
        np.maximum.at(self._zmax, nc, self._zmax[oc])

    def _zone_rebuild(self, old_blocks: np.ndarray, nb: int) -> None:
        """After a rewrite renumbers blocks (new block ``i`` holds old
        block ``old_blocks[i]``), rebuild chunk bounds as unions of each
        new chunk's contributing old chunks."""
        if not self._zone_cols:
            return
        zc = len(self._zone_cols)
        n_chunks = self._zone_chunks(nb)
        cap = max(n_chunks, 8)
        zmin = np.full((cap, zc), np.inf)
        zmax = np.full((cap, zc), -np.inf)
        if nb:
            nc = np.arange(nb, dtype=np.int64) // self.ZONE_CHUNK
            oc = np.asarray(old_blocks, np.int64) // self.ZONE_CHUNK
            np.minimum.at(zmin, nc, self._zmin[oc])
            np.maximum.at(zmax, nc, self._zmax[oc])
        self._zmin, self._zmax = zmin, zmax

    @property
    def zone_columns(self) -> List[str]:
        """Columns with zone maps (numeric schema kinds)."""
        return list(self._zone_cols)

    def zone_block_mask(
        self,
        column: str,
        lo: Optional[float] = None,
        hi: Optional[float] = None,
        slack: float = 0.0,
    ) -> Optional[np.ndarray]:
        """Keep-mask ``bool[n_blocks]``: False = zone maps prove no row of
        the block can satisfy ``lo <= value <= hi`` (widened by ``slack``,
        the worst-case quantization error of the predicate's decoded
        values).  ``None`` when the column has no zone map; NaN-poisoned
        chunks always keep."""
        j = self._zcol_idx.get(column)
        if j is None:
            return None
        nc = self._zone_chunks(self.n_blocks)
        self._zone_ensure(nc)
        zmin = self._zmin[:nc, j]
        zmax = self._zmax[:nc, j]
        drop = np.zeros(nc, dtype=bool)
        if lo is not None and math.isfinite(lo):
            drop |= zmax < (float(lo) - slack)   # NaN compares False: keep
        if hi is not None and math.isfinite(hi):
            drop |= zmin > (float(hi) + slack)
        blocks = np.arange(self.n_blocks, dtype=np.int64)
        return ~drop[blocks // self.ZONE_CHUNK]

    def _append_block(
        self,
        codes: np.ndarray,
        n_rows: int,
        fast: bool,
        rows: Optional[Sequence[Dict[str, Any]]] = None,
        template: int = 0,
    ) -> None:
        self._append_codes(codes)
        self._grow_index(1)
        self.n_blocks += 1
        self._offsets[self.n_blocks] = self.used
        self._fast[self.n_blocks - 1] = fast
        self._plan_ver[self.n_blocks - 1] = self.current_version
        self._tmpl[self.n_blocks - 1] = template
        if rows is not None:
            self._zone_widen(np.full(len(rows), self.n_blocks - 1, np.int64), rows)
        self.block_rows.append(n_rows)
        if self.codec.block_tuples == 1:
            self._grow_rows(n_rows)
            self._row2block[self._rows_stored] = self.n_blocks - 1
            self._init_new_blocks(self.n_blocks - 1, 1, np.asarray([self._rows_stored]))
        self._rows_stored += n_rows
        self.sanitize_boundary("append_block")

    @property
    def block_offsets(self) -> np.ndarray:
        """CSR offsets ``int64[n_blocks + 1]`` into the code arena."""
        return self._offsets[:self.n_blocks + 1]

    @property
    def block_fast(self) -> np.ndarray:
        """Per-block flag: True when the block decodes on the compiled path."""
        return self._fast[:self.n_blocks]

    @property
    def block_templates(self) -> np.ndarray:
        """Per-block plan-family member ``uint8[n_blocks]`` (fast blocks)."""
        return self._tmpl[:self.n_blocks]

    # -- write path ------------------------------------------------------
    def append(self, row: Dict[str, Any]) -> None:
        self._pending.append(row)
        if len(self._pending) >= self.codec.block_tuples:
            self.flush()

    def extend(self, rows: Sequence[Dict[str, Any]]) -> None:
        """Bulk insert: one vectorized encode for all plan-conforming rows."""
        rows = list(rows)
        if self.codec.block_tuples != 1 or self.codec.compile() is None:
            # blitzlint: waive[BL001] -- extend falls back to per-row append only for non-conforming rows (escape path)
            for r in rows:
                self.append(r)
            return
        self.flush()
        codes, offsets, tmpl = self.codec.compress_rows(rows)
        base = self.used
        self._append_codes(codes)
        n = len(rows)
        self._grow_index(n)
        self._offsets[self.n_blocks + 1:self.n_blocks + 1 + n] = base + offsets[1:]
        self._fast[self.n_blocks:self.n_blocks + n] = tmpl >= 0
        self._plan_ver[self.n_blocks:self.n_blocks + n] = self.current_version
        self._tmpl[self.n_blocks:self.n_blocks + n] = np.maximum(tmpl, 0)
        self._zone_widen(np.arange(self.n_blocks, self.n_blocks + n), rows)
        self._init_new_blocks(
            self.n_blocks, n, np.arange(self._rows_stored, self._rows_stored + n)
        )
        self._grow_rows(n)
        self._row2block[self._rows_stored:self._rows_stored + n] = np.arange(
            self.n_blocks, self.n_blocks + n
        )
        self.n_blocks += n
        self.block_rows.extend([1] * n)
        self._rows_stored += n
        self._enforce_budget()
        self.sanitize_boundary("extend")

    def flush(self) -> None:
        if not self._pending:
            return
        rows, self._pending = self._pending, []
        # Scalar encode (cheapest for one row; identical codes either way),
        # plus a cheap pure-Python probe for the plan-family member.
        plan = self.codec.compile()
        tmpl = (plan.row_template(rows[0])
                if plan is not None and len(rows) == 1 else -1)
        codes = self.codec._scalar_compress(rows)
        self._append_block(codes, len(rows), tmpl >= 0, rows=rows,
                           template=max(tmpl, 0))
        self._enforce_budget()

    def __len__(self) -> int:
        return self._rows_stored + len(self._pending)

    # -- read path -------------------------------------------------------
    def get(self, i: int) -> Dict[str, Any]:
        """Random access: decompress the block containing row ``i``.

        Raises :class:`KeyError` for tombstoned rows (single-tuple
        granularity; see :meth:`delete_many`).
        """
        i = int(i)
        if self.codec.block_tuples == 1:
            if i < self._rows_stored:
                b = int(self._row2block[i])
                if b < 0:
                    raise KeyError(f"row {i} is deleted")
                return self.get_block(b)[0]
            return dict(self._pending[i - self._rows_stored])
        bt = self.codec.block_tuples
        b = i // bt  # blocks are fixed-size except the trailing pending rows
        if b < self.n_blocks:
            return self.get_block(b)[i % bt]
        return dict(self._pending[i - bt * self.n_blocks])

    def _block_codes(self, b: int) -> np.ndarray:
        """A block's code run — read through to disk for spilled blocks.

        Scalar reads never promote (no row re-pointing): a point lookup of
        one cold block costs one pread, and the batched :meth:`get_many`
        path is the one that faults blocks back to residency.
        """
        if self._res is not None:
            if not self._resident[b]:
                self._res.scalar_faults += 1
                try:
                    raw = self._res.disk.read_checked(
                        int(self._disk_off[b]), 2 * int(self._disk_len[b])
                    )
                except (ExtentCorruptionError, ArenaReadError) as e:
                    self._res.quarantined += 1
                    raise SpillCorruptionError([int(self._block2row[b])]) from e
                return np.frombuffer(raw, dtype=np.uint16)
            self._ref[b] = 1
        return self.arena[self._offsets[b]:self._offsets[b + 1]]

    def get_block(self, b: int) -> List[Dict[str, Any]]:
        codes = self._block_codes(b)
        codec = self._codecs[self._plan_ver[b]]  # decode under the block's
        return codec.decompress_block(codes, self.block_rows[b])  # own plan

    def _resolve_backend(
        self, backend: Optional[str], n_rows: int, codec: Optional[TableCodec] = None
    ) -> str:
        """The decode backend for ``n_rows`` fast rows under ``codec``.

        An explicit ``"pallas"`` (the argument, or ``use_pallas=True`` when
        the argument is None) runs the kernel when the plan can; a plan
        with conditional slots cannot, and that downgrade to numpy is
        counted (``repro.plan.pallas_downgrade``).  Auto mode picks the
        kernel only for large batches on a TPU (:mod:`repro.device`).
        """
        plan = (codec or self.codec).compile()
        eligible = plan is not None and plan.pallas_ok
        if backend == "pallas" or (backend is None and self.use_pallas):
            if eligible:
                return "pallas"
            _C_PALLAS_DOWNGRADE.inc()
            return "numpy"
        if backend == "numpy" or not eligible or self.use_pallas is False:
            return "numpy"
        if n_rows >= self.PALLAS_MIN_ROWS and not device.interpret_default():
            return "pallas"
        return "numpy"

    def get_many(
        self, indices: Sequence[int], backend: Optional[str] = None
    ) -> List[Optional[Dict[str, Any]]]:
        """Batched point gets (``None`` for tombstoned rows).

        Rows in plan-conforming single-tuple blocks decode with ONE
        ``decode_select`` call *per (plan version, family member) present in
        the batch* (a block's fast flag and template certify it against the
        plan it was encoded with); the rest fall back to per-block scalar
        decode (each touched block decoded once, under its own version's
        codec).
        """
        self.sanitize_boundary("get_many")
        idx_arr = np.asarray(list(indices), dtype=np.int64)
        n = idx_arr.size
        out: List[Optional[Dict[str, Any]]] = [None] * n
        bt = self.codec.block_tuples
        scalar_blocks: Dict[int, List[Tuple[int, int]]] = {}
        if bt == 1:
            if not n:
                return out
            # logical row -> physical block; -2 = pending tail, -1 = deleted
            in_store = idx_arr < self._rows_stored
            blks = np.full(n, -2, dtype=np.int64)
            blks[in_store] = self._row2block[idx_arr[in_store]]
            if self._res is not None:
                # grouped fault-in: every spilled block this batch needs is
                # promoted with ONE coalesced read, then decoded below by
                # the same vectorized decode_select as resident blocks
                sb = blks[blks >= 0]
                if sb.size:
                    cold = np.unique(sb[~self._resident[sb]])
                    if cold.size:
                        self._fault_in(cold)
                        blks[in_store] = self._row2block[idx_arr[in_store]]
                    self._ref[blks[blks >= 0]] = 1  # clock: referenced
            fmask = np.zeros(n, dtype=bool)
            stored = blks >= 0
            if stored.any():
                # fast flags are self-certifying: a block is only flagged
                # fast if its version's codec compiled at encode time
                fmask[stored] = self._fast[blks[stored]]
            fast_pos = np.nonzero(fmask)[0]
            if fast_pos.size:
                fb = blks[fast_pos]
                route = self._plan_ver[fb].astype(np.int64) << 8 | self._tmpl[fb]
                for g in np.unique(route).tolist():
                    sel = fast_pos[route == g]
                    codec_v = self._codecs[g >> 8]
                    be = self._resolve_backend(backend, sel.size, codec_v)
                    rows = codec_v.decompress_rows(
                        self.arena[:self.used],
                        self.block_offsets,
                        blks[sel],
                        backend=be,
                        template=g & 0xFF,
                        device_codes=self._device_arena if be == "pallas" else None,
                    )
                    # blitzlint: waive[BL001] -- scatters scalar-decoded escape rows back into the batched result
                    for j, r in zip(sel.tolist(), rows):
                        out[j] = r
            for j in np.nonzero(~fmask)[0].tolist():
                b = int(blks[j])
                if b == -2:
                    out[j] = dict(self._pending[int(idx_arr[j]) - self._rows_stored])
                elif b >= 0:
                    scalar_blocks.setdefault(b, []).append((j, 0))
                # b == -1: tombstone, leave None
        else:
            for j in range(n):
                i = int(idx_arr[j])
                if i >= self._rows_stored:
                    out[j] = dict(self._pending[i - self._rows_stored])
                else:
                    b = i // bt
                    scalar_blocks.setdefault(b, []).append((j, i - b * bt))
        for b, items in scalar_blocks.items():
            blk = self.get_block(b)
            seen: set = set()
            for j, off in items:
                # duplicate indices get independent dicts, matching get()
                out[j] = blk[off] if off not in seen else dict(blk[off])
                seen.add(off)
        if self._res is not None:
            self._enforce_budget()  # fault-ins may have overrun the budget
        return out

    # -- mutation path (DESIGN.md §3; single-tuple granularity only) -----
    def _require_mutable(self, what: str) -> None:
        if self.codec.block_tuples != 1:
            raise ValueError(
                f"{what} requires block_tuples == 1 (multi-tuple blocks "
                "share code runs across rows)")

    def _retire_blocks(self, blocks: np.ndarray) -> None:
        """Account the code runs of abandoned physical blocks as dead.

        A spilled block's in-memory run was already counted dead when it
        spilled, so retiring it only frees its disk extent."""
        if not blocks.size:
            return
        if self._res is not None:
            self._block2row[blocks] = -1
            sp = ~self._resident[blocks]
            if sp.any():
                cold = blocks[sp]
                for o, ln in zip(
                    self._disk_off[cold].tolist(), self._disk_len[cold].tolist()
                ):
                    self._res.disk.free(o, framed_len(2 * ln))
                self._spilled_codes -= int(self._disk_len[cold].sum())
                self._resident[cold] = True
                self._disk_off[cold] = -1
                self._disk_len[cold] = 0
                blocks = blocks[~sp]
        if blocks.size:
            self._dead_codes += int(
                (self._offsets[blocks + 1] - self._offsets[blocks]).sum()
            )

    def replace_many(
        self, indices: Sequence[int], rows: Sequence[Dict[str, Any]]
    ) -> None:
        """Re-encode ``rows`` in place of ``indices`` (delta-merge step).

        New code runs are appended to the arena through the bulk
        ``compress_rows`` path (one ``encode_batch`` call for conforming
        rows); the old runs are tombstoned in place and counted as dead
        bytes until :meth:`rewrite` reclaims them.  ``indices`` must be
        unique; replacing a tombstoned row resurrects it.
        """
        self._require_mutable("replace_many")
        self.flush()
        idx = np.asarray(list(indices), dtype=np.int64)
        n = idx.size
        if n != len(rows):
            raise ValueError("indices and rows length mismatch")
        if not n:
            return
        if idx.min() < 0 or idx.max() >= self._rows_stored:
            raise IndexError("replace_many index out of range")
        if np.unique(idx).size != n:
            # duplicates would double-count dead bytes and orphan runs
            raise ValueError("replace_many indices must be unique")
        codes, offsets, tmpl = self.codec.compress_rows(list(rows))
        base = self.used
        self._append_codes(codes)
        self._grow_index(n)
        first = self.n_blocks
        self._offsets[first + 1:first + 1 + n] = base + offsets[1:]
        self._fast[first:first + n] = tmpl >= 0
        self._plan_ver[first:first + n] = self.current_version
        self._tmpl[first:first + n] = np.maximum(tmpl, 0)
        self._zone_widen(np.arange(first, first + n), list(rows))
        self._init_new_blocks(first, n, idx)
        self.n_blocks += n
        self.block_rows.extend([1] * n)
        old = self._row2block[idx]
        live = old >= 0
        self._retire_blocks(old[live])
        self._n_deleted -= int(n - np.count_nonzero(live))  # resurrections
        self._row2block[idx] = np.arange(first, first + n)
        self._enforce_budget()

    def delete_many(self, indices: Sequence[int]) -> int:
        """Tombstone rows: their code runs become dead bytes.  Returns the
        number of rows newly deleted (repeat deletes are no-ops)."""
        self._require_mutable("delete_many")
        self.flush()
        idx = np.unique(np.asarray(list(indices), dtype=np.int64))
        if not idx.size:
            return 0
        if idx[0] < 0 or idx[-1] >= self._rows_stored:
            raise IndexError("delete_many index out of range")
        old = self._row2block[idx]
        live = old >= 0
        self._retire_blocks(old[live])
        self._row2block[idx[live]] = -1
        newly = int(np.count_nonzero(live))
        self._n_deleted += newly
        return newly

    def is_live(self, i: int) -> bool:
        """True when logical row ``i`` exists and is not tombstoned."""
        i = int(i)
        if i < 0 or i >= len(self):
            return False
        if self.codec.block_tuples != 1 or i >= self._rows_stored:
            return True
        return self._row2block[i] >= 0

    @property
    def n_live(self) -> int:
        return len(self) - self._n_deleted

    @property
    def dead_bytes(self) -> int:
        """Bytes of abandoned (replaced/deleted) code runs in the arena."""
        return 2 * self._dead_codes

    def rewrite(self) -> int:
        """Compact the arena: copy live runs, drop dead ones, renumber
        physical blocks.  Spilled blocks survive as zero-length resident
        runs carrying their residency tags (disk extent, fast flag, plan
        version) — compaction never forces a fault-in.  Returns the number
        of bytes reclaimed."""
        with telemetry.span("repro.store.rewrite"):
            self._require_mutable("rewrite")
            self.flush()
            reclaimed = self.dead_bytes
            nrows = self._rows_stored
            live_rows = np.nonzero(self._row2block[:nrows] >= 0)[0]
            blks = self._row2block[live_rows]
            starts = self._offsets[blks]
            lens = self._offsets[blks + 1] - starts
            res = self._res
            if res is not None:
                res_mask = self._resident[blks]
                lens = np.where(res_mask, lens, 0)  # spilled: no memory run
            total = int(lens.sum())
            new_off = np.zeros(live_rows.size + 1, dtype=np.int64)
            np.cumsum(lens, out=new_off[1:])
            gather = np.repeat(starts - new_off[:-1], lens) + np.arange(total)
            arena = np.zeros(max(total, 1024), dtype=np.uint16)
            arena[:total] = self.arena[gather]
            nb = live_rows.size
            offs = np.zeros(max(nb + 1, 1024), dtype=np.int64)
            offs[:nb + 1] = new_off
            fast = np.zeros(offs.size - 1, dtype=bool)
            fast[:nb] = self._fast[blks]
            ver = np.zeros(offs.size - 1, dtype=np.uint16)
            ver[:nb] = self._plan_ver[blks]  # tags survive compaction
            tmpl = np.zeros(offs.size - 1, dtype=np.uint8)
            tmpl[:nb] = self._tmpl[blks]
            if res is not None:
                resident = np.ones(offs.size - 1, dtype=bool)
                resident[:nb] = res_mask
                doff = np.full(offs.size - 1, -1, dtype=np.int64)
                doff[:nb] = np.where(res_mask, -1, self._disk_off[blks])
                dlen = np.zeros(offs.size - 1, dtype=np.int64)
                dlen[:nb] = np.where(res_mask, 0, self._disk_len[blks])
                ref = np.zeros(offs.size - 1, dtype=np.uint8)
                ref[:nb] = self._ref[blks]
                b2r = np.full(offs.size - 1, -1, dtype=np.int64)
                b2r[:nb] = live_rows
                self._resident, self._disk_off, self._disk_len = resident, doff, dlen
                self._ref, self._block2row = ref, b2r
                # the clock hand's position is meaningless after renumbering
                res.hand = 0
            self.arena, self.used = arena, total
            self._arena_gen += 1
            self._offsets, self._fast, self.n_blocks = offs, fast, nb
            self._plan_ver, self._tmpl = ver, tmpl
            self._zone_rebuild(blks, nb)
            self.block_rows = [1] * nb
            self._row2block[:nrows] = -1
            self._row2block[live_rows] = np.arange(nb)
            self._dead_codes = 0
            self.rewrites += 1
        return reclaimed

    # -- durability (DESIGN.md §7) ---------------------------------------
    def close(self, unlink: bool = False) -> None:
        """Release the spill file (if any); the table stays readable for
        resident blocks but must not touch disk afterwards."""
        if self._res is not None:
            self._res.close(unlink=unlink)

    def _snapshot_escapes(self) -> Dict[int, Dict[str, Any]]:
        """Per-version drift counters of every *compiled* plan.

        Plans are stripped from pickled codecs (pure functions of the
        models), but their escape counters are live adaptive state: replay
        must resume from the same window or the next drift check would
        diverge from the pre-crash schedule."""
        out: Dict[int, Dict[str, Any]] = {}
        for v, codec in enumerate(self._codecs):
            plan = codec._plan
            if plan is None:
                continue
            out[v] = {
                "escape_counts": dict(plan.escape_counts),
                "window_escapes": dict(plan.window_escapes),
                "rows_seen": int(plan.rows_seen),
                "window_rows": int(plan.window_rows),
            }
        return out

    def _restore_escapes(self, escapes: Dict[int, Dict[str, Any]]) -> None:
        for v, st in escapes.items():
            plan = self._codecs[int(v)].compile()
            if plan is None:
                continue
            plan.escape_counts.update(st["escape_counts"])
            plan.window_escapes.update(st["window_escapes"])
            plan.rows_seen = int(st["rows_seen"])
            plan.window_rows = int(st["window_rows"])

    def snapshot_state(self, embed_spilled: Optional[bool] = None) -> Dict[str, Any]:
        """Everything needed to rebuild this table bit-identically.

        Spilled payloads are handled one of two ways.  *Embedded* mode
        reads them back (CRC-verified) into the snapshot: self-contained,
        so the spill file never needs to survive a crash.  *Extent* mode
        (the default whenever the spill file is a named durable path)
        records only ``(offset, length)`` references — the spill file's
        own CRC frames already protect the payloads, so re-embedding them
        would double the checkpoint for no extra safety; the file is
        fsynced first so the references are durable.  Corruption found
        here surfaces as :class:`SpillCorruptionError` so the owner can
        repair from the WAL and retry."""
        nb, n = self.n_blocks, self._rows_stored
        st: Dict[str, Any] = {
            "codecs": self._codecs,
            "use_pallas": self.use_pallas,
            "arena": self.arena[:self.used].copy(),
            "offsets": self._offsets[:nb + 1].copy(),
            "fast": self._fast[:nb].copy(),
            "plan_ver": self._plan_ver[:nb].copy(),
            "template": self._tmpl[:nb].copy(),
            "block_rows": list(self.block_rows),
            "row2block": self._row2block[:n].copy(),
            "rows_stored": n,
            "dead_codes": self._dead_codes,
            "n_deleted": self._n_deleted,
            "rewrites": self.rewrites,
            "migrated_rows": self.migrated_rows,
            "pending": [dict(r) for r in self._pending],
            "escapes": self._snapshot_escapes(),
            "zones": {
                "chunk": self.ZONE_CHUNK,
                "cols": list(self._zone_cols),
                "zmin": self._zmin[:self._zone_chunks(nb)].copy(),
                "zmax": self._zmax[:self._zone_chunks(nb)].copy(),
            },
        }
        if self._res is not None:
            spilled = np.nonzero(~self._resident[:nb])[0]
            res_st: Dict[str, Any] = {
                "budget": self._res.budget,
                "config": self._res.config,
                "resident": self._resident[:nb].copy(),
                "ref": self._ref[:nb].copy(),
                "block2row": self._block2row[:nb].copy(),
                "disk_len": self._disk_len[:nb].copy(),
            }
            embed = (embed_spilled if embed_spilled is not None
                     else self._res.disk.path is None)
            if embed:
                try:
                    payloads = self._res.disk.read_many_checked(
                        self._disk_off[spilled], 2 * self._disk_len[spilled]
                    )
                except ExtentCorruptionError as e:
                    bad = spilled[np.asarray(e.indices, dtype=np.int64)]
                    self._res.quarantined += len(e.indices)
                    raise SpillCorruptionError(self._block2row[bad].tolist()) from e
                res_st["payloads"] = {int(b): p for b, p in zip(spilled, payloads)}
            else:
                self._res.disk.fsync()
                res_st["spill_file"] = self._res.disk.path
                res_st["extents"] = {
                    int(b): (int(self._disk_off[b]), int(self._disk_len[b]))
                    for b in spilled}
            st["residency"] = res_st
        return st

    @classmethod
    def from_state(
        cls,
        state: Dict[str, Any],
        spill_path: Optional[str] = None,
        spill_io: Optional[Any] = None,
    ) -> "CompressedTable":
        """Rebuild a table from :meth:`snapshot_state` output.

        Previously spilled blocks are re-spilled into a fresh spill file,
        so the resident/cold split (and therefore ``nbytes``) matches the
        snapshot exactly."""
        t = cls(state["codecs"][0], use_pallas=state["use_pallas"])
        t._codecs = list(state["codecs"])
        arena = checked_asarray(state["arena"], np.uint16, where="from_state arena")
        t.arena = np.zeros(max(arena.size, 1024), dtype=np.uint16)
        t.arena[:arena.size] = arena
        t.used = int(arena.size)
        t._arena_gen += 1
        nb = len(state["block_rows"])
        cap = max(nb + 1, 1024)
        t._offsets = np.zeros(cap, dtype=np.int64)
        t._offsets[:nb + 1] = state["offsets"]
        t._fast = np.zeros(cap - 1, dtype=bool)
        t._fast[:nb] = state["fast"]
        t._plan_ver = np.zeros(cap - 1, dtype=np.uint16)
        t._plan_ver[:nb] = state["plan_ver"]
        t._tmpl = np.zeros(cap - 1, dtype=np.uint8)
        if "template" in state:  # older snapshots predate plan families
            t._tmpl[:nb] = state["template"]
        t.n_blocks = nb
        t.block_rows = list(state["block_rows"])
        n = int(state["rows_stored"])
        t._row2block = np.full(max(n, 1024), -1, dtype=np.int64)
        t._row2block[:n] = state["row2block"]
        t._rows_stored = n
        t._dead_codes = int(state["dead_codes"])
        t._n_deleted = int(state["n_deleted"])
        t.rewrites = int(state["rewrites"])
        t.migrated_rows = int(state["migrated_rows"])
        t._pending = [dict(r) for r in state["pending"]]
        res_state = state.get("residency")
        if res_state is not None:
            payload_map = res_state.get("payloads")
            if payload_map is None:
                # Extent-mode checkpoint: payloads live in the (durable)
                # spill file referenced by the snapshot.  Read them out
                # BEFORE constructing the ResidencyManager — opening a
                # named spill path truncates it, and recovery commonly
                # reuses the same path.
                payload_map = _read_spill_extents(
                    res_state["spill_file"], res_state["extents"],
                    res_state["block2row"])
            t._res = ResidencyManager(
                res_state["budget"], spill_path, res_state.get("config"), io=spill_io
            )
            t._resident = np.ones(cap - 1, dtype=bool)
            t._resident[:nb] = res_state["resident"]
            t._disk_off = np.full(cap - 1, -1, dtype=np.int64)
            t._disk_len = np.zeros(cap - 1, dtype=np.int64)
            t._disk_len[:nb] = res_state["disk_len"]
            t._ref = np.zeros(cap - 1, dtype=np.uint8)
            t._ref[:nb] = res_state["ref"]
            t._block2row = np.full(cap - 1, -1, dtype=np.int64)
            t._block2row[:nb] = res_state["block2row"]
            spilled = sorted(payload_map)
            if spilled:
                offs = t._res.disk.write_many([payload_map[b] for b in spilled])
                t._disk_off[np.asarray(spilled, dtype=np.int64)] = np.asarray(
                    offs, dtype=np.int64
                )
            t._spilled_codes = int(t._disk_len[:nb].sum())
        zst = state.get("zones")
        if (zst is not None and zst["chunk"] == t.ZONE_CHUNK
                and zst["cols"] == t._zone_cols):
            t._zone_ensure(max(t._zone_chunks(nb), 8))
            nc = np.asarray(zst["zmin"]).shape[0]
            t._zmin[:nc] = zst["zmin"]
            t._zmax[:nc] = zst["zmax"]
        elif t._zone_cols and nb:
            # Older snapshot (or layout change): poison every chunk so
            # pruning is disabled but never wrong; fresh inserts land in
            # new chunks and prune normally.
            t._zone_ensure(t._zone_chunks(nb))
            t._zmin[:t._zone_chunks(nb)] = np.nan
            t._zmax[:t._zone_chunks(nb)] = np.nan
        t._restore_escapes(state.get("escapes") or {})
        return t

    @property
    def nbytes(self) -> int:
        """Compressed footprint: code arena + block index + unflushed rows.

        Offsets are counted at 4 B each (a uint32 arena index suffices for
        <8 GiB of codes) plus the bits per block that tell a slow block from
        the fast ones of each stored plan-family template (1 bit, the fast
        flag, while every fast block is the modal plan's); pending
        rows sit uncompressed and are charged at their raw size.  At
        single-tuple granularity the row->block indirection (mutation
        support) adds 4 B per logical row.  Once a refit installs a second
        codec the per-block plan-version tag is charged at 1 B per block
        (a single-version table needs no tags).  Dead bytes from replaced
        or deleted runs are *included* — they are held memory until
        :meth:`rewrite` — and reported separately via :attr:`dead_bytes`.

        Under a memory budget this is the *resident* footprint, matching
        how the paper counts the budget: spilled code runs live on disk
        and are excluded (reported via :attr:`spilled_bytes`), while the
        per-block residency metadata (packed disk extent + flags, 9 B per
        block) is charged here.
        """
        pending = sum(_raw_row_bytes(r) for r in self._pending)
        indirection = (4 * self._rows_stored if self.codec.block_tuples == 1 else 0)
        ver_tags = self.n_blocks if len(self._codecs) > 1 else 0
        res_meta = 9 * self.n_blocks if self._res is not None else 0
        zone_bytes = (16 * len(self._zone_cols) * self._zone_chunks(self.n_blocks))
        nb = self.n_blocks
        routes = int(self._tmpl[:nb].max()) + 1 if nb else 1  # + the slow state
        return (self.used * 2 + 4 * (nb + 1)
                + (nb * routes.bit_length() + 7) // 8 + indirection + ver_tags
                + res_meta + zone_bytes + pending)
