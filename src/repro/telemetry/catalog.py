"""Central catalog of every telemetry series name (DESIGN.md §9/§10).

One flat tuple, one name per series.  blitzlint rule BL002 parses this
file (without importing it) and fails CI when a literal name at a call
site is missing here — so a typo can no longer fork a metric series —
and when the catalog itself holds a duplicate or a name that violates
the ``repro.<subsystem>.<verb>`` pattern.

Names constructed dynamically (the ``repro.scan.<field>`` counters
generated from ``ScanStats._FIELDS``) are enumerated here explicitly and
pinned by ``tests/test_blitzlint.py::test_scan_stats_fields_catalogued``.
"""

from __future__ import annotations

from typing import FrozenSet, Tuple

METRICS: Tuple[str, ...] = (
    # -- core encode/decode (leaf phases of the wall-time breakdown) ----
    "repro.core.decode",
    "repro.core.decode.rows",
    "repro.core.decode.scalar_block",
    "repro.core.encode",
    "repro.core.encode.rows",
    "repro.core.encode.scalar",
    "repro.core.encode.scalar_block",
    # -- plan compilation and kernel caches -----------------------------
    "repro.plan.cache.hit",
    "repro.plan.cache.miss",
    "repro.plan.cache.pallas_hit",
    "repro.plan.cache.pallas_miss",
    "repro.plan.compile",
    "repro.plan.compile.pallas_jit",
    "repro.plan.pallas_downgrade",
    "repro.plan.pallas_pack",
    "repro.plan.pallas_pack.events",
    # -- residency / out-of-core tier ------------------------------------
    "repro.residency.fault_in",
    "repro.residency.fault_in.blocks",
    "repro.residency.fault_in.rows",
    "repro.residency.fault_in.rows.count",
    "repro.residency.spill",
    "repro.residency.spill.blocks",
    "repro.residency.spill.rows",
    "repro.residency.spill.rows.count",
    # -- row stores -------------------------------------------------------
    "repro.store.merge",
    "repro.store.merge.events",
    "repro.store.migrate.rows",
    "repro.store.overlay.hits",
    "repro.store.rewrite",
    # -- write-ahead log --------------------------------------------------
    "repro.wal.append",
    "repro.wal.bytes",
    "repro.wal.fsync",
    "repro.wal.fsyncs",
    "repro.wal.records",
    # -- compiled execution engine (plan/run split, DESIGN.md §11) ---------
    "repro.exec.lower",
    "repro.exec.plan.hit",
    "repro.exec.plan.miss",
    "repro.exec.replay",
    "repro.exec.replay.rows",
    # -- db engine (batched verbs; span + rows-counter pairs) -------------
    "repro.db.delete_many",
    "repro.db.delete_many.rows",
    "repro.db.get_many",
    "repro.db.get_many.rows",
    "repro.db.insert_many",
    "repro.db.insert_many.rows",
    "repro.db.shard_calls",
    "repro.db.update_many",
    "repro.db.update_many.rows",
    # -- scan engine (repro.scan.<field> mirrors ScanStats._FIELDS) -------
    "repro.scan.blocks_fallback",
    "repro.scan.blocks_lut",
    "repro.scan.blocks_pruned",
    "repro.scan.blocks_scalar",
    "repro.scan.blocks_total",
    "repro.scan.rows_decoded",
    "repro.scan.rows_matched",
    "repro.scan.rows_prefix_decoded",
    "repro.scan.scan_table",
    "repro.scan.spilled_reads",
    "repro.scan.versions",
    # -- sanitizer (DESIGN.md §10: boundary-check accounting) --------------
    "repro.sanitize.checks",
    "repro.sanitize.failures",
    # -- benchmark self-instrumentation ------------------------------------
    "repro.bench.telemetry.counter",
    "repro.bench.telemetry.hist",
)

CATALOG: FrozenSet[str] = frozenset(METRICS)


def is_catalogued(name: str) -> bool:
    """True when ``name`` is a registered series name."""
    return name in CATALOG
