"""OLTP scenario (paper §6/§7): an in-memory row store under a YCSB-style
mixed workload, comparing Blitzcrank against zstd / Raman / uncompressed
through the unified batched RowStore protocol (DESIGN.md §3), with the
§6.5 LRU fast path for read-modify-write transactions.

Run:  PYTHONPATH=src python examples/oltp_store.py
      PYTHONPATH=src python examples/oltp_store.py --mix   # update-heavy
                                                           # TPC-C mix with
                                                           # delta-merge stats
      PYTHONPATH=src python examples/oltp_store.py --drift # drifting mix:
                                                           # adaptive refit
                                                           # on vs off
      PYTHONPATH=src python examples/oltp_store.py --db    # full multi-table
                                                           # TPC-C through the
                                                           # repro.db engine
      PYTHONPATH=src python examples/oltp_store.py --budget # out-of-core
                                                           # cold tier under a
                                                           # memory budget
      PYTHONPATH=src python examples/oltp_store.py --durable # WAL + checkpoint:
                                                           # close, reopen,
                                                           # verify recovery
      PYTHONPATH=src python examples/oltp_store.py --crash-demo # kill the
                                                           # process at a crash
                                                           # point, recover
      PYTHONPATH=src python examples/oltp_store.py --metrics # telemetry
                                                           # snapshot: counters,
                                                           # percentiles, phase
                                                           # breakdown (§9)
"""

import argparse
import os
import shutil
import tempfile
import time

import numpy as np

from repro.adaptive import DriftConfig, MaintenanceConfig
from repro.oltp import tpcc
from repro.oltp.store import (BlitzStore, LRUFastPath, RamanStore,
                              UncompressedStore, ZstdStore)


def compare_stores(n_rows=4000, n_reads=1500, n_rmw=500):
    schema, gen = tpcc.TABLES["customer"]
    rows = gen(n_rows)
    raw = tpcc.row_bytes(rows)
    rng = np.random.default_rng(0)
    read_keys = tpcc.zipf_keys(rng, n_rows, n_reads, a=1.2)
    rmw_keys = tpcc.zipf_keys(rng, n_rows, n_rmw, a=1.2)

    print(f"{'store':12s} {'factor':>7s} {'read us':>9s} {'rmw us':>9s} "
          f"{'hit%':>6s}")
    for cls in (UncompressedStore, ZstdStore, RamanStore, BlitzStore):
        try:
            store = cls(schema, rows[: n_rows // 2])
        except ImportError:  # optional backend (zstandard) not installed
            continue
        store.insert_many(rows)

        t0 = time.perf_counter()
        tpcc.batched_point_gets(store, read_keys, batch=256)
        t_read = (time.perf_counter() - t0) / n_reads

        fp = LRUFastPath(store, capacity=256)
        t0 = time.perf_counter()
        for i in rmw_keys:
            fp.read_modify_write(int(i),
                                 lambda r: r.update(c_balance=r["c_balance"] + 1))
        t_rmw = (time.perf_counter() - t0) / n_rmw
        fp.sync()
        hit = fp.hits / max(fp.hits + fp.misses, 1)
        print(f"{store.name:12s} {raw / store.nbytes:7.2f} "
              f"{1e6 * t_read:9.1f} {1e6 * t_rmw:9.1f} {100 * hit:6.1f}")

    print("\nBlitzcrank: highest factor; the fast path absorbs Zipfian "
          "updates (paper Fig. 13).")


def update_heavy_mix(n_rows=8000, n_ops=30000):
    """Payment-heavy TPC-C mix: the delta overlay merges back into the
    arena instead of growing forever (DESIGN.md §3)."""
    schema, gen = tpcc.TABLES["customer"]
    rows = gen(n_rows)
    store = BlitzStore(schema, rows, sample=1 << 13)
    store.insert_many(rows)
    post_load = store.stats()
    print(f"loaded {post_load['n_live']} rows, "
          f"{post_load['nbytes'] / 1024:.0f} KiB compressed "
          f"(factor {tpcc.row_bytes(rows) / post_load['nbytes']:.2f})")

    t0 = time.perf_counter()
    counts = tpcc.run_transaction_mix(
        store, n_ops, seed=3, p_payment=0.6, p_order_status=0.25,
        p_new_order=0.10, p_delivery=0.05, new_row_fn=tpcc.customer_row)
    dt = time.perf_counter() - t0
    s = store.stats()
    print(f"\n{n_ops} ops in {dt:.1f}s "
          f"({1e6 * dt / n_ops:.1f} us/op): {counts}")
    print(f"bytes: total {s['nbytes'] / 1024:.0f} KiB "
          f"(= {s['nbytes'] / post_load['nbytes']:.2f}x post-load) | "
          f"arena {s['arena_bytes'] / 1024:.0f} KiB, "
          f"overlay {s['overlay_bytes'] / 1024:.1f} KiB "
          f"({s['overlay_rows']} rows), dead {s['dead_bytes'] / 1024:.1f} KiB")
    print(f"compaction: {s['merges']} merges, {s['rewrites']} arena "
          f"rewrites; live rows {s['n_live']} (+{counts['inserts']} inserted, "
          f"-{counts['deletes']} deleted)")
    escapes = {k: v for k, v in s["escapes"].items() if v}
    print(f"escape counters (refit hook): {escapes}")


def drifting_mix(n_rows=5000, n_ops=50000):
    """Drifting TPC-C mix (DESIGN.md §4): over the run, new customers carry
    previously unseen names/cities/employers and widening balances.  With
    adaptive maintenance off the fitted models degrade toward raw size;
    with it on, drift detection + background refit + plan-version migration
    hold the compression factor."""
    schema, gen = tpcc.TABLES["customer"]
    rows = gen(n_rows)
    maint = MaintenanceConfig(
        drift=DriftConfig(rate_threshold=0.02, min_escapes=32,
                          min_window_rows=256),
        check_every=1024, migrate_rows_per_step=2048, numeric_headroom=2.0)
    for label, adaptive in (("refit off", False), ("refit on ", maint)):
        store = BlitzStore(schema, rows, sample=1 << 13,
                           merge_min_bytes=1 << 14, adaptive=adaptive)
        store.insert_many(rows)
        t0 = time.perf_counter()
        tpcc.run_transaction_mix(
            store, n_ops, seed=3, p_payment=0.25, p_order_status=0.15,
            p_new_order=0.55, p_delivery=0.05,
            new_row_fn=tpcc.drifting_customer_row, drift=1.0)
        dt = time.perf_counter() - t0
        s = store.stats()
        raw = tpcc.row_bytes([r for _, r in store.scan()])
        line = (f"{label}: factor {raw / s['nbytes']:.2f} "
                f"({s['nbytes'] / 1024:.0f} KiB for {s['n_live']} rows, "
                f"{1e6 * dt / n_ops:.0f} us/op)")
        if s.get("maintenance"):
            m = s["maintenance"]
            line += (f" | {m['refits']} refits -> {s['plan_versions']} plan "
                     f"versions, {s['migrated_rows']} rows migrated, "
                     f"frozen: {m['frozen_columns']}")
        print(line)
    print("\nRefit-on holds the compression factor as the workload drifts "
          "(paper §5 dynamic value sets; BENCH_adaptive_refit.json).")


def multi_table_db(n_ops=1500):
    """Full multi-table TPC-C through the repro.db engine (DESIGN.md §5):
    seven hash-partitioned tables in one Database catalog, the cross-table
    NewOrder/Payment/OrderStatus/Delivery mix, and the whole-database
    compression factor the paper's §6 is about."""
    print("loading the 7-table TPC-C database (blitzcrank vs silo)...")
    db, pop = tpcc.build_tpcc_database(
        backend="blitzcrank", n_shards=4, n_warehouses=2,
        districts_per_wh=10, customers_per_district=150, n_items=1000,
        orders_per_district=50)
    silo, _ = tpcc.build_tpcc_database(backend="silo", n_shards=4,
                                       population=pop)
    print(f"loaded {db.n_live} rows across {len(db)} tables; "
          f"post-load factor {silo.nbytes / db.nbytes:.2f}x")

    t0 = time.perf_counter()
    counts = tpcc.run_tpcc_mix(db, n_ops, seed=7)
    dt = time.perf_counter() - t0
    tpcc.run_tpcc_mix(silo, n_ops, seed=7)
    db.merge_all()
    print(f"\n{n_ops} transactions in {dt:.1f}s "
          f"({1e6 * dt / n_ops:.0f} us/txn): {counts}")
    s, ss = db.stats(), silo.stats()
    print(f"{'table':11s} {'rows':>7s} {'blitz KiB':>10s} {'silo KiB':>9s} "
          f"{'factor':>7s} {'shards':>7s}")
    for name in db.table_names:
        ts, tss = s["tables"][name], ss["tables"][name]
        print(f"{name:11s} {ts['n_live']:7d} {ts['nbytes'] / 1024:10.1f} "
              f"{tss['nbytes'] / 1024:9.1f} "
              f"{tss['nbytes'] / ts['nbytes']:7.2f} {ts['n_shards']:7d}")
    print(f"{'TOTAL':11s} {s['n_live']:7d} {s['nbytes'] / 1024:10.1f} "
          f"{ss['nbytes'] / 1024:9.1f} {ss['nbytes'] / s['nbytes']:7.2f}")
    print(f"\nwhole-database factor {ss['nbytes'] / s['nbytes']:.2f}x "
          f"(models {s['model_bytes'] / 1024:.0f} KiB reported separately); "
          "see BENCH_db_tpcc.json for the acceptance run.")


def out_of_core(budget_frac=0.25, n_ops=2000):
    """Cold-tier demo (paper §6.4, DESIGN.md §6): cap the blitz store at a
    fraction of its fully-resident size and watch cold blocks spill to
    disk and fault back in while reads stay bit-identical."""
    schema, gen = tpcc.TABLES["customer"]
    rows = gen(6000)
    ref = BlitzStore(schema, rows, sample=1 << 13)
    ref.insert_many(rows)
    budget = int(budget_frac * ref.stats()["nbytes"])
    store = BlitzStore(schema, rows, sample=1 << 13, memory_budget=budget)
    store.insert_many(rows)
    t0 = time.perf_counter()
    tpcc.run_transaction_mix(store, n_ops, seed=5)
    dt = time.perf_counter() - t0
    tpcc.run_transaction_mix(ref, n_ops, seed=5)  # same ops, uncapped
    store.merge()
    ref.merge()
    s = store.stats()
    res = s["residency"]
    print(f"budget {budget / 1024:.0f} KiB "
          f"({budget_frac:.0%} of the resident store)")
    print(f"resident {s['nbytes'] / 1024:.0f} KiB (arena + overlay + "
          f"metadata), spilled {s['spilled_bytes'] / 1024:.0f} KiB on disk "
          f"({res['spilled_blocks']} blocks)")
    print(f"{n_ops} zipfian ops in {dt:.2f}s: {res['faults']} faults in "
          f"{res['fault_batches']} grouped reads, {res['spills']} spills")
    probe = list(range(0, len(rows), 7))
    ok = store.get_many(probe) == ref.get_many(probe)
    print(f"reads bit-identical to the uncapped store: {ok}; "
          "see BENCH_out_of_core.json for the Fig. 15-style run.")


def durable(n_rows=3000, n_ops=800):
    """Durability demo (DESIGN.md §7): run a TPC-C mix against a durable
    database (per-table WAL + checksummed spill pages), close it with a
    checkpoint, reopen from disk, and verify the recovered reads are
    bit-identical."""
    from repro.db import Database, TableSchema

    root = tempfile.mkdtemp(prefix="oltp_durable_")
    try:
        schema, gen = tpcc.TABLES["customer"]
        rows = gen(n_rows)
        db = Database(backend="blitzcrank", memory_limit=64 * 1024,
                      durability=root)
        table = db.create_table(TableSchema("customer", schema, "c_id"),
                                sample_rows=rows[: n_rows // 2])
        table.insert_many(rows)
        # keyed table: NewOrder ids must be fresh, not len(store)-based
        next_id = iter(range(n_rows, n_rows + n_ops))

        t0 = time.perf_counter()
        tpcc.run_transaction_mix(
            table, n_ops, seed=5,
            new_row_fn=lambda rng, _i: tpcc.customer_row(rng, next(next_id)))
        dt = time.perf_counter() - t0
        wal_kib = os.path.getsize(os.path.join(root, "customer.wal")) / 1024
        print(f"{n_ops} ops in {dt:.2f}s against the durable store "
              f"(WAL {wal_kib:.0f} KiB, fsync per batch)")
        keys = [k for k, _ in table.scan()]
        want = table.get_many(keys)
        db.close()  # final checkpoint: codecs + block index + residency
        ckpt_kib = os.path.getsize(os.path.join(root, "checkpoint.bin")) / 1024

        t0 = time.perf_counter()
        rdb = Database.open(root)
        dt = time.perf_counter() - t0
        ok = rdb["customer"].get_many(keys) == want
        print(f"reopened from checkpoint ({ckpt_kib:.0f} KiB) in {dt:.2f}s; "
              f"{len(keys)} recovered reads bit-identical: {ok}")
        rdb.close()
    finally:
        shutil.rmtree(root, ignore_errors=True)


def crash_demo(point="apply.before"):
    """Fault-injection demo (DESIGN.md §7): arm a named crash point so the
    simulated process dies mid-operation, then recover from WAL +
    checkpoint and verify against an uncrashed reference."""
    from repro.durability import harness

    print(f"arming crash point {point!r} (one of {len(harness.CRASH_POINTS)}"
          " named points; the CI recovery-matrix job sweeps them all)...")
    r = harness.run_crash_scenario(point, backend="blitzcrank", seed=0)
    state = "crashed mid-run" if r["crashed"] else "never crashed"
    print(f"workload {state} after {r['applied']} applied batches; "
          f"recovery must replay {r.get('expected_batches', '?')} from "
          "checkpoint + WAL tail")
    verdict = "bit-identical" if r["ok"] else f"MISMATCH: {r['errors']}"
    print(f"recovered database vs uncrashed reference: {verdict}")

    print("\ninjecting a bit flip into a spilled page: the CRC frame "
          "catches it and the row is rebuilt from the WAL")
    errs = harness._scenario_spill_bitflip(0)
    print("spill corruption repaired, reads clean:", not errs)


def metrics_demo(n_ops=1200, json_path=None):
    """Telemetry demo (DESIGN.md §9): run a short multi-table TPC-C mix
    with the always-on instrumentation, then pretty-print the registry —
    top counters, latency percentiles per hot path, and the per-phase
    wall-time breakdown that locates the OLTP speed gap."""
    import json as _json

    from repro import telemetry

    telemetry.reset()
    db, _ = tpcc.build_tpcc_database(
        backend="blitzcrank", n_shards=2, n_warehouses=2,
        districts_per_wh=4, customers_per_district=80, n_items=400,
        orders_per_district=20)
    base = telemetry.REGISTRY.hist_seconds()
    t0 = time.perf_counter()
    counts = tpcc.run_tpcc_mix(db, n_ops, seed=11)
    wall = time.perf_counter() - t0
    print(f"{n_ops} transactions in {wall:.2f}s "
          f"({1e6 * wall / n_ops:.0f} us/txn): {counts}\n")

    snap = telemetry.snapshot()
    top = sorted(snap["counters"].items(), key=lambda kv: -kv[1])[:12]
    print(f"{'counter':40s} {'value':>12s}")
    for name, value in top:
        print(f"{name:40s} {value:12d}")

    print(f"\n{'histogram':40s} {'count':>8s} {'p50 us':>9s} "
          f"{'p95 us':>9s} {'p99 us':>9s}")
    hists = sorted(snap["histograms"].items(),
                   key=lambda kv: -kv[1]["total_s"])[:12]
    for name, h in hists:
        print(f"{name:40s} {h['count']:8d} {h['p50_us']:9.1f} "
              f"{h['p95_us']:9.1f} {h['p99_us']:9.1f}")

    bd = telemetry.phase_breakdown(wall, since=base)
    print(f"\nper-phase breakdown of the mix "
          f"(coverage {bd['coverage']:.2f}):")
    for phase, frac in sorted(bd["phase_frac"].items(),
                              key=lambda kv: -kv[1]):
        bar = "#" * int(50 * frac)
        print(f"  {phase:12s} {100 * frac:5.1f}%  {bar}")
    print("\npython_glue is interpreter time between instrumented "
          "kernels — the 7.5x-gap residual (DESIGN.md §9.4).")

    if json_path:
        doc = dict(snap, phases=bd)
        with open(json_path, "w") as f:
            _json.dump(doc, f, indent=2, sort_keys=True)
        print(f"full snapshot written to {json_path}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mix", action="store_true",
                    help="run the update-heavy TPC-C transaction mix "
                         "with delta-merge stats")
    ap.add_argument("--drift", action="store_true",
                    help="drifting TPC-C mix over 50k ops: adaptive "
                         "refit on vs off compression factor")
    ap.add_argument("--db", action="store_true",
                    help="full multi-table TPC-C through the repro.db "
                         "engine (catalog + hash-partitioned shards)")
    ap.add_argument("--budget", action="store_true",
                    help="out-of-core cold tier: spill/fault under a "
                         "memory budget (DESIGN.md §6)")
    ap.add_argument("--durable", action="store_true",
                    help="WAL + checkpoint: run a mix durably, close, "
                         "reopen, verify bit-identical recovery (§7)")
    ap.add_argument("--crash-demo", action="store_true",
                    help="fault injection: kill at a named crash point, "
                         "recover, verify against a reference (§7)")
    ap.add_argument("--metrics", action="store_true",
                    help="short TPC-C mix + telemetry snapshot: top "
                         "counters, latency percentiles, phase "
                         "breakdown (DESIGN.md §9)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="with --metrics: also write the full telemetry "
                         "snapshot as JSON")
    args = ap.parse_args()
    if args.metrics:
        metrics_demo(json_path=args.json)
    elif args.crash_demo:
        crash_demo()
    elif args.durable:
        durable()
    elif args.budget:
        out_of_core()
    elif args.db:
        multi_table_db()
    elif args.drift:
        drifting_mix()
    elif args.mix:
        update_heavy_mix()
    else:
        compare_stores()


if __name__ == "__main__":
    main()
