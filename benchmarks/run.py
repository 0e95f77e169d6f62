"""Benchmark harness: one module per paper table/figure and per DESIGN.md section.

Prints ``name,us_per_call,derived`` CSV lines (harness contract).  ``--full``
uses paper-scale row counts; the default is CPU-quick.  ``--smoke`` runs
every bench at tiny sizes with BENCH_*.json artifact writes disabled — the
CI job runs it so benchmark scripts can't silently rot.
"""

from __future__ import annotations

import argparse
import inspect
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes, no artifact writes (CI rot check)")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names")
    args, _ = ap.parse_known_args()
    quick = not args.full

    from benchmarks import (artifact, bench_adaptive_refit, bench_archive,
                            bench_batch_decode, bench_compression,
                            bench_db_tpcc, bench_entropy_coders,
                            bench_exec_engine, bench_fastpath,
                            bench_granularity, bench_htap,
                            bench_out_of_core, bench_recovery,
                            bench_sampling, bench_sanitize,
                            bench_telemetry, bench_update_merge)

    if args.smoke:
        artifact.set_smoke(True)

    benches = {
        "compression": bench_compression,        # Fig 9
        "batch_decode": bench_batch_decode,      # DESIGN.md §2 fast path
        "update_merge": bench_update_merge,      # DESIGN.md §3 delta merge
        "adaptive_refit": bench_adaptive_refit,  # DESIGN.md §4 drift/refit
        "db_tpcc": bench_db_tpcc,                # DESIGN.md §5 engine, §6
        "exec_engine": bench_exec_engine,        # DESIGN.md §11 plan/run
        "out_of_core": bench_out_of_core,        # DESIGN.md §6 cold tier
        "recovery": bench_recovery,              # DESIGN.md §7 durability
        "htap": bench_htap,                      # DESIGN.md §8 scan engine
        "telemetry": bench_telemetry,            # DESIGN.md §9 overhead gate
        "sanitize": bench_sanitize,              # DESIGN.md §10 overhead note

        "sampling": bench_sampling,              # Fig 10
        "entropy": bench_entropy_coders,         # Fig 11
        "granularity": bench_granularity,        # Fig 12
        "fastpath": bench_fastpath,              # Fig 13
        "archive": bench_archive,                # App F / Table 3
    }
    only = set(args.only.split(",")) if args.only else None
    print("name,us_per_call,derived")
    for name, mod in benches.items():
        if only and name not in only:
            continue
        kwargs = {"quick": quick}
        if args.smoke and "smoke" in inspect.signature(mod.main).parameters:
            kwargs["smoke"] = True
        t0 = time.time()
        try:
            mod.main(**kwargs)
            print(f"bench_{name}_wall,{1e6*(time.time()-t0):.0f},ok")
        except Exception as e:  # noqa: BLE001
            print(f"bench_{name}_wall,0,ERROR={type(e).__name__}:{e}")
            raise


if __name__ == "__main__":
    main()
