"""CI benchmark-regression gate (ISSUE 5 satellite).

Two layers of protection, both cheap enough to run on every PR:

1. **Committed artifacts** — every ``BENCH_*.json`` at the repo root must
   carry its ``git_sha``/``schema_name`` stamps, its recorded
   ``acceptance.pass`` must be true, and the headline numbers must still
   clear their bounds (factor lower bounds, latency upper bounds with
   slack).  A PR that regresses a benchmark and re-runs it cannot land a
   failing artifact quietly; a PR that edits an artifact by hand trips
   the same checks.

2. **Fresh smoke run** — the ``name,us_per_call,derived`` CSV emitted by
   ``python -m benchmarks.run --smoke`` is checked against bounds that
   are meaningful at toy sizes: every bench must have completed (its
   ``bench_*_wall`` line says ``ok``), correctness booleans
   (``identical=True``) must hold, compression factors must clear loose
   floors, and smoke latencies must stay within a generous slack of the
   committed full-scale numbers — toy sizes are overhead-dominated, so
   the slack catches order-of-magnitude rot, not noise.

Usage (CI wires this right after the smoke step)::

    python -m benchmarks.run --smoke | tee smoke.csv
    python -m benchmarks.check_regression --csv smoke.csv

Exits non-zero listing every violated bound.  ``--skip-smoke`` checks
only the committed artifacts (useful pre-push).
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from typing import Dict, List, Optional, Tuple

REPO_ROOT = Path(__file__).resolve().parent.parent

# Every bench registered in benchmarks/run.py must complete in smoke mode.
REQUIRED_BENCHES = [
    "compression",
    "batch_decode",
    "update_merge",
    "adaptive_refit",
    "db_tpcc",
    "exec_engine",
    "out_of_core",
    "recovery",
    "htap",
    "telemetry",
    "sampling",
    "entropy",
    "granularity",
    "fastpath",
    "archive",
]

# Correctness booleans that hold at any scale: decode paths must stay
# bit-identical to their references even at smoke sizes.
SMOKE_IDENTICAL = [
    "batch_decode_R64_numpy",
    "batch_decode_R256_numpy",
    "update_merge_merge",
    "adaptive_refit_refit_on",
    "db_tpcc_acceptance",
    # prepared batched replay must match the scalar verb loop bit-for-bit
    "exec_engine_acceptance",
    "out_of_core_acceptance",
    "recovery_acceptance",
    "htap_acceptance",
    # enabled vs disabled telemetry must leave bit-identical db contents
    "telemetry_acceptance",
]

# (csv name, derived key, lower bound) — loose floors for smoke scale,
# roughly half of the observed toy-size values, far below full scale.
SMOKE_DERIVED_MIN: List[Tuple[str, str, float]] = [
    ("fig9_customer_blitzcrank", "factor", 1.5),
    ("fig9_stock_blitzcrank", "factor", 1.5),
    ("fig9_orderline_blitzcrank", "factor", 1.2),
    ("db_tpcc_blitzcrank", "factor", 1.0),
    # prepared replay beats the scalar loop even at toy sizes, and the
    # plan cache must hit once each bucket is lowered
    ("exec_engine_get_prepared", "speedup", 2.0),
    ("exec_engine_get_prepared", "hit_rate", 0.9),
    ("batch_decode_R64_numpy", "speedup", 1.5),
    ("batch_decode_R256_numpy", "speedup", 2.0),
]

# Smoke latency vs the committed full-scale artifact, with slack: smoke
# sizes are overhead-dominated, so the ceiling is a large multiple — it
# fires on order-of-magnitude regressions (a broken fast path), never on
# noise.  (csv name, artifact, json path to the committed value, slack).
SMOKE_LATENCY_VS_ARTIFACT: List[Tuple[str, str, List[str], float]] = [
    (
        "db_tpcc_blitzcrank",
        "BENCH_db_tpcc.json",
        ["arms", "blitzcrank", "point_get_us"],
        25.0,
    ),
    (
        "out_of_core_blitzcrank_capped",
        "BENCH_out_of_core.json",
        # the capped arm's own measured rate: a cold-tier slowdown moves
        # this metric even when the uncapped reference is unchanged
        ["arms", "blitzcrank_capped", "median_rate_tps"],
        # us_per_call is 1e6/rate, so the ceiling is slack/rate.
        10.0,
    ),
]

# Committed-artifact invariants: (artifact, json path, kind, bound).
# "min" = factor lower bound, "max" = latency upper bound (with slack
# already folded into the bound), "true" = boolean that must hold.
ARTIFACT_RULES: List[Tuple[str, List[str], str, Optional[float]]] = [
    ("BENCH_db_tpcc.json", ["acceptance", "pass"], "true", None),
    ("BENCH_db_tpcc.json", ["acceptance", "factor_vs_silo"], "min", 2.0),
    ("BENCH_db_tpcc.json", ["arms", "blitzcrank", "point_get_us"], "max", 250.0),
    # ISSUE 10: blitz mix wall time within 2x of silo's, with 1.25x
    # timing-noise slack folded into the bound (2.0 * 1.25)
    ("BENCH_db_tpcc.json", ["acceptance", "txn_ratio_vs_silo"], "max", 2.5),
    ("BENCH_exec_engine.json", ["acceptance", "pass"], "true", None),
    ("BENCH_exec_engine.json", ["acceptance", "read_speedup"], "min", 2.0),
    ("BENCH_exec_engine.json", ["acceptance", "hit_rate"], "min", 0.9),
    ("BENCH_exec_engine.json", ["acceptance", "identical"], "true", None),
    ("BENCH_update_merge.json", ["acceptance", "pass"], "true", None),
    ("BENCH_update_merge.json", ["acceptance", "bytes_ratio"], "max", 1.25),
    ("BENCH_adaptive_refit.json", ["acceptance", "pass"], "true", None),
    ("BENCH_adaptive_refit.json", ["acceptance", "factor_ratio"], "min", 1.5),
    ("BENCH_out_of_core.json", ["acceptance", "pass"], "true", None),
    ("BENCH_out_of_core.json", ["acceptance", "sustained_ratio"], "min", 3.0),
    ("BENCH_out_of_core.json", ["acceptance", "reads_identical"], "true", None),
    ("BENCH_batch_decode.json", ["fast_fraction"], "min", 0.95),
    ("BENCH_recovery.json", ["acceptance", "pass"], "true", None),
    ("BENCH_recovery.json", ["acceptance", "wal_on_ratio"], "min", 0.7),
    ("BENCH_recovery.json", ["acceptance", "replay_s"], "max", 5.0),
    ("BENCH_recovery.json", ["acceptance", "identical"], "true", None),
    ("BENCH_recovery.json", ["acceptance", "ckpt_saved_frac"], "min", 0.5),
    ("BENCH_htap.json", ["acceptance", "pass"], "true", None),
    ("BENCH_htap.json", ["acceptance", "speedup_vs_ref"], "min", 3.0),
    ("BENCH_htap.json", ["acceptance", "identical"], "true", None),
    ("BENCH_htap.json", ["acceptance", "interference_ratio"], "max", 2.0),
    ("BENCH_htap.json", ["acceptance", "residency_neutral"], "true", None),
    # telemetry must be ~free (enabled >= 0.97x disabled throughput) and
    # behaviour-neutral; the TPC-C phase breakdown must account for the
    # mix's wall time (coverage ~1.0; >>1 means double-counting timers)
    ("BENCH_telemetry.json", ["acceptance", "pass"], "true", None),
    ("BENCH_telemetry.json", ["acceptance", "overhead_ratio"], "min", 0.97),
    ("BENCH_telemetry.json", ["acceptance", "identical"], "true", None),
    # the boundary sanitizer's off path must stay a falsy branch: the
    # disabled sweep call is bounded in ns (DESIGN.md §10)
    ("BENCH_sanitize.json", ["acceptance", "pass"], "true", None),
    ("BENCH_sanitize.json", ["acceptance", "boundary_disabled_ns"], "max", 1000.0),
    ("BENCH_db_tpcc.json", ["phases", "coverage"], "min", 0.9),
    ("BENCH_db_tpcc.json", ["phases", "coverage"], "max", 1.25),
]


def parse_csv(text: str) -> Dict[str, Tuple[float, Dict[str, str], str]]:
    """Parse ``name,us_per_call,derived`` lines into a metric map of
    ``name -> (us, derived key=value dict, raw derived string)``."""
    out: Dict[str, Tuple[float, Dict[str, str], str]] = {}
    for line in text.splitlines():
        parts = line.strip().split(",", 2)
        if len(parts) < 2 or parts[0] in ("", "name"):
            continue
        try:
            us = float(parts[1])
        except ValueError:
            continue
        raw = parts[2] if len(parts) == 3 else ""
        derived: Dict[str, str] = {}
        for kv in raw.split(";"):
            if "=" in kv:
                k, v = kv.split("=", 1)
                derived[k] = v
        out[parts[0]] = (us, derived, raw)
    return out


def dig(obj, path: List[str]):
    for key in path:
        if not isinstance(obj, dict) or key not in obj:
            return None
        obj = obj[key]
    return obj


def check_artifacts(root: Path) -> List[str]:
    failures: List[str] = []
    artifacts: Dict[str, dict] = {}
    for path in sorted(root.glob("BENCH_*.json")):
        try:
            artifacts[path.name] = json.loads(path.read_text())
        except json.JSONDecodeError as e:
            failures.append(f"{path.name}: invalid JSON ({e})")
            continue
        doc = artifacts[path.name]
        for stamp in ("git_sha", "schema_name"):
            if not doc.get(stamp):
                failures.append(f"{path.name}: missing {stamp!r} stamp")
        acc = doc.get("acceptance")
        if isinstance(acc, dict) and acc.get("pass") is not True:
            failures.append(f"{path.name}: acceptance.pass is {acc.get('pass')!r}")
    for name, path, kind, bound in ARTIFACT_RULES:
        doc = artifacts.get(name)
        if doc is None:
            failures.append(f"{name}: artifact missing from repo root")
            continue
        val = dig(doc, path)
        where = f"{name}:{'.'.join(path)}"
        if val is None:
            failures.append(f"{where}: key missing")
        elif kind == "true" and val is not True:
            failures.append(f"{where}: expected true, got {val!r}")
        elif kind == "min" and not float(val) >= bound:
            failures.append(f"{where}: {val} < lower bound {bound}")
        elif kind == "max" and not float(val) <= bound:
            failures.append(f"{where}: {val} > upper bound {bound}")
    return failures


def check_smoke(csv_text: str, root: Path) -> List[str]:
    failures: List[str] = []
    metrics = parse_csv(csv_text)
    if "ERROR" in csv_text:
        for line in csv_text.splitlines():
            if "ERROR" in line:
                failures.append(f"smoke: bench errored: {line.strip()}")
    for bench in REQUIRED_BENCHES:
        wall = metrics.get(f"bench_{bench}_wall")
        if wall is None:
            failures.append(f"smoke: bench_{bench}_wall line missing")
        elif wall[2] != "ok":
            failures.append(f"smoke: bench {bench} did not finish ok")
    for name in SMOKE_IDENTICAL:
        m = metrics.get(name)
        if m is None:
            failures.append(f"smoke: metric {name} missing")
        elif m[1].get("identical") != "True":
            failures.append(
                f"smoke: {name} identical={m[1].get('identical')!r}, "
                "decode no longer bit-identical"
            )
    for name, key, bound in SMOKE_DERIVED_MIN:
        m = metrics.get(name)
        if m is None:
            failures.append(f"smoke: metric {name} missing")
            continue
        try:
            val = float(m[1].get(key, "nan"))
        except ValueError:
            val = float("nan")
        if not val >= bound:
            failures.append(f"smoke: {name} {key}={val} < floor {bound}")
    for name, artifact, path, slack in SMOKE_LATENCY_VS_ARTIFACT:
        m = metrics.get(name)
        apath = root / artifact
        if m is None or not apath.exists():
            failures.append(f"smoke: {name} or {artifact} missing")
            continue
        committed = dig(json.loads(apath.read_text()), path)
        if committed is None:
            failures.append(f"smoke: {artifact}:{'.'.join(path)} missing")
            continue
        committed_us = (
            1e6 / float(committed) if path[-1].endswith("_tps") else float(committed)
        )
        ceiling = slack * committed_us
        if not m[0] <= ceiling:
            failures.append(
                f"smoke: {name} at {m[0]}us exceeds {ceiling:.0f}us "
                f"({slack}x the committed full-scale number)"
            )
    return failures


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--csv", default=None, help="smoke CSV (default: stdin)")
    ap.add_argument(
        "--skip-smoke",
        action="store_true",
        help="only validate the committed BENCH_*.json artifacts",
    )
    ap.add_argument("--root", default=str(REPO_ROOT))
    args = ap.parse_args(argv)
    root = Path(args.root)

    failures = check_artifacts(root)
    n_smoke = 0
    if not args.skip_smoke:
        if args.csv:
            csv_text = Path(args.csv).read_text()
        else:
            csv_text = sys.stdin.read()
        smoke_failures = check_smoke(csv_text, root)
        n_smoke = len(smoke_failures)
        failures += smoke_failures

    if failures:
        print(f"REGRESSION GATE: {len(failures)} violation(s)")
        for f in failures:
            print(f"  FAIL {f}")
        return 1
    checked = len(ARTIFACT_RULES)
    if not args.skip_smoke:
        checked += (
            len(REQUIRED_BENCHES) + len(SMOKE_IDENTICAL) + len(SMOKE_DERIVED_MIN)
        )
    print(f"REGRESSION GATE: pass ({checked} bounds checked, {n_smoke} smoke issues)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
