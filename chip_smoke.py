#!/usr/bin/env python3
"""One-chip smoke run of the compressed TPC-C store.

Drives the store's main path once on a TPU, at TPC-C spec cardinalities
for one warehouse (10 districts of 3,000 customers, 100,000 items and
100,000 stock rows, 3,000 orders per district), through the entry points
a user calls:

1. ``tpcc.build_tpcc_database`` (``Database`` / ``create_table`` /
   ``insert_many``) loads the same generated population twice: into a
   blitzcrank database whose fast-path reads all decode in the Pallas
   kernel on the chip (``use_pallas=True``), and into the uncompressed
   silo database that serves as the reference;
2. for every table, ``Table.get_many`` reads sampled keys three ways —
   blitzcrank on the device, blitzcrank with ``backend="numpy"``, and
   silo — once as loaded and once after the mix;
3. ``run_tpcc_mix`` drives a few ticks of the TPC-C mix on both;
4. one ``order_line`` ``scan_where`` and ``aggregate`` run the same three
   ways.

Checks: device and numpy reads are bit-identical (floats included).
Against silo, every non-float column is equal.  A float column is stored
at its schema precision ``p``: a read is within ``p / 2`` of the last
value written under its key.  Silo holds the loaded values exactly, and
the rows the mix wrote are recorded as blitzcrank acknowledged them (the
mix reads quantized floats back, so its writes differ from silo's); the
aggregate's sums are held to ``p / 2`` per summed row the same way.
Every table's reads take the fast path and decode on the device, and no
read is downgraded from the device to numpy — except the tables in
``HOST_ONLY``, named with their reason.

    python3 chip_smoke.py [--seed N]

Exits non-zero, with no result line, when JAX finds no TPU, when the
repository's ``src/`` is missing, or when any phase fails.  The last line
of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

SRC = pathlib.Path(__file__).resolve().parent / "src"

# one warehouse at the spec's cardinalities (TPC-C §1.2 / §4.3.3.1)
SPEC = dict(
    n_warehouses=1,
    districts_per_wh=10,
    customers_per_district=3000,
    n_items=100_000,
    orders_per_district=3000,
)
MIX_BATCH = 512  # rows per coalesced mix tick (benchmarks/bench_db_tpcc.py)
MIX_TICKS = 4
READ_KEYS = 4096  # sampled keys read per table and phase
# tables whose rows all escape the slot plan, so their reads decode on the
# host by design; any other table without device-decoded rows fails
HOST_ONLY = {
    "warehouse": "its one row's strings each occur once, below the string "
                 "dictionary's minimum count of 2, so the row escapes the "
                 "slot plan",
}


class SmokeFailure(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def device_info() -> dict:
    import jax

    devs = jax.devices()
    info = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    log(f"device: platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")
    return info


class CompileCacheEvents:
    """Counts JAX's persistent-cache events for this process: lookups,
    hits, and writes (JAX writes only compiles slower than its threshold)."""

    EVENTS = {
        "/jax/compilation_cache/compile_requests_use_cache": "lookups",
        "/jax/compilation_cache/cache_hits": "hits",
        "/jax/compilation_cache/cache_misses": "writes",
    }

    def __init__(self) -> None:
        import jax

        self.counts = dict.fromkeys(self.EVENTS.values(), 0)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1


def counters() -> dict:
    from repro import telemetry

    return {
        name: telemetry.counter(name).value
        for name in (
            "repro.core.decode.rows",
            "repro.plan.cache.pallas_miss",
            "repro.plan.cache.pallas_hit",
            "repro.plan.pallas_downgrade",
        )
    }


def device_calls(before: dict, after: dict) -> int:
    return sum(
        after[n] - before[n]
        for n in ("repro.plan.cache.pallas_miss", "repro.plan.cache.pallas_hit")
    )


def float_columns(schema) -> dict:
    return {c.name: c.precision for c in schema.columns if c.kind == "float"}


def compare(name, got_rows, silo_rows, written, floats):
    """Non-float columns equal silo's; each float within p/2 of the last
    value written under its key (``written``, else silo's loaded row).

    Returns the largest float difference seen (0.0 without floats).
    """
    check(len(got_rows) == len(silo_rows), f"{name}: row counts differ")
    drift = 0.0
    for (key, b), s in zip(got_rows, silo_rows):
        check((b is None) == (s is None), f"{name}: presence differs")
        if b is None:
            continue
        check(b.keys() == s.keys(), f"{name}: columns differ")
        ref = written.get(key, s)
        for col, v in s.items():
            if col in floats:
                want = float(ref[col])
                d = abs(float(b[col]) - want)
                drift = max(drift, d)
                check(d <= float_bound(floats[col], want),
                      f"{name}.{col}: |{b[col]} - {want}| > p/2")
            else:
                check(b[col] == v, f"{name}.{col}: {b[col]!r} != {v!r}")
    return drift


def float_bound(p: float, v: float) -> float:
    """The store's float guarantee: within p/2 (plus float rounding)."""
    return p / 2 + 1e-9 * max(1.0, abs(v))


class WriteRecorder:
    """A database as ``run_tpcc_mix`` sees it, forwarding every call and
    remembering the last row each table acknowledged under each key."""

    def __init__(self, db) -> None:
        self.db = db
        self.written = {name: {} for name in db.table_names}

    def session(self) -> "WriteRecorder":
        self._ses = self.db.session()
        return self

    def table(self, name: str) -> "_RecordingTable":
        return _RecordingTable(self._ses.table(name), self.written[name])


class _RecordingTable:
    def __init__(self, table, written: dict) -> None:
        self._table = table
        self._written = written

    def __getattr__(self, attr):
        return getattr(self._table, attr)

    def __len__(self) -> int:
        return len(self._table)

    def insert_many(self, rows):
        keys = self._table.insert_many(rows)
        self._written.update(zip(keys, map(dict, rows)))
        return keys

    def update_many(self, keys, rows) -> None:
        self._table.update_many(keys, rows)
        self._written.update(zip(keys, map(dict, rows)))


def sample_keys(table, n_keys: int, rng) -> list:
    keys = [k for k, _ in table.scan(batch=4096)]
    if len(keys) <= n_keys:
        return keys
    pick = rng.choice(len(keys), size=n_keys, replace=False)
    return [keys[int(i)] for i in sorted(pick)]


def three_way_reads(db, silo, keys, phase, per_table, written) -> None:
    for name in db.table_names:
        table = db[name]
        c0 = counters()
        dev = table.get_many(keys[name])
        c1 = counters()
        ref = table.get_many(keys[name], backend="numpy")
        want = silo[name].get_many(keys[name])
        calls = device_calls(c0, c1)
        # rows the slot plan decoded; the rest took the host scalar decoder
        fast = c1["repro.core.decode.rows"] - c0["repro.core.decode.rows"]
        downgrades = (c1["repro.plan.pallas_downgrade"]
                      - c0["repro.plan.pallas_downgrade"])
        check(downgrades == 0, f"{name}: {downgrades} device reads downgraded")
        if name in HOST_ONLY:
            log(f"reads[{phase}] {name}: host decode expected: "
                f"{HOST_ONLY[name]}")
        else:
            check(fast > 0, f"{name}: no read took the fast path")
        check(calls > 0 or fast == 0, f"{name}: fast rows decoded off the device")
        check(dev == ref, f"{name}: device decode differs from numpy")
        drift = compare(name, list(zip(keys[name], dev)), want,
                        written.get(name, {}), float_columns(table.schema))
        per_table[name]["device_calls"] += calls
        per_table[name]["fast_rows"] += fast
        log(f"reads[{phase}] {name}: keys={len(keys[name])} fast_rows={fast} "
            f"device_calls={calls} device==numpy float_drift={drift!r}")


def plan_summary(db, n_keys: int) -> dict:
    """Per table: slot count, bucket counts, packed rows, backend taken."""
    from repro.kernels.delayed_decode import slot_layout

    out = {}
    for name in db.table_names:
        shard = db[name].shards[0]
        plan = shard.codec.compile()
        check(plan is not None and plan.pallas_ok,
              f"{name}: plan cannot run on the device")
        tables, m_bits = plan.pallas_tables()
        _, rows = slot_layout(m_bits)
        backend = shard.table._resolve_backend(None, n_keys)
        check(backend == "pallas", f"{name}: reads resolve to {backend}")
        out[name] = {"S": plan.S, "M_max": 1 << max(m_bits), "rows": rows,
                     "backend": backend, "fast_rows": 0, "device_calls": 0}
        log(f"plan {name}: S={plan.S} M_max={1 << max(m_bits)} "
            f"M_sum={sum(1 << m for m in m_bits)} table_rows={rows} "
            f"table_bytes={tables.size * 4} backend={backend}")
    return out


def scan_three_ways(db, silo, written) -> None:
    """One order_line scan and aggregate, after the mix."""
    from repro.scan.predicates import Range

    ol = db["order_line"]
    o_tail = SPEC["orders_per_district"] * 29 // 30  # recent orders + the mix's
    preds = [Range("ol_o_id", o_tail, None)]
    cols = ["ol_d_id", "ol_o_id", "ol_number", "ol_quantity", "ol_amount"]
    aggs = {
        "n": ("count", None),
        "qty": ("sum", "ol_quantity"),
        "amount": ("sum", "ol_amount"),
        "last_o": ("max", "ol_o_id"),
    }
    c0 = counters()
    hits_dev = ol.scan_where(preds, columns=cols, backend="pallas")
    agg_dev = ol.aggregate(preds, group_by=["ol_d_id"], aggs=aggs,
                           backend="pallas")
    c1 = counters()
    hits_ref = ol.scan_where(preds, columns=cols, backend="numpy")
    agg_ref = ol.aggregate(preds, group_by=["ol_d_id"], aggs=aggs,
                           backend="numpy")
    hits_silo = silo["order_line"].scan_where(preds, columns=cols)
    agg_silo = silo["order_line"].aggregate(preds, group_by=["ol_d_id"],
                                            aggs=aggs)
    calls = device_calls(c0, c1)
    check(calls > 0, "order_line scan: no device decode")
    check(c1["repro.plan.pallas_downgrade"] == c0["repro.plan.pallas_downgrade"],
          "order_line scan: device reads downgraded")
    check(hits_dev == hits_ref, "scan_where: device differs from numpy")
    check(agg_dev == agg_ref, "aggregate: device differs from numpy")
    check([k for k, _ in hits_dev] == [k for k, _ in hits_silo],
          "scan_where: matched keys differ from silo")
    floats = float_columns(ol.schema)
    drift = compare("scan_where", hits_dev, [r for _, r in hits_silo],
                    written, floats)
    check(agg_dev.keys() == agg_silo.keys(), "aggregate: groups differ")
    want_amount = dict.fromkeys(agg_silo, 0.0)
    for key, row in hits_silo:
        want_amount[(row["ol_d_id"],)] += float(
            written.get(key, row)["ol_amount"])
    for g, want in agg_silo.items():
        got = agg_dev[g]
        for exact in ("n", "qty", "last_o"):
            check(got[exact] == want[exact], f"aggregate {g}.{exact} differs")
        d = abs(got["amount"] - want_amount[g])
        drift = max(drift, d)
        bound = (got["n"] * floats["ol_amount"] / 2
                 + 1e-9 * max(1.0, abs(want_amount[g])))
        check(d <= bound, f"aggregate {g}.amount: off by {d} > {bound}")
    log(f"scan order_line ol_o_id>={o_tail}: rows={len(hits_dev)} "
        f"groups={len(agg_dev)} device_calls={calls} device==numpy "
        f"silo_keys_equal float_drift={drift!r}")


def run(args: argparse.Namespace) -> dict:
    import numpy as np

    info = device_info()
    if info["platform"] != "tpu":
        raise SmokeFailure(f"no TPU: JAX's devices are {info['platform']}")

    from repro import device, telemetry
    from repro.oltp import tpcc

    cache_dir = device.configure_compile_cache()
    cache = CompileCacheEvents()
    log(f"compile cache: {cache_dir}")
    rng = np.random.default_rng(args.seed)

    t0 = time.perf_counter()
    pop = tpcc.generate_tpcc(seed=args.seed, **SPEC)
    log(f"generate: {time.perf_counter() - t0!r}s rows="
        + json.dumps({k: len(v) for k, v in pop.items()}))

    t0 = time.perf_counter()
    db, _ = tpcc.build_tpcc_database(population=pop,
                                     store_kwargs={"use_pallas": True})
    t_blitz = time.perf_counter() - t0
    t0 = time.perf_counter()
    silo, _ = tpcc.build_tpcc_database(backend="silo", population=pop)
    log(f"load: blitzcrank {t_blitz!r}s, silo {time.perf_counter() - t0!r}s")

    per_table = plan_summary(db, READ_KEYS)
    keys = {name: sample_keys(silo[name], READ_KEYS, rng)
            for name in db.table_names}
    three_way_reads(db, silo, keys, "loaded", per_table, written={})

    n_ops = MIX_TICKS * MIX_BATCH
    recorder = WriteRecorder(db)
    for label, target in (("blitzcrank", recorder), ("silo", silo)):
        t0 = time.perf_counter()
        c0 = counters()
        counts = tpcc.run_tpcc_mix(target, n_ops, seed=args.seed,
                                   batch=MIX_BATCH)
        log(f"mix {label}: {time.perf_counter() - t0!r}s "
            f"device_calls={device_calls(c0, counters())} "
            + json.dumps(counts))
        check(counts["ops"] == n_ops, f"mix {label}: ran {counts['ops']} ops")
        if label == "blitzcrank":
            blitz_counts = counts
    check(blitz_counts == counts, "mix counts differ between stores")
    check(counters()["repro.plan.pallas_downgrade"] == 0,
          "mix: device reads downgraded")

    # after the mix, read the rows it wrote as well as a fresh sample
    written = recorder.written
    for name in db.table_names:
        fresh = sample_keys(silo[name], READ_KEYS, rng)
        wrote = sorted(written[name])
        if len(wrote) > READ_KEYS:
            pick = rng.choice(len(wrote), size=READ_KEYS, replace=False)
            wrote = [wrote[int(i)] for i in sorted(pick)]
        keys[name] = sorted(set(fresh) | set(wrote))
        log(f"mix wrote {name}: rows={len(written[name])} "
            f"read_back={len(wrote)}")
    three_way_reads(db, silo, keys, "after mix", per_table, written)
    scan_three_ways(db, silo, written["order_line"])

    for name, row in per_table.items():
        log(f"table {name}: " + json.dumps(row))
    for name, row in per_table.items():
        if name not in HOST_ONLY:
            check(row["fast_rows"] > 0 and row["device_calls"] > 0,
                  f"{name}: no read decoded on the device")
    check(any(row["device_calls"] for row in per_table.values()),
          "no table decoded on the device")
    c = counters()
    jit_s = telemetry.histogram("repro.plan.compile.pallas_jit").total_seconds()
    log(f"device decode calls: compiled={c['repro.plan.cache.pallas_miss']} "
        f"replayed={c['repro.plan.cache.pallas_hit']} "
        f"downgrades={c['repro.plan.pallas_downgrade']}")
    log(f"compile: first-call seconds={jit_s!r} cache="
        + json.dumps(cache.counts) + f" cache_hit={cache.counts['hits'] > 0}")
    return info


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repository source at {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    try:
        info = run(args)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
