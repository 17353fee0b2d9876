#!/usr/bin/env python
"""Benchmark entry — run by the driver on real TPU hardware.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "extra"}.
A run that finds no TPU exits non-zero and measures nothing: there is no
CPU fallback (`extra["device"]` names the platform, kind and count).

Covers the five BASELINE.json configs:
  q1_sf1    TPC-H Q1  SF1   — hash aggregation over lineitem
  q6_sf10   TPC-H Q6  SF10  — scan-filter-aggregate
  q3_sf10   TPC-H Q3  SF10  — 3-way join
  q9        TPC-H Q9  — multi-join + partitioned aggregation
            (scale from BENCH_SF_Q9, default 100; may budget-downscale)
  q64       TPC-DS Q64 — wide star-join (tpcds connector; BENCH_SF_Q64)

Result keys record the sf that ACTUALLY ran (e.g. q9_sf10) and every
record carries "sf_actual" — no config key may claim a scale it didn't run.

Crash-safety architecture (round-4 redesign): the parent process NEVER
imports jax — each config runs in a subprocess with its own wall-clock
cap, so a pathological compile or a hung device can only burn one
config's budget, not the whole driver window (and the chip belongs to one
process at a time: a parent on JAX would starve every child). Results
accumulate in the parent after every config, and a
SIGTERM/SIGINT handler emits the final JSON line immediately — an
external `timeout` kill still leaves driver-parseable evidence.

Data path: every config reads parquet through ParquetConnector (the real
storage layer — row groups, column pruning, dictionary-preserving decode).
Datasets generate ONCE into BENCH_DATA_DIR (default .bench_data/) and are
reused across configs AND rounds. XLA executables persist across rounds
via the compilation cache (presto_tpu.__init__).

The headline metric stays TPC-H Q1 rows/s vs the reference fork's own
published number (presto-orc results.txt:19: Aria selective reader runs
the Q1 scan kernel over SF1 lineitem in 0.79 s = 7.6M rows/s). We run the
FULL Q1 (scan + filter + aggregate + sort), not just the scan. Q6 likewise
(results.txt:18). Q3/Q9/Q64 have no published reference numbers; raw
rows/s + seconds are recorded for cross-round tracking.

Env knobs:
  BENCH_CONFIGS   comma list (default: all five)
  BENCH_BUDGET_S  total wall budget (default 2400)
  BENCH_DATA_DIR  dataset directory (default <repo>/.bench_data)
  BENCH_SF_Q9 / BENCH_SF_Q64  override the big scale factors (default 100)
  BENCH_SF_SERVING / BENCH_SERVING_CLIENTS / BENCH_SERVING_QUERIES
                  serving_slo closed-loop knobs (default 0.1 / 8 / 4)
  BENCH_PALLAS=1  run aggregation configs with the Pallas MXU kernel
  BENCH_SPILL_ROWS  build-side rows for the spill_skew config (default 400000)
  BENCH_SF_MULTIWAY  scale factor for the multiway_ab join-chain A/B
                  (default 0.1)
  BENCH_ADAPTIVE_ROWS  rows for the adaptive_ab mis-estimated group-by
                  (default 16000)
"""

import json
import os
import shutil
import signal
import subprocess
import sys
import time

_T0 = time.time()
_HERE = os.path.dirname(os.path.abspath(__file__))
DATA_DIR = os.environ.get("BENCH_DATA_DIR", os.path.join(_HERE, ".bench_data"))


def _log(msg: str):
    print(f"[bench +{time.time() - _T0:7.1f}s] {msg}", file=sys.stderr, flush=True)


Q1 = """
select l_returnflag, l_linestatus,
       sum(l_quantity) as sum_qty,
       sum(l_extendedprice) as sum_base_price,
       sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
       sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge,
       avg(l_quantity) as avg_qty,
       avg(l_extendedprice) as avg_price,
       avg(l_discount) as avg_disc,
       count(*) as count_order
from lineitem
where l_shipdate <= date '1998-12-01' - interval '90' day
group by l_returnflag, l_linestatus
order by l_returnflag, l_linestatus
"""

Q6 = """
select sum(l_extendedprice * l_discount) as revenue
from lineitem
where l_shipdate >= date '1994-01-01'
  and l_shipdate < date '1995-01-01'
  and l_discount between 0.05 and 0.07
  and l_quantity < 24
"""

Q3 = """
select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
       o_orderdate, o_shippriority
from customer, orders, lineitem
where c_mktsegment = 'BUILDING' and c_custkey = o_custkey
  and l_orderkey = o_orderkey and o_orderdate < date '1995-03-15'
  and l_shipdate > date '1995-03-15'
group by l_orderkey, o_orderdate, o_shippriority
order by revenue desc, o_orderdate limit 10
"""

# q3-shaped probe/build microbench: the lineitem→orders join + group-by
# that dominates q3, without the customer dimension — isolates the
# pipeline-breaker cost the radix partitioning targets
JOIN_SF1 = """
select o_orderpriority, count(*) as c,
       sum(l_extendedprice * (1 - l_discount)) as revenue
from lineitem join orders on l_orderkey = o_orderkey
where o_orderdate < date '1995-03-15' and l_shipdate > date '1995-03-15'
group by o_orderpriority
order by o_orderpriority
"""

Q9 = """
select nation, o_year, sum(amount) as sum_profit
from (
  select n_name as nation,
         extract(year from o_orderdate) as o_year,
         l_extendedprice * (1 - l_discount) - ps_supplycost * l_quantity as amount
  from part, supplier, lineitem, partsupp, orders, nation
  where s_suppkey = l_suppkey and ps_suppkey = l_suppkey
    and ps_partkey = l_partkey and p_partkey = l_partkey
    and o_orderkey = l_orderkey and s_nationkey = n_nationkey
    and p_name like '%green%'
) profit
group by nation, o_year
order by nation, o_year desc
"""

# TPC-DS Q64 (spec shape): two-instance CTE over the cross-channel star
# join, self-joined on item across consecutive years. The heavy lifting —
# store_sales ⋈ store_returns ⋈ catalog_sales + five dimension joins —
# matches the spec text; cs_ui / cross-year predicates included.
Q64 = """
with cross_sales as (
  select i_product_name as product_name, i_item_sk as item_sk,
         s_store_name as store_name, s_zip as store_zip,
         d_year as syear,
         count(*) as cnt,
         sum(ss_wholesale_cost) as s1,
         sum(ss_list_price) as s2,
         sum(ss_coupon_amt) as s3
  from store_sales, store_returns, date_dim, store, item, customer
  where ss_item_sk = i_item_sk
    and ss_item_sk = sr_item_sk and ss_ticket_number = sr_ticket_number
    and ss_sold_date_sk = d_date_sk
    and ss_store_sk = s_store_sk
    and ss_customer_sk = c_customer_sk
    and i_current_price between 35 and 44
    and i_product_name is not null
  group by i_product_name, i_item_sk, s_store_name, s_zip, d_year
)
select cs1.product_name, cs1.store_name, cs1.store_zip,
       cs1.syear, cs1.cnt, cs1.s1, cs1.s2, cs1.s3,
       cs2.s1 as s1_2, cs2.s2 as s2_2, cs2.s3 as s3_2, cs2.syear as syear_2,
       cs2.cnt as cnt_2
from cross_sales cs1, cross_sales cs2
where cs1.item_sk = cs2.item_sk
  and cs1.syear = 2000 and cs2.syear = 2001
  and cs2.cnt <= cs1.cnt
  and cs1.store_name = cs2.store_name and cs1.store_zip = cs2.store_zip
order by cs1.product_name, cs1.store_name, cs2.cnt limit 100
"""

# reference: Aria selective reader scan kernels over SF1 lineitem
# (presto-orc/src/main/java/com/facebook/presto/orc/results.txt:18-19)
_SF1_ROWS = 6_001_215
_REF = {
    "q1": _SF1_ROWS / 0.79,   # rows/s
    "q6": _SF1_ROWS / 0.54,
}

# name -> (sql, dataset kind, nominal sf, driving table, exec overrides).
# q9/q64 carry NO sf in their key: their scale comes from BENCH_SF_Q9/Q64
# with budget-driven downscaling, and a key like "q9_sf100" that silently
# ran SF10 poisoned cross-round comparisons. Every result record carries
# "sf_actual" — the scale that really ran.
_CONFIGS = {
    "q1_sf1": (Q1, "tpch", 1.0, "lineitem", {}),
    # fragment-fusion A/B: the same Q1 with the fused lax.scan ingest
    # disabled — the per-batch dispatch loop this round removes. The
    # rows/s delta between q1_sf1 and this key IS the dispatch-collapse
    # win
    "q1_nofuse_sf1": (Q1, "tpch", 1.0, "lineitem",
                      {"fragment_fusion": False}),
    "q6_sf10": (Q6, "tpch", 10.0, "lineitem", {}),
    "q3_sf10": (Q3, "tpch", 10.0, "lineitem", {}),
    "join_sf1": (JOIN_SF1, "tpch", 1.0, "lineitem",
                 {"radix_partitions": 8}),
    # breaker-engine A/B: the same keyed aggregation forced through the
    # Pallas linear-probing hash engine vs the sort/segment engine. The
    # rows/s delta between the pair IS the hash-engine win on a
    # high-duplication group-by (on TPU the hash path replaces the
    # O(n log n) sort with one MXU-free probe pass; the CBO picks it
    # when est. duplication x4+ — plan/stats.choose_breaker_engine)
    "groupby_engine_ab_sf1": (Q1, "tpch", 1.0, "lineitem",
                              {"breaker_engine": "hash"}),
    "groupby_engine_ab_sort_sf1": (Q1, "tpch", 1.0, "lineitem",
                                   {"breaker_engine": "sort"}),
    "q9": (Q9, "tpch", None, "lineitem", {"runs": 2}),
    "q64": (Q64, "tpcds", None, "store_sales",
            {"agg_capacity": 1 << 16, "runs": 2}),
}

# legacy config names (pre-rename BENCH_CONFIGS env values keep working)
_ALIASES = {"q9_sf100": "q9", "q64_sf100": "q64"}

# Per-config wall caps (seconds): one slow compile can only burn this much.
_CAPS = {"q1_sf1": 420, "q1_nofuse_sf1": 420, "q6_sf10": 420,
         "q3_sf10": 600, "join_sf1": 420, "q9": 900, "q64": 900,
         "groupby_engine_ab_sf1": 420, "groupby_engine_ab_sort_sf1": 420}


def _dataset_ready(kind: str, sf: float) -> bool:
    marker = "lineitem" if kind == "tpch" else "store_sales"
    d = os.path.join(DATA_DIR, f"{kind}_sf{sf:g}")
    return (os.path.exists(os.path.join(d, f"{marker}.parquet"))
            or os.path.exists(os.path.join(d, f"{marker}.parts")))


def _resolve_sf(kind: str, sf: float, remaining: float) -> float:
    """Downscale a config's SF when its dataset is absent AND generating it
    cannot fit the remaining wall budget (SF100 generation is hours)."""
    if _dataset_ready(kind, sf):
        return sf
    est_per_sf = 60.0  # measured ~55 s/SF for the chunked tpch exporter
    if sf * est_per_sf < remaining * 0.5:
        return sf
    for cand in (10.0, 1.0, 0.1):
        if cand >= sf:
            continue
        if _dataset_ready(kind, cand) or cand * est_per_sf < remaining * 0.4:
            _log(f"{kind} sf={sf:g}: dataset absent and generation won't "
                 f"fit the budget — downscaling to sf={cand:g}")
            return cand
    return 0.1


# ---------------------------------------------------------------- child ----

def _child(name: str, sf: float, cap_s: float = 0.0):
    """Run ONE config in this process; print a single JSON result line.
    `cap_s` is the parent's kill deadline: once one timed run landed,
    further runs are skipped if they might not fit — ONE number inside
    the cap beats the best of three outside it."""
    sql, kind, _, driving_table, over = _CONFIGS[name]
    from presto_tpu.catalog.parquet import (
        ParquetConnector, export_tpch_chunked, export_tpcds_chunked,
    )
    from presto_tpu.connector import Catalog
    from presto_tpu.exec import ExecConfig, LocalRunner

    d = os.path.join(DATA_DIR, f"{kind}_sf{sf:g}")
    t0 = time.time()
    if kind == "tpch":
        export_tpch_chunked(d, sf, log=_log)
    else:
        export_tpcds_chunked(d, sf, log=_log)
    gen_s = round(time.time() - t0, 1)
    if gen_s > 1:
        _log(f"{kind} sf={sf:g}: dataset ensured in {gen_s}s -> {d}")
    cat = Catalog()
    conn = ParquetConnector(d, name=kind)
    cat.register(kind, conn, default=True)
    nrows = int(conn.get_table(driving_table).row_count)

    runs = over.get("runs", 3)
    cfg = {k: v for k, v in over.items() if k != "runs"}
    # ahead-of-stream precompilation on by default: chain programs trace
    # on a side pool while the scan decodes, shrinking warmup_s
    cfg.setdefault("precompile_workers", 2)
    # device cost/HBM accounting on for bench children: the roofline block
    # below needs XLA's per-program analysis; its cost lands in warmup
    cfg.setdefault("devprof", "on")
    runner = LocalRunner(cat, ExecConfig(batch_rows=1 << 20, **cfg))
    from presto_tpu.exec import programs
    snap0 = programs.snapshot()
    t0 = time.time()
    runner.run_batch(sql)  # warm-up: compiles + host/device caches
    warm_s = round(time.time() - t0, 1)
    snap1 = programs.snapshot()
    _log(f"{name}: warmup (compile + cache fill) {warm_s}s "
         f"({snap1['compiles'] - snap0['compiles']} compiles, "
         f"{snap1['trace_wall_s'] - snap0['trace_wall_s']:.1f}s trace wall)")
    times = []
    for _ in range(runs):
        if times and cap_s and (
                time.time() - _T0 + max(times) > cap_s * 0.85):
            _log(f"{name}: skipping remaining runs (cap {cap_s:.0f}s)")
            break
        t0 = time.perf_counter()
        out = runner.run_batch(sql)
        out.num_live()  # block on device completion
        times.append(time.perf_counter() - t0)
    best = min(times)
    _log(f"{name}: best {best:.3f}s of {sorted(round(t, 3) for t in times)} "
         f"({nrows} {driving_table} rows)")
    snap2 = programs.snapshot()
    lookups = snap2["hits"] + snap2["misses"]
    # dispatch-collapse accounting (exec/fragment_jit.py): how many fused
    # window dispatches vs per-batch step dispatches the LAST timed run
    # issued — the counters EXPLAIN ANALYZE and /v1/metrics also expose
    st = getattr(runner, "last_stats", {}) or {}
    print(json.dumps({
        "seconds": round(best, 4), "rows": nrows, "sf": sf, "sf_actual": sf,
        "rows_per_sec": round(nrows / best, 1), "warmup_s": warm_s,
        "fragment": {
            "fused_dispatches": st.get("fragment.dispatches", 0),
            "fused_batches": st.get("fragment.fused_batches", 0),
            "batch_dispatches": st.get("fragment.batch_dispatches", 0),
        },
        "compile": {
            "warm_compiles": snap1["compiles"] - snap0["compiles"],
            "post_warm_compiles": snap2["compiles"] - snap1["compiles"],
            "cache_hits": snap2["hits"],
            "cache_misses": snap2["misses"],
            "hit_rate": round(snap2["hits"] / lookups, 3) if lookups else 0.0,
            "trace_wall_s": round(snap2["trace_wall_s"], 2),
        },
        "hbo": _hbo_snapshot(st),
        "roofline": _roofline_snapshot(best),
    }), flush=True)


def _roofline_snapshot(wall_s):
    """Device cost/HBM accounting for a bench child record: call-weighted
    FLOPs and bytes the timed run dispatched, achieved rates over the best
    wall time, and the honest device label — on CPU the device block says
    available=false, so readers know the numbers are XLA static analysis
    over real wall time, not hardware counters."""
    from presto_tpu.obs import devprof

    s = devprof.summary(wall_s=wall_s)
    return {
        "programs_analyzed": s["programs"],
        "total_flops": round(s["total_flops"], 1),
        "total_bytes_accessed": round(s["total_bytes_accessed"], 1),
        "arithmetic_intensity": (round(s["arithmetic_intensity"], 4)
                                 if s["arithmetic_intensity"] else None),
        "achieved_flops_per_s": round(s.get("achieved_flops_per_s", 0.0), 1),
        "achieved_bytes_per_s": round(s.get("achieved_bytes_per_s", 0.0), 1),
        "peak_program_footprint_bytes": s["peak_program_footprint_bytes"],
        "device": s["device"],
    }


def _hbo_snapshot(st):
    """Runtime-statistics feedback accounting for a bench child record:
    replay waves paid this query + the process HBO counters."""
    from presto_tpu.obs import runstats
    snap = runstats.snapshot()
    return {
        "replay_waves": st.get("breaker.replay_waves", 0),
        "observations": sum(snap["observations"].values()),
        "would_flip": sum(snap["would_flip"].values()),
        "corrections": sum(snap["corrections"].values()),
        "history_entries": len(snap["history"]),
    }


def _histogram_quantile(body: str, family: str, q: float):
    """Quantile from a Prometheus log-bucket histogram exposition, summed
    over every label set of the family (cumulative counts add across
    groups at equal `le` edges). Linear interpolation inside the bucket;
    None when the family has no samples."""
    import re

    pat = re.compile(rf"^{family}_bucket{{(.*)}} (\S+)$")
    buckets = {}
    for ln in body.splitlines():
        m = pat.match(ln)
        if not m:
            continue
        le = None
        for part in m.group(1).split(","):
            k, _, v = part.partition("=")
            if k.strip() == "le":
                le = float("inf") if v.strip('"') == "+Inf" else float(
                    v.strip('"'))
        if le is not None:
            buckets[le] = buckets.get(le, 0.0) + float(m.group(2))
    if not buckets:
        return None
    edges = sorted(buckets)
    total = buckets[edges[-1]]
    if total <= 0:
        return None
    target = q * total
    prev_edge, prev_count = 0.0, 0.0
    for e in edges:
        c = buckets[e]
        if c >= target:
            if e == float("inf"):
                return prev_edge
            span = c - prev_count
            frac = (target - prev_count) / span if span > 0 else 1.0
            return prev_edge + frac * (e - prev_edge)
        prev_edge, prev_count = e, c
    return edges[-2] if len(edges) > 1 else edges[-1]


def _serving_child(sf: float, n_clients: int, per_client: int):
    """One closed-loop serving run: boot an in-process cluster over the
    parquet dataset, drive n_clients concurrent client threads through a
    mixed TPC-H workload over the real statement protocol, then read
    p50/p99 queue-wait and e2e off the lifecycle SLO histograms the
    coordinator scraped up (/v1/metrics — the same numbers an operator's
    dashboard would chart)."""
    import threading
    import urllib.request

    from presto_tpu.catalog.parquet import ParquetConnector, export_tpch_chunked
    from presto_tpu.connector import Catalog
    from presto_tpu.server.coordinator import DistributedRunner

    d = os.path.join(DATA_DIR, f"tpch_sf{sf:g}")
    export_tpch_chunked(d, sf, log=_log)
    cat = Catalog()
    conn = ParquetConnector(d, name="tpch")
    cat.register("tpch", conn, default=True)
    nrows = int(conn.get_table("lineitem").row_count)
    dr = DistributedRunner(cat, n_workers=2)
    base = dr.coordinator.url
    mix = [Q1, Q6, JOIN_SF1]
    errors = []
    client_walls = []
    lock = threading.Lock()

    def client(cid: int):
        for i in range(per_client):
            sql = mix[(cid + i) % len(mix)]
            t0 = time.perf_counter()
            try:
                req = urllib.request.Request(
                    base + "/v1/statement", data=sql.encode(),
                    headers={"X-Presto-User": f"bench-{cid}",
                             "Content-Type": "text/plain"})
                doc = json.loads(urllib.request.urlopen(
                    req, timeout=600).read())
                while doc.get("nextUri"):
                    doc = json.loads(urllib.request.urlopen(
                        doc["nextUri"], timeout=600).read())
                if doc.get("error"):
                    raise RuntimeError(doc["error"].get("message"))
                with lock:
                    client_walls.append(time.perf_counter() - t0)
            except Exception as e:  # noqa: BLE001
                with lock:
                    errors.append(f"{type(e).__name__}: {e}")
                return

    t0 = time.time()
    threads = [threading.Thread(target=client, args=(c,))
               for c in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.time() - t0
    body = urllib.request.urlopen(
        base + "/v1/metrics", timeout=30).read().decode()
    dr.close()
    rec = {
        "clients": n_clients, "queries": len(client_walls),
        "errors": errors[:5], "sf": sf, "sf_actual": sf, "rows": nrows,
        "wall_s": round(wall, 2),
        "queries_per_sec": round(len(client_walls) / wall, 3) if wall else 0,
    }
    for seg, fam in (("queue_wait", "presto_tpu_query_queue_wait_seconds"),
                     ("e2e", "presto_tpu_query_e2e_seconds")):
        for q, label in ((0.5, "p50"), (0.99, "p99")):
            v = _histogram_quantile(body, fam, q)
            rec[f"{seg}_{label}_s"] = round(v, 4) if v is not None else None
    print(json.dumps(rec), flush=True)


def _serving_cached_child(sf: float):
    """Result-cache economics: the same mixed workload served twice over
    the statement protocol with ``result_cache=query`` on the session.
    Round 1 (cold) pays plan+compile+execute; rounds 2-3 (warm) must be
    served out of the fingerprint-keyed result cache — the record carries
    cold/warm p50, the hit rate, and the bytes the cache holds for it."""
    import statistics
    import urllib.request

    from presto_tpu.catalog.parquet import ParquetConnector, export_tpch_chunked
    from presto_tpu.connector import Catalog
    from presto_tpu.server.coordinator import DistributedRunner

    d = os.path.join(DATA_DIR, f"tpch_sf{sf:g}")
    export_tpch_chunked(d, sf, log=_log)
    cat = Catalog()
    conn = ParquetConnector(d, name="tpch")
    cat.register("tpch", conn, default=True)
    dr = DistributedRunner(cat, n_workers=2)
    base = dr.coordinator.url
    mix = [Q1, Q6, JOIN_SF1]

    def run_one(sql):
        t0 = time.perf_counter()
        req = urllib.request.Request(
            base + "/v1/statement", data=sql.encode(),
            headers={"X-Presto-User": "bench-cached",
                     "X-Presto-Session": "result_cache=query",
                     "Content-Type": "text/plain"})
        doc = json.loads(urllib.request.urlopen(req, timeout=600).read())
        while doc.get("nextUri"):
            doc = json.loads(urllib.request.urlopen(
                doc["nextUri"], timeout=600).read())
        if doc.get("error"):
            raise RuntimeError(doc["error"].get("message"))
        return time.perf_counter() - t0

    cold = [run_one(sql) for sql in mix]
    warm = [run_one(sql) for _ in range(2) for sql in mix]
    body = urllib.request.urlopen(
        base + "/v1/metrics", timeout=30).read().decode()
    dr.close()

    def _gauge(name):
        for line in body.splitlines():
            if line.startswith(name + "{") or line.startswith(name + " "):
                try:
                    return float(line.rsplit(" ", 1)[1])
                except ValueError:
                    pass
        return 0.0

    hits = _gauge("presto_tpu_result_cache_hits_total")
    misses = _gauge("presto_tpu_result_cache_misses_total")
    cold_p50 = statistics.median(cold)
    warm_p50 = statistics.median(warm)
    rec = {
        "sf": sf, "queries": len(mix),
        "cold_p50_s": round(cold_p50, 4), "warm_p50_s": round(warm_p50, 4),
        "speedup": round(cold_p50 / warm_p50, 1) if warm_p50 else None,
        "cache_hits": int(hits), "cache_misses": int(misses),
        "hit_rate": round(hits / (hits + misses), 3) if hits + misses else 0,
        "cache_bytes": int(_gauge("presto_tpu_result_cache_bytes")),
    }
    print(json.dumps(rec), flush=True)


def _spill_child(n_rows: int):
    """Skew-adversarial spilled join: 90% one-hot build keys joined under a
    memory pool ~40x smaller than the build side, vs the same join
    unconstrained. The slowdown factor is the price of graceful degradation
    under memory pressure; the stat block records how the dynamic hybrid
    hash converged (partition leaves, next-bit repartitions, role
    reversals) and the checksum proves the degraded path stayed correct."""
    import numpy as np
    import pandas as pd

    from presto_tpu.catalog.memory import MemoryConnector
    from presto_tpu.connector import Catalog
    from presto_tpu.exec import ExecConfig, LocalRunner
    from presto_tpu.exec.runtime import ExecContext, run_plan
    from presto_tpu.verifier import result_checksum

    rng = np.random.default_rng(47)
    bk = np.where(rng.random(n_rows) < 0.9, 7,
                  rng.integers(0, 50_000, n_rows)).astype(np.int64)
    conn = MemoryConnector()
    conn.add_table("build", pd.DataFrame({
        "bk": bk, "w": rng.normal(size=n_rows)}))
    n_probe = n_rows // 2
    conn.add_table("probe", pd.DataFrame({
        "k": rng.integers(0, 50_000, n_probe).astype(np.int64),
        "v": rng.normal(size=n_probe)}))
    cat = Catalog()
    cat.register("m", conn, default=True)
    sql = ("select probe.v, build.w from probe join build "
           "on probe.k = build.bk")

    base = LocalRunner(cat, ExecConfig(batch_rows=1 << 15))
    base.run_batch(sql)  # warm-up: compiles
    t0 = time.perf_counter()
    ref = base.run_batch(sql)
    ref.num_live()
    base_s = time.perf_counter() - t0

    pool = max(1 << 17, (n_rows * 16) // 40)
    lim = LocalRunner(cat, ExecConfig(
        batch_rows=1 << 15, memory_pool_bytes=pool, spill_partitions=8,
        spill_max_depth=4))
    times, last = [], None
    for i in range(3):  # first iteration doubles as spill-path warm-up
        qp = lim.plan(sql)
        ctx = ExecContext(cat, lim.config)
        t0 = time.perf_counter()
        out = run_plan(qp, ctx)
        out.num_live()
        if i > 0:
            times.append(time.perf_counter() - t0)
        last = (ctx, out)
    ctx, out = last
    best = min(times)
    print(json.dumps({
        "rows": n_rows + n_probe, "seconds": round(best, 4),
        "rows_per_sec": round((n_rows + n_probe) / best, 1),
        "unconstrained_seconds": round(base_s, 4),
        "degradation_factor": round(best / base_s, 2) if base_s else None,
        "pool_bytes": pool,
        "spilled_bytes": ctx.spill_manager.total_spilled_bytes,
        "spill_partitions": ctx.stats.get("spill.partitions", 0),
        "spill_repartitions": ctx.stats.get("spill.repartitions", 0),
        "spill_role_reversals": ctx.stats.get("spill.role_reversals", 0),
        "spill_revocations": ctx.stats.get("spill.revocations", 0),
        "checksum_equal": result_checksum(out) == result_checksum(ref),
    }), flush=True)


def _multiway_child(sf: float):
    """Star-chain join A/B (PR18 multiway engine): q3/q9/q64-shaped
    chains run binary (join_mode=off — the pre-collapse path) vs forced
    multiway in one process. Per mode: best wall, compiled-program count
    (process cache reset between modes so each pays its own compiles),
    and for the q3 shape a 2-worker distributed leg counting exchanged
    bytes (OutputBuffer page lengths) and plan fragments. The checksum
    ties the A and B legs to the same answer."""
    from presto_tpu.catalog.tpch import tpch_catalog
    from presto_tpu.exec import ExecConfig, LocalRunner, programs
    from presto_tpu.verifier import result_checksum

    cat = tpch_catalog(sf)
    queries = {
        "q3_shape": (
            "select o.o_orderkey, sum(l.l_extendedprice) rev "
            "from lineitem l "
            "join orders o on l.l_orderkey = o.o_orderkey "
            "join customer c on o.o_custkey = c.c_custkey "
            "where c.c_mktsegment = 'BUILDING' "
            "group by o.o_orderkey"),
        "q9_shape": (
            "select s.s_nationkey, count(*) c, "
            "sum(l.l_extendedprice * (1 - l.l_discount)) v "
            "from lineitem l "
            "join supplier s on l.l_suppkey = s.s_suppkey "
            "join part p on l.l_partkey = p.p_partkey "
            "join orders o on l.l_orderkey = o.o_orderkey "
            "group by s.s_nationkey"),
        "q64_shape": (
            "select n.n_name, count(*) c "
            "from orders o "
            "join customer c on o.o_custkey = c.c_custkey "
            "left join nation n on c.c_nationkey = n.n_nationkey "
            "join lineitem l on o.o_orderkey = l.l_orderkey "
            "group by n.n_name"),
    }
    rec = {"sf_actual": sf}
    for name, sql in queries.items():
        entry = {}
        sums = {}
        for mode in ("binary", "multiway"):
            jm = "off" if mode == "binary" else "multiway"
            r = LocalRunner(cat, ExecConfig(batch_rows=1 << 15,
                                            join_mode=jm))
            programs.reset(counters_only=False)
            r.run_batch(sql)  # warm-up pays compiles
            compiles = programs.snapshot()["compiles"]
            times = []
            for _ in range(2):
                t0 = time.perf_counter()
                out = r.run_batch(sql)
                out.num_live()
                times.append(time.perf_counter() - t0)
            sums[mode] = result_checksum(out)
            entry[mode] = {"wall_s": round(min(times), 4),
                           "programs": int(compiles)}
        entry["checksum_equal"] = sums["binary"] == sums["multiway"]
        b, m = entry["binary"], entry["multiway"]
        entry["speedup"] = (round(b["wall_s"] / m["wall_s"], 2)
                            if m["wall_s"] else None)
        rec[name] = entry

    # distributed leg (q3 shape, small fixed sf): exchanged bytes +
    # fragment count, with broadcast suppressed so the binary chain pays
    # its per-join partitioned exchanges
    from presto_tpu.server import buffers
    from presto_tpu.server.coordinator import DistributedRunner

    dcat = cat if sf <= 0.1 else tpch_catalog(0.05)
    counter = {"bytes": 0, "pages": 0}
    orig = buffers.OutputBuffer.enqueue

    def counted(self, partition, page):
        counter["bytes"] += len(page)
        counter["pages"] += 1
        return orig(self, partition, page)

    buffers.OutputBuffer.enqueue = counted
    try:
        dist = {}
        for mode in ("binary", "multiway"):
            jm = "off" if mode == "binary" else "multiway"
            counter["bytes"] = counter["pages"] = 0
            with DistributedRunner(
                    dcat, n_workers=2,
                    config=ExecConfig(batch_rows=1 << 15, join_mode=jm),
                    broadcast_threshold_rows=0) as dr:
                dplan = dr.plan_distributed(queries["q3_shape"])
                dr.run(queries["q3_shape"])
            dist[mode] = {"exchange_bytes": counter["bytes"],
                          "exchange_pages": counter["pages"],
                          "fragments": len(dplan.fragments)}
        rec["q3_distributed"] = dist
    finally:
        buffers.OutputBuffer.enqueue = orig
    print(json.dumps(rec), flush=True)


def _adaptive_child(n_rows: int):
    """Mis-estimated group-by A/B for in-run adaptation (PR20): grouping
    through `k % 100000` blinds NDV estimation (est = rows*0.1, actual =
    full key NDV), so adaptive=off picks the hash engine, overflows, and
    pays replay waves; adaptive=on flips engines / presizes from the
    wave's OBSERVED group count. Per mode: best wall of two runs, replay
    waves, and acted action counts; the checksum proves adaptation
    changed the schedule, never the answer."""
    import numpy as np
    import pandas as pd

    from presto_tpu.catalog.memory import MemoryConnector
    from presto_tpu.connector import Catalog
    from presto_tpu.exec import ExecConfig, LocalRunner
    from presto_tpu.exec import adaptive as _adaptive
    from presto_tpu.obs import runstats

    conn = MemoryConnector()
    conn.add_table("t", pd.DataFrame({
        "k": np.arange(n_rows, dtype=np.int64),
        "v": np.ones(n_rows, dtype=np.int64)}))
    cat = Catalog()
    cat.register("m", conn, default=True)
    sql = "select k % 100000 as g, sum(v) as s from m.t group by 1"

    rec = {"rows": n_rows}
    frames = {}
    for mode in ("off", "on"):
        times, df, r = [], None, None
        for _ in range(2):  # first run doubles as this mode's compile
            runstats.reset()  # every run is a cold-HBO run with a fresh
            _adaptive.reset()  # plan (flip-at-most-once pins the node)
            r = LocalRunner(cat, ExecConfig(adaptive=mode))
            t0 = time.perf_counter()
            df = r.run(sql)
            times.append(time.perf_counter() - t0)
        frames[mode] = df.sort_values("g", ignore_index=True)
        m = {"wall_s": round(min(times), 4),
             "waves": int(r.last_stats.get("breaker.replay_waves", 0)),
             "engine_flips": int(
                 r.last_stats.get("breaker.engine_flips", 0))}
        if mode == "on":
            acts = {}
            for a in _adaptive.recent_decisions():
                if a.get("acted"):
                    acts[a["kind"]] = acts.get(a["kind"], 0) + 1
            m["actions"] = acts
        rec[mode] = m
    rec["checksum_equal"] = bool(frames["on"].equals(frames["off"]))
    rec["wave_reduction"] = rec["off"]["waves"] - rec["on"]["waves"]
    print(json.dumps(rec), flush=True)


def _compile_tail_child(mode: str):
    """One serving boot + first-seen-query measurement (PR16 compile
    farm A/B). The parent sequences four of these against one cache dir:
    cold (no farm), record (corpus + artifacts), converge (boot #1 — the
    HBO-informed plan fingerprints settle and their programs persist),
    armed (boot #2 — every artifact prewarmed, first query should pay
    neither trace nor backend compile)."""
    import urllib.request

    from presto_tpu.catalog.tpch import tpch_catalog
    from presto_tpu.exec import farm, programs
    from presto_tpu.server.coordinator import DistributedRunner

    agg = ("select l_returnflag as f, sum(l_quantity) as q, count(*) as c "
           "from lineitem where l_discount > 0.02 "
           "group by l_returnflag order by f")
    join = ("select o_orderpriority as p, count(*) as c from lineitem "
            "join orders on l_orderkey = o_orderkey "
            "group by o_orderpriority order by p")

    cat = tpch_catalog(0.01)
    t0 = time.perf_counter()
    dr = DistributedRunner(cat, n_workers=2)
    boot_s = time.perf_counter() - t0
    base = dr.coordinator.url

    def run_sql(s):
        req = urllib.request.Request(
            base + "/v1/statement", data=s.encode(),
            headers={"X-Presto-User": "bench",
                     "Content-Type": "text/plain"})
        doc = json.load(urllib.request.urlopen(req, timeout=300))
        while doc.get("nextUri"):
            doc = json.load(urllib.request.urlopen(doc["nextUri"],
                                                   timeout=300))

    t0 = time.perf_counter()
    run_sql(agg)
    first_agg_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    run_sql(join)
    first_join_s = time.perf_counter() - t0
    if mode in ("record", "converge"):
        farm.drain()  # async artifact persists must land before exit
    snap = programs.snapshot()
    armed = getattr(dr.coordinator, "_farm_armed", 0)
    dr.close()
    print(json.dumps({
        "mode": mode, "boot_s": round(boot_s, 3),
        "first_agg_s": round(first_agg_s, 3),
        "first_join_s": round(first_join_s, 3),
        "compiles": int(snap["compiles"]),
        "restored": int(snap["restored"]),
        "prewarmed": int(snap["prewarmed"]), "armed": int(armed),
    }), flush=True)


def _run_compile_tail(extra: dict, remaining: float):
    """Cold-boot vs farm-armed-boot A/B (CHANGES.md, PR 16): serving
    warmup_s and first-query e2e, four child processes, one cache dir."""
    # a fixed artifact root, emptied per run (the XLA cache is not here:
    # presto_tpu.compile_cache_dir decides that one)
    d = os.path.join(DATA_DIR, "farm_ab")
    shutil.rmtree(d, ignore_errors=True)
    os.makedirs(d)
    rec = {}
    try:
        for mode in ("cold", "record", "converge", "armed"):
            env = dict(os.environ)
            for k in ("PRESTO_TPU_FARM", "PRESTO_TPU_PROGRAM_PERSIST",
                      "PRESTO_TPU_CACHE_DIR"):
                env.pop(k, None)
            if mode != "cold":
                env.update(PRESTO_TPU_CACHE_DIR=d, PRESTO_TPU_FARM="1",
                           PRESTO_TPU_PROGRAM_PERSIST="1")
            p = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--compile-tail-child", mode],
                env=env, stdout=subprocess.PIPE,
                timeout=min(900, max(180, remaining - 15)))
            lines = p.stdout.decode().strip().splitlines()
            if p.returncode != 0 or not lines:
                rec[mode] = {"error": f"child rc={p.returncode}"}
                continue
            rec[mode] = json.loads(lines[-1])
        cold, armed = rec.get("cold", {}), rec.get("armed", {})
        if "first_agg_s" in cold and "first_agg_s" in armed:
            rec["first_query_speedup"] = round(
                cold["first_agg_s"] / max(armed["first_agg_s"], 1e-9), 2)
            rec["armed_onpath_compiles"] = armed["compiles"]
            _log(f"compile_tail: first query {cold['first_agg_s']}s cold "
                 f"vs {armed['first_agg_s']}s farm-armed "
                 f"({rec['first_query_speedup']}x; armed boot "
                 f"{armed['boot_s']}s prewarmed {armed['prewarmed']} "
                 f"artifacts, {armed['compiles']} on-path compiles)")
        extra["compile_tail"] = rec
    except subprocess.TimeoutExpired:
        extra["compile_tail"] = {"error": "timeout", **rec}
    except Exception as e:
        extra["compile_tail"] = {"error": f"{type(e).__name__}: {e}"}
    finally:
        shutil.rmtree(d, ignore_errors=True)


def _run_spill_skew(extra: dict, remaining: float):
    """Skew-adversarial spill bench (see CHANGES.md, PR 15): the
    graceful-degradation price of a join that cannot fit memory."""
    n_rows = int(os.environ.get("BENCH_SPILL_ROWS", "400000"))
    env = dict(os.environ)
    try:
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--spill-child",
             str(n_rows)],
            env=env, stdout=subprocess.PIPE,
            timeout=min(600, max(120, remaining - 15)))
        lines = p.stdout.decode().strip().splitlines()
        if p.returncode == 0 and lines:
            rec = json.loads(lines[-1])
            _log(f"spill_skew: {rec['seconds']}s spilled vs "
                 f"{rec['unconstrained_seconds']}s unconstrained "
                 f"({rec['degradation_factor']}x, "
                 f"{rec['spilled_bytes']}B spilled, "
                 f"{rec['spill_repartitions']} repartitions, "
                 f"{rec['spill_role_reversals']} reversals, "
                 f"checksum_equal={rec['checksum_equal']})")
            extra["spill_skew"] = rec
        else:
            extra["spill_skew"] = {"error": f"child rc={p.returncode}"}
    except subprocess.TimeoutExpired:
        extra["spill_skew"] = {"error": "timeout"}
    except Exception as e:  # noqa: BLE001
        extra["spill_skew"] = {"error": f"{type(e).__name__}: {e}"}


def _run_multiway_ab(extra: dict, remaining: float):
    """Binary-vs-multiway join chain A/B (see CHANGES.md, PR 18):
    wall, compiled-program count, and distributed exchange bytes for the
    q3/q9/q64 star-chain shapes."""
    sf = float(os.environ.get("BENCH_SF_MULTIWAY", "0.1"))
    env = dict(os.environ)
    try:
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--multiway-child",
             str(sf)],
            env=env, stdout=subprocess.PIPE,
            timeout=min(600, max(120, remaining - 15)))
        lines = p.stdout.decode().strip().splitlines()
        if p.returncode == 0 and lines:
            rec = json.loads(lines[-1])
            q3 = rec.get("q3_shape", {})
            d = rec.get("q3_distributed", {})
            _log(f"multiway_ab: q3 {q3.get('speedup')}x "
                 f"(programs {q3.get('binary', {}).get('programs')}"
                 f"->{q3.get('multiway', {}).get('programs')}, "
                 f"exchange "
                 f"{d.get('binary', {}).get('exchange_bytes')}"
                 f"->{d.get('multiway', {}).get('exchange_bytes')}B, "
                 f"checksum_equal={q3.get('checksum_equal')})")
            extra["multiway_ab"] = rec
        else:
            extra["multiway_ab"] = {"error": f"child rc={p.returncode}"}
    except subprocess.TimeoutExpired:
        extra["multiway_ab"] = {"error": "timeout"}
    except Exception as e:  # noqa: BLE001
        extra["multiway_ab"] = {"error": f"{type(e).__name__}: {e}"}


def _run_adaptive_ab(extra: dict, remaining: float):
    """In-run adaptation A/B (see CHANGES.md, PR 20): replay waves,
    wall, and acted adaptive-action counts for adaptive=off vs on on the
    10x-mis-estimated group-by."""
    n_rows = int(os.environ.get("BENCH_ADAPTIVE_ROWS", "16000"))
    env = dict(os.environ)
    try:
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--adaptive-child",
             str(n_rows)],
            env=env, stdout=subprocess.PIPE,
            timeout=min(600, max(120, remaining - 15)))
        lines = p.stdout.decode().strip().splitlines()
        if p.returncode == 0 and lines:
            rec = json.loads(lines[-1])
            off, on = rec.get("off", {}), rec.get("on", {})
            _log(f"adaptive_ab: waves {off.get('waves')}->{on.get('waves')} "
                 f"({off.get('wall_s')}s->{on.get('wall_s')}s, "
                 f"actions={on.get('actions')}, "
                 f"checksum_equal={rec.get('checksum_equal')})")
            extra["adaptive_ab"] = rec
        else:
            extra["adaptive_ab"] = {"error": f"child rc={p.returncode}"}
    except subprocess.TimeoutExpired:
        extra["adaptive_ab"] = {"error": "timeout"}
    except Exception as e:  # noqa: BLE001
        extra["adaptive_ab"] = {"error": f"{type(e).__name__}: {e}"}


def _run_serving_slo_cached(extra: dict, remaining: float):
    """Warm-over-cold serving comparison for the semantic result cache
    (the perf claim: an identical repeat never re-plans, re-compiles, or
    re-executes — see CHANGES.md, PR 14)."""
    sf = float(os.environ.get("BENCH_SF_SERVING", "0.1"))
    env = dict(os.environ)
    try:
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--serving-cached-child", str(sf)],
            env=env, stdout=subprocess.PIPE,
            timeout=min(900, max(120, remaining - 15)))
        lines = p.stdout.decode().strip().splitlines()
        if p.returncode == 0 and lines:
            rec = json.loads(lines[-1])
            _log(f"serving_slo_cached: cold p50={rec['cold_p50_s']}s "
                 f"warm p50={rec['warm_p50_s']}s "
                 f"({rec['speedup']}x, hit rate {rec['hit_rate']}, "
                 f"{rec['cache_bytes']}B held)")
            extra["serving_slo_cached"] = rec
        else:
            extra["serving_slo_cached"] = {"error": f"child rc={p.returncode}"}
    except subprocess.TimeoutExpired:
        extra["serving_slo_cached"] = {"error": "timeout"}
    except Exception as e:  # noqa: BLE001
        extra["serving_slo_cached"] = {"error": f"{type(e).__name__}: {e}"}


def _run_serving_slo(extra: dict, remaining: float):
    """Closed-loop serving-SLO bench: N concurrent protocol clients over a
    mixed TPC-H workload, latencies read from the per-group lifecycle
    histograms (log buckets, so the p99 is bucket-interpolated — same
    fidelity a Prometheus `histogram_quantile` would report)."""
    sf = float(os.environ.get("BENCH_SF_SERVING", "0.1"))
    n_clients = int(os.environ.get("BENCH_SERVING_CLIENTS", "8"))
    per_client = int(os.environ.get("BENCH_SERVING_QUERIES", "4"))
    env = dict(os.environ)
    try:
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--serving-child",
             str(sf), str(n_clients), str(per_client)],
            env=env, stdout=subprocess.PIPE,
            timeout=min(900, max(120, remaining - 15)))
        lines = p.stdout.decode().strip().splitlines()
        if p.returncode == 0 and lines:
            rec = json.loads(lines[-1])
            _log(f"serving_slo: {rec['queries']} queries from "
                 f"{rec['clients']} clients, e2e p50={rec['e2e_p50_s']}s "
                 f"p99={rec['e2e_p99_s']}s, queue p99="
                 f"{rec['queue_wait_p99_s']}s")
            extra["serving_slo"] = rec
        else:
            extra["serving_slo"] = {"error": f"child rc={p.returncode}"}
    except subprocess.TimeoutExpired:
        extra["serving_slo"] = {"error": "timeout"}
    except Exception as e:  # noqa: BLE001
        extra["serving_slo"] = {"error": f"{type(e).__name__}: {e}"}


# --------------------------------------------------------------- parent ----

_STATE = {"extra": {}, "emitted": False, "child": None}


def _emit():
    if _STATE["emitted"]:
        return
    _STATE["emitted"] = True
    extra = _STATE["extra"]

    def by_prefix(prefix, exact):
        # results are keyed by the sf ACTUALLY run; a downscaled run lands
        # under e.g. q1_sf0.1 — still surface it (vs_baseline only applies
        # at the nominal sf)
        r = extra.get(exact)
        if isinstance(r, dict):
            return r, True
        for k, v in extra.items():
            if k.startswith(prefix) and isinstance(v, dict):
                return v, False
        return {}, False

    for prefix, exact, ref in (("q1_sf", "q1_sf1", _REF["q1"]),
                               ("q6_sf", "q6_sf10", _REF["q6"])):
        r, nominal = by_prefix(prefix, exact)
        if nominal and "rows_per_sec" in r:
            r["vs_baseline"] = round(r["rows_per_sec"] / ref, 3)
    q1, q1_nominal = by_prefix("q1_sf", "q1_sf1")
    value = q1.get("rows_per_sec", 0.0)
    print(json.dumps({
        "metric": "tpch_q1_sf1_rows_per_sec",
        "value": value,
        "unit": "rows/s",
        "vs_baseline": (round(value / _REF["q1"], 3)
                        if value and q1_nominal else 0.0),
        "extra": extra,
    }), flush=True)


def _checkpoint():
    try:
        os.makedirs(os.path.join(_HERE, "chiprun_out"), exist_ok=True)
        with open(os.path.join(_HERE, "chiprun_out", "bench_partial.json"),
                  "w") as f:
            json.dump(_STATE["extra"], f, indent=1)
    except OSError:
        pass


def _on_term(signum, frame):
    _log(f"received signal {signum} — emitting partial results")
    _STATE["extra"].setdefault("note", f"killed by signal {signum}")
    child = _STATE.get("child")
    if child is not None and child.poll() is None:
        child.kill()
    _checkpoint()
    _emit()
    sys.exit(0)


def _device_child():
    """Print what JAX finds, as one JSON line. A child, because the parent
    stays off JAX: the chip belongs to one process at a time."""
    import jax

    d = jax.devices()
    print(json.dumps({"platform": d[0].platform, "kind": d[0].device_kind,
                      "count": len(d)}), flush=True)


def _require_tpu() -> dict:
    """The device this run measures, or exit non-zero: a bench number is a
    chip number, and there is no CPU fallback."""
    try:
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--device-child"],
            timeout=300, stdout=subprocess.PIPE)
        dev = json.loads(p.stdout.decode().strip().splitlines()[-1])
    except Exception as e:  # noqa: BLE001
        sys.exit(f"bench: no device ({type(e).__name__}: {e})")
    if dev["platform"] != "tpu":
        sys.exit(f"bench: no TPU — jax found {dev['platform']!r}; "
                 f"nothing was measured")
    return dev


def main():
    if len(sys.argv) >= 2 and sys.argv[1] == "--device-child":
        _device_child()
        return
    if len(sys.argv) >= 4 and sys.argv[1] == "--child":
        _child(sys.argv[2], float(sys.argv[3]),
               float(sys.argv[4]) if len(sys.argv) > 4 else 0.0)
        return
    if len(sys.argv) >= 5 and sys.argv[1] == "--serving-child":
        _serving_child(float(sys.argv[2]), int(sys.argv[3]),
                       int(sys.argv[4]))
        return
    if len(sys.argv) >= 3 and sys.argv[1] == "--serving-cached-child":
        _serving_cached_child(float(sys.argv[2]))
        return
    if len(sys.argv) >= 3 and sys.argv[1] == "--spill-child":
        _spill_child(int(sys.argv[2]))
        return
    if len(sys.argv) >= 3 and sys.argv[1] == "--multiway-child":
        _multiway_child(float(sys.argv[2]))
        return
    if len(sys.argv) >= 3 and sys.argv[1] == "--compile-tail-child":
        _compile_tail_child(sys.argv[2])
        return
    if len(sys.argv) >= 3 and sys.argv[1] == "--adaptive-child":
        _adaptive_child(int(sys.argv[2]))
        return

    signal.signal(signal.SIGTERM, _on_term)
    signal.signal(signal.SIGINT, _on_term)
    budget = float(os.environ.get("BENCH_BUDGET_S", "2400"))
    extra = _STATE["extra"]

    extra["device"] = _require_tpu()

    sf_over = {"q9": float(os.environ.get("BENCH_SF_Q9", "100")),
               "q64": float(os.environ.get("BENCH_SF_Q64", "100"))}
    wanted = os.environ.get(
        "BENCH_CONFIGS", "q1_sf1,q1_nofuse_sf1,q6_sf10,q3_sf10,join_sf1,"
        "groupby_engine_ab_sf1,groupby_engine_ab_sort_sf1,"
        "serving_slo,serving_slo_cached,spill_skew,compile_tail,"
        "multiway_ab,adaptive_ab,q9,q64"
    ).split(",")

    for name in (w.strip() for w in wanted):
        if not name:
            continue
        name = _ALIASES.get(name, name)
        if name == "serving_slo":
            remaining = budget - (time.time() - _T0)
            if remaining < 60:
                _log("serving_slo: SKIPPED (budget exhausted)")
                extra["serving_slo"] = {"skipped": "budget"}
            else:
                _run_serving_slo(extra, remaining)
            _checkpoint()
            continue
        if name == "serving_slo_cached":
            remaining = budget - (time.time() - _T0)
            if remaining < 60:
                _log("serving_slo_cached: SKIPPED (budget exhausted)")
                extra["serving_slo_cached"] = {"skipped": "budget"}
            else:
                _run_serving_slo_cached(extra, remaining)
            _checkpoint()
            continue
        if name == "multiway_ab":
            remaining = budget - (time.time() - _T0)
            if remaining < 60:
                _log("multiway_ab: SKIPPED (budget exhausted)")
                extra["multiway_ab"] = {"skipped": "budget"}
            else:
                _run_multiway_ab(extra, remaining)
            _checkpoint()
            continue
        if name == "adaptive_ab":
            remaining = budget - (time.time() - _T0)
            if remaining < 60:
                _log("adaptive_ab: SKIPPED (budget exhausted)")
                extra["adaptive_ab"] = {"skipped": "budget"}
            else:
                _run_adaptive_ab(extra, remaining)
            _checkpoint()
            continue
        if name == "spill_skew":
            remaining = budget - (time.time() - _T0)
            if remaining < 60:
                _log("spill_skew: SKIPPED (budget exhausted)")
                extra["spill_skew"] = {"skipped": "budget"}
            else:
                _run_spill_skew(extra, remaining)
            _checkpoint()
            continue
        if name == "compile_tail":
            remaining = budget - (time.time() - _T0)
            if remaining < 240:
                _log("compile_tail: SKIPPED (budget exhausted)")
                extra["compile_tail"] = {"skipped": "budget"}
            else:
                _run_compile_tail(extra, remaining)
            _checkpoint()
            continue
        if name not in _CONFIGS:
            _log(f"{name}: UNKNOWN config (valid: {','.join(_CONFIGS)})")
            extra[name] = {"error": "unknown config"}
            continue
        remaining = budget - (time.time() - _T0)
        if remaining < 60:
            _log(f"{name}: SKIPPED (budget {budget:.0f}s exhausted)")
            extra[name] = {"skipped": "budget"}
            _checkpoint()
            continue
        _, kind, sf, _, _ = _CONFIGS[name]
        sf = sf_over.get(name, sf) if sf is None else sf
        sf = _resolve_sf(kind, sf, remaining)
        # the artifact key must record the sf ACTUALLY run, not the
        # config's nominal one (env override / budget downscale)
        label = f"{name.rsplit('_sf', 1)[0]}_sf{sf:g}"
        cap = _CAPS.get(name, 600)
        if not _dataset_ready(kind, sf):
            # cold cache: the child pays dataset generation (~60 s/SF for
            # the chunked exporters) before the measured run — the cap
            # must cover it or the child is killed mid-generation
            cap += sf * 70.0
        cap = min(cap, remaining - 15)
        env = dict(os.environ)
        if os.environ.get("BENCH_PALLAS"):
            env["PRESTO_TPU_PALLAS"] = "1"
        _log(f"{name}: starting (sf={sf:g}, cap={cap:.0f}s)")
        try:
            p = subprocess.Popen(
                [sys.executable, os.path.abspath(__file__),
                 "--child", name, str(sf), str(cap)],
                env=env, stdout=subprocess.PIPE, stderr=None)
            _STATE["child"] = p
            try:
                out, _ = p.communicate(timeout=cap)
            except subprocess.TimeoutExpired:
                p.kill()
                p.communicate()
                raise
            lines = out.decode().strip().splitlines()
            if p.returncode == 0 and lines:
                rec = json.loads(lines[-1])
                rec.setdefault("sf_actual", sf)
                extra[label] = rec
            else:
                extra[label] = {"error": f"child rc={p.returncode}",
                               "sf": sf, "sf_actual": sf}
        except subprocess.TimeoutExpired:
            _log(f"{name}: TIMEOUT after {cap:.0f}s cap — moving on")
            extra[label] = {"error": f"timeout after {cap:.0f}s cap",
                           "sf": sf, "sf_actual": sf}
        except Exception as e:
            _log(f"{name}: FAILED {type(e).__name__}: {e}")
            extra[label] = {"error": f"{type(e).__name__}: {e}",
                           "sf": sf, "sf_actual": sf}
        finally:
            _STATE["child"] = None
        _checkpoint()

    _checkpoint()
    _emit()


if __name__ == "__main__":
    main()
