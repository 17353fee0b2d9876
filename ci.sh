#!/usr/bin/env bash
# Tier-1 verification entry point (the command ROADMAP.md pins), with the
# XLA:CPU process-lifetime crash mitigation from d979a3b wired in: if the
# single-process run dies on a segfault (exit 139), re-run the suite
# sharded across short-lived pytest processes so one crashed process only
# takes its shard down.
set -o pipefail
cd "$(dirname "$0")"

# Observability smoke: boot an in-process coordinator + worker, run one
# query, scrape BOTH /v1/metrics planes, and lint each scrape with the
# exposition validator (obs/exposition.py) — an invalid exposition document
# breaks scrapers long before any test notices.
echo "== observability smoke: metrics exposition lint =="
env JAX_PLATFORMS=cpu python - <<'PYEOF'
import sys
import urllib.request

import numpy as np
import pandas as pd

from presto_tpu.catalog.memory import MemoryConnector
from presto_tpu.connector import Catalog
from presto_tpu.obs.exposition import lint_exposition
from presto_tpu.server.coordinator import DistributedRunner

conn = MemoryConnector()
conn.add_table("t", pd.DataFrame({"k": np.arange(100) % 5,
                                  "v": np.arange(100.0)}))
cat = Catalog()
cat.register("m", conn, default=True)
failed = False
with DistributedRunner(cat, n_workers=1) as dr:
    dr.run("select k, sum(v) as s from t group by k")
    for name, url in [("coordinator", dr.coordinator.url),
                      ("worker", dr.workers[0].url)]:
        with urllib.request.urlopen(f"{url}/v1/metrics", timeout=10) as r:
            body = r.read().decode()
        errs = lint_exposition(body)
        hists = sum(1 for ln in body.splitlines()
                    if ln.startswith("# TYPE") and ln.endswith(" histogram"))
        print(f"{name}: {len(body.splitlines())} lines, "
              f"{hists} histogram families, {len(errs)} lint errors")
        for e in errs:
            print(f"  {name}: {e}", file=sys.stderr)
            failed = True
        if hists < 4:
            print(f"  {name}: expected >= 4 histogram families",
                  file=sys.stderr)
            failed = True
sys.exit(1 if failed else 0)
PYEOF
rc=$?
if [ "$rc" -ne 0 ]; then
  echo "observability smoke FAILED (exit $rc)"
  exit "$rc"
fi

# Radix-partitioned join smoke: the partitioned breakers (including the
# forced hybrid-spill path) must return exactly the unpartitioned result.
echo "== radix smoke: partitioned join/group-by equals unpartitioned =="
env JAX_PLATFORMS=cpu python - <<'PYEOF'
import numpy as np
import pandas as pd

from presto_tpu.catalog.memory import MemoryConnector
from presto_tpu.connector import Catalog
from presto_tpu.exec import ExecConfig, LocalRunner

rng = np.random.default_rng(0)
conn = MemoryConnector()
conn.add_table("b", pd.DataFrame({"id": rng.integers(0, 300, 500),
                                  "tag": rng.integers(0, 9, 500)}))
conn.add_table("p", pd.DataFrame({"fk": rng.integers(0, 400, 3000),
                                  "v": rng.normal(size=3000)}))
cat = Catalog()
cat.register("m", conn, default=True)
sql = ("select p.fk, count(*) as c, sum(p.v) as s, max(b.tag) as t "
       "from p join b on p.fk = b.id group by p.fk order by p.fk")
exp = LocalRunner(cat, ExecConfig()).run(sql)
for kw in ({"radix_partitions": 4},
           {"radix_partitions": 4, "join_spill_budget_bytes": 1}):
    got = LocalRunner(cat, ExecConfig(**kw)).run(sql)
    pd.testing.assert_frame_equal(got.reset_index(drop=True),
                                  exp.reset_index(drop=True),
                                  check_dtype=False)
    print(f"radix smoke OK {kw}")
PYEOF
rc=$?
if [ "$rc" -ne 0 ]; then
  echo "radix smoke FAILED (exit $rc)"
  exit "$rc"
fi

# Compile-plane smoke: the process-wide structural program cache
# (exec/programs.py) must make (1) the same TPC-H query from a SECOND
# runner in one process compile ZERO new XLA programs, and (2) two
# concurrent tasks of one fragment share each program — every program
# both tasks called compiled exactly once, not once per task.
echo "== compile-plane smoke: cold-vs-warm + cross-task sharing =="
env JAX_PLATFORMS=cpu python - <<'PYEOF'
from presto_tpu.catalog.tpch import tpch_catalog
from presto_tpu.exec import ExecConfig, LocalRunner, programs

cat = tpch_catalog(0.01)
sql = ("select l_returnflag as f, count(*) as c, sum(l_quantity) as q "
       "from lineitem where l_discount between 0.02 and 0.08 "
       "group by l_returnflag order by f")
cold = LocalRunner(cat, ExecConfig()).run(sql)
before = programs.snapshot()
# a FRESH runner: new plan objects, so reuse can only come from the
# structural cache, not from per-node jit memoization
warm = LocalRunner(cat, ExecConfig()).run(sql)
after = programs.snapshot()
assert warm.equals(cold)
delta = after["compiles"] - before["compiles"]
assert delta == 0, f"warm run recompiled {delta} programs"
assert after["hits"] > before["hits"], "warm run never hit the cache"
print(f"cold-vs-warm OK: 2nd run 0 compiles "
      f"({after['hits'] - before['hits']} cache hits, "
      f"{before['compiles']} cold compiles, "
      f"{before['trace_wall_s']:.2f}s trace wall)")

# two tasks of one fragment (n_workers=2 → the leaf scan fragment runs
# as two concurrent tasks in this process)
from presto_tpu.server.coordinator import DistributedRunner

programs.reset(counters_only=False)
with DistributedRunner(cat, n_workers=2) as dr:
    out = dr.run("select o_orderpriority, count(*) as c from orders "
                 "group by o_orderpriority order by o_orderpriority")
    assert len(out) == 5
    shared = [e for e in programs.entries() if e.calls >= 2]
    assert shared, "no program was shared across the two tasks"
    multi = [e for e in shared if e.compiles > 1]
    assert not multi, (
        f"{len(multi)} cross-task programs compiled more than once: "
        + ", ".join(f"calls={e.calls} compiles={e.compiles}" for e in multi))
    print(f"cross-task OK: {len(shared)} programs shared by both tasks, "
          f"each compiled exactly once")
PYEOF
rc=$?
if [ "$rc" -ne 0 ]; then
  echo "compile-plane smoke FAILED (exit $rc)"
  exit "$rc"
fi

# Fragment-fusion smoke: a Q1-shaped grouped aggregation over a multi-
# batch scan must collapse to O(1) fused device dispatches per leaf
# fragment (counter-based, so it holds on CPU exactly as on TPU), and
# fragment_fusion=false must return the identical result via the
# per-batch path.
echo "== fragment smoke: fused dispatch collapse + fusion-off equality =="
env JAX_PLATFORMS=cpu python - <<'PYEOF'
import numpy as np
import pandas as pd

from presto_tpu.catalog.memory import MemoryConnector
from presto_tpu.connector import Catalog
from presto_tpu.exec import ExecConfig, LocalRunner

rng = np.random.default_rng(3)
conn = MemoryConnector()
conn.add_table("li", pd.DataFrame({
    "flag": rng.integers(0, 3, 3000),
    "qty": rng.normal(25.0, 5.0, 3000),
    "price": rng.normal(1000.0, 100.0, 3000)}))
cat = Catalog()
cat.register("m", conn, default=True)
sql = ("select flag, count(*) as c, sum(qty) as q, avg(price) as p "
       "from li group by flag order by flag")
# batch_rows=512 over 3000 rows -> ~6 scan batches per fragment
fused = LocalRunner(cat, ExecConfig(batch_rows=512))
got = fused.run(sql)
st = fused.last_stats
fd = st.get("fragment.dispatches", 0)
bd = st.get("fragment.batch_dispatches", 0)
fb = st.get("fragment.fused_batches", 0)
assert fd >= 1, f"fusion never engaged: {st}"
assert fd <= 3, f"expected <= 3 fused dispatches per leaf fragment, got {fd}"
assert bd == 0, f"fused run still dispatched {bd} per-batch steps"
off = LocalRunner(cat, ExecConfig(batch_rows=512, fragment_fusion=False))
exp = off.run(sql)
ost = off.last_stats
pd.testing.assert_frame_equal(got.reset_index(drop=True),
                              exp.reset_index(drop=True))
assert ost.get("fragment.dispatches", 0) == 0
assert ost.get("fragment.batch_dispatches", 0) == fb, (
    f"fused run covered {fb} batches but per-batch path dispatched "
    f"{ost.get('fragment.batch_dispatches', 0)}")
print(f"fragment smoke OK: {fb} batches in {fd} fused dispatches "
      f"(vs {ost['fragment.batch_dispatches']} per-batch); "
      f"fusion-off result identical")
PYEOF
rc=$?
if [ "$rc" -ne 0 ]; then
  echo "fragment smoke FAILED (exit $rc)"
  exit "$rc"
fi

# Breaker-engine smoke: a keyed aggregation and a join forced through
# the Pallas linear-probing hash engine must return exactly the sort
# engine's result, and the engine-labeled dispatch counters must fire.
echo "== breaker smoke: hash engine equals sort + labeled counters =="
env JAX_PLATFORMS=cpu python - <<'PYEOF'
import numpy as np
import pandas as pd

from presto_tpu.catalog.memory import MemoryConnector
from presto_tpu.connector import Catalog
from presto_tpu.exec import ExecConfig, LocalRunner
from presto_tpu.scan import metrics as scan_metrics

rng = np.random.default_rng(11)
conn = MemoryConnector()
conn.add_table("t", pd.DataFrame({"g": rng.integers(0, 300, 4000),
                                  "v": rng.normal(size=4000)}))
conn.add_table("d", pd.DataFrame({"k": np.arange(300),
                                  "w": rng.integers(0, 7, 300)}))
cat = Catalog()
cat.register("m", conn, default=True)
before = scan_metrics.snapshot()
for sql in ("select g, count(*) as c, sum(v) as s from t "
            "group by g order by g",
            "select d.w, count(*) as c, sum(t.v) as s from t "
            "join d on t.g = d.k group by d.w order by d.w"):
    hr = LocalRunner(cat, ExecConfig(batch_rows=512, breaker_engine="hash"))
    sr = LocalRunner(cat, ExecConfig(batch_rows=512, breaker_engine="sort"))
    got, exp = hr.run(sql), sr.run(sql)
    pd.testing.assert_frame_equal(got.reset_index(drop=True),
                                  exp.reset_index(drop=True),
                                  check_dtype=False)
    assert hr.last_stats.get("breaker.engine_hash", 0) >= 1, hr.last_stats
    assert sr.last_stats.get("breaker.engine_sort", 0) >= 1, sr.last_stats
after = scan_metrics.snapshot()
dh = after["breaker_dispatches_hash"] - before["breaker_dispatches_hash"]
ds = after["breaker_dispatches_sort"] - before["breaker_dispatches_sort"]
assert dh >= 2 and ds >= 2, (dh, ds)
print(f"breaker smoke OK: hash==sort on agg+join "
      f"({dh} hash / {ds} sort labeled dispatches)")
PYEOF
rc=$?
if [ "$rc" -ne 0 ]; then
  echo "breaker smoke FAILED (exit $rc)"
  exit "$rc"
fi

# HBO smoke: a skew-heavy group-by whose static NDV estimate is 10×
# wrong must pay at least one overflow-replay wave on its first run,
# then — with history-based correction on — flip to the right engine
# and presize on run 2 with ZERO replay waves and an explicit
# "(hbo: observed)" provenance marker in EXPLAIN ANALYZE. The HBO
# metric rows must also lint clean as an exposition document.
echo "== hbo smoke: run-2 correction, zero replay waves =="
env JAX_PLATFORMS=cpu python - <<'PYEOF'
import os
import tempfile

import numpy as np
import pandas as pd

with tempfile.TemporaryDirectory() as d:
    os.environ["PRESTO_TPU_CACHE_DIR"] = d

    from presto_tpu.catalog.memory import MemoryConnector
    from presto_tpu.connector import Catalog
    from presto_tpu.exec import ExecConfig, LocalRunner
    from presto_tpu.obs import runstats
    from presto_tpu.obs.exposition import lint_exposition
    from presto_tpu.server.metrics import render_metrics

    runstats.reset()
    conn = MemoryConnector()
    # all-distinct keys grouped through an expression: the exact column
    # NDV can't see through `k % 100000`, so the estimate is rows*0.1
    conn.add_table("t", pd.DataFrame({"k": np.arange(6000, dtype=np.int64),
                                      "v": np.ones(6000, dtype=np.int64)}))
    cat = Catalog()
    cat.register("m", conn, default=True)
    sql = "select k % 100000 as g, sum(v) from m.t group by 1"

    r1 = LocalRunner(cat, ExecConfig(hbo="observe"))
    txt1 = r1.explain_analyze(sql)
    w1 = r1.last_stats.get("breaker.replay_waves", 0)
    assert "drift=10x" in txt1, txt1
    assert w1 >= 1, r1.last_stats

    r2 = LocalRunner(cat, ExecConfig(hbo="correct"))
    txt2 = r2.explain_analyze(sql)
    w2 = r2.last_stats.get("breaker.replay_waves", 0)
    assert "(hbo: observed)" in txt2, txt2
    assert w2 == 0, r2.last_stats

    d1 = r1.run(sql).sort_values("g").reset_index(drop=True)
    d2 = r2.run(sql).sort_values("g").reset_index(drop=True)
    assert d1.equals(d2)

    errs = lint_exposition(render_metrics(
        runstats.metric_rows({"plane": "worker"})))
    assert errs == [], errs
    corr = runstats.snapshot()["corrections"]
    print(f"hbo smoke OK: run1 {w1} replay wave(s) observed, run2 0 "
          f"(corrections: {dict(sorted(corr.items()))})")
PYEOF
rc=$?
if [ "$rc" -ne 0 ]; then
  echo "hbo smoke FAILED (exit $rc)"
  exit "$rc"
fi

# Adaptive-execution smoke: on the same 10×-mis-estimated group-by,
# adaptive=on must flip the breaker engine IN-RUN with strictly fewer
# replay waves than off and an identical result; observe must log the
# decision without acting; the adaptive_action events must arrive in
# deterministic seq order with the EXPLAIN [adaptive: ...] marker; and
# adaptive=off must stay bit-identical to the seed engine — result,
# wave count, and an UNARMED metric plane (no adaptive rows scraped).
echo "== adaptive smoke: in-run engine flip, fewer waves, off inert =="
env JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python - <<'PYEOF'
import os
import tempfile

import numpy as np
import pandas as pd

with tempfile.TemporaryDirectory() as d:
    os.environ["PRESTO_TPU_CACHE_DIR"] = d

    from presto_tpu.catalog.memory import MemoryConnector
    from presto_tpu.connector import Catalog
    from presto_tpu.exec import ExecConfig, LocalRunner
    from presto_tpu.exec import adaptive as _adaptive
    from presto_tpu.obs import runstats
    from presto_tpu.obs.events import EVENTS

    conn = MemoryConnector()
    conn.add_table("t", pd.DataFrame({"k": np.arange(6000, dtype=np.int64),
                                      "v": np.ones(6000, dtype=np.int64)}))
    cat = Catalog()
    cat.register("m", conn, default=True)
    sql = "select k % 100000 as g, sum(v) as s from m.t group by 1"

    def run(mode):
        runstats.reset()
        _adaptive.reset()
        r = LocalRunner(cat, ExecConfig(adaptive=mode))
        df = r.run(sql).sort_values("g", ignore_index=True)
        # waves from the run itself — explain_analyze re-executes on the
        # (flip-pinned) cached plan and would overwrite last_stats
        waves = r.last_stats.get("breaker.replay_waves", 0)
        txt = r.explain_analyze(sql)
        return df, waves, txt

    d_off, w_off, t_off = run("off")
    assert w_off >= 1, w_off
    assert "[adaptive:" not in t_off
    assert not _adaptive.armed()
    # unarmed -> zero rows, so both /v1/metrics planes (which extend
    # their scrape from these rows) stay bit-for-bit pre-adaptive
    assert _adaptive.metric_rows({"plane": "worker"}) == []

    d_obs, w_obs, t_obs = run("observe")
    assert d_obs.equals(d_off)
    assert w_obs == w_off, (w_obs, w_off)
    recs = _adaptive.recent_decisions()
    assert recs and all(not a["acted"] for a in recs), recs
    assert "would flip" in t_obs, t_obs

    _adaptive.reset()
    since = EVENTS.last_seq()
    d_on, w_on, t_on = run("on")
    assert d_on.equals(d_off), "adaptive=on changed the answer"
    assert w_on < w_off, (w_on, w_off)
    assert "[adaptive: flip hash->sort]" in t_on, t_on
    evs = EVENTS.events(since=since, kind="adaptive_action")
    assert evs, "no adaptive_action events emitted"
    seqs = [e["seq"] for e in evs]
    assert seqs == sorted(seqs), seqs
    acted = [e for e in evs if e["acted"]]
    assert acted and acted[0]["action"] == "engine_flip", evs
    print(f"adaptive smoke OK: off {w_off} wave(s) -> on {w_on}, "
          f"{len(acted)} acted action(s), off plane unarmed")
PYEOF
rc=$?
if [ "$rc" -ne 0 ]; then
  echo "adaptive smoke FAILED (exit $rc)"
  exit "$rc"
fi

# Mesh data-plane smoke: a Q3-shaped join + keyed aggregation over an
# 8-device CPU mesh must (a) match the local streaming engine's
# checksum, (b) ride the fused single-buffer exchange path for every
# OUT_HASH exchange, and (c) finish without a single overflow replay —
# the stats-sized lanes must be right on the first attempt.
echo "== mesh smoke: fused ICI exchanges + local-vs-mesh checksum =="
env JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python - <<'PYEOF'
from presto_tpu.catalog.tpch import tpch_catalog
from presto_tpu.exec import ExecConfig, LocalRunner
from presto_tpu.parallel.mesh import make_mesh
from presto_tpu.parallel.mesh_exec import MeshExecutor
from presto_tpu.verifier import result_checksum

cat = tpch_catalog(0.01)
mx = MeshExecutor(cat, make_mesh(8),
                  ExecConfig(batch_rows=1 << 12, agg_capacity=1 << 10))
local = LocalRunner(cat, ExecConfig(batch_rows=1 << 13))
q = """
select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
       o_orderdate, o_shippriority
from customer, orders, lineitem
where c_mktsegment = 'BUILDING' and c_custkey = o_custkey
  and l_orderkey = o_orderkey and o_orderdate < date '1995-03-15'
  and l_shipdate > date '1995-03-15'
group by l_orderkey, o_orderdate, o_shippriority
order by revenue desc, o_orderdate limit 10
"""
assert result_checksum(mx.run_batch(q)) == result_checksum(local.run_batch(q))
lr = mx.last_run
assert lr["retries"] == 0, lr
exchanges = lr["attempts"][0]["exchanges"]
fused = [e for e in exchanges if e["fused"]]
assert fused, exchanges
bts = sum(e["bytes"] for e in exchanges)
util = (sum(e["lanes_used"] for e in exchanges)
        / max(sum(e["lanes_total"] for e in exchanges), 1))
print(f"mesh smoke OK: {len(fused)}/{len(exchanges)} fused exchanges, "
      f"{bts} a2a bytes, {100*util:.1f}% lane util, 0 replays")
PYEOF
rc=$?
if [ "$rc" -ne 0 ]; then
  echo "mesh smoke FAILED (exit $rc)"
  exit "$rc"
fi

# Memory observability smoke: a spill-inducing aggregation on a worker
# with a tiny memory pool must leave a nonzero high-water mark in the
# coordinator's GET /v1/memory rollup (fed by real worker heartbeats),
# with the devprof plane honest about device memory on CPU; and the
# cluster low-memory killer must fail a hog with a structured
# CLUSTER_OUT_OF_MEMORY error while dumping an oom_forensics.jsonl
# snapshot under PRESTO_TPU_CACHE_DIR.
echo "== memory smoke: /v1/memory rollup + structured OOM kill =="
tmp_cache="$(mktemp -d)"
env JAX_PLATFORMS=cpu PRESTO_TPU_CACHE_DIR="$tmp_cache" python - <<'PYEOF'
import json, os, threading, time, urllib.request

import numpy as np
import pandas as pd

from presto_tpu.catalog.memory import MemoryConnector
from presto_tpu.connector import Catalog
from presto_tpu.exec import ExecConfig
from presto_tpu.server.coordinator import DistributedRunner

rng = np.random.default_rng(7)
n = 60_000
facts = pd.DataFrame({
    "g": rng.integers(0, 20_000, n), "v": rng.normal(size=n)})
conn = MemoryConnector()
conn.add_table("facts", facts)
cat = Catalog()
cat.register("m", conn, default=True)

dr = DistributedRunner(cat, n_workers=1, config=ExecConfig(
    batch_rows=1 << 13, memory_pool_bytes=1 << 20, spill_partitions=4,
    devprof="on"))
try:
    df = dr.run_batch(
        "select g, sum(v) as s, count(*) as c from facts group by g"
    ).to_pandas()
    assert len(df) == facts["g"].nunique(), len(df)
    # the heartbeat prober (2s cadence) carries the pool's high-water
    # mark + the devprof device doc into the coordinator rollup
    doc, deadline = {}, time.time() + 20
    while time.time() < deadline:
        doc = json.load(urllib.request.urlopen(
            dr.coordinator.url + "/v1/memory"))
        if any(nd.get("peakBytes", 0) > 0 for nd in doc["nodes"].values()):
            break
        time.sleep(0.25)
    peaks = {nid: nd["peakBytes"] for nid, nd in doc["nodes"].items()}
    assert any(p > 0 for p in peaks.values()), doc
    devdocs = [nd.get("deviceMemory") for nd in doc["nodes"].values()]
    assert devdocs and all(d is not None for d in devdocs), doc
    assert all(d.get("available") is False for d in devdocs), devdocs
finally:
    dr.coordinator.close()
    for w in dr.workers:
        w.close()

# Structured kill: a hog query that sits on memory until the killer
# fires. QueryManager + ClusterMemoryManager are the exact objects the
# coordinator wires together; driving update_node/enforce directly makes
# the heartbeat deterministic instead of cadence-dependent.
from presto_tpu.server.cluster_memory import ClusterMemoryManager
from presto_tpu.server.querymanager import FAILED, QueryManager, QueryResult
from presto_tpu.server.session import Session

release = threading.Event()


def execute_fn(session, sql):
    if "hog" in sql:
        release.wait(30)
    return QueryResult(columns=["x"], types=["bigint"], rows=[(1,)])


qm = QueryManager(execute_fn)
cmm = ClusterMemoryManager(limit_bytes=1_000_000, kill_delay_s=0.0)
try:
    hog = qm.create_query(Session(), "select hog")
    deadline = time.time() + 5
    while hog.state != "RUNNING" and time.time() < deadline:
        time.sleep(0.01)
    cmm.update_node("w0", {
        "memory": {"reservedBytes": 2_000_000, "limitBytes": None,
                   "peakBytes": 2_000_000},
        "queryMemory": {hog.query_id: 2_000_000}})
    cmm.enforce(qm)  # arms the pressure timer
    assert cmm.enforce(qm) == hog.query_id
    assert hog.state == FAILED, hog.state
    assert hog.error_type == "CLUSTER_OUT_OF_MEMORY", hog.error_type
finally:
    release.set()
    qm.close()

fpath = os.path.join(os.environ["PRESTO_TPU_CACHE_DIR"],
                     "oom_forensics.jsonl")
assert os.path.exists(fpath), fpath
rec = json.loads(open(fpath).read().splitlines()[-1])
assert rec["event"] == "lowMemoryKill" and rec["victim"] == hog.query_id
assert rec["nodes"]["w0"]["queryMemory"][hog.query_id] == 2_000_000
print(f"memory smoke OK: peakBytes={max(peaks.values())}, devprof "
      f"honest-unavailable on CPU, kill={rec['victim']} "
      f"(CLUSTER_OUT_OF_MEMORY), forensics={os.path.basename(fpath)}")
PYEOF
rc=$?
rm -rf "$tmp_cache"
if [ "$rc" -ne 0 ]; then
  echo "memory smoke FAILED (exit $rc)"
  exit "$rc"
fi

# Spill-pressure smoke: a skew-adversarial join (90% one-hot build keys)
# under a per-worker pool ~40x smaller than the build side must complete
# CORRECTLY via the dynamic hybrid hash path — partitioned spill, mid-build
# growth, role reversal — with zero low-memory kills, nonzero spill
# counters on the worker metrics plane, and an EMPTY spill directory after
# (leak guard). Then the revoke-before-kill ladder is driven
# deterministically over the live coordinator->worker HTTP revoke path and
# its order (spill_revoke_requested BEFORE low_memory_kill) audited from
# /v1/events.
echo "== spill-pressure smoke: skewed join under tiny pool + revoke ladder =="
env JAX_PLATFORMS=cpu python - <<'PYEOF'
import json, os, threading, time, urllib.request

import numpy as np
import pandas as pd

from presto_tpu.catalog.memory import MemoryConnector
from presto_tpu.connector import Catalog
from presto_tpu.exec import ExecConfig, LocalRunner
from presto_tpu.server.coordinator import DistributedRunner
from presto_tpu.verifier import result_checksum

rng = np.random.default_rng(19)
n = 40_000
bk = np.where(rng.random(n) < 0.9, 7,
              rng.integers(0, 2_000, n)).astype(np.int64)
conn = MemoryConnector()
conn.add_table("build", pd.DataFrame({"bk": bk, "w": rng.normal(size=n)}))
conn.add_table("probe", pd.DataFrame({
    "k": rng.integers(0, 2_000, 24_000).astype(np.int64),
    "v": rng.normal(size=24_000)}))
cat = Catalog()
cat.register("m", conn, default=True)
sql = "select probe.v, build.w from probe join build on probe.k = build.bk"

local = LocalRunner(cat, ExecConfig(batch_rows=1 << 13))
dr = DistributedRunner(cat, n_workers=1, config=ExecConfig(
    batch_rows=1 << 13, memory_pool_bytes=128 << 10, spill_partitions=4,
    spill_max_depth=2))
try:
    assert result_checksum(dr.run_batch(sql)) == \
        result_checksum(local.run_batch(sql)), "spilled join result differs"
    w = dr.workers[0]
    assert w.spill_manager.total_spilled_bytes > 0, "join never spilled"
    assert dr.coordinator.cluster_memory.kills == 0, "graceful path killed"
    sd = w.spill_manager._dir
    leaked = os.listdir(sd) if sd and os.path.isdir(sd) else []
    assert leaked == [], f"spill files leaked: {leaked}"
    body = urllib.request.urlopen(w.url + "/v1/metrics",
                                  timeout=10).read().decode()
    for fam in ("presto_tpu_spill_partitions_total",
                "presto_tpu_spill_repartitions_total",
                "presto_tpu_spilled_bytes"):
        assert fam in body, f"{fam} missing from worker metrics"
    parts = [ln for ln in body.splitlines()
             if ln.startswith("presto_tpu_spill_partitions_total")]
    assert parts and float(parts[0].rsplit(" ", 1)[1]) > 0, parts

    # -- revoke-before-kill ladder, deterministically ---------------------
    # A standalone manager (so the live heartbeat cadence can't interleave)
    # wired to the REAL coordinator->worker HTTP revoke path; a registered
    # pool revoker stands in for a mid-build join.
    from presto_tpu.obs.events import EVENTS
    from presto_tpu.server.cluster_memory import ClusterMemoryManager
    from presto_tpu.server.querymanager import (FAILED, QueryManager,
                                                QueryResult)
    from presto_tpu.server.session import Session

    release = threading.Event()

    def execute_fn(session, sql):
        release.wait(30)
        return QueryResult(columns=["x"], types=["bigint"], rows=[(1,)])

    revoked = []
    w.memory_pool.add_revoker(lambda need: revoked.append(need) or 0)
    cmm = ClusterMemoryManager(limit_bytes=1_000_000, kill_delay_s=0.0)
    cmm.spill_revoker = dr.coordinator._revoke_spillable_state
    qm = QueryManager(execute_fn)
    try:
        hog = qm.create_query(Session(), "select hog")
        deadline = time.time() + 5
        while hog.state != "RUNNING" and time.time() < deadline:
            time.sleep(0.01)
        seq0 = EVENTS.last_seq()
        pressure = {"memory": {"reservedBytes": 2_000_000,
                               "limitBytes": None, "peakBytes": 2_000_000},
                    "queryMemory": {hog.query_id: 2_000_000}}
        cmm.update_node("w0", pressure)
        cmm.enforce(qm)  # arms the pressure timer
        assert cmm.enforce(qm) is None, "killed before trying spill revoke"
        assert revoked, "worker pool revoker was never signaled over HTTP"
        assert hog.state == "RUNNING" and cmm.kills == 0
        # pressure persists and the episode's one revoke shot is spent:
        # the next sustained pass must kill
        cmm.enforce(qm)  # re-arms
        assert cmm.enforce(qm) == hog.query_id
        assert hog.state == FAILED
        assert hog.error_type == "CLUSTER_OUT_OF_MEMORY"
        ev = json.load(urllib.request.urlopen(
            dr.coordinator.url + f"/v1/events?since={seq0}", timeout=10))
        kinds = [e["kind"] for e in ev["events"]
                 if e["kind"] in ("spill_revoke_requested",
                                  "low_memory_kill")]
        assert kinds == ["spill_revoke_requested", "low_memory_kill"], (
            f"ladder out of order on /v1/events: {kinds}")
    finally:
        release.set()
        qm.close()
    print(f"spill-pressure smoke OK: checksum equal, "
          f"{w.spill_manager.total_spilled_bytes}B spilled, 0 kills, "
          f"spill dir empty, ladder order spill_revoke -> kill on "
          f"/v1/events ({len(revoked)} revoker signal(s))")
finally:
    dr.close()
PYEOF
rc=$?
if [ "$rc" -ne 0 ]; then
  echo "spill-pressure smoke FAILED (exit $rc)"
  exit "$rc"
fi

# Serving-SLO smoke: boot a shared-process cluster with the slow-query
# and event-stream sinks armed, drive >= 8 concurrent mixed queries over
# the statement protocol split across two resource groups, and assert
# (a) the per-group SLO histogram families scrape lint-clean, (b) live
# progress is monotone nondecreasing and ends at 1.0 with HBO-predicted
# provenance on a fingerprint repeat, (c) /v1/events carries a sampled
# query's lifecycle transitions in canonical order, (d) the five segments
# sum to e2e for every completed query, and (e) a forced latency
# regression (tiny pre-injected HBO baseline) lands on the counter, the
# event stream, AND the slow-query JSONL record.
echo "== serving-SLO smoke: lifecycle + progress + events + regression =="
tmp_slo="$(mktemp -d)"
env JAX_PLATFORMS=cpu PRESTO_TPU_SLO_DIR="$tmp_slo" python - <<'PYEOF'
import json, os, threading, time, urllib.request

from presto_tpu.catalog.tpch import tpch_catalog
from presto_tpu.obs import runstats
from presto_tpu.obs.exposition import lint_exposition
from presto_tpu.server.coordinator import DistributedRunner
from presto_tpu.server.resource_groups import (
    ResourceGroupManager, ResourceGroupSpec, SelectorSpec)

d = os.environ["PRESTO_TPU_SLO_DIR"]
slow_log = os.path.join(d, "slow.jsonl")
events_log = os.path.join(d, "events.jsonl")
cat = tpch_catalog(0.01)
dr = DistributedRunner(cat, n_workers=2, coordinator_kwargs={
    "slow_query_log": slow_log, "slow_query_threshold_s": 0.0,
    "events_log": events_log})
# two leaf groups so the SLO families carry distinct group labels
dr.coordinator.query_manager.resource_groups = ResourceGroupManager(
    ResourceGroupSpec("global", hard_concurrency_limit=16, subgroups=[
        ResourceGroupSpec("adhoc", hard_concurrency_limit=8),
        ResourceGroupSpec("batch", hard_concurrency_limit=8)]),
    [SelectorSpec(group="global.adhoc", source_regex="adhoc"),
     SelectorSpec(group="global.batch", source_regex="batch"),
     SelectorSpec(group="global")])
base = dr.coordinator.url

QUERIES = [
    "select count(*) as c from lineitem where l_discount < 0.05",
    "select l_returnflag as f, sum(l_quantity) as q from lineitem "
    "group by l_returnflag order by f",
    "select o_orderpriority as p, count(*) as c from orders "
    "group by o_orderpriority order by p",
    "select sum(l_extendedprice * l_discount) as rev from lineitem "
    "where l_quantity < 24",
]


def run_sql(sql, source, out, idx):
    try:
        req = urllib.request.Request(
            base + "/v1/statement", data=sql.encode(),
            headers={"X-Presto-User": "smoke", "X-Presto-Source": source,
                     "Content-Type": "text/plain"})
        doc = json.load(urllib.request.urlopen(req, timeout=60))
        prog = doc.get("progressUri")
        fractions = []
        while True:
            if prog:
                p = json.load(urllib.request.urlopen(prog, timeout=30))
                fractions.append(p["fraction"])
            nxt = doc.get("nextUri")
            if not nxt:
                break
            doc = json.load(urllib.request.urlopen(nxt, timeout=60))
            prog = prog or doc.get("progressUri")
        if prog:  # terminal poll: must have pinned to 1.0
            p = json.load(urllib.request.urlopen(prog, timeout=30))
            fractions.append(p["fraction"])
        out[idx] = {"id": doc.get("id"), "state": doc["stats"]["state"],
                    "fractions": fractions, "final": p if prog else None,
                    "error": doc.get("error")}
    except Exception as e:  # noqa: BLE001
        out[idx] = {"error": repr(e)}


# forced-regression target: inject a tiny HBO wall baseline for this
# query's fingerprint BEFORE its first run (note() max-merges, so the
# baseline can only be injected while the history is empty)
REG_SQL = ("select l_linestatus as s, max(l_tax) as t from lineitem "
           "group by l_linestatus order by s")
dplan = dr.plan_distributed(REG_SQL)
fp = runstats.node_fingerprint(dplan.fragments[dplan.root_fid].root, cat)
assert fp, "no fingerprint for regression target"
runstats.note(fp, runstats.QUERY_SITE, wall_s=0.0001)

results = {}
threads = []
jobs = [(QUERIES[i % len(QUERIES)], ("adhoc", "batch")[i % 2])
        for i in range(8)] + [(REG_SQL, "batch")]
# repeat wave: same SQL shapes again so every fingerprint has history
jobs += [(QUERIES[i % len(QUERIES)], ("adhoc", "batch")[i % 2])
         for i in range(4)]
for i, (sql, src) in enumerate(jobs):
    t = threading.Thread(target=run_sql, args=(sql, src, results, i))
    threads.append(t)
for t in threads[:9]:
    t.start()
for t in threads[:9]:
    t.join()
for t in threads[9:]:  # the repeat wave runs after history exists
    t.start()
for t in threads[9:]:
    t.join()

failed = [r for r in results.values() if r.get("state") != "FINISHED"]
assert not failed, failed
assert len(results) == len(jobs)

# (b) progress monotone nondecreasing, ending at 1.0
hbo_final = 0
for r in results.values():
    fr = r["fractions"]
    assert fr == sorted(fr), f"progress went backwards: {fr}"
    assert fr[-1] == 1.0, f"progress never reached 1.0: {fr}"
    if r["final"]["provenance"] == "hbo":
        hbo_final += 1
assert hbo_final >= 4, (
    f"only {hbo_final} queries finished with HBO-predicted provenance")

# (a) per-group SLO families scrape lint-clean
body = urllib.request.urlopen(base + "/v1/metrics", timeout=10).read().decode()
errs = lint_exposition(body)
assert errs == [], errs
for fam in ("presto_tpu_query_queue_wait_seconds",
            "presto_tpu_query_compile_seconds",
            "presto_tpu_query_exec_seconds",
            "presto_tpu_query_e2e_seconds"):
    assert f"# TYPE {fam} histogram" in body, fam
for grp in ('group="global.adhoc"', 'group="global.batch"'):
    assert grp in body, f"{grp} missing from SLO families"
assert "presto_tpu_slo_violations_total" in body

# (c) sampled query's lifecycle transitions in canonical order on /v1/events
sample = next(r for r in results.values() if r["final"])
qid = sample["final"]["queryId"]
ev = json.load(urllib.request.urlopen(
    base + "/v1/events?queryId=" + qid + "&kind=lifecycle", timeout=10))
states = [e["state"] for e in ev["events"]]
canon = ["created", "queued", "admitted", "planning", "compiling",
         "executing", "draining", "finished"]
idxs = [canon.index(s) for s in states]
assert idxs == sorted(idxs), f"out-of-order lifecycle events: {states}"
assert states[0] == "created" and states[-1] == "finished", states
assert "executing" in states, states
assert all(e["traceToken"] == qid for e in ev["events"])
# the JSONL sink mirrors the ring
sunk = [json.loads(l) for l in open(events_log)]
assert any(r.get("queryId") == qid and r.get("state") == "finished"
           for r in sunk)

# (d) segments sum to e2e for every completed query that carries a timeline
qlist = json.load(urllib.request.urlopen(base + "/v1/query", timeout=10))
checked = 0
for q in qlist:
    lc = (q.get("stats") or {}).get("lifecycle")
    if not lc or q["state"] != "FINISHED":
        continue
    segs = lc["segments"]
    s = sum(v for k, v in segs.items() if k != "e2e")
    assert abs(s - segs["e2e"]) < 1e-3, (q["query_id"], segs)
    checked += 1
assert checked >= 9, f"only {checked} completed queries carried timelines"

# (e) forced regression: counter + event stream + slow-log annotation
assert "presto_tpu_latency_regression_total" in body
reg_lines = [l for l in body.splitlines()
             if l.startswith("presto_tpu_latency_regression_total")
             and 'group="global.batch"' in l]
assert reg_lines and float(reg_lines[0].rsplit(" ", 1)[1]) >= 1, reg_lines
rev = json.load(urllib.request.urlopen(
    base + "/v1/events?kind=latency_regression", timeout=10))
assert rev["events"], "no latency_regression event"
assert rev["events"][0]["baselineWallS"] == 0.0001
slow_recs = [json.loads(l) for l in open(slow_log)]
flagged = [r for r in slow_recs if "latencyRegression" in r]
assert flagged, "slow-query log record missing latencyRegression"
assert flagged[0]["latencyRegression"]["fingerprint"] == fp

dr.close()
print(f"serving-SLO smoke OK: {len(results)} queries across 2 groups, "
      f"{hbo_final} HBO-provenance finishes, {checked} timelines "
      f"segment-exact, regression counter/event/slow-log all flagged")
PYEOF
rc=$?
rm -rf "$tmp_slo"
if [ "$rc" -ne 0 ]; then
  echo "serving-SLO smoke FAILED (exit $rc)"
  exit "$rc"
fi

# Result-cache smoke: with result_cache=query on the session, the second
# run of an identical statement must (a) compile nothing, (b) dispatch
# zero breakers, (c) go straight to draining with a cache_hit event and
# a resultCache stat on the wire, (d) book ~zero compile/exec segment
# time, and (e) land a cacheHit doc in the slow-query JSONL. A catalog
# mutation (CTAS) must then bump the snapshot token and force a miss.
echo "== result-cache smoke: identical-query reuse + snapshot invalidation =="
tmp_rcache="$(mktemp -d)"
env JAX_PLATFORMS=cpu PRESTO_TPU_RC_DIR="$tmp_rcache" python - <<'PYEOF'
import json, os, urllib.request

from presto_tpu.catalog.memory import MemoryConnector
from presto_tpu.catalog.tpch import tpch_catalog
from presto_tpu.exec import programs
from presto_tpu.obs import lifecycle
from presto_tpu.scan import metrics as scan_metrics
from presto_tpu.server import result_cache as rcache
from presto_tpu.server.coordinator import DistributedRunner

slow_log = os.path.join(os.environ["PRESTO_TPU_RC_DIR"], "slow.jsonl")
cat = tpch_catalog(0.01)
cat.register("m", MemoryConnector())
dr = DistributedRunner(cat, n_workers=2, coordinator_kwargs={
    "slow_query_log": slow_log, "slow_query_threshold_s": 0.0})
base = dr.coordinator.url

SQL = ("select l_returnflag as f, sum(l_quantity) as q from lineitem "
       "group by l_returnflag order by f")


def run_sql(sql, session="result_cache=query"):
    headers = {"X-Presto-User": "smoke", "Content-Type": "text/plain"}
    if session:
        headers["X-Presto-Session"] = session
    req = urllib.request.Request(base + "/v1/statement",
                                 data=sql.encode(), headers=headers)
    doc = json.load(urllib.request.urlopen(req, timeout=60))
    qid, rows, last = doc["id"], [], doc
    while True:
        rows += doc.get("data") or []
        nxt = doc.get("nextUri")
        if not nxt:
            break
        doc = json.load(urllib.request.urlopen(nxt, timeout=60))
        last = doc
    return qid, rows, last


def breaker_dispatches():
    snap = scan_metrics.snapshot()
    return sum(v for k, v in snap.items()
               if k.startswith("breaker_dispatches"))


q1, rows1, _ = run_sql(SQL)
c0, b0 = programs.snapshot()["compiles"], breaker_dispatches()
q2, rows2, last2 = run_sql(SQL)
c1, b1 = programs.snapshot()["compiles"], breaker_dispatches()
assert rows1 == rows2 and rows1, "cached result must equal computed result"
assert c1 == c0, f"second run compiled ({c1 - c0} programs)"
assert b1 == b0, f"second run dispatched {b1 - b0} breakers"
st = (last2.get("stats") or {}).get("resultCache")
assert st and st["kind"] == "query", st
seg = lifecycle.get(q2).timeline.segments()
assert seg["compile"] == 0.0 and seg["exec"] == 0.0, seg
ev = json.load(urllib.request.urlopen(
    base + "/v1/events?kind=cache_hit", timeout=30))
assert ev["events"], "no cache_hit event on the stream"
slow = [json.loads(l) for l in open(slow_log)]
hit_docs = [r for r in slow if "cacheHit" in r]
assert hit_docs and hit_docs[0]["cacheHit"]["kind"] == "query", slow

# catalog mutation: CTAS in ANY connector bumps the snapshot token
run_sql("create table m.probe as select 1 as one", session=None)
q3, rows3, _ = run_sql(SQL)
assert rows3 == rows1, "post-DDL recompute must still be correct"
snap = rcache.CACHE.counters()
assert snap["hits"] == 1 and snap["misses"] >= 2, snap
assert snap["evictions"] >= 1, "stale entry bytes were not reclaimed"
dr.close()
print(f"result-cache smoke OK: run2 zero compiles / zero breaker "
      f"dispatches, exec segment 0.0s, wire stat {st['bytes']}B, "
      f"{len(ev['events'])} cache_hit event(s), DDL forced recompute "
      f"(counters {snap['hits']}h/{snap['misses']}m)")
PYEOF
rc=$?
rm -rf "$tmp_rcache"
if [ "$rc" -ne 0 ]; then
  echo "result-cache smoke FAILED (exit $rc)"
  exit "$rc"
fi

# Compile-tail smoke: three processes against ONE cache dir.
#   1. record — distributed traffic populates the farm corpus + persisted
#      program artifacts (plus: shape_bucketing off vs pow2 bit-identical).
#   2. boot #1 — coordinator pre-arms from the corpus; the first armed
#      boot still compiles the HBO-converged program set (phase 1's
#      observed cardinalities shift accumulator capacities, so plan
#      fingerprints move once) and persists it.
#   3. boot #2 — pre-arms >0 programs, prewarns every artifact, and a
#      FIRST-SEEN query of a pre-armed fingerprint must run with zero
#      on-path compiles and a ~zero lifecycle compile segment (vs ~8 s
#      without the boot prewarm), with EXPLAIN ANALYZE showing
#      "[farm: armed]".
echo "== compile-tail smoke: farm-armed boot + zero on-path compiles =="
tmp_farm="$(mktemp -d)"
env JAX_PLATFORMS=cpu PRESTO_TPU_CACHE_DIR="$tmp_farm" \
    PRESTO_TPU_FARM=1 PRESTO_TPU_PROGRAM_PERSIST=1 python - <<'PYEOF'
import json, os, urllib.request

from presto_tpu.catalog.tpch import tpch_catalog
from presto_tpu.exec import ExecConfig, LocalRunner, farm
from presto_tpu.server.coordinator import DistributedRunner

cat = tpch_catalog(0.01)
dr = DistributedRunner(cat, n_workers=2)
base = dr.coordinator.url

AGG = ("select l_returnflag as f, sum(l_quantity) as q, count(*) as c "
       "from lineitem where l_discount > 0.02 "
       "group by l_returnflag order by f")
JOIN = ("select o_orderpriority as p, count(*) as c from lineitem "
        "join orders on l_orderkey = o_orderkey "
        "group by o_orderpriority order by p")


def run_sql(sql):
    headers = {"X-Presto-User": "smoke", "Content-Type": "text/plain"}
    req = urllib.request.Request(base + "/v1/statement",
                                 data=sql.encode(), headers=headers)
    doc = json.load(urllib.request.urlopen(req, timeout=120))
    rows = []
    while True:
        rows += doc.get("data") or []
        nxt = doc.get("nextUri")
        if not nxt:
            break
        doc = json.load(urllib.request.urlopen(nxt, timeout=120))
    return rows


for sql in (AGG, JOIN):
    assert run_sql(sql), sql
farm.drain()
dr.close()
corpus = farm.load_corpus()
assert corpus["plans"], "no plans recorded in the farm corpus"
pdir = os.path.join(os.environ["PRESTO_TPU_CACHE_DIR"], "programs")
arts = os.listdir(pdir) if os.path.isdir(pdir) else []
assert arts, "no program artifacts persisted"

# bucketing satellite: pow2 padding must never change a result
r_off = LocalRunner(cat, ExecConfig(shape_bucketing="off"))
r_on = LocalRunner(cat, ExecConfig(shape_bucketing="pow2"))
for sql in (AGG, JOIN):
    assert r_off.run(sql).equals(r_on.run(sql)), \
        f"bucketing diverged: {sql}"
print(f"record OK: {len(corpus['plans'])} plans, {len(arts)} artifacts, "
      f"bucketing off==pow2")
PYEOF
rc=$?
if [ "$rc" -eq 0 ]; then
env JAX_PLATFORMS=cpu PRESTO_TPU_CACHE_DIR="$tmp_farm" \
    PRESTO_TPU_FARM=1 PRESTO_TPU_PROGRAM_PERSIST=1 python - <<'PYEOF'
import json, urllib.request

from presto_tpu.catalog.tpch import tpch_catalog
from presto_tpu.exec import farm, programs
from presto_tpu.server.coordinator import DistributedRunner

cat = tpch_catalog(0.01)
dr = DistributedRunner(cat, n_workers=2)
armed = dr.coordinator._farm_armed
assert armed > 0, f"boot #1 armed nothing ({armed})"
base = dr.coordinator.url

AGG = ("select l_returnflag as f, sum(l_quantity) as q, count(*) as c "
       "from lineitem where l_discount > 0.02 "
       "group by l_returnflag order by f")
JOIN = ("select o_orderpriority as p, count(*) as c from lineitem "
        "join orders on l_orderkey = o_orderkey "
        "group by o_orderpriority order by p")


def run_sql(sql):
    headers = {"X-Presto-User": "smoke", "Content-Type": "text/plain"}
    req = urllib.request.Request(base + "/v1/statement",
                                 data=sql.encode(), headers=headers)
    doc = json.load(urllib.request.urlopen(req, timeout=120))
    rows = []
    while True:
        rows += doc.get("data") or []
        nxt = doc.get("nextUri")
        if not nxt:
            break
        doc = json.load(urllib.request.urlopen(nxt, timeout=120))
    return rows


for sql in (AGG, JOIN):
    assert run_sql(sql), sql
farm.drain()
dr.close()
print(f"boot #1 OK: armed={armed} "
      f"converge_compiles={programs.snapshot()['compiles']}")
PYEOF
rc=$?
fi
if [ "$rc" -eq 0 ]; then
env JAX_PLATFORMS=cpu PRESTO_TPU_CACHE_DIR="$tmp_farm" \
    PRESTO_TPU_FARM=1 PRESTO_TPU_PROGRAM_PERSIST=1 python - <<'PYEOF'
import json, urllib.request

from presto_tpu.catalog.tpch import tpch_catalog
from presto_tpu.exec import programs
from presto_tpu.obs import lifecycle
from presto_tpu.server.coordinator import DistributedRunner

cat = tpch_catalog(0.01)
dr = DistributedRunner(cat, n_workers=2)
armed = dr.coordinator._farm_armed
assert armed > 0, f"boot #2 armed nothing ({armed})"
base = dr.coordinator.url

AGG = ("select l_returnflag as f, sum(l_quantity) as q, count(*) as c "
       "from lineitem where l_discount > 0.02 "
       "group by l_returnflag order by f")
JOIN = ("select o_orderpriority as p, count(*) as c from lineitem "
        "join orders on l_orderkey = o_orderkey "
        "group by o_orderpriority order by p")


def run_sql(sql):
    headers = {"X-Presto-User": "smoke", "Content-Type": "text/plain"}
    req = urllib.request.Request(base + "/v1/statement",
                                 data=sql.encode(), headers=headers)
    doc = json.load(urllib.request.urlopen(req, timeout=120))
    qid, rows = doc["id"], []
    while True:
        rows += doc.get("data") or []
        nxt = doc.get("nextUri")
        if not nxt:
            break
        doc = json.load(urllib.request.urlopen(nxt, timeout=120))
    return qid, rows


c0 = programs.snapshot()["compiles"]
qid, rows = run_sql(AGG)
c1 = programs.snapshot()["compiles"]
assert rows
assert c1 == c0, f"first-seen AGG compiled {c1 - c0} on-path"
seg = lifecycle.get(qid).timeline.segments()
assert seg.get("compile", 0.0) < 1.5, \
    f"compile segment not ~0 on a farm-armed boot: {seg}"
_, rj = run_sql(JOIN)
c2 = programs.snapshot()["compiles"]
assert rj
assert c2 == c1, f"first-seen JOIN compiled {c2 - c1} on-path"
_, out = run_sql("explain analyze " + AGG)
text = "\n".join(str(r[0]) for r in out if r)
assert "[farm: armed]" in text, text[:400]
snap = programs.snapshot()
dr.close()
print(f"boot #2 OK: armed={armed} prewarmed={snap['prewarmed']} "
      f"restored={snap['restored']} on-path compiles 0, "
      f"compile segment {seg['compile']:.2f}s, EXPLAIN shows "
      f"[farm: armed]")
PYEOF
rc=$?
fi
rm -rf "$tmp_farm"
if [ "$rc" -ne 0 ]; then
  echo "compile-tail smoke FAILED (exit $rc)"
  exit "$rc"
fi

# Inflight-telemetry smoke: the mid-flight plane end to end.
#   off-phase — inflight=off run in a fresh process: the /v1/metrics
#     scrape must carry ZERO inflight families (armed-gating) and the
#     query result is the bit-identity baseline.
#   stall phase — a sleep shim on the breaker dispatch path freezes the
#     row watermarks mid-query: assert a stall_detected event naming the
#     injected operator, a forensic JSONL record with >= 2 window
#     snapshots for that operator, and a /v1/query/{id}/doctor verdict
#     whose TOP cause names it.
#   straggler phase — a per-dispatch sleep on task_index 1 skews the
#     site watermarks: assert straggler_detected fingers that task.
#   on-phase scrape must lint clean with all 4 inflight families, and
#     the on-run rows must equal the off-run rows bit for bit.
echo "== inflight smoke: stall/straggler detection + query doctor =="
tmp_inf="$(mktemp -d)"
env JAX_PLATFORMS=cpu PRESTO_TPU_INF_DIR="$tmp_inf" python - <<'PYEOF'
import json, os, time, urllib.request

from presto_tpu.catalog.tpch import tpch_catalog
from presto_tpu.exec import runtime as runtime_mod
from presto_tpu.obs import inflight
from presto_tpu.obs.exposition import lint_exposition
from presto_tpu.server.coordinator import DistributedRunner

d = os.environ["PRESTO_TPU_INF_DIR"]
slow_log = os.path.join(d, "slow.jsonl")
cat = tpch_catalog(0.01)
dr = DistributedRunner(cat, n_workers=2, coordinator_kwargs={
    "slow_query_log": slow_log, "slow_query_threshold_s": 0.0})
base = dr.coordinator.url
inflight.configure(forensics_dir=d)

SQL = ("select l_returnflag as f, sum(l_quantity) as q from lineitem "
       "group by l_returnflag")
TUNING = "batch_rows=4096,fragment_window=2"


def run_sql(sql, session):
    headers = {"X-Presto-User": "smoke", "Content-Type": "text/plain",
               "X-Presto-Session": session}
    req = urllib.request.Request(base + "/v1/statement",
                                 data=sql.encode(), headers=headers)
    doc = json.load(urllib.request.urlopen(req, timeout=120))
    qid, rows = doc["id"], []
    while True:
        rows += doc.get("data") or []
        nxt = doc.get("nextUri")
        if not nxt:
            break
        doc = json.load(urllib.request.urlopen(nxt, timeout=120))
    assert doc["stats"]["state"] == "FINISHED", doc
    # group-by output order is not deterministic — compare as sets
    return qid, sorted(map(repr, rows))


def scrape():
    return urllib.request.urlopen(
        base + "/v1/metrics", timeout=10).read().decode()


INF_FAMS = ("presto_tpu_inflight_queries",
            "presto_tpu_inflight_publishes_total",
            "presto_tpu_stalls_total", "presto_tpu_stragglers_total")

# -- off phase: no families, baseline rows (also warms the program cache
#    so the injected sleeps dominate the stall run's wall)
q_off, rows_off = run_sql(SQL, "inflight=off," + TUNING)
body = scrape()
for fam in INF_FAMS:
    assert fam not in body, f"{fam} leaked into an inflight=off scrape"
assert inflight.snapshot_doc(q_off) is None
assert not inflight.armed()

# -- stall phase: from the 2nd dispatch of whichever breaker op gets
#    there first, every subsequent dispatch of that op sleeps past the
#    stall threshold with the row watermarks frozen
orig_dispatch = runtime_mod._record_fragment_dispatch
counts, injected = {}, {}


def sleepy_dispatch(node, ctx, fused, k=1):
    orig_dispatch(node, ctx, fused, k)
    op = type(node).__name__
    counts[op] = counts.get(op, 0) + 1
    if counts[op] >= 2 and injected.setdefault("op", op) == op:
        time.sleep(0.3)


runtime_mod._record_fragment_dispatch = sleepy_dispatch
try:
    q_stall, rows_stall = run_sql(
        SQL, "inflight=on,stall_threshold_s=0.12," + TUNING)
finally:
    runtime_mod._record_fragment_dispatch = orig_dispatch
assert rows_stall == rows_off, "inflight=on changed query results"
op = injected["op"]

ev = json.load(urllib.request.urlopen(
    base + "/v1/events?kind=stall_detected", timeout=10))
stalls = [e for e in ev["events"] if e["queryId"] == q_stall]
assert stalls, "no stall_detected event for the injected-sleep query"
assert stalls[0]["operator"] == op, (op, stalls[0])
assert stalls[0]["stalledS"] > 0.12

recs = [json.loads(l)
        for l in open(os.path.join(d, "inflight_forensics.jsonl"))]
mine = [r for r in recs if r["queryId"] == q_stall]
assert mine, "no forensic record for the stalled query"
snap_lists = [o["snapshots"] for key, o in mine[-1]["ops"].items()
              if key.endswith("/" + op)]
assert snap_lists and max(len(s) for s in snap_lists) >= 2, (
    f"forensics carries < 2 window snapshots for {op}")

doc = json.load(urllib.request.urlopen(
    base + f"/v1/query/{q_stall}/doctor", timeout=10))
top = doc["causes"][0]
assert top["cause"] == "stall" and top.get("operator") == op, doc["causes"]
assert op in doc["verdict"], doc["verdict"]

inf = json.load(urllib.request.urlopen(
    base + f"/v1/query/{q_stall}/inflight", timeout=10))
assert inf["publishes"] > 0 and inf["stalls"] >= 1
assert op in inf["stallSeconds"]

# -- straggler phase: every dispatch on task_index 1 sleeps, so that
#    site's window watermark falls behind its sibling's in the same
#    fragment while the leader runs at full speed
def lag_dispatch(node, ctx, fused, k=1):
    orig_dispatch(node, ctx, fused, k)
    if getattr(ctx, "task_index", 0) == 1:
        time.sleep(0.15)


runtime_mod._record_fragment_dispatch = lag_dispatch
try:
    q_strag, rows_strag = run_sql(
        SQL, "inflight=on,stall_threshold_s=0.6,straggler_factor=1.5,"
        + TUNING)
finally:
    runtime_mod._record_fragment_dispatch = orig_dispatch
assert rows_strag == rows_off

ev = json.load(urllib.request.urlopen(
    base + "/v1/events?kind=straggler_detected", timeout=10))
strag = [e for e in ev["events"] if e["queryId"] == q_strag]
assert strag, "no straggler_detected event for the lagged-dispatch query"
lag = strag[0]
assert lag["taskId"].split(".")[-1] == "1", lag
assert lag["taskId"] != lag["leaderTaskId"]
assert lag["leaderWindows"] > lag["laggardWindows"]

# -- armed scrape: all 4 families render and the document lints clean
body = scrape()
for fam in INF_FAMS:
    assert f"# TYPE {fam}" in body, f"{fam} missing from armed scrape"
errs = lint_exposition(body)
assert errs == [], errs

# slow-query log carries the doctor verdict for the stalled run
slow = [json.loads(l) for l in open(slow_log)]
doctored = [r for r in slow if r.get("queryId") == q_stall
            and "doctor" in r]
assert doctored, "slow-query record missing doctor annotation"
assert op in doctored[0]["doctor"]["verdict"]

dr.close()
print(f"inflight smoke OK: stall on {op} "
      f"({stalls[0]['stalledS']:.2f}s, {inf['stalls']} episode(s)), "
      f"straggler {lag['taskId']} {lag['laggardWindows']}/"
      f"{lag['leaderWindows']} windows, doctor verdict attributed, "
      f"off-scrape family-free, on/off rows identical")
PYEOF
rc=$?
rm -rf "$tmp_inf"
if [ "$rc" -ne 0 ]; then
  echo "inflight smoke FAILED (exit $rc)"
  exit "$rc"
fi

# Static-analysis step, consolidated: ONE `--all` invocation runs every
# plane — kernel lint, concurrency safety, knob-flow cache-key
# soundness, stale-suppression hygiene, TPC-H plan invariants, and the
# bounded-recompile guard — over the shipped tree with per-pass wall
# timing, and must come back with zero findings. Each plane then proves
# it can actually FAIL on an injected violation (a checker that can't
# fail is decoration).
echo "== analysis: all planes (lint, concurrency, knob-flow, stale, plans, recompile) =="
env JAX_PLATFORMS=cpu python -m presto_tpu.analysis --all
rc=$?
if [ "$rc" -ne 0 ]; then
  echo "analysis step FAILED: shipped tree does not analyze clean (exit $rc)"
  exit 1
fi
inj="$(mktemp -d)/ops"; mkdir -p "$inj"
cat > "$inj/injected.py" <<'PYEOF'
def kernel(x):
    if jnp.any(x > 0):
        return float(x.sum())
    return jnp.zeros(100)
PYEOF
env JAX_PLATFORMS=cpu python -m presto_tpu.analysis "$inj/injected.py" \
    > /tmp/_inj.log 2>&1
rc=$?
rm -rf "$(dirname "$inj")"
if [ "$rc" -eq 0 ]; then
  echo "analysis step FAILED: injected violation was NOT detected"
  cat /tmp/_inj.log
  exit 1
fi
grep -q "injected.py:2: \[traced-branch\]" /tmp/_inj.log \
  && grep -q "injected.py:3: \[host-sync\]" /tmp/_inj.log \
  && grep -q "injected.py:4: \[pow2-capacity\]" /tmp/_inj.log
if [ $? -ne 0 ]; then
  echo "analysis step FAILED: injected findings missing rule/file:line"
  cat /tmp/_inj.log
  exit 1
fi
echo "injected-violation self-check OK (exit $rc, 3 rules attributed)"

# Concurrency self-check: the pass (already run clean under --all above)
# must FAIL on an injected module carrying the three bug classes it
# exists for: an unguarded mutation of lock-guarded state, a
# check-then-act split across two critical sections, and a two-lock
# lock-order cycle.
cinj="$(mktemp -d)"
cat > "$cinj/injected_conc.py" <<'PYEOF'
import threading

_lock = threading.Lock()
_other = threading.Lock()
_cache = {}  # shared: guarded-by(_lock)


def unguarded_put(k, v):
    _cache[k] = v


def check_then_act(k):
    with _lock:
        v = _cache.get(k)
    if v is None:
        v = object()
        with _lock:
            _cache[k] = v
    return v


def order_ab():
    with _lock:
        with _other:
            pass


def order_ba():
    with _other:
        with _lock:
            pass
PYEOF
env JAX_PLATFORMS=cpu python -m presto_tpu.analysis --no-lint --concurrency \
    "$cinj/injected_conc.py" > /tmp/_cinj.log 2>&1
rc=$?
rm -rf "$cinj"
if [ "$rc" -eq 0 ]; then
  echo "concurrency step FAILED: injected violations were NOT detected"
  cat /tmp/_cinj.log
  exit 1
fi
grep -q "injected_conc.py:9: \[unguarded\]" /tmp/_cinj.log \
  && grep -q "injected_conc.py:.*\[check-then-act\]" /tmp/_cinj.log \
  && grep -q "\[lock-order\]" /tmp/_cinj.log
if [ $? -ne 0 ]; then
  echo "concurrency step FAILED: injected findings missing rule/file:line"
  cat /tmp/_cinj.log
  exit 1
fi
echo "concurrency self-check OK (exit $rc, 3 rules attributed)"

# Knob-flow self-check: each of the four cache-key soundness rules must
# fire with file:line attribution on its minimal injected violation — a
# volatile ExecConfig field captured by a program builder closure, an
# undeclared PRESTO_TPU_* env read inside traced code, a key consumer
# reading outside its declared covers() set, and an operator-state
# NamedTuple missing from the pytree serialization table.
kinj="$(mktemp -d)"; mkdir -p "$kinj/ops"
cat > "$kinj/injected_leak.py" <<'PYEOF'
def build(node, ctx):
    hbo = ctx.config.hbo

    def fn(x):
        return x if hbo == "off" else x + 1
    return _node_jit(node, "probe", lambda: fn)
PYEOF
cat > "$kinj/injected_knob.py" <<'PYEOF'
import os

import jax


@jax.jit
def kernel(x):
    return x if os.environ.get("PRESTO_TPU_TURBO") else -x
PYEOF
cat > "$kinj/injected_adaptive.py" <<'PYEOF'
def build(node, ctx):
    mode = ctx.config.adaptive

    def fn(x):
        return x + 1 if mode == "on" else x
    return _node_jit(node, "probe", lambda: fn)
PYEOF
cat > "$kinj/injected_drift.py" <<'PYEOF'
def derive(root):  # fp: key(inj-key) covers(plan-structure)
    return hash(root)


def consume(root, config):  # fp: uses-key(inj-key)
    k = derive(root)
    return (k, config.batch_rows)
PYEOF
cat > "$kinj/ops/injected_state.py" <<'PYEOF'
from typing import NamedTuple


class InjectedState(NamedTuple):
    rows: int
PYEOF
env JAX_PLATFORMS=cpu python -m presto_tpu.analysis --no-lint --knob-flow \
    "$kinj" > /tmp/_kinj.log 2>&1
rc=$?
rm -rf "$kinj"
if [ "$rc" -eq 0 ]; then
  echo "knob-flow step FAILED: injected violations were NOT detected"
  cat /tmp/_kinj.log
  exit 1
fi
grep -q "injected_leak.py:6: \[volatile-leak\]" /tmp/_kinj.log \
  && grep -q "injected_adaptive.py:6: \[volatile-leak\]" /tmp/_kinj.log \
  && grep -q "injected_knob.py:8: \[unfingerprinted-knob\]" /tmp/_kinj.log \
  && grep -q "injected_drift.py:7: \[cache-key-drift\]" /tmp/_kinj.log \
  && grep -q "ops/injected_state.py:4: \[unregistered-state\]" /tmp/_kinj.log
if [ $? -ne 0 ]; then
  echo "knob-flow step FAILED: injected findings missing rule/file:line"
  cat /tmp/_kinj.log
  exit 1
fi
echo "knob-flow self-check OK (exit $rc, 4 rules attributed + adaptive leak)"

# Stale-suppression self-check: an allow() whose rule does not fire at
# its site must be flagged (a suppression that outlives its bug hides
# the next real one).
sinj="$(mktemp -d)"
printf 'x = 1  # lint: allow(host-sync)\n' > "$sinj/injected_stale.py"
env JAX_PLATFORMS=cpu python -m presto_tpu.analysis --no-lint \
    --stale-suppressions "$sinj" > /tmp/_sinj.log 2>&1
rc=$?
rm -rf "$sinj"
if [ "$rc" -eq 0 ]; then
  echo "stale-suppression step FAILED: stale allow() was NOT detected"
  cat /tmp/_sinj.log
  exit 1
fi
if ! grep -q "injected_stale.py:1: \[stale-suppression\]" /tmp/_sinj.log; then
  echo "stale-suppression step FAILED: finding missing rule/file:line"
  cat /tmp/_sinj.log
  exit 1
fi
echo "stale-suppression self-check OK (exit $rc)"

# Knob-inventory drift check: the README's embedded knob table must
# match the auto-generated one (the inventory is the documentation of
# record for every knob's cache semantics — a new knob lands with its
# volatility class decided and published, or CI fails here).
env JAX_PLATFORMS=cpu python -m presto_tpu.analysis --knobs > /tmp/_knobs.md
rc=$?
if [ "$rc" -ne 0 ]; then
  echo "knob-inventory step FAILED: --knobs exited $rc"
  exit 1
fi
awk '/<!-- knobs:begin -->/{f=1;next} /<!-- knobs:end -->/{f=0} f' \
    README.md > /tmp/_knobs_readme.md
if ! diff -u /tmp/_knobs_readme.md /tmp/_knobs.md > /tmp/_knobs.diff; then
  echo "knob-inventory step FAILED: README table drifted from --knobs output"
  cat /tmp/_knobs.diff
  exit 1
fi
echo "knob-inventory drift check OK ($(wc -l < /tmp/_knobs.md | tr -d ' ') lines)"

# Multiway-join smoke: a q3-shaped star chain forced through the fused
# N-ary probe must (1) return checksum-identical results to the binary
# path, (2) dispatch strictly fewer breaker programs, (3) plan strictly
# fewer fragments/exchanges distributed (binary pays per-join partitioned
# exchanges once broadcast is suppressed), (4) carry the EXPLAIN verdict
# marker, and (5) leave join_mode=off bit-for-bit on the pre-collapse
# plan and result.
echo "== multiway smoke: fused star-chain vs binary join chain =="
env JAX_PLATFORMS=cpu python - <<'PYEOF'
from presto_tpu.catalog.tpch import tpch_catalog
from presto_tpu.exec import ExecConfig, LocalRunner
from presto_tpu.verifier import result_checksum

cat = tpch_catalog(0.01)
sql = ("select o.o_orderkey, sum(l.l_extendedprice) rev "
       "from lineitem l "
       "join orders o on l.l_orderkey = o.o_orderkey "
       "join customer c on o.o_custkey = c.c_custkey "
       "where c.c_mktsegment = 'BUILDING' "
       "group by o.o_orderkey")


def breaker_dispatches(stats):
    return sum(v for k, v in stats.items()
               if k.startswith("breaker.engine_"))


off = LocalRunner(cat, ExecConfig(batch_rows=1 << 13, join_mode="off"))
mw = LocalRunner(cat, ExecConfig(batch_rows=1 << 13, join_mode="multiway"))
ref = off.run_batch(sql)
got = mw.run_batch(sql)
assert result_checksum(got) == result_checksum(ref), "checksum mismatch"
assert mw.last_stats.get("multiway.fused_dispatches", 0) >= 1
bd_off, bd_mw = breaker_dispatches(off.last_stats), \
    breaker_dispatches(mw.last_stats)
assert bd_mw < bd_off, f"breaker dispatches {bd_mw} !< {bd_off}"
out = mw.explain(sql)
assert "MultiwayJoin" in out and "[join=multiway" in out, out
out_off = off.explain(sql)
assert "MultiwayJoin" not in out_off and "[join=" not in out_off, \
    "join_mode=off must leave the pre-collapse plan untouched"
# off is bit-for-bit the binary path: same plan string, same checksum
binary = LocalRunner(cat, ExecConfig(batch_rows=1 << 13))
assert result_checksum(binary.run_batch(sql)) == result_checksum(ref)
print(f"local multiway smoke OK: checksums equal, breaker dispatches "
      f"{bd_off} binary -> {bd_mw} multiway, EXPLAIN marker present")

# distributed: strictly fewer fragments AND exchanges once broadcast is
# suppressed (each binary join pays two partitioned exchange edges)
from presto_tpu.server.coordinator import DistributedRunner


def exchange_edges(dplan):
    return sum(len(f.remote_sources()) for f in dplan.fragments.values())


counts = {}
for jm in ("off", "multiway"):
    with DistributedRunner(cat, n_workers=2,
                           config=ExecConfig(batch_rows=1 << 13,
                                             join_mode=jm),
                           broadcast_threshold_rows=0) as dr:
        dplan = dr.plan_distributed(sql)
        counts[jm] = (len(dplan.fragments), exchange_edges(dplan),
                      result_checksum(dr.run_batch(sql)))
assert counts["off"][2] == counts["multiway"][2] == result_checksum(ref)
assert counts["multiway"][0] < counts["off"][0], \
    f"fragments {counts['multiway'][0]} !< {counts['off'][0]}"
assert counts["multiway"][1] < counts["off"][1], \
    f"exchanges {counts['multiway'][1]} !< {counts['off'][1]}"
print(f"distributed multiway smoke OK: fragments "
      f"{counts['off'][0]} -> {counts['multiway'][0]}, exchange edges "
      f"{counts['off'][1]} -> {counts['multiway'][1]}, checksums equal")
PYEOF
rc=$?
if [ "$rc" -ne 0 ]; then
  echo "multiway smoke FAILED (exit $rc)"
  exit "$rc"
fi

# tier-1 as the driver runs it (/root/TESTS_LAST_RUN.json): six workers, 1,470 s
rm -f /tmp/_t1.log
timeout -k 10 1470 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
  --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 --dist load \
  -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)"
echo "WORKERS_DOWN=$(grep -acE '\[gw[0-9]+\] node down' /tmp/_t1.log)"
exit $rc
