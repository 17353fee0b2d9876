"""Grace (hash-partitioned) aggregation — the high-NDV GROUP BY path.

Reference: operator/aggregation/builder/SpillableHashAggregationBuilder
(partitioned spill + bucket-wise finalize) and adaptive partial
aggregation. TPU-native trigger: above ExecConfig.agg_cap_ceiling a
fixed-capacity group table would make every merge sort millions of dead
slots, so raw input hash-partitions to spill (host-side) and each
partition merges independently at small capacity; a partial-step
aggregation instead emits per-row state contributions (the final step
after the exchange does the one real merge)."""

import numpy as np
import pandas as pd
import pytest

from presto_tpu.catalog.memory import MemoryConnector
from presto_tpu.connector import Catalog
from presto_tpu.exec import ExecConfig, LocalRunner


N = 40_000
NDV = 9_000


@pytest.fixture(scope="module")
def cat():
    rng = np.random.default_rng(23)
    conn = MemoryConnector()
    g = rng.integers(0, NDV, N)
    conn.add_table("t", pd.DataFrame({
        "g": g,
        "x": rng.integers(0, 1000, N),
        "f": rng.normal(size=N),
        "s": np.array([f"name{v % 97}" for v in g]),
    }))
    c = Catalog()
    c.register("m", conn, default=True)
    return c


SQL = ("select g, count(*) as c, sum(x) as sx, min(f) as mn, max(s) as mx "
       "from t group by g")


_BASELINE = {}


def _baseline(cat):
    # big ceiling: the plain in-memory table path. Memoized per catalog —
    # every caller reads the same immutable answer, no point re-running.
    if id(cat) not in _BASELINE:
        r = LocalRunner(cat, ExecConfig(batch_rows=1 << 12,
                                        agg_capacity=1 << 14,
                                        agg_cap_ceiling=1 << 22))
        _BASELINE[id(cat)] = r.run(SQL).sort_values("g", ignore_index=True)
    return _BASELINE[id(cat)]


def _check(df, base):
    df = df.sort_values("g", ignore_index=True)
    assert len(df) == len(base)
    assert len(base) > NDV * 0.95  # high-NDV: far above any test ceiling
    for c in ("g", "c", "sx", "mn", "mx"):
        got, want = df[c].tolist(), base[c].tolist()
        if c == "mn":
            assert all(abs(a - b) < 1e-12 for a, b in zip(got, want))
        else:
            assert got == want, c


def test_grace_from_start_matches_baseline(cat):
    """CBO pre-size above the ceiling routes straight to the partitioned
    path (no in-memory merge at all during ingest)."""
    base = _baseline(cat)
    r = LocalRunner(cat, ExecConfig(batch_rows=1 << 12,
                                    agg_capacity=1 << 8,
                                    agg_cap_ceiling=1 << 9,
                                    spill_partitions=4))
    _check(r.run(SQL), base)
    assert r.last_stats["spill.partitions"] >= 4


def test_grace_recursive_repartition_high_ndv(cat):
    """A spilled partition whose group count still exceeds the grace
    ceiling at finalize must split by the NEXT hash bits and recurse
    (dynamic hybrid hash), not fail or grow an oversized table: with
    ~2250 groups per partition against a 512 ceiling, repartition waves
    are mandatory — and the answer must still match. Deliberately the
    exact config of test_grace_from_start_matches_baseline so every
    program comes out of the shared structural cache; this test adds
    only the stats assertion and the replayed exec."""
    from presto_tpu.exec.runtime import ExecContext, run_plan

    base = _baseline(cat)
    r = LocalRunner(cat, ExecConfig(batch_rows=1 << 12,
                                    agg_capacity=1 << 8,
                                    agg_cap_ceiling=1 << 9,
                                    spill_partitions=4))
    qp = r.plan(SQL)
    ctx = ExecContext(cat, r.config)
    got = run_plan(qp, ctx).to_pandas()
    assert ctx.stats.get("spill.repartitions", 0) > 0, \
        "finalize never recursively repartitioned"
    _check(got, base)


def test_grace_depth_bound_fails_structured(cat):
    """spill_max_depth=0 forbids recursive repartitioning: a partition
    over the grace ceiling must fail with a structured
    SPILL_LIMIT_EXCEEDED, not loop or silently grow past the ceiling."""
    from presto_tpu.spiller import SpillLimitExceeded

    r = LocalRunner(cat, ExecConfig(
        batch_rows=1 << 12, agg_capacity=1 << 8, agg_cap_ceiling=1 << 9,
        spill_partitions=4, spill_max_depth=0))
    with pytest.raises(SpillLimitExceeded, match="grace ceiling"):
        r.run(SQL)


def test_midstream_overflow_switches_to_grace(cat):
    """A table that outgrows the ceiling mid-stream (_GraceOverflow): the
    confirmed accumulator spills as state pages, the unmerged window +
    remaining input as raw rows, and each leaf merges both. The key is an
    expression, so the CBO guesses 4,000 groups and sizes the table at the
    ceiling instead of going grace from the start as every plain `group by
    g` of this file does; 8,880 arrive. (Before PR 33 this test asserted
    two replay waves and never left the grace-from-start path: the waves
    were the leaves', which climbed from agg_capacity one by one and are
    now sized from their rows.)"""
    base = _baseline(cat)
    r = LocalRunner(cat, ExecConfig(batch_rows=1 << 12,
                                    agg_capacity=1 << 8,
                                    agg_cap_ceiling=1 << 13,
                                    spill_partitions=4,
                                    breaker_engine="sort"))
    _check(r.run(SQL.replace("select g,", "select g + 0 as g,")
                 .replace("group by g", "group by g + 0")), base)
    assert "breaker.replay_waves" not in r.last_stats
    assert r.last_stats["spill.partitions"] >= 4


def test_distributed_partial_passthrough(cat):
    """step='partial' above the ceiling emits per-row state contributions
    (adaptive partial-agg bypass); the final step after the exchange does
    the real merge. Cross-checked against the local engine."""
    from presto_tpu.server.coordinator import DistributedRunner

    base = _baseline(cat)
    dist = DistributedRunner(
        cat, n_workers=2,
        config=ExecConfig(batch_rows=1 << 12, agg_capacity=1 << 8,
                          agg_cap_ceiling=1 << 9, spill_partitions=4))
    try:
        _check(dist.run(SQL), base)
    finally:
        dist.close()


def test_grace_with_nulls_and_global(cat):
    rng = np.random.default_rng(5)
    conn = cat.connectors["m"]
    vals = rng.integers(0, 100, 5000).astype(object)
    vals[::7] = None
    conn.add_table("n", pd.DataFrame({
        "g": rng.integers(0, 3000, 5000), "v": vals}))
    r = LocalRunner(cat, ExecConfig(batch_rows=1 << 10,
                                    agg_capacity=1 << 6,
                                    agg_cap_ceiling=1 << 8,
                                    spill_partitions=4))
    rbig = LocalRunner(cat, ExecConfig(batch_rows=1 << 10,
                                       agg_capacity=1 << 13,
                                       agg_cap_ceiling=1 << 22))
    q = "select g, count(v) as c, sum(v) as s from n group by g"
    a = r.run(q).sort_values("g", ignore_index=True)
    b = rbig.run(q).sort_values("g", ignore_index=True)
    assert a.c.tolist() == b.c.tolist()
    assert [x if x is None or not pd.isna(x) else None for x in a.s.tolist()] \
        == [x if x is None or not pd.isna(x) else None for x in b.s.tolist()]


# ---- grace × memory-pool interplay (the branches that interact:
# spill on/off, grace bypass, revocation, small pools) ------------------

def test_grace_under_tight_pool(cat):
    """Grace-from-start WITH a small memory pool: partition replay's
    absorb runs with allow_spill=False and must stay inside the pool
    (accounting was only exercised pool-less before)."""
    base = _baseline(cat)
    r = LocalRunner(cat, ExecConfig(
        batch_rows=1 << 12, agg_capacity=1 << 8, agg_cap_ceiling=1 << 9,
        memory_pool_bytes=24_000_000, spill_partitions=16))
    _check(r.run(SQL), base)
    assert r.last_stats["spill.partitions"] >= 16


def test_midstream_overflow_with_pool_and_revocation(cat):
    """The in-memory table grows, crosses the revoke threshold (spilling
    state pages), THEN outgrows the ceiling mid-stream (raw grace
    handoff): both spillers finalize bucket-wise into one answer."""
    base = _baseline(cat)
    r = LocalRunner(cat, ExecConfig(
        batch_rows=1 << 12, agg_capacity=1 << 8, agg_cap_ceiling=1 << 12,
        memory_pool_bytes=16_000_000,
        memory_revoking_threshold=0.5, memory_revoking_target=0.2))
    _check(r.run(SQL), base)
    # grace from the start, and no leaf climbs from agg_capacity any more
    assert "breaker.replay_waves" not in r.last_stats
    assert r.last_stats["spill.partitions"] >= 8


def test_grace_disabled_when_spill_off(cat):
    """spill_enabled=False forbids the grace path: the table must grow in
    memory instead and still answer correctly (growth-ladder replay)."""
    base = _baseline(cat)
    r = LocalRunner(cat, ExecConfig(
        batch_rows=1 << 12, agg_capacity=1 << 8, agg_cap_ceiling=1 << 9,
        spill_enabled=False))
    _check(r.run(SQL), base)
    assert "spill.partitions" not in r.last_stats


def test_tiny_pool_without_spill_fails_cleanly(cat):
    """No spill + a pool too small for the group table: a clean
    ExceededMemoryLimit, not a wrong answer or a hang."""
    r = LocalRunner(cat, ExecConfig(
        batch_rows=1 << 12, agg_capacity=1 << 8, spill_enabled=False,
        memory_pool_bytes=400_000))
    with pytest.raises(Exception, match="memory"):
        r.run(SQL)


def test_grace_distributed_with_pool(cat):
    """Distributed partial-passthrough + final grace merge under
    per-worker pools: worker-shared accounting with revokers must not
    corrupt across the exchange."""
    from presto_tpu.server.coordinator import DistributedRunner

    base = _baseline(cat)
    cfg = ExecConfig(batch_rows=1 << 12, agg_capacity=1 << 8,
                     agg_cap_ceiling=1 << 10,
                     memory_pool_bytes=32_000_000)
    with DistributedRunner(cat, n_workers=2, config=cfg) as dist:
        _check(dist.run(SQL), base)


# ---- PR 15: dynamic hybrid hash — skew-adversarial grace matrix --------


def test_grace_one_hot_group_skew(cat):
    """One-hot skew: 95% of rows share ONE group, the tail spreads over
    39 more — the hot group concentrates in one spill partition (low NDV
    there, huge row count) while several partitions land zero rows; both
    extremes must finalize cleanly and match the in-memory answer."""
    rng = np.random.default_rng(31)
    conn = cat.connectors["m"]
    n = 30_000
    g = np.where(rng.random(n) < 0.95, 7, rng.integers(0, 40, n))
    conn.add_table("sk", pd.DataFrame({
        "g": g.astype(np.int64), "v": rng.integers(0, 1000, n)}))
    q = "select g, count(*) as c, sum(v) as s from sk group by g"
    big = LocalRunner(cat, ExecConfig(batch_rows=1 << 12,
                                      agg_capacity=1 << 13,
                                      agg_cap_ceiling=1 << 22))
    grace = LocalRunner(cat, ExecConfig(batch_rows=1 << 12,
                                        agg_capacity=1 << 4,
                                        agg_cap_ceiling=1 << 4,
                                        spill_partitions=16))
    a = grace.run(q).sort_values("g", ignore_index=True)
    b = big.run(q).sort_values("g", ignore_index=True)
    assert a.g.tolist() == b.g.tolist()
    assert a.c.tolist() == b.c.tolist()
    assert a.s.tolist() == b.s.tolist()


# ---- PR 33: TPC-H Q18 through the served path; a spilled leaf replays as
# whole batches at one capacity, so its programs do not follow the seed -----

Q18_SF, Q18_QUANTITY = 0.01, "250"  # QUANTITY 300 leaves no order at SF 0.01
# 15,000 groups against a ceiling of 2,048: the inner aggregate's final step
# goes grace from its first page (its partial step's decision), four
# partitions of about 15,000 rows whose 3,750 groups outnumber the ceiling,
# so each splits once and its children fit
Q18_CONFIG = dict(batch_rows=1 << 13, agg_capacity=1 << 8,
                  agg_cap_ceiling=1 << 11, spill_partitions=4,
                  breaker_engine="sort")


Q18_SEEDS = (2147484018, 3000000019)


def _q18_served(seed):
    """Q18 at SF 0.01 on the seed's data through a DistributedRunner: its
    rows as the reference prints them, the reference's, the statement's
    phases by name (thread roles summed), the leaves as read back (rows,
    batch capacity, batches), the spill files it made, the aggregation's
    programs minted so far in the process and the waves counted so far."""
    from benchmark import data as bdata, run as brun, traffic
    from benchmark.refutil import date_str
    from presto_tpu import spiller
    from presto_tpu.exec import programs
    from presto_tpu.obs import trace
    from presto_tpu.scan import metrics as scan_metrics
    from presto_tpu.server.__main__ import build_catalog
    from presto_tpu.server.coordinator import DistributedRunner

    query = traffic.load_query("q18")
    data = bdata.generate(Q18_SF, seed, sorted(query["tables"]))
    catalog = build_catalog([f"tpch:sf={Q18_SF:g}"])
    bdata.install(catalog, Q18_SF, seed, data)
    want = brun.load_reference("q18")(data, {"quantity": Q18_QUANTITY})

    leaves, files = [], []
    read_batches = spiller.PartitioningSpiller.read_batches
    init = spiller.SpillFile.__init__

    def read_noting(self, p, capacity):
        leaf = [self.partition_rows(p), capacity, 0]
        leaves.append(leaf)
        for b in read_batches(self, p, capacity):
            assert b.capacity == capacity
            leaf[2] += 1
            yield b

    def init_noting(self, path, *a, **kw):
        files.append(path)
        init(self, path, *a, **kw)

    with pytest.MonkeyPatch.context() as mp, DistributedRunner(
            catalog, n_workers=1, config=ExecConfig(**Q18_CONFIG)) as dist:
        mp.setattr(spiller.PartitioningSpiller, "read_batches", read_noting)
        mp.setattr(spiller.SpillFile, "__init__", init_noting)
        df = dist.run(query["template"].format(quantity=Q18_QUANTITY))
    got = [[r[0], r[1], r[2], date_str(r[3]), r[4], int(r[5])]
           for r in df.values.tolist()]
    phases = {}
    for by_name in trace.summaries()[-1]["phases"].values():
        for name, agg in by_name.items():
            tot = phases.setdefault(name, {"n": 0, "items": 0})
            tot["n"] += agg["n"]
            tot["items"] += agg.get("items", 0)
    minted = {e.fp: e.compiles for e in programs.entries()
              if "|Aggregate|" in str(e.fp)}
    return dict(got=got, want=want, phases=phases, leaves=leaves, files=files,
                minted=minted,
                waves=scan_metrics.snapshot()["agg_replay_waves"])


@pytest.fixture(scope="module")
def q18_runs():
    """The statement once on each of two seeds' data, in that order."""
    from presto_tpu.scan import metrics as scan_metrics

    waves0 = scan_metrics.snapshot()["agg_replay_waves"]
    return waves0, [_q18_served(seed) for seed in Q18_SEEDS]


@pytest.mark.parametrize("i", range(len(Q18_SEEDS)))
def test_q18_served_equals_the_reference(q18_runs, i):
    run = q18_runs[1][i]
    assert run["got"] == run["want"] and len(run["want"]) > 10


def test_q18_phases_carry_their_counts(q18_runs):
    """The phases of ISSUE 33's table, with their `n` and `items`."""
    run = q18_runs[1][0]
    phases, leaves = run["phases"], run["leaves"]
    assert phases["agg_partition"] == {"n": 1, "items": 8}  # 59,997 rows / 2^13
    # a leaf whose groups outnumber the ceiling still splits, by four, and
    # every leaf begun is an occurrence, the four at the root included
    splits = phases["agg_repartition"]["n"]
    assert splits >= 1
    assert max(rows for rows, _, _ in leaves) > Q18_CONFIG["agg_cap_ceiling"]
    assert phases["agg_replay"]["n"] == len(leaves) == 4 + 4 * splits
    assert phases["agg_replay"]["items"] == sum(n for _, _, n in leaves)
    # a batch routed, and a page re-routed when its leaf split
    assert phases["host_sync:agg_spill_rows"]["n"] >= 8
    assert phases["agg_spill_write"]["n"] >= 8
    assert phases["agg_spill_write"]["items"] > 100_000  # bytes
    # every page is read back once, a split leaf's once more by its split
    assert phases["agg_spill_read"]["n"] > phases["agg_spill_write"]["n"]
    assert phases["agg_spill_read"]["items"] > phases["agg_spill_write"]["items"]


@pytest.mark.parametrize("i", range(len(Q18_SEEDS)))
def test_q18_leaf_is_whole_batches_at_one_capacity(q18_runs, i):
    """A leaf is at most ceil(rows / capacity) merges (one that overflowed
    stopped there), the capacity one power of two from its rows, and no
    leaf is merged again at a bigger table."""
    waves0, runs = q18_runs
    for rows, capacity, batches in runs[i]["leaves"]:
        assert capacity == min(1 << 13, max(1 << 8, 1 << (rows - 1).bit_length()))
        assert batches <= -(-rows // capacity)
        if rows <= Q18_CONFIG["agg_cap_ceiling"]:  # its groups fit for sure
            assert batches == -(-rows // capacity) <= 1
    assert "agg_replay_wave" not in runs[i]["phases"]
    assert runs[i]["waves"] == waves0


def test_q18_programs_do_not_follow_the_seed(q18_runs):
    """Another seed's data mints no aggregation program: same keys, same
    shapes (a join's build is as wide as the orders that pass the HAVING)."""
    first, second = q18_runs[1]
    assert sum(first["minted"].values()) >= 4
    assert second["minted"] == first["minted"]
    assert {c for _, c, _ in second["leaves"]} == {c for _, c, _ in first["leaves"]}
    assert second["phases"]["agg_replay"]["n"] == first["phases"]["agg_replay"]["n"]


@pytest.mark.parametrize("i", range(len(Q18_SEEDS)))
def test_q18_spill_files_are_gone_with_the_statement(q18_runs, i):
    import os

    files = q18_runs[1][i]["files"]
    assert files and not [f for f in files if os.path.exists(f)]
