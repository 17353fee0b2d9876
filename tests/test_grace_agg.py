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
    """A small initial capacity grows via replay until it crosses the
    ceiling mid-stream (_GraceOverflow): the confirmed accumulator spills
    as state pages, the unmerged window + remaining input as raw rows."""
    base = _baseline(cat)
    # ceiling low enough that growth crosses it, capacity lower still
    r = LocalRunner(cat, ExecConfig(batch_rows=1 << 12,
                                    agg_capacity=1 << 8,
                                    agg_cap_ceiling=1 << 10,
                                    spill_partitions=4))
    _check(r.run(SQL), base)
    # the table grew by replay before it crossed the ceiling and spilled
    assert r.last_stats["breaker.replay_waves"] >= 2
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
    assert r.last_stats["breaker.replay_waves"] >= 2
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
