"""Memory accounting + spill (reference: presto-memory-context,
MemoryPool/ClusterMemoryManager, MemoryRevokingScheduler, spiller/,
SpillableHashAggregationBuilder, HashBuilderOperator spill states)."""

import numpy as np
import pandas as pd
import pytest

from presto_tpu.batch import Batch
from presto_tpu.connector import Catalog
from presto_tpu.catalog.memory import MemoryConnector
from presto_tpu.exec import ExecConfig, LocalRunner
from presto_tpu.memory import (
    AggregatedMemoryContext,
    ExceededMemoryLimit,
    LocalMemoryContext,
    MemoryPool,
    batch_device_bytes,
)
from presto_tpu.spiller import SpillManager

from conftest import assert_frames_match


def test_pool_reserve_free_peak():
    pool = MemoryPool(1000)
    c = LocalMemoryContext(pool, "op")
    c.set_bytes(400)
    assert pool.reserved == 400
    c.set_bytes(100)
    assert pool.reserved == 100
    assert pool.peak == 400
    c.close()
    assert pool.reserved == 0


def test_pool_limit_enforced():
    pool = MemoryPool(1000)
    c = LocalMemoryContext(pool, "op")
    with pytest.raises(ExceededMemoryLimit):
        c.set_bytes(2000)


def test_pool_revocation():
    pool = MemoryPool(1000, revoke_threshold=0.8, revoke_target=0.3)
    victim = LocalMemoryContext(pool, "agg")
    victim.set_bytes(700)
    revoked = []

    def revoker(need):
        revoked.append(need)
        freed = victim.bytes
        victim.set_bytes(0)
        return freed

    pool.add_revoker(revoker)
    other = LocalMemoryContext(pool, "join")
    other.set_bytes(200)  # 700+200 > 800 → revoke down toward 300
    assert revoked, "revoker not invoked"
    assert pool.reserved == 200


def test_aggregated_context_rollup():
    pool = MemoryPool(None)
    agg = AggregatedMemoryContext(pool, "task")
    a, b = agg.new_local("op1"), agg.new_local("op2")
    a.set_bytes(10)
    b.set_bytes(20)
    assert agg.bytes == 30
    agg.close()
    assert pool.reserved == 0


def test_spill_file_roundtrip(tmp_path, rng):
    from presto_tpu.types import BIGINT, DOUBLE

    sm = SpillManager(str(tmp_path))
    sp = sm.partitioning_spiller(["k"], 4, "t")
    n = 1000
    k = rng.integers(0, 50, n)
    v = rng.normal(size=n)
    b = Batch.from_numpy({"k": k, "v": v}, {"k": BIGINT, "v": DOUBLE})
    sp.spill(b)
    sp.spill(b)
    back_k, back_v = [], []
    seen_parts = 0
    for p in range(4):
        batches = list(sp.read_partition(p))
        if batches:
            seen_parts += 1
        for rb in batches:
            d = rb.to_pydict()
            back_k.extend(d["k"])
            back_v.extend(d["v"])
    assert seen_parts > 1  # actually partitioned
    assert sorted(back_k) == sorted(list(k) * 2)
    np.testing.assert_allclose(sorted(back_v), sorted(list(v) * 2))
    sp.close()


@pytest.fixture(scope="module")
def spill_tables(rng):
    n = 60_000
    cat = Catalog()
    conn = MemoryConnector()
    conn.add_table("facts", pd.DataFrame({
        "g": rng.integers(0, 20_000, n),
        "v": rng.normal(size=n),
        "k": rng.integers(0, 5_000, n),
    }))
    conn.add_table("dim", pd.DataFrame({
        "id": np.arange(5_000),
        "w": rng.normal(size=5_000),
    }))
    cat.register("m", conn, default=True)
    return cat


def _runners(cat, pool_bytes):
    unlimited = LocalRunner(cat, ExecConfig(batch_rows=1 << 13))
    limited = LocalRunner(cat, ExecConfig(
        batch_rows=1 << 13, memory_pool_bytes=pool_bytes,
        spill_partitions=4,
    ))
    return unlimited, limited


def test_aggregation_spills_and_matches(spill_tables):
    sql = "select g, sum(v) as s, count(*) as c, avg(v) as a from facts group by g"
    unlimited, limited = _runners(spill_tables, 1 << 20)
    exp = unlimited.run(sql)
    ctx_probe = {}
    # run limited and capture that spill actually happened
    from presto_tpu.exec.runtime import ExecContext, run_plan

    qp = limited.plan(sql)
    ctx = ExecContext(limited.catalog, limited.config)
    got = run_plan(qp, ctx).to_pandas()
    assert ctx.spill_manager.spill_count > 0, "expected the aggregation to spill"
    assert_frames_match(got, exp, sort_by=["g"])


def test_join_build_spills_and_matches(spill_tables):
    sql = """select dim.w, facts.v from facts join dim on facts.k = dim.id
             where facts.g < 1000"""
    unlimited, limited = _runners(spill_tables, 100 << 10)
    exp = unlimited.run(sql)
    from presto_tpu.exec.runtime import ExecContext, run_plan

    qp = limited.plan(sql)
    ctx = ExecContext(limited.catalog, limited.config)
    got = run_plan(qp, ctx).to_pandas()
    assert ctx.spill_manager.spill_count >= 2  # build + probe spillers
    assert_frames_match(got, exp, sort_by=["w", "v"])


def test_left_join_spill_preserves_outer_rows(spill_tables):
    # k ranges to 5000, dim ids cover all → add filter making some unmatched
    sql = """select facts.k, dim.w from facts left join dim
             on facts.k = dim.id and dim.w > 0.5 where facts.g < 300"""
    unlimited, limited = _runners(spill_tables, 100 << 10)
    exp = unlimited.run(sql)
    got = limited.run(sql)
    assert_frames_match(got, exp, sort_by=["k", "w"])


def test_spilled_join_string_keys_cross_dictionary(rng):
    """Spill routing must hash string CONTENT, not dictionary codes: the two
    sides are encoded against different dictionaries, so equal strings have
    different codes — code-hash routing would send matches to different
    buckets and silently drop rows."""
    n = 40_000
    keys_probe = [f"k{i:05d}" for i in rng.integers(0, 3000, n)]
    # build dictionary has a DIFFERENT value set (superset w/ extra values)
    dim_keys = [f"k{i:05d}" for i in range(4000)]
    cat = Catalog()
    conn = MemoryConnector()
    conn.add_table("f", pd.DataFrame({"sk": keys_probe, "v": rng.normal(size=n)}))
    conn.add_table("d", pd.DataFrame({"dk": dim_keys,
                                      "w": rng.normal(size=len(dim_keys))}))
    cat.register("m", conn, default=True)
    sql = "select d.w, f.v from f join d on f.sk = d.dk"
    unlimited = LocalRunner(cat, ExecConfig(batch_rows=1 << 13))
    limited = LocalRunner(cat, ExecConfig(batch_rows=1 << 13,
                                          memory_pool_bytes=48 << 10,
                                          spill_partitions=4))
    exp = unlimited.run(sql)
    from presto_tpu.exec.runtime import ExecContext, run_plan

    qp = limited.plan(sql)
    ctx = ExecContext(limited.catalog, limited.config)
    got = run_plan(qp, ctx).to_pandas()
    assert ctx.spill_manager.spill_count >= 2, "join did not spill"
    assert len(got) == len(exp) == n  # every probe row matches
    assert_frames_match(got, exp, sort_by=["w", "v"])


def test_memory_limit_without_spill_fails(spill_tables):
    runner = LocalRunner(spill_tables, ExecConfig(
        batch_rows=1 << 13, memory_pool_bytes=512 << 10, spill_enabled=False,
    ))
    with pytest.raises(ExceededMemoryLimit):
        runner.run("select g, sum(v) as s from facts group by g")


def test_distributed_query_with_spill(spill_tables):
    from presto_tpu.server.coordinator import DistributedRunner
    from presto_tpu.server.worker import Worker

    unlimited = LocalRunner(spill_tables, ExecConfig(batch_rows=1 << 13))
    sql = "select g, sum(v) as s from facts group by g"
    exp = unlimited.run(sql)
    r = DistributedRunner(spill_tables, n_workers=2,
                          config=ExecConfig(batch_rows=1 << 13,
                                            memory_pool_bytes=256 << 10,
                                            spill_partitions=4))
    try:
        assert all(w.memory_pool.limit == 256 << 10 for w in r.workers)
        got = r.run(sql)
        assert_frames_match(got, exp, sort_by=["g"])
        assert any(w.spill_manager.spill_count > 0 for w in r.workers)
        # status endpoint reports memory + spill
        st = r.workers[0].status()
        assert "memory" in st and "spilledBytes" in st
    finally:
        r.close()


# -- PR 15: dynamic hybrid hash spill plane --------------------------------


def test_spill_file_names_never_collide(tmp_path, rng):
    """Spill paths derive from a process-monotonic counter, not id(self):
    two spillers alive at different times (id() is recycled after GC) must
    never map the same tag+partition to the same path."""
    sm = SpillManager(str(tmp_path))
    a = sm.partitioning_spiller(["k"], 4, "t")
    paths_a = {f.path for f in a.files}
    a.close()
    b = sm.partitioning_spiller(["k"], 4, "t")
    paths_b = {f.path for f in b.files}
    b.close()
    assert len(paths_a) == len(paths_b) == 4
    assert not (paths_a & paths_b)
    f1, f2 = sm.spill_file("x"), sm.spill_file("x")
    assert f1.path != f2.path
    f1.close()
    f2.close()


def _one_spill_file(tmp_path, rng, n=500):
    from presto_tpu.types import BIGINT, DOUBLE

    sm = SpillManager(str(tmp_path))
    f = sm.spill_file("crc")
    b = Batch.from_numpy({"k": rng.integers(0, 50, n), "v": rng.normal(size=n)},
                         {"k": BIGINT, "v": DOUBLE})
    f.append(b)
    f.append(b)
    f.finish_writing()
    return f


def test_spill_crc_bit_flip_detected(tmp_path, rng):
    """A flipped bit in a spilled page must surface as a structured
    SpillCorruption naming the file and page, never as garbage rows."""
    from presto_tpu.spiller import SpillCorruption

    f = _one_spill_file(tmp_path, rng)
    with open(f.path, "r+b") as fh:
        fh.seek(40)  # inside the first page's payload
        byte = fh.read(1)
        fh.seek(40)
        fh.write(bytes([byte[0] ^ 0xFF]))
    with pytest.raises(SpillCorruption, match="crc32 mismatch") as ei:
        list(f.read())
    assert ei.value.path == f.path
    assert ei.value.page == 0


def test_spill_truncation_detected(tmp_path, rng):
    """A torn write (file truncated mid-page) must fail the replay loudly
    with the framing diagnosis, not silently drop the tail rows."""
    import os as _os

    from presto_tpu.spiller import SpillCorruption

    f = _one_spill_file(tmp_path, rng)
    size = _os.path.getsize(f.path)
    with open(f.path, "r+b") as fh:
        fh.truncate(size - 7)
    with pytest.raises(SpillCorruption, match="truncated"):
        list(f.read())


def test_spill_leak_guard_on_mid_spill_failure(rng):
    """A query killed mid-spill (spill-directory byte budget exhausted)
    must not strand spill files: run_plan's teardown closes and unlinks
    every spill resource the context ever opened."""
    import os as _os

    from presto_tpu.exec.runtime import ExecContext, run_plan
    from presto_tpu.spiller import SpillLimitExceeded

    n = 60_000
    cat = Catalog()
    conn = MemoryConnector()
    conn.add_table("f", pd.DataFrame({"k": rng.integers(0, 5_000, n),
                                      "v": rng.normal(size=n)}))
    conn.add_table("d", pd.DataFrame({"id": np.arange(5_000),
                                      "w": rng.normal(size=5_000)}))
    cat.register("m", conn, default=True)
    r = LocalRunner(cat, ExecConfig(
        batch_rows=1 << 13, memory_pool_bytes=100 << 10, spill_partitions=4,
        spill_dir_budget_bytes=24 << 10))
    qp = r.plan("select d.w, f.v from f join d on f.k = d.id")
    ctx = ExecContext(cat, r.config)
    with pytest.raises(SpillLimitExceeded, match="byte budget"):
        run_plan(qp, ctx)
    assert ctx.spill_manager.in_use_bytes == 0
    assert _os.listdir(ctx.spill_manager.dir) == []


def test_spill_leak_guard_on_cancel(spill_tables):
    """An abandoned (canceled) query leaves its spill generators unclosed;
    task teardown's cleanup_spill must still unlink every spill file."""
    import os as _os

    from presto_tpu.exec.runtime import ExecContext, execute_node

    cfg = ExecConfig(batch_rows=1 << 13, memory_pool_bytes=100 << 10,
                     spill_partitions=4)
    r = LocalRunner(spill_tables, cfg)
    qp = r.plan("select dim.w, facts.v from facts join dim on facts.k = dim.id")
    ctx = ExecContext(spill_tables, cfg)
    stream = execute_node(qp.root.child, ctx)
    next(stream)  # partial consumption: the join has spilled and is replaying
    assert ctx.spill_resources, "join did not spill"
    assert ctx.spill_manager.in_use_bytes > 0
    ctx.cleanup_spill()  # what TaskExecution/run_plan teardown calls
    assert ctx.spill_manager.in_use_bytes == 0
    assert _os.listdir(ctx.spill_manager.dir) == []


# -- skew-adversarial matrix ----------------------------------------------


def test_spilled_join_role_reversal_on_skewed_build(rng):
    """One-hot build keys: 95% of build rows share one key, so no amount of
    next-hash-bit splitting shrinks the hot partition. Its probe partition
    is small — replay must REVERSE roles (build the probe side, stream the
    hot side) instead of recursing to the depth bound and failing."""
    from presto_tpu.exec.runtime import ExecContext, run_plan

    n_build, n_probe = 24_000, 32_000
    bk = np.where(rng.random(n_build) < 0.95, 7,
                  rng.integers(0, 2_000, n_build)).astype(np.int64)
    cat = Catalog()
    conn = MemoryConnector()
    conn.add_table("probe", pd.DataFrame({
        "k": rng.integers(0, 2_000, n_probe).astype(np.int64),
        "v": rng.normal(size=n_probe)}))
    conn.add_table("build", pd.DataFrame({"bk": bk,
                                          "w": rng.normal(size=n_build)}))
    cat.register("m", conn, default=True)
    sql = "select probe.v, build.w from probe join build on probe.k = build.bk"
    exp = LocalRunner(cat, ExecConfig(batch_rows=1 << 13)).run(sql)
    limited = LocalRunner(cat, ExecConfig(
        batch_rows=1 << 13, memory_pool_bytes=96 << 10, spill_partitions=4,
        spill_max_depth=2))
    qp = limited.plan(sql)
    ctx = ExecContext(cat, limited.config)
    got = run_plan(qp, ctx).to_pandas()
    assert ctx.stats.get("spill.role_reversals", 0) > 0, \
        "hot partition did not reverse roles"
    assert ctx.stats.get("spill.repartitions", 0) > 0
    assert_frames_match(got, exp, sort_by=["v", "w"])


def test_spilled_join_depth_bound_fails_structured(rng):
    """Identical keys on BOTH sides: hash bits can never split the hot
    partition and role reversal cannot rescue it (the probe side is just
    as hot) — recursion must stop at spill_max_depth with a structured
    SPILL_LIMIT_EXCEEDED, not loop forever or OOM."""
    import os as _os

    from presto_tpu.exec.runtime import ExecContext, run_plan
    from presto_tpu.spiller import SpillLimitExceeded

    n = 40_000
    cat = Catalog()
    conn = MemoryConnector()
    conn.add_table("a", pd.DataFrame({"k": np.zeros(n, dtype=np.int64),
                                      "v": rng.normal(size=n)}))
    conn.add_table("b", pd.DataFrame({"j": np.zeros(n, dtype=np.int64),
                                      "w": rng.normal(size=n)}))
    cat.register("m", conn, default=True)
    r = LocalRunner(cat, ExecConfig(
        batch_rows=1 << 13, memory_pool_bytes=128 << 10, spill_partitions=4,
        spill_max_depth=2))
    qp = r.plan("select a.v, b.w from a join b on a.k = b.j")
    ctx = ExecContext(cat, r.config)
    with pytest.raises(SpillLimitExceeded, match="max recursion depth"):
        run_plan(qp, ctx)
    # the structured failure still tears down cleanly
    assert _os.listdir(ctx.spill_manager.dir) == []


def test_spilled_join_zero_row_partitions(rng):
    """NDV below the partition count leaves most partitions empty, and
    probe-only keys leave build partitions empty while their probe side is
    populated — both must replay cleanly (skip, no output) not crash."""
    from presto_tpu.exec.runtime import ExecContext, run_plan

    cat = Catalog()
    conn = MemoryConnector()
    conn.add_table("bl", pd.DataFrame({
        "k": np.repeat(np.arange(3, dtype=np.int64), 800),
        "w": rng.normal(size=2_400)}))
    conn.add_table("pr", pd.DataFrame({
        "j": rng.integers(0, 9, 4_000).astype(np.int64),
        "v": rng.normal(size=4_000)}))
    cat.register("m", conn, default=True)
    sql = "select pr.v, bl.w from pr join bl on pr.j = bl.k"
    exp = LocalRunner(cat, ExecConfig(batch_rows=1 << 13)).run(sql)
    # four partitions hold the case as surely as eight: the build's three
    # keys leave partition 3 without a build row, and the probe's keys 3 and
    # 7 land there
    from presto_tpu.spiller import np_bucket_ids

    build_parts = set(np_bucket_ids([(np.arange(3), None, None)], 4))
    probe_parts = set(np_bucket_ids([(np.arange(9), None, None)], 4))
    assert probe_parts - build_parts, "no build-empty partition is probed"
    limited = LocalRunner(cat, ExecConfig(
        batch_rows=1 << 13, memory_pool_bytes=32 << 10, spill_partitions=4,
        join_spill_budget_bytes=64 << 10))
    qp = limited.plan(sql)
    ctx = ExecContext(cat, limited.config)
    got = run_plan(qp, ctx).to_pandas()
    assert ctx.spill_manager.spill_count >= 2, "join did not spill"
    assert ctx.stats["spill.partitions"] >= 4
    assert_frames_match(got, exp, sort_by=["v", "w"])


@pytest.mark.parametrize("ndv,dup", [(50, 160), (4_000, 2)])
def test_spilled_join_ndv_duplication_matrix(rng, ndv, dup):
    """Duplication-vs-NDV sweep: heavy duplication (few fat keys) and high
    NDV (many thin keys) stress opposite corners of the partitioner; both
    must match the in-memory oracle bit-for-bit on values."""
    from presto_tpu.exec.runtime import ExecContext, run_plan

    bk = np.repeat(np.arange(ndv, dtype=np.int64), dup)
    cat = Catalog()
    conn = MemoryConnector()
    conn.add_table("bl", pd.DataFrame({"k": bk,
                                       "w": rng.normal(size=len(bk))}))
    conn.add_table("pr", pd.DataFrame({
        "j": rng.integers(0, ndv, 4_000).astype(np.int64),
        "v": rng.normal(size=4_000)}))
    cat.register("m", conn, default=True)
    sql = "select pr.v, bl.w from pr join bl on pr.j = bl.k"
    exp = LocalRunner(cat, ExecConfig(batch_rows=1 << 13)).run(sql)
    limited = LocalRunner(cat, ExecConfig(
        batch_rows=1 << 13, memory_pool_bytes=48 << 10, spill_partitions=4))
    qp = limited.plan(sql)
    ctx = ExecContext(cat, limited.config)
    got = run_plan(qp, ctx).to_pandas()
    assert ctx.spill_manager.spill_count >= 2, "join did not spill"
    assert ctx.stats["spill.partitions"] >= 4
    assert_frames_match(got, exp, sort_by=["v", "w"])


def test_hbo_seeds_spill_partitions_fewer_waves(tmp_path, monkeypatch, rng):
    """Two-run acceptance loop: run 1 under-estimates the partition count
    and pays repartition waves; run 2 with hbo=correct seeds the converged
    leaf count from history and must see STRICTLY fewer waves."""
    from presto_tpu.exec.runtime import ExecContext, run_plan
    from presto_tpu.obs import runstats

    monkeypatch.setenv("PRESTO_TPU_CACHE_DIR", str(tmp_path))
    runstats.reset()
    try:
        n = 20_000
        cat = Catalog()
        conn = MemoryConnector()
        conn.add_table("bl", pd.DataFrame({
            "k": rng.integers(0, 5_000, n).astype(np.int64),
            "w": rng.normal(size=n)}))
        conn.add_table("pr", pd.DataFrame({
            "j": rng.integers(0, 5_000, 8_000).astype(np.int64),
            "v": rng.normal(size=8_000)}))
        cat.register("m", conn, default=True)
        sql = "select pr.v, bl.w from pr join bl on pr.j = bl.k"

        def _run(hbo):
            r = LocalRunner(cat, ExecConfig(
                batch_rows=1 << 13, memory_pool_bytes=96 << 10,
                spill_partitions=2, spill_max_depth=3, hbo=hbo))
            qp = r.plan(sql)
            ctx = ExecContext(cat, r.config)
            out = run_plan(qp, ctx).to_pandas()
            return out, ctx.stats.get("spill.repartitions", 0)

        got1, waves1 = _run("observe")
        assert waves1 > 0, "first run should pay repartition waves"
        got2, waves2 = _run("correct")
        assert waves2 < waves1, (
            f"hbo=correct run paid {waves2} repartition waves, "
            f"first run paid {waves1}")
        assert_frames_match(got2, got1.copy(), sort_by=["v", "w"])
    finally:
        runstats.reset()
