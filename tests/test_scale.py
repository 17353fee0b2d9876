"""Scale-stress tier: TPC-H at SF 0.1 with deliberately hostile knobs —
tiny batches (many batches per scan), undersized group tables (growth +
replay past several recompiles), small memory pools (spill), and skewed
keys. The failure modes SF100 hits, exercised in CI sizes
(round-2 verdict: nothing tested capacity growth past one recompile)."""

import numpy as np
import pytest

from presto_tpu.catalog.tpch import tpch_catalog
from presto_tpu.exec import ExecConfig, LocalRunner

SF = 0.1


@pytest.fixture(scope="module")
def catalog():
    return tpch_catalog(SF)


# Every runner of this module takes the sort engine: it is the one the chip
# runs for these statements. Under `auto` XLA:CPU answers Q1's four groups
# with the Pallas hash engine, which here is the interpreter (ROADMAP D2):
# it took nine tenths of this module's minutes, most of them in the
# comfortable reference, and is not what "scale stress" stresses. The hash
# engine keeps its own file (test_pallas_breakers.py).
@pytest.fixture(scope="module")
def reference(catalog):
    """Baseline results from a comfortably-sized engine."""
    return LocalRunner(catalog, ExecConfig(batch_rows=1 << 20,
                                           breaker_engine="sort"))


@pytest.fixture(scope="module")
def stressed(catalog):
    """Same data, hostile knobs: 8k-row batches, 128-slot group tables,
    2-partition spill."""
    return LocalRunner(
        catalog,
        ExecConfig(batch_rows=1 << 13, agg_capacity=128,
                   spill_partitions=2, agg_pipeline_depth=2,
                   breaker_engine="sort"))


Q1 = """
select l_returnflag, l_linestatus, sum(l_quantity) as sq,
       sum(l_extendedprice) as se, avg(l_discount) as ad,
       count(*) as n
from lineitem where l_shipdate <= date '1998-09-02'
group by l_returnflag, l_linestatus order by l_returnflag, l_linestatus
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

# Every predicate holds for (nearly) every order, and the CBO discounts each
# one: it expects a few hundred groups and sizes the table at 512 slots
# where 5,000 custkeys arrive. Without them the table is sized from
# o_custkey's NDV and never grows. (Function calls, not LIKEs: a LIKE's share
# is read from the column's dictionary since PR 35, and is about 1 here.)
GROWTH = """
select o_custkey, count(*) as n, sum(o_totalprice) as s
from orders
where length(o_comment) > 0 and strpos(o_clerk, 'Clerk') = 1
  and o_orderpriority <> 'x'
group by o_custkey order by n desc, o_custkey limit 20
"""


def _same(a, b):
    assert list(a.columns) == list(b.columns)
    assert len(a) == len(b)
    for c in a.columns:
        ga, gb = a[c], b[c]
        try:
            np.testing.assert_allclose(ga.astype(float), gb.astype(float),
                                       rtol=1e-9, err_msg=c)
        except (TypeError, ValueError):
            assert ga.tolist() == gb.tolist(), c


def test_q1_under_stress(reference, stressed):
    _same(stressed.run(Q1), reference.run(Q1))
    # many batches per scan: the 600k rows went through in 8k-row batches
    assert stressed.last_stats["fragment.fused_batches"] >= 64


def test_q3_multibatch_join(reference, stressed):
    _same(stressed.run(Q3), reference.run(Q3))
    # the group table behind the join went grace from the start and both of
    # its leaves, each sized from its rows, replayed without a wave (before
    # PR 33 each climbed from 128 slots by one); growth by replay is
    # test_group_table_growth_ladder's
    assert "breaker.replay_waves" not in stressed.last_stats
    assert stressed.last_stats["spill.partitions"] >= 2


def test_group_table_growth_ladder(reference, stressed):
    # 5,000 custkeys against a table the CBO sized at 512 slots: overflow,
    # growth and replay from the checkpoint must be exact
    _same(stressed.run(GROWTH), reference.run(GROWTH))
    assert stressed.last_stats["breaker.replay_waves"] >= 1


def test_spill_with_tiny_pool(catalog, reference):
    r = LocalRunner(
        catalog,
        ExecConfig(batch_rows=1 << 13, agg_capacity=1 << 10,
                   memory_pool_bytes=1 << 20, spill_partitions=4,
                   breaker_engine="sort"))
    _same(r.run(GROWTH), reference.run(GROWTH))
    # a 24 MiB pool held the whole table: nothing spilled. 1 MiB does not.
    assert r.last_stats["spill.partitions"] >= 4


def test_skewed_distributed_partitions(catalog, reference):
    """2-worker cluster with skew: most lineitems hash to few orders."""
    from presto_tpu.obs import trace
    from presto_tpu.server.coordinator import DistributedRunner

    dist = DistributedRunner(catalog, n_workers=2,
                             config=ExecConfig(batch_rows=1 << 13,
                                               agg_capacity=1 << 8,
                                               breaker_engine="sort"))
    try:
        got = dist.run(Q1)
        phases = trace.summaries()[-1]["phases"]
    finally:
        dist.close()
    _same(got, reference.run(Q1))
    # the 74 batches of 8k rows, read by the two workers' tasks
    assert phases["scan-prefetch"]["scan_read"]["n"] >= 64
