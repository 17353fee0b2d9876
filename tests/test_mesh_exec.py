"""Mesh SQL executor: real fragmented plans as one shard_map program over
the 8-device CPU mesh, cross-checked against the streaming LocalRunner.

Reference: SURVEY §2e TPU-native equivalent — intra-slice shuffle as
all_to_all collectives replacing PartitionedOutputOperator→HTTP→
ExchangeClient; AddExchanges.java:141 fragment boundaries become
collective boundaries.
"""

import numpy as np
import pytest

from presto_tpu.catalog.tpch import tpch_catalog
from presto_tpu.exec import ExecConfig, LocalRunner
from presto_tpu.parallel.mesh import make_mesh
from presto_tpu.parallel.mesh_exec import MeshExecutor


@pytest.fixture(scope="module")
def env():
    cat = tpch_catalog(0.01)
    conn = cat.connectors["tpch"]
    for t in ("customer", "orders", "lineitem", "nation", "region",
              "supplier", "part", "partsupp"):
        conn._ensure(t)
    mesh = make_mesh(8)
    mx = MeshExecutor(cat, mesh, ExecConfig(batch_rows=1 << 12,
                                            agg_capacity=1 << 10))
    local = LocalRunner(cat, ExecConfig(batch_rows=1 << 13))
    return mx, local


def _same(got, exp, float_cols=()):
    assert len(got) == len(exp)
    for c in got.columns:
        g, e = got[c].tolist(), exp[c].tolist()
        if c in float_cols:
            assert all(abs(float(a) - float(b)) < 1e-6 for a, b in zip(g, e)), c
        else:
            assert [str(v) for v in g] == [str(v) for v in e], c


def test_grouped_aggregate(env):
    mx, local = env
    q = ("select l_returnflag as f, l_linestatus as s, count(*) as c, "
         "sum(l_extendedprice) as tot, avg(l_discount) as ad "
         "from lineitem group by l_returnflag, l_linestatus order by f, s")
    _same(mx.run(q), local.run(q), float_cols=("ad",))


def test_grouped_aggregate_key_ownership(env):
    """Each group is finalized on exactly one device: the gathered answer
    of a 1,000-group aggregate, taken without ORDER BY or LIMIT, has every
    key once and every row counted once (a key owned by two devices would
    come back as two rows)."""
    mx, _ = env
    orders = mx.catalog.connectors["tpch"].tables["orders"]
    keys = np.asarray(orders.arrays["o_custkey"])
    got = mx.run("select o_custkey as k, count(*) as c from orders "
                 "group by o_custkey")
    assert len(got) == len(set(got.k.tolist())) == len(np.unique(keys))
    assert int(got.c.sum()) == len(keys)


def test_q3_three_way_join(env):
    mx, local = env
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
    _same(mx.run(q), local.run(q), float_cols=("revenue",))


def test_q5_shape_multi_dim_join(env):
    mx, local = env
    q = """
select n_name, sum(l_extendedprice * (1 - l_discount)) as revenue
from customer, orders, lineitem, supplier, nation, region
where c_custkey = o_custkey and l_orderkey = o_orderkey
  and l_suppkey = s_suppkey and c_nationkey = s_nationkey
  and s_nationkey = n_nationkey and n_regionkey = r_regionkey
  and r_name = 'ASIA' and o_orderdate >= date '1994-01-01'
group by n_name order by revenue desc
"""
    _same(mx.run(q), local.run(q), float_cols=("revenue",))


def test_global_aggregate(env):
    mx, local = env
    q = ("select count(*) as c, sum(l_quantity) as q, min(l_shipdate) as lo, "
         "max(l_shipdate) as hi from lineitem where l_discount between 0.02 and 0.08")
    _same(mx.run(q), local.run(q))


def test_fanout_join(env):
    mx, local = env
    # orders→lineitem is a fanout (non-unique build when lineitem builds):
    # force probe=orders, build=lineitem shape via aggregation over join
    q = ("select o_orderpriority as p, count(*) as c from orders, lineitem "
         "where o_orderkey = l_orderkey group by o_orderpriority order by p")
    _same(mx.run(q), local.run(q))


def test_semijoin(env):
    mx, local = env
    q = ("select count(*) as c from orders where o_custkey in "
         "(select c_custkey from customer where c_mktsegment = 'BUILDING')")
    _same(mx.run(q), local.run(q))


def test_union_all_on_mesh(env):
    """UNION ALL on-mesh: rr redistribution is the identity (every device
    keeps its shard), the downstream aggregate runs per device."""
    mx, local = env
    q = ("select s, count(*) as n, sum(k) as sk from ("
         "  select o_orderstatus as s, o_custkey as k from orders"
         "  union all"
         "  select o_orderpriority as s, o_orderkey as k from orders"
         ") u group by s order by s")
    _same(mx.run(q), local.run(q))


def test_unnest_on_mesh(env):
    mx, local = env
    q = ("select e, count(*) as n from orders "
         "cross join unnest(array[1, 2]) as u(e) "
         "group by e order by e")
    _same(mx.run(q), local.run(q))


def test_window_on_mesh(env):
    """Window functions trace into the shard_map program (the gathered
    SINGLE fragment is replicated per device; build_window_compute is the
    same traceable kernel the streaming engine jits)."""
    mx, local = env
    q = ("select o_custkey, o_orderkey, "
         "row_number() over (partition by o_custkey "
         "order by o_totalprice desc) as rn, "
         "sum(o_totalprice) over (partition by o_custkey) as tot "
         "from orders where o_custkey < 50 order by o_custkey, rn")
    _same(mx.run(q), local.run(q), float_cols=("tot",))


def test_full_outer_join_on_mesh(env):
    """FULL OUTER: probe-null tail + per-device build remainder (the
    fragmenter never broadcasts a full join's build side, so each device
    owns disjoint build rows)."""
    mx, local = env
    q = ("select c_custkey, count(o_orderkey) as n "
         "from customer full outer join orders on c_custkey = o_custkey "
         "group by c_custkey order by c_custkey")
    g, e = mx.run(q), local.run(q)
    assert len(g) == len(e)
    assert list(g.n) == list(e.n)


def test_right_outer_join_on_mesh(env):
    """RIGHT OUTER normalizes to LEFT at analysis; rows with no match keep
    NULL left columns."""
    mx, local = env
    q = ("select o_orderkey, c_name from orders "
         "right outer join customer on o_custkey = c_custkey "
         "where c_custkey < 100 order by c_name, o_orderkey")
    _same(mx.run(q), local.run(q))


def test_intersect_except_on_mesh(env):
    mx, local = env
    qi = ("select o_custkey as k from orders intersect "
          "select c_custkey as k from customer where c_custkey < 500 "
          "order by k")
    _same(mx.run(qi), local.run(qi))
    qe = ("select c_custkey as k from customer except "
          "select o_custkey as k from orders order by k")
    _same(mx.run(qe), local.run(qe))


def test_residual_semijoin_on_mesh(env):
    """Correlated EXISTS / NOT EXISTS with non-equi residuals (Q21 shape):
    the mesh pairs, evaluates the residual and ANY-reduces per probe row —
    previously the residual was silently ignored."""
    mx, local = env
    q = ("select count(*) as c from lineitem l1 "
         "where l1.l_receiptdate > l1.l_commitdate "
         "and exists (select * from lineitem l2 "
         "            where l2.l_orderkey = l1.l_orderkey "
         "              and l2.l_suppkey <> l1.l_suppkey) "
         "and not exists (select * from lineitem l3 "
         "                where l3.l_orderkey = l1.l_orderkey "
         "                  and l3.l_suppkey <> l1.l_suppkey "
         "                  and l3.l_receiptdate > l3.l_commitdate)")
    _same(mx.run(q), local.run(q))


def test_scalar_subquery_param_on_mesh(env):
    """Uncorrelated scalar subqueries bind coordinator-side before
    fragmenting (Q11/Q15/Q22 shape) — previously unbound Params reached
    the mesh compiler."""
    mx, local = env
    q = ("select count(*) as c from orders "
         "where o_totalprice > (select avg(o_totalprice) from orders)")
    _same(mx.run(q), local.run(q))


def test_not_in_nulls_on_mesh(env):
    """NOT IN three-valued logic on the mesh path: a NULL anywhere in the
    subquery's values makes NOT IN yield no row (unless the probe key is
    NULL too — then UNKNOWN), and an EMPTY subquery keeps every row.
    Cross-checked against the local engine on both shapes."""
    mx, local = env
    # non-empty subquery WITH a NULL-able derivation: nullif plants NULLs
    q1 = ("select count(*) as c from orders "
          "where o_custkey not in "
          "(select nullif(c_custkey, 3) from customer)")
    _same(mx.run(q1), local.run(q1))
    # empty subquery: NOT IN over the empty set is TRUE for every row
    q2 = ("select count(*) as c from orders "
          "where o_custkey not in "
          "(select c_custkey from customer where c_custkey < 0)")
    _same(mx.run(q2), local.run(q2))
    # no NULLs, plain anti-join semantics
    q3 = ("select count(*) as c from orders "
          "where o_custkey not in "
          "(select c_custkey from customer where c_nationkey = 5)")
    _same(mx.run(q3), local.run(q3))


# ---------------------------------------------------------------------------
# local-vs-mesh verifier sweeps (checksum equality over the TPC-H suite)


def _tpch_queries():
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "tpch_queries", os.path.join(os.path.dirname(__file__),
                                     "test_tpch.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.QUERIES


@pytest.mark.parametrize("name", ["q1", "q3", "q6", "q13", "q18"])
def test_tpch_subset_mesh_matches_local(env, name):
    """Non-slow representative subset, a case a query: agg-only (q1),
    join-heavy (q3), filter+agg (q6), outer-join agg (q13), large-fanout
    agg (q18)."""
    from presto_tpu.verifier import Verifier, report

    mx, local = env
    outcome = Verifier(local, mx).verify(_tpch_queries()[name], name)
    assert outcome.ok, report([outcome])


@pytest.mark.slow
def test_tpch_sweep_mesh_matches_local(env):
    from presto_tpu.verifier import Verifier, report

    mx, local = env
    queries = _tpch_queries()
    outcomes = Verifier(local, mx).run_suite(
        sorted(queries.items(), key=lambda kv: int(kv[0][1:])))
    assert all(o.ok for o in outcomes), report(outcomes)


@pytest.mark.slow
def test_tpch_sweep_mesh_hash_engine_matches_local(env):
    """Force every on-mesh breaker through the Pallas hash engine
    (interpret mode on CPU) and sweep the full suite — the hash kernels
    must be drop-in inside the shard_map program too."""
    from presto_tpu.catalog.tpch import tpch_catalog
    from presto_tpu.verifier import Verifier, report

    mx, local = env
    hashed = MeshExecutor(mx.catalog, mx.mesh,
                          ExecConfig(batch_rows=1 << 12,
                                     agg_capacity=1 << 10,
                                     breaker_engine="hash"))
    queries = _tpch_queries()
    outcomes = Verifier(local, hashed).run_suite(
        sorted(queries.items(), key=lambda kv: int(kv[0][1:])))
    assert all(o.ok for o in outcomes), report(outcomes)
