"""N-ary multiway join engine (plan/multiway.py collapse pass,
plan/stats.choose_join_mode, exec/runtime._execute_multiway_join).

Parity matrix: star/snowflake chains of 2-4 joins x NDV x skew x null
keys x inner/left mix, join_mode=off (the pre-collapse binary path) as
control vs forced multiway. Plus: collapse eligibility, the CBO verdict
and its HBO-observed provenance, EXPLAIN markers, the session property,
cascade fallbacks (left-fanout legs and build memory pressure), the
plan_check invariant rules with injected violations, and forced-multiway
TPC-H/TPC-DS verifier sweeps."""

import numpy as np
import pandas as pd
import pytest

from presto_tpu.catalog.memory import MemoryConnector
from presto_tpu.catalog.tpch import tpch_catalog
from presto_tpu.connector import Catalog
from presto_tpu.exec import ExecConfig, LocalRunner
from presto_tpu.verifier import Verifier, report

from conftest import assert_frames_match


# ---------------------------------------------------------------------------
# parity matrix: MemoryConnector star schemas


def _star_catalog(n_fact=1500, ndv=211, skew=False, nulls=False,
                  dup_dims=False, seed=11):
    """Fact table f(rid, k1..k4, v) + dims d1..d4(p_i, a_i). `skew`
    concentrates 90% of fact keys on one hot value; `nulls` pokes NULLs
    into the fact keys (Int64 nullable); `dup_dims` gives every dim key
    two payload rows so non-unique builds exercise the fanout legs."""
    rng = np.random.default_rng(seed)
    conn = MemoryConnector()
    f = {"rid": np.arange(n_fact), "v": rng.normal(0.0, 10.0, n_fact)}
    for i in range(1, 5):
        k = rng.integers(0, ndv, size=n_fact)
        if skew:
            hot = rng.random(n_fact) < 0.9
            k = np.where(hot, ndv // 2, k)
        # 10% misses: keys outside every dim -> inner drops, left extends
        miss = rng.random(n_fact) < 0.1
        k = np.where(miss, ndv + 17, k)
        col = pd.array(k, dtype="Int64")
        if nulls:
            col[rng.random(n_fact) < 0.08] = pd.NA
        f[f"k{i}"] = col
    conn.add_table("f", pd.DataFrame(f))
    for i in range(1, 5):
        p = np.arange(ndv)
        if dup_dims:
            p = np.repeat(p, 2)
        conn.add_table(f"d{i}", pd.DataFrame({
            f"p{i}": p,
            f"a{i}": [f"d{i}_{int(x)}_{j % 2}" for j, x in enumerate(p)],
        }))
    cat = Catalog()
    cat.register("mem", conn, default=True)
    return cat


def _chain_sql(n_joins, kinds):
    sel = ["f.rid", "f.v"] + [f"d{i}.a{i}" for i in range(1, n_joins + 1)]
    joins = "".join(
        f" {k} join d{i} on f.k{i} = d{i}.p{i}"
        for i, k in zip(range(1, n_joins + 1), kinds))
    return f"select {', '.join(sel)} from f{joins}"


_SHAPES = {
    "plain": dict(ndv=211),
    "skew+dup": dict(ndv=7, skew=True, dup_dims=True),
    "nulls": dict(ndv=97, nulls=True),
}


@pytest.mark.parametrize("shape", sorted(_SHAPES))
@pytest.mark.parametrize("kinds", ["inner", "mixed"])
@pytest.mark.parametrize("n_joins", [2, 3, 4])
def test_parity_matrix(n_joins, kinds, shape):
    cat = _star_catalog(**_SHAPES[shape])
    kind_list = (["inner"] * n_joins if kinds == "inner"
                 else [("left" if i % 2 else "inner")
                       for i in range(n_joins)])
    sql = _chain_sql(n_joins, kind_list)
    base = dict(batch_rows=1 << 10)
    off = LocalRunner(cat, ExecConfig(join_mode="off", **base))
    mw = LocalRunner(cat, ExecConfig(join_mode="multiway", **base))
    assert_frames_match(mw.run(sql), off.run(sql))
    assert mw.last_stats.get("multiway.joins", 0) >= 1
    assert mw.last_stats.get("multiway.legs", 0) >= n_joins


def test_snowflake_key_through_unique_build_payload():
    """q10-ish snowflake: nation's probe key comes from customer's
    payload, eligible only because customer's build is unique."""
    cat = tpch_catalog(0.01)
    sql = ("select o.o_orderkey, c.c_name, n.n_name from orders o "
           "join customer c on o.o_custkey = c.c_custkey "
           "left join nation n on c.c_nationkey = n.n_nationkey")
    base = dict(batch_rows=1 << 13)
    off = LocalRunner(cat, ExecConfig(join_mode="off", **base))
    mw = LocalRunner(cat, ExecConfig(join_mode="multiway", **base))
    assert_frames_match(mw.run(sql), off.run(sql))
    assert mw.last_stats.get("multiway.joins", 0) == 1
    assert mw.last_stats.get("multiway.fused_dispatches", 0) >= 1
    assert "MultiwayJoin" in mw.explain(sql)


# ---------------------------------------------------------------------------
# collapse eligibility, CBO verdict, EXPLAIN, session property


def test_explain_marker_and_off_mode_plan_unchanged():
    cat = _star_catalog()
    sql = _chain_sql(2, ["inner", "inner"])
    mw = LocalRunner(cat, ExecConfig(join_mode="multiway"))
    out = mw.explain(sql)
    assert "MultiwayJoin" in out and "[join=multiway" in out
    assert "session join_mode=multiway" in out
    off = LocalRunner(cat, ExecConfig(join_mode="off"))
    out_off = off.explain(sql)
    assert "MultiwayJoin" not in out_off and "[join=" not in out_off


def test_binary_override_keeps_chain_and_says_why():
    cat = _star_catalog()
    sql = _chain_sql(2, ["inner", "inner"])
    r = LocalRunner(cat, ExecConfig(join_mode="binary"))
    out = r.explain(sql)
    assert "MultiwayJoin" not in out
    assert "[join=binary: session join_mode=binary]" in out


def test_residual_join_not_collapsed():
    """A chain join carrying a residual is never collapse-eligible, even
    under forced multiway — the fused probe has no residual slot. (No
    SQL in this dialect reaches that plan shape, so inject it at the
    plan level.)"""
    from presto_tpu.expr.ir import Constant
    from presto_tpu.plan.multiway import collapse_multiway
    from presto_tpu.plan.nodes import HashJoin, MultiwayJoin
    from presto_tpu.types import BIGINT, BOOLEAN

    def tree(residual):
        f = _pc_scan([("k1", BIGINT), ("k2", BIGINT)])
        d1 = _pc_scan([("p1", BIGINT)])
        d2 = _pc_scan([("p2", BIGINT)])
        j0 = HashJoin("inner", f, d1, ["k1"], ["p1"])
        return HashJoin("inner", j0, d2, ["k2"], ["p2"],
                        residual=residual)

    # control: the same chain without the residual does collapse
    clean = collapse_multiway(tree(None), None, mode="multiway")
    assert isinstance(clean, MultiwayJoin)
    kept = collapse_multiway(tree(Constant(BOOLEAN, True)), None,
                             mode="multiway")
    assert isinstance(kept, HashJoin)
    assert not any(isinstance(n, MultiwayJoin) for n in _walk(kept))


def _walk(node):
    yield node
    for c in node.children():
        yield from _walk(c)


def test_single_join_not_collapsed():
    cat = _star_catalog()
    sql = "select f.rid, d1.a1 from f join d1 on f.k1 = d1.p1"
    r = LocalRunner(cat, ExecConfig(join_mode="multiway"))
    assert "MultiwayJoin" not in r.explain(sql)


def test_choose_join_mode_thresholds():
    from presto_tpu.plan import stats as ps

    class _J:
        def __init__(self, unique):
            self.build_unique = unique

    # override always wins, both directions
    assert ps.choose_join_mode([_J(True)] * 2, None,
                               override="multiway")[0] == "multiway"
    mode, why = ps.choose_join_mode([_J(True)] * 2, None, override="binary")
    assert mode == "binary" and "join_mode=binary" in why


def test_hbo_observed_provenance_in_verdict():
    """After one multiway run, hbo=correct swaps estimated build sizes
    for the observed history and the EXPLAIN why carries the
    provenance suffix."""
    cat = _star_catalog(seed=29)
    sql = _chain_sql(2, ["inner", "inner"])
    warm = LocalRunner(cat, ExecConfig(join_mode="multiway", hbo="observe"))
    warm.run(sql)
    r = LocalRunner(cat, ExecConfig(join_mode="auto", hbo="correct"))
    out = r.explain(sql)
    assert "[join=" in out
    assert "(hbo: observed)" in out


def test_join_mode_session_property():
    from presto_tpu.server.session import Session, SessionPropertyError

    s = Session()
    assert s.exec_config().join_mode == "auto"
    s.set("join_mode", "MULTIWAY")
    assert s.exec_config().join_mode == "multiway"
    with pytest.raises(SessionPropertyError):
        s.set("join_mode", "triangular")


# ---------------------------------------------------------------------------
# cascade fallbacks


def test_left_fanout_leg_falls_back_to_cascade():
    """A left leg whose build exceeds the hash-engine gate has no exact
    counts, so the node must decompose into the binary cascade — and
    still match the pre-collapse path."""
    cat = tpch_catalog(0.01)
    sql = ("select o.o_orderkey, l.l_linenumber, c.c_name from orders o "
           "left join lineitem l on o.o_orderkey = l.l_orderkey "
           "left join customer c on o.o_custkey = c.c_custkey")
    base = dict(batch_rows=1 << 13)
    off = LocalRunner(cat, ExecConfig(join_mode="off", **base))
    mw = LocalRunner(cat, ExecConfig(join_mode="multiway", **base))
    assert_frames_match(mw.run(sql), off.run(sql))
    assert mw.last_stats.get("multiway.cascade_fallbacks", 0) >= 1
    assert mw.last_stats.get("multiway.fused_dispatches", 0) == 0


def test_build_memory_pressure_falls_back_to_cascade_and_spill():
    """The orders build blows a 256 KiB pool mid-collect: the node must
    hand the already-collected batches to the binary cascade, whose
    PR 15 spiller finishes the job — same answer as the unconstrained
    binary path. LEFT JOINs keep orders a build: inner joins alone are
    reordered, and orders, the largest, would probe."""
    cat = tpch_catalog(0.01)
    sql = ("select n.n_name, count(*) c, sum(o.o_totalprice) s "
           "from customer c "
           "left join orders o on c.c_custkey = o.o_custkey "
           "left join nation n on c.c_nationkey = n.n_nationkey "
           "group by n.n_name")
    base = dict(batch_rows=1 << 13)
    off = LocalRunner(cat, ExecConfig(join_mode="off", **base))
    mw = LocalRunner(cat, ExecConfig(
        join_mode="multiway", memory_pool_bytes=1 << 18,
        spill_enabled=True, **base))
    assert_frames_match(mw.run(sql), off.run(sql), sort_by=["n_name"])
    assert mw.last_stats.get("multiway.cascade_fallbacks", 0) >= 1
    assert mw.last_stats.get("spill.partitions", 0) >= 1


# ---------------------------------------------------------------------------
# plan_check invariant rules: injected violations


def _pc_scan(cols):
    from presto_tpu.plan.nodes import TableScan

    return TableScan(catalog="m", table="t",
                     assignments={s: s for s, _ in cols}, output=list(cols))


def _pc_node(**over):
    from presto_tpu.plan.nodes import MultiwayJoin
    from presto_tpu.types import BIGINT

    kw = dict(
        probe=_pc_scan([("a", BIGINT), ("b", BIGINT)]),
        builds=[_pc_scan([("k0", BIGINT), ("p0", BIGINT)]),
                _pc_scan([("k1", BIGINT)])],
        kinds=["inner", "inner"],
        probe_keys=[["a"], ["p0"]],
        build_keys=[["k0"], ["k1"]],
        build_unique=[True, True],
    )
    kw.update(over)
    return MultiwayJoin(**kw)


def _pc_check(node):
    from presto_tpu.analysis.plan_check import check_plan
    from presto_tpu.plan.nodes import Output

    return check_plan(Output(node, ["a"], ["a"]))


def test_plan_check_clean_multiway_has_no_findings():
    assert _pc_check(_pc_node()) == []


def test_plan_check_key_from_nonunique_build_is_dangling():
    """Leg 1's probe key rides build 0's payload; flipping build 0 to
    non-unique makes that key ill-defined per probe row."""
    findings = _pc_check(_pc_node(build_unique=[False, True]))
    assert any(f.rule == "dangling-column" and "'p0'" in f.message
               for f in findings)


def test_plan_check_per_position_dtype_mismatch():
    from presto_tpu.types import BIGINT, DOUBLE

    findings = _pc_check(_pc_node(
        builds=[_pc_scan([("k0", BIGINT), ("p0", BIGINT)]),
                _pc_scan([("k1", DOUBLE)])]))
    assert any(f.rule == "key-dtype-mismatch" and "leg 1" in f.message
               and "int64" in f.message and "float64" in f.message
               for f in findings)


def test_plan_check_key_arity_mismatch():
    findings = _pc_check(_pc_node(probe_keys=[["a", "b"], ["p0"]]))
    assert any(f.rule == "key-dtype-mismatch" and "arity" in f.message
               for f in findings)


def test_plan_check_leg_array_length_mismatch():
    findings = _pc_check(_pc_node(kinds=["inner"]))
    assert any(f.rule == "multiway-shape" and "length" in f.message
               for f in findings)


def test_plan_check_bad_kind():
    findings = _pc_check(_pc_node(kinds=["inner", "full"]))
    assert any(f.rule == "multiway-shape" and "'full'" in f.message
               for f in findings)


def test_plan_check_dangling_build_key():
    findings = _pc_check(_pc_node(build_keys=[["k0"], ["gone"]]))
    assert any(f.rule == "dangling-column" and "'gone'" in f.message
               and "build keys" in f.message for f in findings)


# ---------------------------------------------------------------------------
# forced-multiway verifier sweeps vs the binary path


def _tpch_queries():
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "tpch_queries", os.path.join(os.path.dirname(__file__),
                                     "test_tpch.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.QUERIES


@pytest.fixture(scope="module")
def tpch_engines():
    cat = tpch_catalog(0.01)
    control = LocalRunner(cat, ExecConfig(batch_rows=1 << 13,
                                          join_mode="off"))
    test = LocalRunner(cat, ExecConfig(batch_rows=1 << 13,
                                       join_mode="multiway"))
    return control, test


@pytest.mark.parametrize("name", ["q2", "q7", "q8"])
def test_tpch_subset_multiway_matches_binary(tpch_engines, name):
    """The TPC-H queries whose join chains the forced mode collapses on
    this catalog: q2 and q7 (one MultiwayJoin each), q8 (two). The former
    picks (q3, q5, q9, q10) are declined by the collapse at SF 0.01, so
    both engines ran the same binary plan; the slow sweep still has them."""
    control, test = tpch_engines
    sql = _tpch_queries()[name]
    assert "MultiwayJoin" in test.explain(sql)
    assert "MultiwayJoin" not in control.explain(sql)
    outcome = Verifier(control, test).verify(sql, name)
    assert outcome.ok, report([outcome])


@pytest.mark.slow
def test_tpch_sweep_multiway_matches_binary(tpch_engines):
    control, test = tpch_engines
    queries = _tpch_queries()
    v = Verifier(control, test)
    outcomes = v.run_suite(sorted(queries.items(),
                                  key=lambda kv: int(kv[0][1:])))
    assert all(o.ok for o in outcomes), report(outcomes)


@pytest.mark.slow
def test_tpcds_sweep_multiway_matches_binary():
    from presto_tpu.catalog.tpcds import tpcds_catalog

    from test_tpcds_answers import Q

    cat = tpcds_catalog(0.005)
    cfg = dict(batch_rows=1 << 13, agg_capacity=1 << 12)
    control = LocalRunner(cat, ExecConfig(join_mode="off", **cfg))
    test = LocalRunner(cat, ExecConfig(join_mode="multiway", **cfg))
    v = Verifier(control, test)
    outcomes = v.run_suite(list(Q.items()))
    assert all(o.ok for o in outcomes), report(outcomes)
