"""`plan/builder.py:_derives_unique`: a proof from the plan's structure that a
set of keys is unique on a node's output. A wrong True is silent data loss
(`probe_unique` returns one match a row), so the rule is held to hand-built
plans here, case by case, to the plans the 22 TPC-H texts get (which of them
it changes), and to `explain`; `tests/test_sqlite_oracle.py` holds the flag
to executed data."""

import json
import os

import pytest

from presto_tpu.catalog.tpch import tpch_catalog
from presto_tpu.exec import ExecConfig, LocalRunner
from presto_tpu.expr.ir import Call, Constant, InputRef
from presto_tpu.plan import builder
from presto_tpu.plan.builder import _derives_unique, plan_query
from presto_tpu.plan.nodes import (Aggregate, Filter, HashJoin, IndexJoin,
                                   Limit, MultiwayJoin, NestedLoopJoin,
                                   Project, RemoteSource, SemiJoin, SetOp,
                                   Sort, SortItem, TableScan, Unnest, Window,
                                   WindowFunc)
from presto_tpu.plan.optimizer import optimize
from presto_tpu.types import BIGINT, BOOLEAN
from test_tpch import QUERIES

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The TPC-H texts whose plans the rule changes: a build that holds a join
# and is unique all the same (hash joins marked unique, before -> after).
# Q9 since PR 35 (a LIKE's share is read from the dictionary, so the 2 % of
# lineitem under green parts builds, and orders and partsupp probe it): two
# of its five joins fan out, whichever way the rule answers.
UNIQUE_ABOVE_A_JOIN = {"q2": (3, 6), "q3": (1, 2), "q5": (1, 2), "q7": (2, 5),
                       "q8": (3, 4), "q9": (2, 3), "q10": (1, 3),
                       "q11": (1, 2), "q18": (1, 2), "q21": (1, 2)}


def scan(table, cols, pk):
    node = TableScan("tpch", table, {c: c for c in cols},
                     [(c, BIGINT) for c in cols])
    node.primary_key_symbols = list(pk)
    return node


def orders():
    return scan("orders", ["o_orderkey", "o_custkey"], ["o_orderkey"])


def customer():
    return scan("customer", ["c_custkey", "c_nationkey"], ["c_custkey"])


def lineitem():
    return scan("lineitem", ["l_orderkey", "l_linenumber", "l_suppkey"],
                ["l_orderkey", "l_linenumber"])


def join(left, right, lkeys, rkeys, kind="inner", unique=None, **more):
    if unique is None:
        unique = _derives_unique(right, rkeys)
    return HashJoin(kind=kind, left=left, right=right, left_keys=lkeys,
                    right_keys=rkeys, build_unique=unique, **more)


def orders_customer(kind="inner", **more):
    """orders probing customer on the customer's primary key: Q3's first join."""
    return join(orders(), customer(), ["o_custkey"], ["c_custkey"], kind, **more)


def ref(name):
    return InputRef(BIGINT, name)


def not_null(node, key):
    return Filter(node, Call(BOOLEAN, "is_not_null", (ref(key),)))


def identity(node, names):
    return Project(node, [(n, ref(n)) for n in names])


CASES = {
    # -- what the rule proves
    "inner join, unique build": (orders_customer, ["o_orderkey"], True),
    "left join, unique build": (lambda: orders_customer("left"), ["o_orderkey"], True),
    "a residual only removes pairs": (
        lambda: orders_customer(residual=Call(
            BOOLEAN, "lt", (ref("o_custkey"), ref("c_nationkey")))),
        ["o_orderkey"], True),
    "the Filter that _notnull_side adds": (
        lambda: not_null(orders_customer(), "o_orderkey"), ["o_orderkey"], True),
    "not-null Filters under the join too": (
        lambda: join(not_null(orders(), "o_custkey"),
                     not_null(customer(), "c_custkey"),
                     ["o_custkey"], ["c_custkey"]), ["o_orderkey"], True),
    "an identity Project": (
        lambda: identity(orders_customer(), ["o_orderkey", "c_nationkey"]),
        ["o_orderkey"], True),
    "a superset of the key": (orders_customer, ["o_orderkey", "o_custkey"], True),
    "a superset that reaches into the build": (
        orders_customer, ["c_nationkey", "o_orderkey"], True),
    "two joins deep (lineitem's key above both)": (
        lambda: join(join(lineitem(), orders(), ["l_orderkey"], ["o_orderkey"]),
                     customer(), ["o_custkey"], ["c_custkey"]),
        ["l_orderkey", "l_linenumber"], True),
    "grouping keys under the join": (
        lambda: join(Aggregate(lineitem(), ["l_orderkey"], []), orders(),
                     ["l_orderkey"], ["o_orderkey"]), ["l_orderkey"], True),
    # -- what it must not
    "a build that is not unique": (
        lambda: join(orders(), lineitem(), ["o_orderkey"], ["l_orderkey"]),
        ["o_orderkey"], False),
    "a unique build whose flag was not set": (
        lambda: orders_customer(unique=False), ["o_orderkey"], False),
    "kind full (its tail has NULL probe columns)": (
        lambda: orders_customer("full"), ["o_orderkey"], False),
    "keys from the build side only": (orders_customer, ["c_custkey"], False),
    "a key not unique on the probe side": (
        lambda: join(lineitem(), orders(), ["l_orderkey"], ["o_orderkey"]),
        ["l_orderkey"], False),
    "a non-key of the probe side": (orders_customer, ["o_custkey"], False),
    "no keys": (orders_customer, [], False),
    "a non-identity Project": (
        lambda: Project(orders_customer(), [("o_orderkey", Call(
            BIGINT, "div", (ref("o_orderkey"), Constant(BIGINT, 2))))]),
        ["o_orderkey"], False),
    "a Project that renames another column to the key": (
        lambda: Project(orders_customer(), [("o_orderkey", ref("o_custkey"))]),
        ["o_orderkey"], False),
    # -- every node kind the rule does not name, over a child that is unique
    "NestedLoopJoin": (lambda: NestedLoopJoin(orders(), customer()),
                       ["o_orderkey"], False),
    "SemiJoin": (lambda: SemiJoin(orders(), customer(), ["o_custkey"],
                                  ["c_custkey"]), ["o_orderkey"], False),
    "SetOp (union all)": (
        lambda: SetOp("union", True, orders(), orders(),
                      ["o_orderkey", "o_custkey"], [BIGINT, BIGINT]),
        ["o_orderkey"], False),
    "Unnest": (lambda: Unnest(orders(), ["o_custkey"], ["o_orderkey"], [["e"]],
                              [[BIGINT]]), ["o_orderkey"], False),
    "Window": (lambda: Window(orders(), ["o_custkey"], [SortItem("o_orderkey")],
                              [WindowFunc("rn", "row_number", BIGINT)]),
               ["o_orderkey"], False),
    "RemoteSource (an exchange)": (
        lambda: RemoteSource(1, [("o_orderkey", BIGINT)]), ["o_orderkey"], False),
    "Sort": (lambda: Sort(orders(), [SortItem("o_orderkey")]),
             ["o_orderkey"], False),
    "Limit": (lambda: Limit(orders(), 10), ["o_orderkey"], False),
    "MultiwayJoin": (
        lambda: MultiwayJoin(orders(), [customer()], ["inner"], [["o_custkey"]],
                             [["c_custkey"]], [True]), ["o_orderkey"], False),
    "IndexJoin": (
        lambda: IndexJoin("inner", orders(), "tpch", "customer", ["o_custkey"],
                          ["c_custkey"], {"c_custkey": "c_custkey"},
                          [("c_custkey", BIGINT)]), ["o_orderkey"], False),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_proof(case):
    build, keys, expected = CASES[case]
    node = build()
    assert all(k in dict(node.output) for k in keys), "a case of its own plan"
    assert _derives_unique(node, keys) is expected


@pytest.fixture(scope="module")
def runner():
    return LocalRunner(tpch_catalog(0.01),
                       ExecConfig(batch_rows=1 << 14, agg_capacity=1 << 10))


def hash_joins(node):
    if isinstance(node, HashJoin):
        yield node
    for child in node.children():
        yield from hash_joins(child)


def unique_joins_by_text(catalog):
    return {name: [j.build_unique for j in
                   hash_joins(optimize(plan_query(sql, catalog), catalog).root)]
            for name, sql in QUERIES.items()}


def test_the_tpch_plans_it_changes(runner, monkeypatch):
    """The 22 texts planned twice: with the rule, and with the parent's
    answer at a join (False, anywhere below the build)."""
    after = unique_joins_by_text(runner.catalog)
    rule = builder._derives_unique
    monkeypatch.setattr(
        builder, "_derives_unique",
        lambda node, keys: not isinstance(node, HashJoin) and rule(node, keys))
    before = unique_joins_by_text(runner.catalog)
    changed = {name: (sum(before[name]), sum(flags))
               for name, flags in after.items() if flags != before[name]}
    assert changed == UNIQUE_ABOVE_A_JOIN
    assert [sum(map(len, d.values())) for d in (before, after)] == [52, 52]
    assert [sum(map(sum, d.values())) for d in (before, after)] == [22, 37]


def test_explain_marks_both_of_q3s_joins_and_none_of_a_fan_out(runner):
    with open(os.path.join(ROOT, "benchmark", "queries", "q3.json")) as f:
        params = json.load(f)["params"]["fixed"]
    with open(os.path.join(ROOT, "benchmark", "queries", "q3.sql")) as f:
        joins = [line for line in runner.explain(f.read().format(**params))
                 .splitlines() if "HashJoin[" in line]
    assert len(joins) == 2 and all("; unique]" in line for line in joins), joins
    assert "['l_orderkey'] = ['o_orderkey']" in joins[0]
    # orders probing a build of two months' lineitems (the smaller side
    # once filtered) on the order's key
    fan_out = runner.explain("select o_orderkey, l_quantity from orders "
                             "join lineitem on o_orderkey = l_orderkey "
                             "where l_shipdate < date '1992-03-01'")
    (line,) = [line for line in fan_out.splitlines() if "HashJoin[" in line]
    assert "['o_orderkey'] = ['l_orderkey']" in line and "unique" not in line
