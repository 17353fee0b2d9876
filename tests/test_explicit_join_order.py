"""Explicit joins enter the join graph (plan/builder.py: `plan_join`,
`_order_joins`, `_plan_outer`).

A chain of `JOIN ... ON` (and `CROSS JOIN`) is a set of leaves and ON
conjuncts, ordered with the WHERE conjuncts by the same DP as comma-FROM: TPC-DS
Q72 written with explicit JOINs plans the joins and build sides of its
comma-FROM inner block. A LEFT (or RIGHT) JOIN is a barrier: its preserved
side is assembled alone with the WHERE conjuncts over its columns, nothing is
pushed into the side that supplies NULLs, and a WHERE conjunct over that side
stays above the join. The TPC-H texts of the benchmark, comma-FROM all of them,
print the EXPLAIN they printed before explicit joins were reordered. Answers
are held to sqlite.
"""

import hashlib
import json
import os
import sqlite3

import numpy as np
import pandas as pd
import pytest

from presto_tpu.catalog.memory import MemoryConnector
from presto_tpu.connector import Catalog
from presto_tpu.exec import ExecConfig, LocalRunner

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def query_text(qid):
    base = os.path.join(ROOT, "benchmark", "queries", qid)
    with open(base + ".json") as f:
        params = json.load(f)["params"]["fixed"]
    with open(base + ".sql") as f:
        return f.read().format(**params).strip()


def plan_lines(explain: str, columns: bool = False):
    """The EXPLAIN's lines without the annotations in brackets after a
    node, nor (unless `columns`) a scan's column list."""
    out = []
    for line in explain.splitlines():
        line = line.split("   [")[0]
        if line.lstrip().startswith("TableScan[") and not columns:
            line = line.split("]")[0] + "]"
        out.append(line)
    return out


def join_tree(explain: str):
    """The joins, filters and scans from the first inner join down, each at
    its depth below that join."""
    lines = plan_lines(explain)
    top = next(i for i, l in enumerate(lines) if "HashJoin[inner" in l)
    base = len(lines[top]) - len(lines[top].lstrip())
    tree = [lines[top].lstrip()]
    for line in lines[top + 1:]:
        depth = len(line) - len(line.lstrip())
        if depth <= base:
            break
        if line.lstrip().startswith(("HashJoin", "Filter", "TableScan")):
            tree.append(" " * (depth - base) + line.lstrip())
    return tree


# Q72's inner block, comma-FROM: its ON conjuncts first, then its WHERE
Q72_COMMA_BLOCK = """
select i_item_desc, w_warehouse_name, d1.d_week_seq, cs_promo_sk, cs_item_sk,
       cs_order_number
from catalog_sales, inventory, warehouse, item, customer_demographics,
     household_demographics, date_dim d1, date_dim d2, date_dim d3
where cs_item_sk = inv_item_sk and w_warehouse_sk = inv_warehouse_sk
  and i_item_sk = cs_item_sk and cs_bill_cdemo_sk = cd_demo_sk
  and cs_bill_hdemo_sk = hd_demo_sk and cs_sold_date_sk = d1.d_date_sk
  and inv_date_sk = d2.d_date_sk and cs_ship_date_sk = d3.d_date_sk
  and d1.d_week_seq = d2.d_week_seq and inv_quantity_on_hand < cs_quantity
  and d3.d_date > d1.d_date + 5 and hd_buy_potential = '>10000'
  and d1.d_year = 1999 and cd_marital_status = 'D'
"""


@pytest.mark.parametrize("sf", [0.01, 1])
def test_q72_explicit_joins_plan_as_the_comma_block(sf):
    """EXPLAIN only: at SF1 the generator's tables are made for their
    statistics (23.5 M inventory rows), nothing runs."""
    from presto_tpu.catalog.tpcds import tpcds_catalog

    runner = LocalRunner(tpcds_catalog(sf), ExecConfig())
    explicit = runner.explain(query_text("q72"))
    tree = join_tree(explicit)
    assert tree == join_tree(runner.explain(Q72_COMMA_BLOCK))
    # the two LEFT JOINs as written, above the inner block
    lefts = [l.strip() for l in plan_lines(explicit) if "HashJoin[left" in l]
    assert lefts == [
        "HashJoin[left; ['cs_item_sk', 'cs_order_number'] = "
        "['cr_item_sk', 'cr_order_number']]",
        "HashJoin[left; ['cs_promo_sk'] = ['p_promo_sk']; unique]"]
    # inventory probes on two keys, and nothing builds inventory
    assert tree[1].strip() == "Filter[lt(inv_quantity_on_hand, cs_quantity)]"
    assert tree[2].strip() == ("HashJoin[inner; ['inv_item_sk', 'inv_date_sk'] "
                               "= ['cs_item_sk', 'd_date_sk#1']]")
    assert tree[3].strip() == "TableScan[tpcds.inventory]"
    assert not [l for l in tree if "['cs_item_sk'] = ['inv_item_sk']" in l]
    # every WHERE filter of the preserved side is below both LEFT JOINs
    for conj in ("eq(d_year, 1999)", "eq(cd_marital_status, 'D')",
                 "eq(hd_buy_potential, '>10000')"):
        assert sum(conj in l for l in tree) == 1, conj


# sha256 of the benchmark's TPC-H texts' EXPLAIN at SF 0.01, `plan_lines`
# with the scans' columns joined by newlines, as the engine printed them
# before explicit joins were reordered
TPCH_PLANS = {
    "q3": "e77ab34274c2a3653b9a5f56ed0e0166881384f3e0dac94a91222d0e9cdd691e",
    "q9": "fc370c81b61db9364b4e6564a698cc07e6eacbcdd1bc9ebc8278a02e3a5e5fbe",
    "q18": "8aac36f1130b40846ed07694bb0561f4be61eec847e479e5a00ac31dae4fbbdd",
}


@pytest.fixture(scope="module")
def tpch_runner():
    from presto_tpu.catalog.tpch import tpch_catalog

    return LocalRunner(tpch_catalog(0.01), ExecConfig())


@pytest.mark.parametrize("qid", sorted(TPCH_PLANS))
def test_the_benchmarks_tpch_texts_plan_as_before(tpch_runner, qid):
    text = "\n".join(plan_lines(tpch_runner.explain(query_text(qid)),
                                columns=True))
    assert hashlib.sha256(text.encode()).hexdigest() == TPCH_PLANS[qid], text


# -- small tables, answers held to sqlite

@pytest.fixture(scope="module")
def small():
    rng = np.random.default_rng(7)
    tables = {
        "f": pd.DataFrame({"fk": np.arange(400) % 37, "gk": np.arange(400) % 11,
                           "hk": rng.integers(0, 60, 400),
                           "v": rng.integers(0, 100, 400)}),
        # keys 0..29: seven of f's 37 find no row
        "d": pd.DataFrame({"dk": np.arange(30), "a": rng.integers(0, 10, 30)}),
        "e": pd.DataFrame({"ek": np.arange(11), "b": rng.integers(0, 10, 11)}),
        # half of f's hk find no row, the rest one or two
        "h": pd.DataFrame({"hk2": np.r_[np.arange(0, 60, 2), np.arange(0, 20, 2)],
                           "c": rng.integers(0, 10, 40)}),
    }
    conn = MemoryConnector()
    db = sqlite3.connect(":memory:")
    for name, df in tables.items():
        conn.add_table(name, df)
        df.to_sql(name, db, index=False)
    cat = Catalog()
    cat.register("m", conn, default=True)
    return LocalRunner(cat, ExecConfig(batch_rows=128)), db


def held_to_sqlite(small, sql):
    runner, db = small
    got = runner.run(sql)
    want = pd.read_sql_query(sql, db)
    assert list(got.columns) == list(want.columns)
    norm = lambda df: sorted(  # noqa: E731
        tuple(-1 if pd.isna(v) else int(v) for v in row)
        for row in df.itertuples(index=False))
    assert norm(got) == norm(want)
    return got


ANSWERS = {
    "inner_chain_with_where": """
        select fk, v, a, b from f join d on fk = dk join e on gk = ek
        where a > 3 and b < 8""",
    "inner_chain_written_backwards": """
        select fk, v, a, b from e join f on gk = ek join d on dk = fk
        where v > 40""",
    "cross_join_and_on": """
        select fk, a, b from f cross join e join d on fk = dk
        where gk = ek and b > 5""",
    "non_equi_on": """
        select fk, dk from f join d on fk < dk where v < 5 and a = 2""",
    "left_after_inner_chain": """
        select fk, v, a, c from f join d on fk = dk left join h on hk = hk2
        where a < 6""",
    "inner_after_left": """
        select fk, v, b, c from f left join h on hk = hk2 join e on gk = ek
        where b > 2""",
    "right_join_turned_round": """
        select fk, v, c from h right join f on hk2 = hk where v > 50""",
    "left_of_an_inner_chain_on_the_null_side": """
        select fk, v, c, b from f left join (h join e on c = ek) on hk = hk2
        where fk < 20""",
    "filter_on_the_null_side_kept_above": """
        select fk, hk, c from f left join h on hk = hk2
        where c is null or c > 4""",
    "full_join_of_an_inner_chain": """
        select fk, a, c from (select * from f join d on fk = dk where v < 30) x
        full join h on hk = hk2""",
}


@pytest.mark.parametrize("name", sorted(ANSWERS))
def test_explicit_joins_answer_as_sqlite(small, name):
    got = held_to_sqlite(small, ANSWERS[name])
    assert len(got) > 0


def test_a_filter_on_the_null_supplying_side_stays_above_the_left_join(small):
    """NULL-rejection is not assumed: `c is null or c > 4` keeps the rows
    of f that h does not match, so it is evaluated after the join; the
    preserved side's own conjunct goes below it."""
    runner, _ = small
    sql = ("select fk, hk, c from f join d on fk = dk left join h on hk = hk2 "
           "where (c is null or c > 4) and a < 6")
    lines = plan_lines(runner.explain(sql))
    left = next(i for i, l in enumerate(lines) if "HashJoin[left" in l)
    null_side = [i for i, l in enumerate(lines) if "is_null(c)" in l]
    assert null_side and all(i < left for i in null_side)
    assert [i for i, l in enumerate(lines) if "lt(a, 6)" in l][0] > left
    got = held_to_sqlite(small, sql)
    assert got["c"].isna().any() and (got["c"].dropna() > 4).all()
