"""TPC-DS Q67 on the CPU: its text, with DMS = 1200, served through
`POST /v1/statement` by one coordinator and one worker on the shipped
`ExecConfig()` and an empty session, as the configuration `tpcds_sf1_q67`
runs it, against the plain reference `benchmark/reference/q67.py`, exactly,
on three seeds at SF 0.01; `rank()` over tied sums; a ROLLUP whose keys a
projection above it drops; and the generator's two columns Q67 added.
"""

import decimal
import hashlib
import os
import sys

import numpy as np
import pandas as pd
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compare, data_tpcds_q67 as bdata, traffic  # noqa: E402
from benchmark.run import load_reference  # noqa: E402

SF = 0.01
QUERY = traffic.load_query("q67")
PARAMS = QUERY["params"]["fixed"]


def served(catalog, sql):
    """(columns, rows) of `sql` through the statement protocol."""
    from presto_tpu import client
    from presto_tpu.exec import ExecConfig
    from presto_tpu.server.coordinator import DistributedRunner

    runner = DistributedRunner(catalog, n_workers=1, config=ExecConfig())
    try:
        st = client.StatementClient(runner.coordinator.url, sql,
                                    client.ClientSession(user="test"))
        rows = list(st.rows())
        return st.columns, rows
    finally:
        runner.close()


@pytest.fixture(scope="module")
def answers():
    """seed -> (data, columns, rows): one cluster a seed, the compiled
    programs shared by all three."""
    from presto_tpu.server.__main__ import build_catalog

    got = {}

    def of(seed):
        if seed not in got:
            data = bdata.generate(SF, seed, sorted(QUERY["tables"]))
            catalog = build_catalog([f"tpcds:sf={SF:g}"])
            bdata.install(catalog, SF, seed, data)
            sql = QUERY["template"].format(**PARAMS).strip()
            got[seed] = (data, *served(catalog, sql))
        return got[seed]
    return of


@pytest.mark.parametrize("seed", [7, 2147484011, 3000000019])
def test_q67_served_equals_the_reference(answers, seed):
    data, columns, rows = answers(seed)
    want = load_reference("q67")(data, PARAMS)
    diff, _ = compare.compare_rows(columns, rows, want,
                                   QUERY["result_columns"])
    assert diff is None, diff
    assert len(want) == 100
    # every level of the rollup reaches the answer: NULL-padded keys from
    # the category's total down to an item's month in a store
    padded = {sum(v is None for v in r[:8]) for r in want}
    assert {0, 1, 4} <= padded and want[0][0] == "Books"
    assert all(r[9] <= 100 for r in want)


def test_q67_with_its_sets_spilling_equals_the_reference(answers, monkeypatch):
    """At SF1 the estimates of six sets pass `agg_cap_ceiling`: their
    partial steps pass rows through and their final steps go grace from
    the start, on string keys, under the union. Here a ceiling of 2^12
    (the served path lowers the session to `ExecConfig`) puts the same
    sets on that path at SF 0.01."""
    from presto_tpu.exec import ExecConfig
    from presto_tpu.obs import trace
    from presto_tpu.server import session
    from presto_tpu.server.__main__ import build_catalog

    monkeypatch.setattr(session, "ExecConfig", lambda **kw: ExecConfig(
        **{**kw, "agg_cap_ceiling": 1 << 12}))

    data = answers(7)[0]
    catalog = build_catalog([f"tpcds:sf={SF:g}"])
    bdata.install(catalog, SF, 7, data)
    sql = QUERY["template"].format(**PARAMS).strip()
    columns, rows = served(catalog, sql)
    diff, _ = compare.compare_rows(columns, rows,
                                   load_reference("q67")(data, PARAMS),
                                   QUERY["result_columns"])
    assert diff is None, diff
    spilled = sum(by_name.get("agg_partition", {}).get("n", 0)
                  for by_name in trace.summaries()[-1]["phases"].values())
    assert spilled >= 2


# -- rank() over tied sums, under a ROLLUP

def test_rank_shares_a_tie_and_skips_after_it():
    from presto_tpu.catalog.memory import MemoryConnector
    from presto_tpu.connector import Catalog
    from presto_tpu.exec import LocalRunner

    # category a: b1 and b2 sum to 10 each, b3 to 4; b: one brand of 7
    conn = MemoryConnector()
    conn.add_table("s", pd.DataFrame({
        "cat": ["a", "a", "a", "a", "a", "b"],
        "brand": ["b1", "b1", "b2", "b3", "b3", "b4"],
        "v": [4, 6, 10, 1, 3, 7]}))
    cat = Catalog()
    cat.register("m", conn, default=True)
    got = LocalRunner(cat).run(
        "select cat, brand, total, rank() over (partition by cat order by "
        "total desc) rk from (select cat, brand, sum(v) total from s "
        "group by rollup(cat, brand)) t order by cat, brand")
    rows = [tuple(None if pd.isna(v) else v for v in r)
            for r in got.itertuples(index=False)]
    assert rows == [
        ("a", "b1", 10, 2), ("a", "b2", 10, 2), ("a", "b3", 4, 4),
        ("a", None, 24, 1),
        ("b", "b4", 7, 1), ("b", None, 7, 1),
        (None, None, 31, 1)]   # the grand total: a partition of its own


# -- a projection above a ROLLUP that drops one of its keys

def test_a_rollup_key_dropped_above_it():
    from presto_tpu.catalog.tpcds import tpcds_catalog
    from presto_tpu.exec import LocalRunner

    sql = ("select i_category, sumsales from (select i_category, i_brand, "
           "sum(ss_sales_price) sumsales from store_sales, item "
           "where ss_item_sk = i_item_sk "
           "group by rollup(i_category, i_brand)) dw1")
    catalog = tpcds_catalog(SF)
    got = LocalRunner(catalog).run(sql)
    assert list(got.columns) == ["i_category", "sumsales"]
    conn = catalog.connectors["tpcds"]
    sales = conn.gen.store_sales_and_returns()[0]
    item = conn.gen.item()
    j = pd.DataFrame({"i_item_sk": sales["ss_item_sk"],
                      "cents": sales["ss_sales_price"][1]}).merge(
        pd.DataFrame({"i_item_sk": item["i_item_sk"],
                      "i_category": item["i_category"],
                      "i_brand": item["i_brand"]}))
    want = [(k[0], int(s)) for k, s in
            j.groupby(["i_category", "i_brand"]).cents.sum().items()]
    want += [(c, int(s)) for c, s in j.groupby("i_category").cents.sum().items()]
    want += [(None, int(j.cents.sum()))]
    have = [(None if pd.isna(c) else c, int(decimal.Decimal(s).scaleb(2)))
            for c, s in got.itertuples(index=False)]
    key = (lambda r: (r[0] is None, r[0] or "", r[1]))
    assert sorted(have, key=key) == sorted(want, key=key)


def test_a_union_all_drops_a_column_nobody_reads():
    from presto_tpu.catalog.memory import MemoryConnector
    from presto_tpu.connector import Catalog
    from presto_tpu.exec import LocalRunner

    conn = MemoryConnector()
    conn.add_table("t", pd.DataFrame({"x": [1, 2], "y": [10, 20]}))
    conn.add_table("u", pd.DataFrame({"p": [3, 2], "q": [30, 40]}))
    cat = Catalog()
    cat.register("m", conn, default=True)
    r = LocalRunner(cat)
    got = r.run("select a from (select x a, y b from t union all "
                "select p, q from u) z order by a")
    assert got.a.tolist() == [1, 2, 2, 3]
    # UNION (distinct) compares whole rows: the unread column still counts
    got = r.run("select a from (select x a, y b from t union "
                "select p, q from u) z order by a")
    assert got.a.tolist() == [1, 2, 2, 3]
    got = r.run("select a from (select x a, y b from t union "
                "select x, y from t) z order by a")
    assert got.a.tolist() == [1, 2]


# -- the generator's two columns

# sha256 (24 hex digits) of every column these tables had before Q67's two
# were added, names and values in order, at SF 0.01: what the generator
# made then
_BEFORE = {"date_dim": "85d49f589dca2527d678f350",
           "item": "871e9e985e6afc40fa0098e7",
           "store": "e7f220500db67b0a2128187f",
           "store_sales": "8ff0d7122dd86608f308dbd8"}
# sf1_q72's referenced arrays at SF 0.01, seed 2147484011, as
# `benchmark/data_tpcds.py` makes them, by the same digest
_Q72 = {"catalog_sales": "47964e740f3573c776355799",
        "inventory": "633f68bf1694c8ea6bceedde",
        "warehouse": "5172b6efc3d95fc84c9f132c",
        "item": "d611ed099eaeaecf2498c71e",
        "customer_demographics": "d0de3bb3068db21f1f0d1c43",
        "household_demographics": "ae94d9cbfa942e32d6f4301f",
        "date_dim": "b07f6cdd1639864422905b44",
        "promotion": "66bfecef8478b3f3399f7d5e",
        "catalog_returns": "cf33f857609b3f27e0ed775b"}
_ADDED = ("d_month_seq", "i_class")


def _digest(cols, names):
    h = hashlib.sha256()
    for c in names:
        v = cols[c]
        a = np.asarray(v[1] if isinstance(v, tuple) else v)
        h.update(c.encode())
        if a.dtype == object:
            h.update("\x00".join(map(str, a)).encode())
        else:
            h.update(np.ascontiguousarray(a.astype(np.int64)).tobytes())
    return h.hexdigest()[:24]


def test_every_earlier_column_keeps_its_values():
    from presto_tpu.catalog.tpcds import TpcdsGenerator

    gen = TpcdsGenerator(SF)
    for table, digest in _BEFORE.items():
        cols = (gen.store_sales_and_returns()[0] if table == "store_sales"
                else getattr(gen, table)())
        assert _digest(cols, [c for c in cols if c not in _ADDED]) == digest, table
    assert list(gen.date_dim())[-1] == "d_month_seq"
    assert list(gen.item())[-1] == "i_class"


def test_q72s_referenced_arrays_are_the_same():
    from benchmark import data_tpcds

    q72 = traffic.load_query("q72")
    data = data_tpcds.generate(SF, 2147484011, sorted(q72["tables"]))
    assert {t: _digest(data[t], cols) for t, cols in q72["tables"].items()} == _Q72


def test_month_seq_and_class():
    from presto_tpu.catalog.tpcds import _CLASSES, TpcdsGenerator

    gen = TpcdsGenerator(SF)
    dd = gen.date_dim()
    day = int(np.datetime64("2000-01-01", "D").astype(np.int64))
    at = int(np.nonzero(dd["d_date"] == day)[0][0])
    assert dd["d_month_seq"][at] == 1200
    assert dd["d_month_seq"][at - 1] == 1199 and dd["d_moy"][at - 1] == 12
    assert set(dd["d_month_seq"][dd["d_year"] == 2000]) == set(range(1200, 1212))
    item = gen.item()
    pairs = set(zip(item["i_category"], item["i_class"]))
    assert all(cls in _CLASSES[c] for c, cls in pairs)
    # one class a brand, so category, class and brand nest
    brands = pd.DataFrame({"b": item["i_brand"], "c": item["i_class"]})
    assert (brands.groupby("b").c.nunique() == 1).all()
    assert len({cls for _, cls in pairs}) > 60
