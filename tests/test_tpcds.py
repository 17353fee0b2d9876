"""TPC-DS connector + star-join queries vs a pandas oracle
(presto-tpcds analog; the Q64 star is BASELINE config #5's shape)."""

import numpy as np
import pandas as pd
import pytest

from presto_tpu.catalog.tpcds import tpcds_catalog
from presto_tpu.exec import ExecConfig, LocalRunner

SF = 0.01


@pytest.fixture(scope="module")
def env():
    cat = tpcds_catalog(SF)
    runner = LocalRunner(cat, ExecConfig(batch_rows=1 << 14, agg_capacity=1 << 10))
    conn = cat.connectors["tpcds"]

    def df(t):
        conn._ensure(t)
        mt = conn.tables[t]
        d = {}
        for c, arr in mt.arrays.items():
            if c in mt.dicts:
                d[c] = mt.dicts[c].decode(arr)
            elif hasattr(mt.types[c], "scale"):
                d[c] = arr / (10 ** mt.types[c].scale)
            else:
                d[c] = arr
        return pd.DataFrame(d)

    return runner, df


def test_scaling_table():
    from presto_tpu.catalog.tpcds import TpcdsGenerator

    g1, g100 = TpcdsGenerator(1.0), TpcdsGenerator(100.0)
    assert g1.n_customer == 100_000 and g100.n_customer == 2_000_000
    assert g1.n_item == 18_000 and g100.n_item == 204_000
    assert g1.n_store == 12 and g100.n_store == 402
    assert g1.n_store_sales == 2_880_404


def test_referential_integrity(env):
    runner, _ = env
    for fact_key, dim in (("ss_sold_date_sk", "select d_date_sk from date_dim"),
                          ("ss_item_sk", "select i_item_sk from item"),
                          ("ss_store_sk", "select s_store_sk from store")):
        out = runner.run(
            f"select count(*) as dangling from store_sales "
            f"where {fact_key} not in ({dim})"
        )
        assert int(out.dangling[0]) == 0, fact_key


def test_q64_star(env):
    runner, df = env
    out = runner.run("""
        select i_product_name, s_store_name, d_year,
               count(*) as cnt, sum(ss_wholesale_cost) as s1,
               sum(ss_list_price) as s2, sum(ss_coupon_amt) as s3
        from store_sales, date_dim, store, customer, item
        where ss_sold_date_sk = d_date_sk and ss_store_sk = s_store_sk
          and ss_customer_sk = c_customer_sk and ss_item_sk = i_item_sk
          and i_current_price between 35 and 44
        group by i_product_name, s_store_name, d_year
        order by s1 limit 100
    """)
    ss, dd, st, cu, it = (df("store_sales"), df("date_dim"), df("store"),
                          df("customer"), df("item"))
    m = (ss.merge(dd, left_on="ss_sold_date_sk", right_on="d_date_sk")
           .merge(st, left_on="ss_store_sk", right_on="s_store_sk")
           .merge(cu, left_on="ss_customer_sk", right_on="c_customer_sk")
           .merge(it, left_on="ss_item_sk", right_on="i_item_sk"))
    m = m[(m.i_current_price >= 35) & (m.i_current_price <= 44)]
    g = (m.groupby(["i_product_name", "s_store_name", "d_year"], as_index=False)
          .agg(cnt=("ss_quantity", "count"), s1=("ss_wholesale_cost", "sum"),
               s2=("ss_list_price", "sum"), s3=("ss_coupon_amt", "sum"))
          .sort_values("s1").head(100))
    assert len(out) == len(g)
    np.testing.assert_allclose(sorted(out.s1.astype(float)), sorted(g.s1),
                               rtol=1e-9)


def test_returns_join(env):
    runner, df = env
    out = runner.run("""
        select count(*) as c, sum(sr_return_quantity) as q
        from store_sales join store_returns
          on ss_ticket_number = sr_ticket_number and ss_item_sk = sr_item_sk
    """)
    ss, sr = df("store_sales"), df("store_returns")
    m = ss.merge(sr, left_on=["ss_ticket_number", "ss_item_sk"],
                 right_on=["sr_ticket_number", "sr_item_sk"])
    assert int(out.c[0]) == len(m)
    assert int(out.q[0]) == int(m.sr_return_quantity.sum())


# -- full 24-table surface (round 3: catalog/web channels + inventory) -------


def test_all_24_tables_present():
    from presto_tpu.catalog.tpcds import TpcdsConnector

    conn = TpcdsConnector(0.01)
    names = conn.table_names()
    assert len(names) == 24
    for t in names:
        h = conn.get_table(t)
        assert h.row_count >= 1, t


def test_catalog_channel_referential_integrity():
    from presto_tpu.catalog.tpcds import tpcds_catalog
    from presto_tpu.exec import ExecConfig, LocalRunner

    r = LocalRunner(tpcds_catalog(0.01), ExecConfig(batch_rows=1 << 14))
    # every catalog_returns row joins back to a catalog_sales order+item
    out = r.run(
        "select count(*) as n from catalog_returns cr "
        "join catalog_sales cs on cr.cr_order_number = cs.cs_order_number "
        "and cr.cr_item_sk = cs.cs_item_sk")
    nret = r.run("select count(*) as n from catalog_returns")
    assert out.n[0] == nret.n[0]


def test_web_channel_star_join():
    from presto_tpu.catalog.tpcds import tpcds_catalog
    from presto_tpu.exec import ExecConfig, LocalRunner

    r = LocalRunner(tpcds_catalog(0.01), ExecConfig(batch_rows=1 << 14))
    out = r.run(
        "select w.web_name, count(*) as n, sum(ws.ws_ext_sales_price) as s "
        "from web_sales ws join web_site w on ws.ws_web_site_sk = w.web_site_sk "
        "join date_dim d on ws.ws_sold_date_sk = d.d_date_sk "
        "where d.d_year = 2000 group by w.web_name order by w.web_name")
    assert len(out) >= 1
    assert (out.n > 0).all()


def test_inventory_grain():
    from presto_tpu.catalog.tpcds import tpcds_catalog
    from presto_tpu.exec import ExecConfig, LocalRunner

    r = LocalRunner(tpcds_catalog(0.01), ExecConfig(batch_rows=1 << 16))
    dates = r.run("select count(distinct inv_date_sk) as d from inventory")
    assert dates.d[0] == 261  # weekly snapshots over the 5-year window
    n = r.run("select count(*) as n from inventory")
    # grain = (date, item, warehouse): row count divides evenly
    assert n.n[0] % 261 == 0


# -- the columns TPC-DS Q72 reads beyond the rest ---------------------------

# (and i_class, which Q67 reads, drawn after i_item_desc)
_ADDED = {"catalog_sales": ("cs_bill_cdemo_sk", "cs_bill_hdemo_sk"),
          "web_sales": ("ws_bill_cdemo_sk", "ws_bill_hdemo_sk"),
          "item": ("i_item_desc", "i_class")}
# sha256 (24 hex digits) of every column these tables had before the three
# were added, names and values in order: what the generator made then
_BEFORE = {
    0.01: {"catalog_sales": "3189c3819535691b0511e253",
           "web_sales": "ba312a5320eb5de92025aa63",
           "item": "2f193727f71df11cbe90aad4"},
    1: {"catalog_sales": "c2a017a0a18f2c4a4065e4db",
        "web_sales": "e1db8d5153cb5789383b4394",
        "item": "2f193727f71df11cbe90aad4"},
}


def _digest_of_the_rest(table, cols):
    import hashlib

    h = hashlib.sha256()
    for c, v in cols.items():
        if c in _ADDED[table]:
            continue
        a = np.asarray(v[1] if isinstance(v, tuple) else v)
        h.update(c.encode())
        if a.dtype == object:
            h.update("\x00".join(map(str, a)).encode())
        else:
            h.update(np.ascontiguousarray(a.astype(np.int64)).tobytes())
    return h.hexdigest()[:24]


@pytest.mark.parametrize("sf", sorted(_BEFORE))
def test_every_earlier_column_keeps_its_values(sf):
    """The three are drawn after every column of their table, from the
    table's own stream: what was there is the same bit for bit."""
    from presto_tpu.catalog.tpcds import TpcdsGenerator

    gen = TpcdsGenerator(sf)
    for table, digest in _BEFORE[sf].items():
        cols = getattr(gen, table)()
        assert list(cols)[-len(_ADDED[table]):] == list(_ADDED[table])
        assert _digest_of_the_rest(table, cols) == digest, table


def test_the_added_columns_hit_their_dimensions():
    from presto_tpu.catalog.tpcds import TpcdsGenerator, tpcds_catalog
    from presto_tpu.exec import ExecConfig, LocalRunner

    gen = TpcdsGenerator(0.01)
    for prefix, sales in (("cs", gen.catalog_sales()), ("ws", gen.web_sales())):
        cdemo, hdemo = sales[f"{prefix}_bill_cdemo_sk"], sales[f"{prefix}_bill_hdemo_sk"]
        assert 1 <= cdemo.min() and cdemo.max() <= gen.n_cdemo
        assert 1 <= hdemo.min() and hdemo.max() <= gen.n_hdemo
        assert len(np.unique(hdemo)) > gen.n_hdemo // 2  # uniform over the keys
    d, codes = gen.item()["i_item_desc"]
    assert len(codes) == gen.n_item and len(d) > gen.n_item // 2
    assert all(len(s.split()) == 5 for s in d.decode(codes[:50]))
    r = LocalRunner(tpcds_catalog(0.01), ExecConfig(batch_rows=1 << 14))
    n = r.run("select count(*) as n from catalog_sales").n[0]
    joined = r.run(
        "select count(*) as n from catalog_sales "
        "join customer_demographics on cs_bill_cdemo_sk = cd_demo_sk "
        "join household_demographics on cs_bill_hdemo_sk = hd_demo_sk "
        "join item on cs_item_sk = i_item_sk")
    assert joined.n[0] == n
