"""TPC-DS Q72, plain: the catalog sales of the year whose bill-to household
and customer demographics pass, their sold and ship dates looked up by
merge; each meets the inventory rows of its item in its sale's week (the
week of every inventory date from date_dim) whose quantity on hand is below
the sale's quantity; then the warehouse's name, the item's description,
the promotion and the catalog returns of the sale, both kept where absent
(`how="left"`); counted by (description, warehouse, week) and ordered as
the query orders them, the first 100.

The answer holds strings, a week number and three counts: nothing in it is
a float or a decimal, so `arith` changes nothing ("float32" is accepted and
gives the exact answer, as a counting engine in float32 would).
"""

import numpy as np
import pandas as pd

from benchmark.data_tpcds import strings


def _frame(table, cols, names=None):
    return pd.DataFrame({(names or {}).get(c, c): table[c] for c in cols})


def answer(data, params, arith="exact"):
    cs, inv, dd = data["catalog_sales"], data["inventory"], data["date_dim"]
    dates = _frame(dd, ("d_date_sk", "d_date", "d_year", "d_week_seq"))
    hd = pd.DataFrame({"hd_demo_sk": data["household_demographics"]["hd_demo_sk"],
                       "bp": strings(data["household_demographics"]["hd_buy_potential"])})
    cd = pd.DataFrame({"cd_demo_sk": data["customer_demographics"]["cd_demo_sk"],
                       "ms": strings(data["customer_demographics"]["cd_marital_status"])})
    s = _frame(cs, ("cs_sold_date_sk", "cs_ship_date_sk", "cs_item_sk",
                    "cs_order_number", "cs_quantity", "cs_promo_sk",
                    "cs_bill_cdemo_sk", "cs_bill_hdemo_sk"))
    s = s.merge(dates.rename(columns={"d_date_sk": "cs_sold_date_sk",
                                      "d_date": "sold", "d_year": "year",
                                      "d_week_seq": "week"}))
    s = s[s.year == int(params["year"])]
    s = s.merge(hd[hd.bp == params["bp"]], left_on="cs_bill_hdemo_sk",
                right_on="hd_demo_sk")
    s = s.merge(cd[cd.ms == params["ms"]], left_on="cs_bill_cdemo_sk",
                right_on="cd_demo_sk")
    s = s.merge(dates[["d_date_sk", "d_date"]].rename(columns={
        "d_date_sk": "cs_ship_date_sk", "d_date": "shipped"}))
    s = s[s.shipped > s.sold + 5]
    # inventory: its rows of the items and weeks left, by a key of both
    week_of = dict(zip(dates.d_date_sk.tolist(), dates.d_week_seq.tolist()))
    inv_dates = np.unique(inv["inv_date_sk"])
    inv_week = pd.Series([week_of[d] for d in inv_dates.tolist()],
                         index=inv_dates).reindex(inv["inv_date_sk"]).to_numpy()
    key = inv["inv_item_sk"] * 1_000_000 + inv_week
    wanted = np.isin(key, (s.cs_item_sk * 1_000_000 + s.week).to_numpy())
    i = pd.DataFrame({"cs_item_sk": inv["inv_item_sk"][wanted],
                      "week": inv_week[wanted],
                      "w_sk": inv["inv_warehouse_sk"][wanted],
                      "qoh": inv["inv_quantity_on_hand"][wanted]})
    j = s.merge(i, on=["cs_item_sk", "week"])
    j = j[j.qoh < j.cs_quantity]
    w = data["warehouse"]
    j = j.merge(pd.DataFrame({"w_sk": w["w_warehouse_sk"],
                              "w_name": strings(w["w_warehouse_name"])}))
    it = data["item"]
    j = j.merge(pd.DataFrame({"cs_item_sk": it["i_item_sk"],
                              "desc": strings(it["i_item_desc"])}))
    j = j.merge(pd.DataFrame({"cs_promo_sk": data["promotion"]["p_promo_sk"],
                              "has_promo": True}), how="left")
    cr = data["catalog_returns"]
    j = j.merge(pd.DataFrame({"cs_item_sk": cr["cr_item_sk"],
                              "cs_order_number": cr["cr_order_number"]}),
                how="left")
    j["promo"] = j.has_promo.notna().astype(np.int64)
    j["no_promo"] = 1 - j.promo
    g = j.groupby(["desc", "w_name", "week"], sort=False).agg(
        no_promo=("no_promo", "sum"), promo=("promo", "sum"),
        total_cnt=("promo", "size")).reset_index()
    g = g.sort_values(["total_cnt", "desc", "w_name", "week"],
                      ascending=[False, True, True, True], kind="stable")
    return [[str(r.desc), str(r.w_name), int(r.week), int(r.no_promo),
             int(r.promo), int(r.total_cnt)] for r in g.head(100).itertuples()]
