"""TPC-H Q9, plain: the parts whose name holds COLOR, their lineitems
(`isin`), then pandas merges of partsupp on (partkey, suppkey), supplier,
nation and orders onto them, in the order that is shortest to write and not
the engine's; `amount` in exact integers at scale 4 — cents x (100 -
hundredths) less cents x quantity x 100 — summed by (nation, year of
o_orderdate), ordered nation, year descending.

`arith="float32"` is the control: prices, discounts, costs and quantities in
float32 and the sums with them. A nation's year sums some 5e11 units of a
ten-thousandth where float32 holds integers to 2^24 = 1.7e7.
"""

import numpy as np
import pandas as pd

from benchmark.data import strings
from benchmark.refutil import dec, dec_from_float


def answer(data, params, arith="exact"):
    li, part, ps = data["lineitem"], data["part"], data["partsupp"]
    supp, nation, orders = data["supplier"], data["nation"], data["orders"]
    names = pd.Series(strings(part["p_name"]))
    wanted = part["p_partkey"][
        names.str.contains(params["color"], regex=False).to_numpy()]
    keep = np.isin(li["l_partkey"], wanted)
    j = pd.DataFrame({k: li[k][keep] for k in (
        "l_orderkey", "l_partkey", "l_suppkey", "l_quantity",
        "l_extendedprice", "l_discount")})
    j = j.merge(pd.DataFrame({k: ps[k] for k in (
        "ps_partkey", "ps_suppkey", "ps_supplycost")}),
        left_on=["l_partkey", "l_suppkey"], right_on=["ps_partkey", "ps_suppkey"])
    j = j.merge(pd.DataFrame({"s_suppkey": supp["s_suppkey"],
                              "s_nationkey": supp["s_nationkey"]}),
                left_on="l_suppkey", right_on="s_suppkey")
    j = j.merge(pd.DataFrame({"n_nationkey": nation["n_nationkey"],
                              "nation": strings(nation["n_name"])}),
                left_on="s_nationkey", right_on="n_nationkey")
    j = j.merge(pd.DataFrame({"o_orderkey": orders["o_orderkey"],
                              "o_orderdate": orders["o_orderdate"]}),
                left_on="l_orderkey", right_on="o_orderkey")
    j["o_year"] = j.o_orderdate.to_numpy().astype("datetime64[D]") \
        .astype("datetime64[Y]").astype(np.int64) + 1970
    if arith == "exact":
        j["amount"] = j.l_extendedprice * (100 - j.l_discount) \
            - j.ps_supplycost * j.l_quantity * 100
    else:
        f = lambda c, by=1: j[c].to_numpy().astype(np.float32) / np.float32(by)
        j["amount"] = f("l_extendedprice", 100) * (np.float32(1) - f("l_discount", 100)) \
            - f("ps_supplycost", 100) * f("l_quantity")
    g = j.groupby(["nation", "o_year"], sort=False)["amount"].sum().reset_index()
    g = g.sort_values(["nation", "o_year"], ascending=[True, False])
    money = (lambda v: dec(v, 4)) if arith == "exact" else \
        (lambda v: dec_from_float(v, 4))
    return [[str(r.nation), int(r.o_year), money(r.amount)]
            for r in g.itertuples()]
