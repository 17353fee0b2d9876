"""TPC-H Q3, plain: pandas merges customer -> orders -> lineitem, exact
int64 revenue per order, top 10 by (revenue desc, orderdate).

`arith="float32"` is the control: revenue in float32.
"""

import numpy as np
import pandas as pd

from benchmark.data import strings
from benchmark.refutil import date_str, day, dec, dec_from_float


def answer(data, params, arith="exact"):
    cust, orders, li = data["customer"], data["orders"], data["lineitem"]
    cutoff = day(params["date"])
    c = pd.DataFrame({"c_custkey": cust["c_custkey"],
                      "seg": strings(cust["c_mktsegment"])})
    c = c[c.seg == params["segment"]]
    o = pd.DataFrame({k: orders[k] for k in
                      ("o_orderkey", "o_custkey", "o_orderdate", "o_shippriority")})
    o = o[o.o_orderdate < cutoff].merge(c, left_on="o_custkey",
                                        right_on="c_custkey")
    keep = li["l_shipdate"] > cutoff
    l = pd.DataFrame({k: li[k][keep] for k in
                      ("l_orderkey", "l_extendedprice", "l_discount")})
    j = l.merge(o, left_on="l_orderkey", right_on="o_orderkey")
    if arith == "exact":
        j["revenue"] = j.l_extendedprice * (100 - j.l_discount)
    else:
        p = j.l_extendedprice.to_numpy().astype(np.float32) / np.float32(100)
        d = j.l_discount.to_numpy().astype(np.float32) / np.float32(100)
        j["revenue"] = p * (np.float32(1) - d)
    g = j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"],
                  sort=False)["revenue"].sum().reset_index()
    g = g.sort_values(["revenue", "o_orderdate"], ascending=[False, True],
                      kind="stable").head(10)
    money = (lambda v: dec(v, 4)) if arith == "exact" else \
        (lambda v: dec_from_float(v, 4))
    return [[int(r.l_orderkey), money(r.revenue), date_str(r.o_orderdate),
             int(r.o_shippriority)] for r in g.itertuples()]
