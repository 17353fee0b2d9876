"""TPC-H Q18, plain: pandas sums l_quantity per order over all of lineitem,
keeps the orders past QUANTITY, takes their lines (`isin`), merges orders and
customer onto them, sums the quantities again per (name, custkey, orderkey,
orderdate, totalprice) and keeps the top 100 by (totalprice desc, orderdate).

`arith="float32"` is the control: quantities and o_totalprice in float32.
Sums of integer quantities up to a few hundred are exact in float32, so the
`HAVING` and `total_qty` survive; what breaks is `o_totalprice` - float32
holds cents only up to 2^24 = 167,772.16 and the top hundred orders stand at
three to five times that - and with it the order the prices induce.

Ties: the text orders by (o_totalprice desc, o_orderdate) and says nothing of
two orders equal in both. The sort here is stable over the orders as lineitem
first names them, ascending o_orderkey; a tie inside the first hundred in the
other order would be judged a difference. Prices are cents over seven digits
and at most a few hundred orders pass the HAVING, so none has been seen.
"""

import numpy as np
import pandas as pd

from benchmark.data import strings
from benchmark.refutil import date_str, dec, dec_from_float


def answer(data, params, arith="exact"):
    cust, orders, li = data["customer"], data["orders"], data["lineitem"]
    qty = li["l_quantity"]
    price = orders["o_totalprice"]
    if arith != "exact":
        qty = qty.astype(np.float32)
        price = price.astype(np.float32) / np.float32(100)
    l = pd.DataFrame({"l_orderkey": li["l_orderkey"], "l_quantity": qty})
    per_order = l.groupby("l_orderkey", sort=False)["l_quantity"].sum()
    big = per_order.index[per_order > int(params["quantity"])]
    o = pd.DataFrame({"o_orderkey": orders["o_orderkey"],
                      "o_custkey": orders["o_custkey"],
                      "o_orderdate": orders["o_orderdate"],
                      "o_totalprice": price})
    c = pd.DataFrame({"c_custkey": cust["c_custkey"],
                      "c_name": strings(cust["c_name"])})
    j = l[l.l_orderkey.isin(big)] \
        .merge(o[o.o_orderkey.isin(big)], left_on="l_orderkey",
               right_on="o_orderkey") \
        .merge(c, left_on="o_custkey", right_on="c_custkey")
    g = j.groupby(["c_name", "c_custkey", "o_orderkey", "o_orderdate",
                   "o_totalprice"], sort=False)["l_quantity"].sum().reset_index()
    g = g.sort_values(["o_totalprice", "o_orderdate"], ascending=[False, True],
                      kind="stable").head(100)
    money = (lambda v: dec(v, 2)) if arith == "exact" else \
        (lambda v: dec_from_float(v, 2))
    return [[str(r.c_name), int(r.c_custkey), int(r.o_orderkey),
             date_str(r.o_orderdate), money(r.o_totalprice),
             int(r.l_quantity)] for r in g.itertuples()]
