"""TPC-DS Q67, plain: the store sales of the twelve months from DMS, their
date, store and item looked up by merge; summed in whole cents by each of
the ROLLUP's nine sets (the eight keys, then one fewer from the right, down
to none), each key a set leaves out padded as NULL; ranked within each
category by the sum, descending, as SQL's rank() ranks (equal sums share a
rank and the next rank skips; the NULL category of the grand total is a
partition of its own); the rows ranked at most 100, ordered by the eight
keys (NULLS LAST, Presto's default for ascending keys), the sum and the
rank, the first 100.

`arith="float32"` is the control: a sale's price and quantity in float32 and
every sum accumulated in float32. float32 holds cents only up to 2^24 =
167,772.16, and a category's or a class's year stands at 1e8 to 1e10 cents,
so cents are lost, and with them the order the sums induce.
"""

import numpy as np
import pandas as pd

from benchmark.data_tpcds import column_array, strings
from benchmark.refutil import dec, dec_from_float

KEYS = ("i_category", "i_class", "i_brand", "i_product_name",
        "d_year", "d_qoy", "d_moy", "s_store_id")


def _sums(j, keys, values, dtype):
    """One set's groups: the rows of their key values, and each group's sum
    of `values` accumulated in `dtype`."""
    if keys:
        codes = j.groupby(list(keys), sort=False).ngroup().to_numpy()
    else:
        codes = np.zeros(len(j), np.int64)
    n = int(codes.max()) + 1 if len(codes) else 0
    first = np.unique(codes, return_index=True)[1]
    total = np.zeros(n, dtype)
    np.add.at(total, codes, values)
    rows = j.iloc[first][list(keys)].itertuples(index=False)
    return [list(r) for r in rows], total


def answer(data, params, arith="exact"):
    dms = int(params["dms"])
    dd, ss = data["date_dim"], data["store_sales"]
    dates = pd.DataFrame({c: dd[c] for c in (
        "d_date_sk", "d_month_seq", "d_year", "d_qoy", "d_moy")})
    dates = dates[(dates.d_month_seq >= dms) & (dates.d_month_seq <= dms + 11)]
    it, st = data["item"], data["store"]
    items = pd.DataFrame({"i_item_sk": it["i_item_sk"],
                          **{c: strings(it[c]) for c in KEYS[:4]}})
    stores = pd.DataFrame({"s_store_sk": st["s_store_sk"],
                           "s_store_id": strings(st["s_store_id"])})
    s = pd.DataFrame({"d_date_sk": ss["ss_sold_date_sk"],
                      "i_item_sk": ss["ss_item_sk"],
                      "s_store_sk": ss["ss_store_sk"],
                      "price": column_array(ss["ss_sales_price"]),
                      "qty": ss["ss_quantity"]})
    j = s.merge(dates).merge(stores).merge(items)
    if arith == "exact":
        values, dtype = (j.price * j.qty).to_numpy(np.int64), np.int64
        money = lambda v: dec(v, 2)  # noqa: E731
    else:
        values = (j.price.to_numpy(np.float32) / np.float32(100)
                  * j.qty.to_numpy(np.float32))
        dtype, money = np.float32, lambda v: dec_from_float(v, 2)  # noqa: E731

    rows = []  # [eight keys, sum]
    for k in range(len(KEYS), -1, -1):
        groups, total = _sums(j, KEYS[:k], values, dtype)
        pad = [None] * (len(KEYS) - k)
        rows += [[*g, *pad, t] for g, t in zip(groups, total.tolist())]

    # rank() over (partition by i_category order by sumsales desc): one
    # more than the rows of the category with a greater sum
    frame = pd.DataFrame({"category": [r[0] for r in rows],
                          "sum": [r[8] for r in rows]})
    rank = frame.groupby("category", dropna=False)["sum"].rank(
        method="min", ascending=False).to_numpy()
    ranked = [r + [int(rk)] for r, rk in zip(rows, rank) if rk <= 100]

    def order(r):  # ascending, NULLS LAST
        return tuple((v is None, v if v is not None else 0) for v in r)

    ranked.sort(key=order)
    return [[*(None if v is None else (int(v) if i in (4, 5, 6) else str(v))
               for i, v in enumerate(r[:8])),
             money(r[8]), r[9]] for r in ranked[:100]]
