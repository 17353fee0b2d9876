"""TPC-H Q1, plain: one mask to a group over lineitem's arrays, sums exact in
int64 over unscaled decimals, averages as float64 quotients of exact sums.

`arith="float32"` is the control: products, sums and averages in float32.
"""

import numpy as np

from benchmark.data import strings
from benchmark.refutil import day, dec, dec_from_float


def answer(data, params, arith="exact"):
    li = data["lineitem"]
    keep = li["l_shipdate"] <= day("1998-12-01") - int(params["delta"])
    (flags, rf), (stati, ls) = li["l_returnflag"], li["l_linestatus"]
    rf, ls = rf[keep].astype(np.int64), ls[keep].astype(np.int64)
    group = rf * (int(ls.max(initial=0)) + 1) + ls
    qty, price, disc, tax = (li[c][keep] for c in (
        "l_quantity", "l_extendedprice", "l_discount", "l_tax"))
    if arith == "float32":
        price, disc, tax = (c.astype(np.float32) / np.float32(100)
                            for c in (price, disc, tax))
        qty, one, acc = qty.astype(np.float32), np.float32(1), np.float32
    else:
        one, acc = 100, np.int64
    disc_price = price * (one - disc)
    charge = disc_price * (one + tax)
    rows = []
    for g in np.unique(group):
        m = group == g
        at = int(np.argmax(m))  # a row of the group, for its two strings
        n = int(m.sum())
        sums = [c[m].sum(dtype=acc) for c in
                (qty, price, disc_price, charge, disc)]
        key = [strings((flags, rf[at:at + 1]))[0],
               strings((stati, ls[at:at + 1]))[0]]
        if arith == "exact":
            sq, sb, sdp, sc, sd = (int(v) for v in sums)
            rows.append(key + [sq, dec(sb, 2), dec(sdp, 4), dec(sc, 6),
                               sq / n, sb / 100 / n, sd / 100 / n, n])
        else:
            sq, sb, sdp, sc, sd = sums
            rows.append(key + [int(round(float(sq))), dec_from_float(sb, 2),
                               dec_from_float(sdp, 4), dec_from_float(sc, 6),
                               float(sq / np.float32(n)),
                               float(sb / np.float32(n)),
                               float(sd / np.float32(n)), n])
    rows.sort(key=lambda r: (r[0], r[1]))
    return rows
