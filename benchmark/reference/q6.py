"""TPC-H Q6, plain: one filter and one exact sum over lineitem's arrays.

`arith="exact"` is the reference (int64 over unscaled decimals, no rounding
anywhere). `arith="float32"` is the control: the same sum in the chip's
native float type, as an engine that gave up exact decimals would compute it.
"""

import numpy as np

from benchmark.refutil import cents, day, dec, dec_from_float


def answer(data, params, arith="exact"):
    li = data["lineitem"]
    ship, disc = li["l_shipdate"], li["l_discount"]
    m = ((ship >= day(params["date_lo"])) & (ship < day(params["date_hi"]))
         & (disc >= cents(params["disc_lo"])) & (disc <= cents(params["disc_hi"]))
         & (li["l_quantity"] < int(params["quantity"])))
    price, d = li["l_extendedprice"][m], disc[m]
    if arith == "exact":
        return [[dec((price * d).sum(), 4)]]
    p = price.astype(np.float32) / np.float32(100)
    f = d.astype(np.float32) / np.float32(100)
    return [[dec_from_float((p * f).sum(dtype=np.float32), 4)]]
