"""Each plain reference against a second, slower formulation at SF 0.01; the
float32 control judged not correct; row and byte counts against the arrays."""

import decimal
import os
import sys
from collections import defaultdict

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import data as bdata, traffic  # noqa: E402
from benchmark.control import control_verdict  # noqa: E402
from benchmark.refutil import date_str, day  # noqa: E402
from benchmark.run import load_reference  # noqa: E402

SF, SEED = 0.01, 2147484001


@pytest.fixture(scope="module")
def data():
    return bdata.generate(SF, SEED, ["customer", "orders", "lineitem"])


def D(unscaled, scale):
    return decimal.Decimal(int(unscaled)).scaleb(-scale)


def loop_q6(data, p):
    li = data["lineitem"]
    total = 0
    lo, hi = day(p["date_lo"]), day(p["date_hi"])
    dlo, dhi = int(decimal.Decimal(p["disc_lo"]) * 100), int(decimal.Decimal(p["disc_hi"]) * 100)
    for ship, disc, qty, price in zip(li["l_shipdate"].tolist(), li["l_discount"].tolist(),
                                      li["l_quantity"].tolist(), li["l_extendedprice"].tolist()):
        if lo <= ship < hi and dlo <= disc <= dhi and qty < int(p["quantity"]):
            total += price * disc
    return [[D(total, 4)]]


def loop_q1(data, p):
    li = data["lineitem"]
    flags = bdata.strings(li["l_returnflag"]).tolist()
    status = bdata.strings(li["l_linestatus"]).tolist()
    cut = day("1998-12-01") - int(p["delta"])
    acc = defaultdict(lambda: [0, 0, 0, 0, 0, 0])
    for f, s, q, e, d, t, ship in zip(flags, status, li["l_quantity"].tolist(),
                                      li["l_extendedprice"].tolist(), li["l_discount"].tolist(),
                                      li["l_tax"].tolist(), li["l_shipdate"].tolist()):
        if ship <= cut:
            a = acc[(f, s)]
            a[0] += q
            a[1] += e
            a[2] += e * (100 - d)
            a[3] += e * (100 - d) * (100 + t)
            a[4] += d
            a[5] += 1
    return [[f, s, a[0], D(a[1], 2), D(a[2], 4), D(a[3], 6), a[0] / a[5],
             a[1] / 100 / a[5], a[4] / 100 / a[5], a[5]]
            for (f, s), a in sorted(acc.items())]


def loop_q3(data, p):
    cut = day(p["date"])
    seg = bdata.strings(data["customer"]["c_mktsegment"]).tolist()
    custs = {k for k, s in zip(data["customer"]["c_custkey"].tolist(), seg)
             if s == p["segment"]}
    o = data["orders"]
    orders = {k: (d, sp) for k, c, d, sp in zip(
        o["o_orderkey"].tolist(), o["o_custkey"].tolist(), o["o_orderdate"].tolist(),
        o["o_shippriority"].tolist()) if d < cut and c in custs}
    li = data["lineitem"]
    rev = defaultdict(int)
    for k, e, d, ship in zip(li["l_orderkey"].tolist(), li["l_extendedprice"].tolist(),
                             li["l_discount"].tolist(), li["l_shipdate"].tolist()):
        if ship > cut and k in orders:
            rev[k] += e * (100 - d)
    top = sorted(rev.items(), key=lambda kv: (-kv[1], orders[kv[0]][0]))[:10]
    return [[k, D(r, 4), date_str(orders[k][0]), orders[k][1]] for k, r in top]


@pytest.mark.parametrize("qid,loop", [("q6", loop_q6), ("q1", loop_q1), ("q3", loop_q3)])
def test_reference_matches_second_formulation(data, qid, loop):
    meta = traffic.load_query(qid)
    params = meta["params"]["fixed"]
    got = load_reference(qid)(data, params)
    want = loop(data, params)
    assert len(got) == len(want) > 0
    for g, w in zip(got, want):
        for a, b in zip(g, w):
            if isinstance(b, float):
                assert a == pytest.approx(b, rel=1e-12)
            else:
                assert a == b
    assert len(got[0]) == len(meta["result_columns"])


# sf1_q3's control and exact reference are held, cell and all, in
# test_benchmark_join_cell.py; here Q3's reference and its float32 control
# are held through the query's own files (below)
@pytest.mark.parametrize("cell", ["sf10_q6", "sf10_q1", "sf1_q6_qgen"])
@pytest.mark.parametrize("seed", [11, 2147484002, 3000000019])
def test_float32_control_is_judged_not_correct(cell, seed):
    v = control_verdict(cell, seed, sf=SF)
    assert v["correct"] is False
    assert v["compared"]["wrong_statements"]["value"] >= 1


@pytest.mark.parametrize("cell", ["sf10_q6", "sf10_q1", "sf1_q6_qgen"])
def test_exact_reference_in_its_own_place_is_correct(cell):
    assert control_verdict(cell, 11, sf=SF, arith="exact")["correct"] is True


def test_rows_and_bytes_from_the_query_files(data):
    n_li = len(data["lineitem"]["l_orderkey"])
    n_o = len(data["orders"]["o_orderkey"])
    n_c = len(data["customer"]["c_custkey"])
    q6, q1, q3 = (traffic.load_query(q) for q in ("q6", "q1", "q3"))
    assert bdata.scanned_rows(q6, data) == n_li
    assert bdata.scanned_rows(q3, data) == n_li + n_o + n_c
    assert bdata.referenced_bytes(q6, data) == 4 * 8 * n_li
    assert bdata.referenced_bytes(q1, data) == (5 * 8 + 2 * 4) * n_li
    # c_mktsegment is generated as strings; the device holds int32 codes
    assert bdata.referenced_bytes(q3, data) == 4 * 8 * n_li + 4 * 8 * n_o \
        + (8 + 4) * n_c


def test_seed_makes_the_data(data):
    again = bdata.generate(SF, SEED, ["lineitem"])
    assert np.array_equal(again["lineitem"]["l_extendedprice"],
                          data["lineitem"]["l_extendedprice"])
    other = bdata.generate(SF, SEED + 1, ["lineitem"])
    assert not np.array_equal(other["lineitem"]["l_quantity"][:1000],
                              data["lineitem"]["l_quantity"][:1000])


def test_chunked_generation_keeps_keys_consistent(monkeypatch):
    monkeypatch.setattr(bdata, "CHUNK_ORDERS", 7000)
    d = bdata.generate(0.02, 7, ["orders", "lineitem"])
    assert len(d["orders"]["o_orderkey"]) == 30000
    assert np.all(np.diff(d["orders"]["o_orderkey"]) > 0)
    assert np.isin(d["lineitem"]["l_orderkey"], d["orders"]["o_orderkey"]).all()


@pytest.mark.parametrize("seed", [11, 2147484002, 3000000019])
def test_q3_float32_control_differs_from_the_reference(data, seed):
    from benchmark import compare

    d = bdata.generate(SF, seed, ["customer", "orders", "lineitem"])
    meta = traffic.load_query("q3")
    answer, params = load_reference("q3"), meta["params"]["fixed"]
    want = answer(d, params)
    got = [[str(v) if isinstance(v, decimal.Decimal) else v for v in row]
           for row in answer(d, params, arith="float32")]
    diff, _ = compare.compare_rows(meta["result_columns"], got, want,
                                   meta["result_columns"])
    assert diff is not None
    same, _ = compare.compare_rows(
        meta["result_columns"],
        [[str(v) if isinstance(v, decimal.Decimal) else v for v in row]
         for row in want], want, meta["result_columns"])
    assert same is None


@pytest.mark.parametrize("bad", [float("nan"), float("inf"), "NaN", None])
def test_a_double_that_is_no_number_is_a_difference(bad):
    from benchmark import compare

    cols = [{"name": "avg_qty", "type": "double"}]
    diff, gap = compare.compare_rows(cols, [[bad]], [[25.5]], cols)
    assert diff is not None and gap == 0.0
    verdict = compare.judge(
        [{"index": 0, "query": "q", "params_key": "", "error": None,
          "columns": cols, "rows": [[bad]]}],
        {("q", ""): ([[25.5]], cols)},
        {"wrong_statements": 0, "double_rel_err_max": 1e-9})
    assert verdict["correct"] is False
    assert verdict["compared"]["wrong_statements"]["value"] == 1
    same, gap = compare.compare_rows(cols, [[25.5]], [[25.5]], cols)
    assert same is None and gap == 0.0
