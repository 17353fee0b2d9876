"""Aggregate + scalar function breadth (reference: operator/aggregation/*
96 files, operator/scalar/* 133 files — the statistics, boolean, approx,
argmax aggregate families and regexp/json/bitwise scalars)."""

import numpy as np
import pandas as pd
import pytest

from presto_tpu.connector import Catalog
from presto_tpu.catalog.memory import MemoryConnector
from presto_tpu.exec import ExecConfig, LocalRunner

from conftest import assert_frames_match


@pytest.fixture(scope="module")
def runner(rng):
    n = 5000
    cat = Catalog()
    conn = MemoryConnector()
    g = rng.integers(0, 40, n)
    df = pd.DataFrame({
        "g": g,
        "x": rng.normal(loc=10, scale=3, size=n),
        "y": rng.normal(size=n) + 0.5 * g,
        "b": rng.random(n) > 0.3,
        "pos": rng.random(n) + 0.1,
        "s": [f"id-{i%97:03d}" for i in range(n)],
    })
    # sprinkle NULLs through a nullable float column (None → SQL NULL)
    null_mask = rng.random(n) < 0.1
    df["xn"] = np.array([None if m else float(v)
                         for m, v in zip(null_mask, df.x)], dtype=object)
    conn.add_table("t", df)
    conn.add_table(
        "j", pd.DataFrame({
            "js": ['{"a": 1, "b": {"c": "hi"}, "arr": [1,2,3]}',
                   '{"a": 2, "arr": []}', 'not json'],
            "ja": ['[1,2,3]', '[]', '{"x":1}'],
        }),
    )
    cat.register("m", conn, default=True)
    r = LocalRunner(cat, ExecConfig(batch_rows=1 << 11))
    r.df = df
    return r


def test_variance_family(runner):
    got = runner.run("""
        select g, var_samp(x) as vs, var_pop(x) as vp,
               stddev(x) as sd, stddev_pop(x) as sdp
        from t group by g order by g""")
    exp = runner.df.groupby("g").agg(
        vs=("x", "var"), vp=("x", lambda s: s.var(ddof=0)),
        sd=("x", "std"), sdp=("x", lambda s: s.std(ddof=0)),
    ).reset_index()
    assert_frames_match(got, exp, sort_by=["g"], rtol=1e-6)


def test_variance_with_nulls(runner):
    got = runner.run("select stddev(xn) as sd, count(xn) as c from t")
    dfv = runner.df.xn.dropna()
    np.testing.assert_allclose(float(got.sd[0]), dfv.std(), rtol=1e-6)
    assert int(got.c[0]) == len(dfv)


def test_covar_corr(runner):
    got = runner.run("""
        select covar_pop(x, y) as cp, covar_samp(x, y) as cs,
               corr(x, y) as r from t""")
    df = runner.df
    np.testing.assert_allclose(float(got.cp[0]), np.cov(df.x, df.y, ddof=0)[0, 1], rtol=1e-6)
    np.testing.assert_allclose(float(got.cs[0]), np.cov(df.x, df.y, ddof=1)[0, 1], rtol=1e-6)
    np.testing.assert_allclose(float(got.r[0]), np.corrcoef(df.x, df.y)[0, 1], rtol=1e-6)


def test_geometric_mean(runner):
    got = runner.run("select geometric_mean(pos) as gm from t")
    exp = np.exp(np.log(runner.df.pos).mean())
    np.testing.assert_allclose(float(got.gm[0]), exp, rtol=1e-9)


def test_bool_and_or_count_if(runner):
    got = runner.run("""
        select g, bool_and(b) as ba, bool_or(b) as bo, every(b) as ev,
               count_if(b) as ci
        from t group by g order by g""")
    exp = runner.df.groupby("g").agg(
        ba=("b", "all"), bo=("b", "any"), ev=("b", "all"), ci=("b", "sum"),
    ).reset_index()
    assert list(got.ba) == list(exp.ba)
    assert list(got.bo) == list(exp.bo)
    assert list(got.ev) == list(exp.ev)
    assert list(got.ci.astype(int)) == list(exp.ci)


def test_approx_distinct_within_error(runner):
    # HLL-backed since round 3 (see tests/test_sketches.py for the full
    # sketch suite); small cardinalities use linear counting → near-exact
    got = runner.run("select approx_distinct(s) as d from t")
    exact = runner.df.s.nunique()
    assert abs(int(got.d[0]) - exact) <= max(2, int(0.05 * exact))


def test_checksum_order_independent(runner):
    a = runner.run("select checksum(x) as c from t")
    b = runner.run("select checksum(x) as c from (select x from t order by x desc) q")
    assert int(a.c[0]) == int(b.c[0])
    c = runner.run("select checksum(y) as c from t")
    assert int(a.c[0]) != int(c.c[0])


def test_arbitrary(runner):
    got = runner.run("select g, arbitrary(s) as v from t group by g")
    df = runner.df
    valid = {g: set(sub.s) for g, sub in df.groupby("g")}
    for _, row in got.iterrows():
        assert row.v in valid[row.g]


def test_approx_percentile(runner):
    got = runner.run("""
        select g, approx_percentile(x, 0.5) as med from t group by g order by g""")
    df = runner.df
    for _, row in got.iterrows():
        vals = np.sort(df[df.g == row.g].x.values)
        k = max(int(np.ceil(0.5 * len(vals))) - 1, 0)
        # quantized-histogram sketch: value-space relative error <= 2^-12
        np.testing.assert_allclose(row.med, vals[k], rtol=1e-3)


def test_max_by_min_by(runner):
    got = runner.run("""
        select g, max_by(s, x) as hi, min_by(s, x) as lo
        from t group by g order by g""")
    df = runner.df
    for _, row in got.iterrows():
        sub = df[df.g == row.g]
        assert row.hi == sub.loc[sub.x.idxmax(), "s"]
        assert row.lo == sub.loc[sub.x.idxmin(), "s"]


def test_mixed_decomposable_and_materialized(runner):
    got = runner.run("""
        select g, count(*) as c, approx_percentile(x, 0.9) as p90,
               sum(x) as sx
        from t group by g order by g""")
    df = runner.df
    exp_c = df.groupby("g").size()
    for _, row in got.iterrows():
        assert int(row.c) == exp_c[row.g]
        vals = np.sort(df[df.g == row.g].x.values)
        k = max(int(np.ceil(0.9 * len(vals))) - 1, 0)
        np.testing.assert_allclose(row.p90, vals[k], rtol=1e-12)


@pytest.fixture(scope="module")
def cluster(runner):
    from presto_tpu.server.coordinator import DistributedRunner

    with DistributedRunner(runner.catalog, n_workers=2,
                           config=runner.config) as r:
        yield r


def test_distributed_stats_aggs(runner, cluster):
    """Variance/covar decompose through partial/final across the
    exchange."""
    sql = """select g, stddev(x) as sd, corr(x, y) as r,
                    count_if(b) as ci from t group by g order by g"""
    assert_frames_match(cluster.run(sql), runner.run(sql), sort_by=["g"],
                        rtol=1e-6)


def test_distributed_percentile_gathers(runner, cluster):
    """approx_percentile gathers to a single task."""
    sql = "select g, approx_percentile(x, 0.5) as m from t group by g order by g"
    assert "gather" in cluster.explain_distributed(sql)
    assert_frames_match(cluster.run(sql), runner.run(sql), sort_by=["g"])


# ---- scalars ---------------------------------------------------------------


def test_bitwise(runner):
    got = runner.run("""
        select bitwise_and(g, 12) as a, bitwise_or(g, 5) as o,
               bitwise_xor(g, 7) as x, bitwise_not(g) as n,
               bitwise_left_shift(g, 2) as ls
        from t limit 100""")
    g = runner.df.g.values[:len(got)]
    # row order of limit is arbitrary; compare as multisets via sort
    assert sorted(got.a) == sorted(gv & 12 for gv in runner.df.g.values[:len(got)]) or True
    # deterministic check instead: full table
    got = runner.run("select g, bitwise_and(g, 12) as a, bitwise_not(g) as n from t")
    assert all(got.a == (got.g & 12))
    assert all(got.n == ~got.g)


def test_regexp_extract_replace(runner):
    got = runner.run("""
        select s, regexp_extract(s, '([0-9]+)', 1) as num,
               regexp_replace(s, '^id-', 'X') as rep
        from t limit 5""")
    for _, row in got.iterrows():
        assert row.num == row.s.split("-")[1]
        assert row.rep == "X" + row.s.split("-")[1]


def test_json_functions(runner):
    got = runner.run("""
        select json_extract_scalar(js, '$.a') as a,
               json_extract_scalar(js, '$.b.c') as c,
               json_array_length(ja) as n
        from j""")
    # absent paths / non-scalar values are SQL NULL (Presto JsonFunctions),
    # observable through IS NULL / count
    def norm(col):
        return [v if isinstance(v, str) else None for v in col]

    assert norm(got.a) == ["1", "2", None]
    assert norm(got.c) == ["hi", None, None]
    # non-array input → NULL (JsonFunctions.jsonArrayLength semantics)
    n = [None if v is None or v != v else int(v) for v in got.n]
    assert n == [3, 0, None]
    cnt = runner.run("""
        select count(json_extract_scalar(js, '$.b.c')) as c,
               count_if(json_extract_scalar(js, '$.a') is null) as n_null
        from j""")
    assert int(cnt.c[0]) == 1 and int(cnt.n_null[0]) == 1


def test_json_family(runner):
    """json_extract / json_array_get / json_size / json_format /
    json_parse / json_array_contains / is_json_scalar
    (operator/scalar/JsonFunctions.java)."""
    got = runner.run("""
        select json_extract(js, '$.b') as b,
               json_array_get(ja, 0) as a0,
               json_array_get(ja, -1) as al,
               json_size(js, '$.arr') as nsz,
               json_format(json_parse(ja)) as fmt,
               json_array_contains(ja, 2) as has2,
               is_json_scalar(ja) as scal
        from j""")

    def norm(col):
        return [v if isinstance(v, str) else None for v in col]

    assert norm(got.b) == ['{"c":"hi"}', None, None]
    assert norm(got.a0) == ["1", None, None]
    assert norm(got.al) == ["3", None, None]
    nsz = [None if v is None or v != v else int(v) for v in got.nsz]
    assert nsz == [3, 0, None]  # [] has size 0; malformed json → NULL
    assert norm(got.fmt) == ["[1,2,3]", "[]", '{"x":1}']
    assert [bool(v) for v in got.has2] == [True, False, False]
    assert [bool(v) for v in got.scal] == [False, False, False]
    one = runner.run(
        "select is_json_scalar(json_extract(js, '$.a')) as s from j limit 1")
    assert bool(one.s[0])


def test_unixtime_roundtrip(runner):
    got = runner.run("select to_unixtime(from_unixtime(x)) as u, x from t limit 10")
    # timestamps have microsecond resolution — roundtrip is exact to 1µs
    np.testing.assert_allclose(got.u.values.astype(float),
                               got.x.values.astype(float), atol=1e-6)


def test_levenshtein(runner):
    got = runner.run("select levenshtein_distance(s, 'id-000') as d from t limit 1")
    assert int(got.d[0]) >= 0
