"""All 22 TPC-H queries verified against sqlite3 — a NON-self-referential
oracle (an independent SQL engine, the H2QueryRunner analog from
presto-tests/.../H2QueryRunner.java; duckdb is absent from this image, and
sqlite is the stdlib's full SQL engine).

The same query text runs on both engines modulo a mechanical dialect
transform (date literals/arithmetic, extract, substring). A shared
misunderstanding of SQL semantics between our engine and a hand-written
pandas oracle cannot pass here.
"""

import dataclasses
import re
import sqlite3

import numpy as np
import pandas as pd
import pytest

from presto_tpu.catalog.tpch import tpch_catalog
from presto_tpu.exec import ExecConfig, LocalRunner
from presto_tpu.exec.runtime import execute_node
from presto_tpu.types import DecimalType

SF = 0.01

# ---------------------------------------------------------------------------
# queries (engine dialect; sqlite text derived mechanically)

from test_derives_unique import UNIQUE_ABOVE_A_JOIN, hash_joins  # noqa: E402
from test_tpch import QUERIES  # noqa: E402  (the 22 canonical texts)


def to_sqlite_sql(sql: str) -> str:
    # date '1998-12-01' - interval '90' day  ->  date('1998-12-01', '-90 day')
    sql = re.sub(
        r"date\s+'(\d{4}-\d{2}-\d{2})'\s*-\s*interval\s+'(\d+)'\s+(day|month|year)",
        r"date('\1', '-\2 \3')", sql)
    sql = re.sub(
        r"date\s+'(\d{4}-\d{2}-\d{2})'\s*\+\s*interval\s+'(\d+)'\s+(day|month|year)",
        r"date('\1', '+\2 \3')", sql)
    # date '1995-03-15' -> '1995-03-15'  (dates are ISO text in sqlite)
    sql = re.sub(r"date\s+'(\d{4}-\d{2}-\d{2})'", r"'\1'", sql)
    # extract(year from x) -> cast(strftime('%Y', x) as integer)
    sql = re.sub(r"extract\s*\(\s*year\s+from\s+([a-z_][\w.]*)\s*\)",
                 r"cast(strftime('%Y', \1) as integer)", sql, flags=re.I)
    # year(x) / month(x) / day(x) shorthand (Presto dialect) -> strftime
    sql = re.sub(r"\byear\s*\(\s*([a-z_][\w.]*)\s*\)",
                 r"cast(strftime('%Y', \1) as integer)", sql, flags=re.I)
    sql = re.sub(r"\bmonth\s*\(\s*([a-z_][\w.]*)\s*\)",
                 r"cast(strftime('%m', \1) as integer)", sql, flags=re.I)
    sql = re.sub(r"\bday\s*\(\s*([a-z_][\w.]*)\s*\)",
                 r"cast(strftime('%d', \1) as integer)", sql, flags=re.I)
    # substring(x from a for b) -> substr(x, a, b)
    sql = re.sub(r"substring\s*\(\s*([\w.]+)\s+from\s+(\d+)\s+for\s+(\d+)\s*\)",
                 r"substr(\1, \2, \3)", sql, flags=re.I)
    return sql


@pytest.fixture(scope="module")
def engines():
    cat = tpch_catalog(SF)
    runner = LocalRunner(cat, ExecConfig(batch_rows=1 << 14,
                                         agg_capacity=1 << 10))
    conn = cat.connectors["tpch"]
    db = sqlite3.connect(":memory:")
    for t in conn.table_names():
        conn._ensure(t)
        mt = conn.tables[t]
        cols, arrays = [], []
        for c, arr in mt.arrays.items():
            tt = mt.types[c]
            if isinstance(tt, DecimalType):
                cols.append((c, "REAL"))
                arrays.append(arr.astype(np.float64) / 10 ** tt.scale)
            elif tt.is_string:
                cols.append((c, "TEXT"))
                arrays.append(mt.dicts[c].decode(arr))
            elif tt.name == "date":
                cols.append((c, "TEXT"))
                arrays.append(
                    (np.asarray(arr, "int64").astype("datetime64[D]")
                     ).astype(str))
            else:
                cols.append((c, "INTEGER"))
                arrays.append(arr)
        db.execute(f"create table {t} ({', '.join(f'{c} {ct}' for c, ct in cols)})")
        rows = list(zip(*[a.tolist() for a in arrays]))
        db.executemany(
            f"insert into {t} values ({', '.join('?' * len(cols))})", rows)
    # the oracle's own indexes: without them sqlite answers q21's
    # correlated EXISTS in 80 s and q19 in 15 s, with them all 22 in under
    # a second. They change how sqlite searches, not what it answers.
    for stmt in ("create index li_ok on lineitem(l_orderkey)",
                 "create index o_ok on orders(o_orderkey)",
                 "create index ps_pk on partsupp(ps_partkey, ps_suppkey)",
                 "create index li_pk on lineitem(l_partkey, l_suppkey)"):
        db.execute(stmt)
    db.commit()
    yield runner, db
    db.close()


def _normalize(df: pd.DataFrame) -> pd.DataFrame:
    """Comparable form: dates → epoch days, decimals → float, text stays."""
    import decimal

    out = {}
    for c in df.columns:
        vals = df[c].to_numpy()
        first = next((v for v in vals if v is not None and v == v), None)
        if isinstance(first, str) and re.fullmatch(r"\d{4}-\d{2}-\d{2}", first):
            out[c] = pd.to_datetime(df[c]).map(
                lambda v: (v - pd.Timestamp("1970-01-01")).days
                if v == v else np.nan)
        elif isinstance(first, decimal.Decimal):
            out[c] = df[c].map(lambda v: float(v) if v is not None else np.nan)
        elif isinstance(first, (float, int, np.floating, np.integer)):
            out[c] = pd.to_numeric(df[c], errors="coerce")
        else:
            out[c] = df[c]
    return pd.DataFrame(out)


def assert_matches_sqlite(runner, db, name):
    sql = QUERIES[name]
    got = _normalize(runner.run(sql))
    cur = db.execute(to_sqlite_sql(sql))
    cols = [d[0] for d in cur.description]
    exp = _normalize(pd.DataFrame(cur.fetchall(), columns=cols))
    assert list(got.columns) == list(exp.columns), (got.columns, exp.columns)
    assert len(got) == len(exp), f"{name}: {len(got)} vs {len(exp)} rows"
    # order-insensitive compare (ORDER BY ties differ between engines)
    by = [c for c in got.columns
          if got[c].dtype != object or got[c].map(type).eq(str).all()]
    g = got.sort_values(by=by, ignore_index=True, na_position="last")
    e = exp.sort_values(by=by, ignore_index=True, na_position="last")
    for c in got.columns:
        gv, ev = g[c].to_numpy(), e[c].to_numpy()
        if np.issubdtype(np.asarray(ev).dtype, np.number):
            np.testing.assert_allclose(
                np.asarray(gv, float), np.asarray(ev, float),
                rtol=1e-6, atol=1e-9, err_msg=f"{name}.{c}")
        else:
            assert list(gv) == list(ev), f"{name}.{c}"


@pytest.mark.parametrize("name", sorted(QUERIES, key=lambda s: int(s[1:])))
def test_tpch_vs_sqlite(engines, name):
    assert_matches_sqlite(*engines, name)


# -- the planner's `unique` flag, held to executed data ----------------------
# `plan/builder.py:_derives_unique` marks a build unique from the plan's
# structure, through a join below it too (tests/test_derives_unique.py); the
# probe then returns one match a row, so a wrong flag loses rows in silence.

ABOVE_A_JOIN = list(UNIQUE_ABOVE_A_JOIN)


@pytest.mark.parametrize("name", ABOVE_A_JOIN)
def test_a_build_marked_unique_holds_distinct_keys(engines, name):
    """Every HashJoin the plan marks `unique`, its build side executed at
    this scale: the live rows' non-null keys are distinct."""
    runner, _ = engines
    joins = [j for j in hash_joins(runner.plan(QUERIES[name]).root)
             if j.build_unique]
    assert len(joins) == UNIQUE_ABOVE_A_JOIN[name][1]
    for j in joins:
        ctx = runner._new_ctx()
        keys = pd.concat([b.to_pandas()[list(j.right_keys)]
                          for b in execute_node(j.right, ctx)]).dropna()
        assert len(keys) > 0, (name, j.right_keys)
        assert not keys.duplicated().any(), (name, j.right_keys)


@pytest.fixture(scope="module")
def sort_runner(engines):
    """The chip's engine (`auto` answers `hash` on the CPU): the sorted
    build and `probe_unique`, which trusts the flag."""
    runner, _ = engines
    return LocalRunner(runner.catalog, dataclasses.replace(
        runner.config, breaker_engine="sort"))


@pytest.mark.parametrize("name", ABOVE_A_JOIN)
def test_unique_above_a_join_vs_sqlite_under_the_sort_engine(
        engines, sort_runner, name):
    assert_matches_sqlite(sort_runner, engines[1], name)


# -- a unique probe's output, gathered at the size of what matched ------------
# `exec/runtime.py`: the probe's program stops at each row's build index and
# the count of rows the join hands on; `_merging_output` reads that count (its
# one read a batch) and the output is gathered compacted at
# `round_up_capacity(n)` lanes (inner, sparse), at the probe's capacity with
# the probe's columns handed on (dense, LEFT / FULL), or not at all (no row).

FEW_ORDERS = "o_orderdate < date '1992-02-01'"
LO = "lineitem {} join orders on l_orderkey = o_orderkey"
OC = ("(select o_orderkey, o_custkey, o_totalprice from orders{}) o "
      "full join customer on o_custkey = c_custkey")
JOIN_EMIT = {          # (kind, density of the join's output): FROM ... WHERE
    ("inner", "sparse"): LO.format("") + f" where {FEW_ORDERS}",
    ("inner", "dense"): LO.format(""),
    # orders of January 1992 ship no line in the second half of 1998
    ("inner", "empty"): LO.format("") + f" where {FEW_ORDERS} "
                                        "and l_shipdate > date '1998-06-01'",
    ("left", "sparse"): LO.format("left") + f" and {FEW_ORDERS} "
                                            "where l_shipdate < date '1992-03-01'",
    ("left", "dense"): LO.format("left") + f" and {FEW_ORDERS}",
    ("left", "empty"): LO.format("left") + " where l_shipdate < date '1990-01-01'",
    ("full", "sparse"): OC.format(f" where {FEW_ORDERS}"),
    ("full", "dense"): OC.format(""),
    ("full", "empty"): OC.format(" where o_orderdate < date '1990-01-01'"),
}
# `grouped`: an aggregate pulls the join through `_fused_child`, so the count is
# read before anything is gathered; `rows`: the projection above the join needs
# a real batch, gathered at the probe's capacity before the count is read
SELECT = {
    ("grouped", "full"): "select c_nationkey, count(*) as n, count(o_orderkey) as m, "
                         "sum(o_totalprice) as p, sum(c_acctbal) as b from {} "
                         "group by c_nationkey",
    ("rows", "full"): "select o_orderkey, o_totalprice, c_custkey, c_acctbal from {}",
    ("grouped", None): "select l_linenumber, count(*) as n, count(o_orderdate) as m, "
                       "sum(l_quantity) as q, sum(o_totalprice) as p, "
                       "min(o_orderdate) as d from {} group by l_linenumber",
    ("rows", None): "select l_orderkey, l_linenumber, l_quantity, o_orderdate, "
                    "o_totalprice from {}",
}
DRIVERS = {"plain": {}, "radix": {"radix_partitions": 4},
           "spilled": {"memory_pool_bytes": 100 << 10, "spill_partitions": 4},
           "unmerged": {"merge_sparse_output": False}}
JOIN_EMIT_CASES = (
    [(k, d, e, "plain", "grouped") for (k, d) in JOIN_EMIT for e in ("sort", "hash")]
    + [(k, d, "sort", "plain", "rows") for (k, d) in JOIN_EMIT]
    + [(k, d, "sort", "radix", "grouped") for (k, d) in JOIN_EMIT]
    + [(k, "sparse", "sort", d, "grouped") for k in ("inner", "left", "full")
       for d in ("spilled", "unmerged")])


@pytest.fixture(scope="module")
def emit_runners(engines):
    runner, _ = engines
    made = {}

    def get(engine, driver):
        if (engine, driver) not in made:
            made[engine, driver] = LocalRunner(runner.catalog, dataclasses.replace(
                runner.config, breaker_engine=engine, **DRIVERS[driver]))
        return made[engine, driver]

    return get


@pytest.mark.parametrize("kind,density,engine,driver,form", JOIN_EMIT_CASES)
def test_a_unique_probes_output_vs_sqlite(engines, emit_runners, kind, density,
                                          engine, driver, form):
    from presto_tpu.obs import trace as obs_trace

    runner, joined = emit_runners(engine, driver), JOIN_EMIT[kind, density]
    sql = SELECT[form, kind if kind == "full" else None].format(joined)
    (join,) = hash_joins(runner.plan(sql).root)
    assert join.build_unique and join.kind == kind
    QUERIES["join_emit"] = sql
    try:
        assert_matches_sqlite(runner, engines[1], "join_emit")
    finally:
        del QUERIES["join_emit"]
    rows = engines[1].execute(
        to_sqlite_sql(f"select count(*) from {joined}")).fetchone()[0]
    assert (rows == 0) == (density == "empty" and kind != "full")
    phases = {}                  # every thread role's occurrences summed
    for by_name in obs_trace.phases_by_role(runner.last_trace.spans()).values():
        for name, agg in by_name.items():
            total = phases.setdefault(name, {"n": 0, "items": 0})
            total["n"] += agg["n"]
            total["items"] += agg.get("items", 0)
    assert "host_sync:join_total" not in phases
    if driver == "spilled":
        assert runner.last_stats["spill.partitions"] > 0
        return
    if kind == "full":           # each prober's tail is a batch more
        return
    probes = phases.get("join_probe", {"n": 0})["n"]
    if driver == "unmerged":     # nobody reads a count: all at capacity
        assert "host_sync:join_output_rows" not in phases
        assert phases["join_emit"]["n"] == probes
        return
    # one read of the live count a probe batch
    assert phases.get("host_sync:join_output_rows", {"n": 0})["n"] == probes
    emit = phases.get("join_emit", {"n": 0, "items": 0})
    if form == "rows":
        assert emit["n"] >= probes           # gathered before the projection
    elif rows == 0:
        assert emit["n"] == 0                # nothing is gathered for no row
    elif (kind, density) == ("inner", "sparse"):
        # every batch came compacted at the bucket of its own count
        assert 0 < emit["n"] <= probes
        assert emit["items"] <= 2 * rows + 128 * emit["n"]
    elif density == "dense":
        # at the probe's capacity: one occurrence a batch, never compacted
        assert emit["n"] == probes
