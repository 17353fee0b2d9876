"""The join path's engine phases (`exec/runtime.py`: `_join_with_spill`,
`_JoinProber`): TPC-H Q3 at SF 0.01 through the served path leaves
`join_build`, `join_probe` and the `host_sync:join_*` sites in the
statement's summary, as often as the plan has joins and probe batches; the
general path alone reads `join_total` once a batch, so `join_probe`'s `n` less
`host_sync:join_total`'s is the batches that took the single-match path
(every batch of Q3, none of a join that fans out); with `tracing=false` the
answer is the same and nothing is recorded; a statement without a join
records none of them; `join_emit` is one occurrence a batch whose output was
gathered once its count was read, `items` its lanes. And the one thing the tracer learned for it: an
occurrence that a generator leaves before a `yield` and enters again after
counts once. A batch on the general path also leaves `join_expand` (`items` =
its rows), `join_expand_lanes` (the lanes its chunks gathered: a prober's first
batch expands at `out_cap`, a later one's chunk 0 at the bucket of twice the
previous batch's total, `join_expand_sized` where that is below `out_cap`)
and, where a key's matches pass the counting scan of 8, `join_fanout_overflow`;
the single-match path leaves none. Planted tables show the sized chunks lose
no row: a skewed batch behind a sparse one, its keys past the hash engine's
match width, inner and LEFT. A sorted build leaves `join_build_table`
(`items` = its live rows), and a LEFT join one `join_outer` a batch it hands
on whole (`items` = the lanes). TPC-H Q9 through the same path, its two-key join general in the
plan, answers as `benchmark/reference/q9.py` does."""

import json
import math
import os
import time

import numpy as np
import pandas as pd
import pytest

from presto_tpu import client
from presto_tpu.batch import round_up_capacity
from presto_tpu.obs import trace as obs_trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BATCH = 8192
EXPAND_PHASES = ("join_expand", "join_expand_lanes", "join_expand_sized",
                 "join_fanout_overflow")
JOIN_PHASES = ("join_build", "join_probe", "join_search", "join_verify",
               "join_emit", "join_build_table", "join_outer",
               *EXPAND_PHASES, "host_sync:join_build_rows",
               "host_sync:join_total", "host_sync:join_overflow",
               "host_sync:join_output_rows", "host_sync:join_selectivity")


# a join that fans out: the build (`lineitem` of two months' shipments, the
# smaller side once filtered) holds an order's key several times, so every
# batch of orders probes it on the general path
FAN_OUT = ("select count(*) as n, sum(l_quantity) as q from orders "
           "join lineitem on o_orderkey = l_orderkey "
           "where l_shipdate < date '1992-03-01'")


def query_text(qid):
    with open(os.path.join(ROOT, "benchmark", "queries", qid + ".json")) as f:
        params = json.load(f)["params"]["fixed"]
    with open(os.path.join(ROOT, "benchmark", "queries", qid + ".sql")) as f:
        return f.read().format(**params).strip()


@pytest.fixture(scope="module")
def url():
    from presto_tpu.server.__main__ import build_catalog
    from presto_tpu.server.coordinator import DistributedRunner

    with DistributedRunner(build_catalog(["tpch:sf=0.01"]), n_workers=1) as dr:
        yield dr.coordinator.url


def statement(url, sql, **properties):
    """(rows, the statement's summary or None) over `/v1/statement`."""
    session = client.ClientSession(user="test")
    session.properties.update(batch_rows=str(BATCH), **properties)
    before = {d["queryId"] for d in obs_trace.summaries()}
    _, rows = client.execute(url, sql, session)
    new = [d for d in obs_trace.summaries() if d["queryId"] not in before]
    assert len(new) <= 1
    return rows, (new[0] if new else None)


def all_phases(summary):
    return {name: agg for by_name in summary["phases"].values()
            for name, agg in by_name.items()}


def expand_chunks(totals, out_cap):
    """The lanes of each chunk that one prober's general batches gather, in
    order, from their totals: the first batch's chunk 0 is `out_cap`, a later
    batch's the bucket of twice the previous total, at most `out_cap`; the
    chunks after it cover the rest of the batch's total, each at the bucket
    of what remains, at most `out_cap`."""
    chunks, prev = [], None
    for tot in totals:
        first = out_cap if prev is None else min(
            out_cap, round_up_capacity(2 * prev))
        batch = [first]
        while sum(batch) < tot:
            batch.append(min(out_cap, round_up_capacity(tot - sum(batch))))
        chunks.append(batch)
        prev = tot
    return chunks


def per_batch(keys, matches, batches):
    """Each probe batch's total: the matches (a count a key) of the keys
    each of `batches` even splits of the probe column holds, as the memory
    connector splits a table."""
    n = len(keys)
    return [int(matches.reindex(keys[n * i // batches:n * (i + 1) // batches])
                .fillna(0).sum()) for i in range(batches)]


def unique_batches(task):
    """Probe batches on the single-match path: the general path reads
    `total` exactly once a batch, the single-match path never."""
    general = task.get("host_sync:join_total", {"n": 0})["n"]
    return task["join_probe"]["n"] - general


def test_q3_records_a_phase_for_every_join_and_every_probe_batch(url):
    (orders,), = statement(url, "select count(*) from orders")[0]
    (lineitem,), = statement(url, "select count(*) from lineitem")[0]
    batches = math.ceil(orders / BATCH) + math.ceil(lineitem / BATCH)
    statement(url, query_text("q3"))              # compile
    rows, first = statement(url, query_text("q3"))
    rows_again, second = statement(url, query_text("q3"))
    assert rows == rows_again and len(rows) == 10
    task = first["phases"]["task"]
    # two joins, both with a unique build: customer built and probed by
    # orders, that join's output (an order survives it at most once) built
    # and probed by lineitem
    assert task["join_build"]["n"] == 2
    assert task["join_build"]["items"] >= 2      # build batches drained
    assert task["host_sync:join_build_rows"]["n"] == 2
    assert task["host_sync:join_selectivity"]["n"] == 2
    # one occurrence a probe batch, one chunk each
    assert task["join_probe"]["n"] == task["join_probe"]["items"] == batches
    # every batch took the single-match path: no `total`, no overflow count
    assert unique_batches(task) == batches
    assert "host_sync:join_total" not in task
    assert "host_sync:join_overflow" not in task
    # and each join's output is counted once a batch where it is merged
    assert task["host_sync:join_output_rows"]["n"] == batches
    # and gathered after it, at most once a batch (never for no row), at
    # lanes that repeat exactly
    emit, emit_again = (d["phases"]["task"]["join_emit"] for d in (first, second))
    assert 0 < emit["n"] <= batches
    assert (emit["n"], emit["items"]) == (emit_again["n"], emit_again["items"])
    # the program calls and the reads are the phases' children: what is
    # left to `join_build` and `join_probe` is the host's own share
    for name in ("join_build", "join_probe"):
        assert 0 <= task[name]["self_s"] < task[name]["busy_s"]
    named = sum(agg["self_s"] for agg in task.values())
    assert 0 < named <= first["task_wall_s"] * 1.01
    # the single-match path expands nothing
    assert not set(EXPAND_PHASES) & set(all_phases(first))
    # the counts repeat exactly
    counts = {k: (v["n"], v.get("items")) for k, v in task.items()}
    assert counts == {k: (v["n"], v.get("items"))
                      for k, v in second["phases"]["task"].items()}


def test_a_builds_search_steps_ride_with_its_row_count(url):
    """`join_search`: one occurrence a build beside `join_build_rows`, its
    `items` the table's `search_steps` (a few halvings inside a bucket, not
    log2 of the build's capacity), and the process counter alike."""
    from presto_tpu.scan import metrics

    before = metrics.snapshot()["join_search_steps"]
    _, summary = statement(url, query_text("q3"))
    task = summary["phases"]["task"]
    search = task["join_search"]
    assert search["n"] == task["host_sync:join_build_rows"]["n"] == 2
    # 1,500 customers and their orders in builds of 2^11 lanes and more
    assert 2 <= search["items"] <= 2 * 6
    assert metrics.snapshot()["join_search_steps"] - before == search["items"]
    assert search["busy_s"] < 0.01 * task["join_build"]["busy_s"]


def test_a_builds_verify_width_rides_with_its_row_count(url):
    """`join_verify`: one occurrence a build beside `join_build_rows`, its
    `items` the table's `verify_width` - the lanes a unique probe may verify
    past its bucket search, one in each of Q3's builds of distinct keys -
    and the process counter alike; with tracing off the answer is the same
    and no phase is recorded."""
    from presto_tpu.scan import metrics

    before = metrics.snapshot()["join_verify_width"]
    rows, summary = statement(url, query_text("q3"))
    task = summary["phases"]["task"]
    verify = task["join_verify"]
    assert verify["n"] == task["host_sync:join_build_rows"]["n"] == 2
    assert verify["items"] == 2
    assert metrics.snapshot()["join_verify_width"] - before == verify["items"]
    assert verify["busy_s"] < 0.01 * task["join_build"]["busy_s"]
    rows_off, none = statement(url, query_text("q3"), tracing="false")
    assert rows_off == rows and none is None


def test_tracing_off_gives_the_same_answer_and_records_nothing(url):
    rows, summary = statement(url, query_text("q3"))
    assert summary is not None
    rows_off, none = statement(url, query_text("q3"), tracing="false")
    assert rows_off == rows
    assert none is None


def test_a_join_that_fans_out_counts_no_unique_batch(url):
    (orders,), = statement(url, "select count(*) from orders")[0]
    (n, _), = statement(url, "select count(*), sum(l_quantity) from lineitem "
                             "where l_shipdate < date '1992-03-01'")[0]
    rows, first = statement(url, FAN_OUT)
    rows_again, second = statement(url, FAN_OUT)
    assert rows == rows_again and rows[0][0] == n > 0
    task = first["phases"]["task"]
    general = math.ceil(orders / BATCH)
    assert task["join_probe"]["n"] == task["join_probe"]["items"] == general
    # the general path reads `total` and the overflow count once a batch
    assert task["host_sync:join_total"]["n"] == general
    assert task["host_sync:join_overflow"]["n"] == general
    assert unique_batches(task) == 0
    counts = {k: (v["n"], v.get("items")) for k, v in task.items()}
    assert counts == {k: (v["n"], v.get("items"))
                      for k, v in second["phases"]["task"].items()}


def test_a_small_join_out_capacity_adds_chunks_not_occurrences(url):
    rows, whole = statement(url, FAN_OUT)
    rows_cut, cut = statement(url, FAN_OUT, join_out_capacity="16")
    assert rows_cut == rows
    probe, probe_cut = (d["phases"]["task"]["join_probe"] for d in (whole, cut))
    # the general path's batches now yield their matches 16 rows at a time:
    # the phase is left before each chunk and entered again after it
    assert probe_cut["n"] == probe["n"] == probe["items"]
    assert probe_cut["items"] > probe_cut["n"]
    # the single-match path has one chunk a batch whatever the capacity
    _, q3 = statement(url, query_text("q3"), join_out_capacity="16")
    unique = q3["phases"]["task"]
    assert unique["join_probe"]["items"] == unique["join_probe"]["n"] \
        == unique_batches(unique)


# a key with more matches than the counting pass scans (8): the build
# (`customer`, the side a LEFT JOIN supplies, as written) holds some sixty
# customers a nation; every nation has customers, so no row is NULL-extended
OVERFLOWS = ("select count(*) as n from nation left join customer "
             "on n_nationkey = c_nationkey")


@pytest.mark.parametrize("out_capacity", [None, "16"])
def test_a_general_batch_records_what_it_expanded_to(url, out_capacity):
    from presto_tpu.scan import metrics

    more = {} if out_capacity is None else {"join_out_capacity": out_capacity}
    before = metrics.snapshot()["join_expand_rows"]
    rows, summary = statement(url, FAN_OUT, **more)
    task = summary["phases"]["task"]
    general = task["host_sync:join_total"]["n"]
    expand, lanes = task["join_expand"], task["join_expand_lanes"]
    # one occurrence a general batch, with no time of its own
    assert expand["n"] == lanes["n"] == general == task["join_probe"]["n"]
    assert expand["busy_s"] + lanes["busy_s"] < 0.01 * task["join_probe"]["busy_s"]
    # `items`: the rows the batches' chunks hold live - every one reaches
    # the count(*) - and what the chunks gathered, rows or not
    assert expand["items"] == rows[0][0] > 0
    assert metrics.snapshot()["join_expand_rows"] - before == expand["items"]
    # the lanes of every chunk gathered: the first batch's at `out_cap`,
    # the next one's from the first's total (orders' keys, a batch's split,
    # against the two months' lines of each order)
    from presto_tpu.catalog.tpch import TpchGenerator

    orders, lines = TpchGenerator(0.01).orders_and_lineitem()
    shipped = lines["l_shipdate"] < np.datetime64("1992-03-01", "D").astype(int)
    totals = per_batch(orders["o_orderkey"],
                       pd.Series(lines["l_orderkey"][shipped]).value_counts(),
                       general)
    assert sum(totals) == expand["items"]
    chunks = expand_chunks(totals, int(out_capacity or BATCH))
    assert lanes["items"] == sum(map(sum, chunks))
    assert task["join_probe"]["items"] == sum(map(len, chunks))
    assert expand["items"] <= lanes["items"]
    # an order has seven lines at the most: no key passes the scan of 8
    assert "join_fanout_overflow" not in task


def test_a_key_with_more_matches_than_the_scan_records_an_overflow(url):
    from presto_tpu.scan import metrics

    (customers,), = statement(url, "select count(*) from customer")[0]
    before = metrics.snapshot()
    rows, summary = statement(url, OVERFLOWS, breaker_engine="sort")
    assert rows == [[customers]]
    task = summary["phases"]["task"]
    assert task["host_sync:join_total"]["n"] == task["join_expand"]["n"] == 1
    assert task["join_expand"]["items"] == customers
    # each of the 25 nations' candidates passed the scan
    overflow = task["join_fanout_overflow"]
    assert (overflow["n"], overflow["items"]) == (1, 25)
    after = metrics.snapshot()
    assert after["join_fanout_overflow_rows"] - before["join_fanout_overflow_rows"] == 25
    assert after["join_expand_rows"] - before["join_expand_rows"] == customers
    # with tracing off the answer is the same and nothing is recorded
    rows_off, none = statement(url, OVERFLOWS, breaker_engine="sort", tracing="false")
    assert rows_off == rows and none is None


def test_a_sorted_builds_live_rows_ride_as_join_build_table(url):
    """`join_build_table`: one occurrence a sorted build beside
    `join_build_rows`, no time of its own, its `items` the build's live rows
    - Q3's BUILDING customers, then their orders before the date - and the
    process counter alike; with tracing off the answer is the same and no
    phase is recorded."""
    from presto_tpu.scan import metrics

    (customers,), = statement(url, "select count(*) from customer "
                                   "where c_mktsegment = 'BUILDING'")[0]
    (orders,), = statement(url, "select count(*) from orders join customer "
                                "on o_custkey = c_custkey where c_mktsegment = "
                                "'BUILDING' and o_orderdate < date '1995-03-15'")[0]
    before = metrics.snapshot()["join_build_rows"]
    rows, summary = statement(url, query_text("q3"))
    task = summary["phases"]["task"]
    table = task["join_build_table"]
    assert table["n"] == task["host_sync:join_build_rows"]["n"] == 2
    assert table["items"] == customers + orders > 0
    assert metrics.snapshot()["join_build_rows"] - before == table["items"]
    assert table["busy_s"] < 0.01 * task["join_build"]["busy_s"]
    rows_off, none = statement(url, query_text("q3"), tracing="false")
    assert rows_off == rows and none is None


# LEFT JOINs, each batch of orders handed on whole: a unique build of
# BUILDING customers (the ON residual filters the build), and a build of two
# months' lineitems that fans out (the general path)
OUTER = {
    "single_match": ("select count(*) as n, count(c_custkey) as m from orders "
                     "left join customer on o_custkey = c_custkey "
                     "and c_mktsegment = 'BUILDING'",
                     "select count(*) from orders join customer on o_custkey "
                     "= c_custkey where c_mktsegment = 'BUILDING'"),
    "general": ("select count(*) as n, count(l_orderkey) as m from orders "
                "left join lineitem on o_orderkey = l_orderkey "
                "and l_shipdate < date '1992-03-01'",
                "select count(*) from lineitem "
                "where l_shipdate < date '1992-03-01'"),
}


@pytest.mark.parametrize("path", sorted(OUTER))
def test_a_left_join_records_each_batch_it_hands_on_whole(url, path):
    """`join_outer`: one occurrence a probe batch, no time of its own, its
    `items` the lanes the batch was gathered at - a single-match probe's
    dense emit (`join_emit` of the same lanes), the general path's
    NULL-extended rows (a batch's capacity); none with tracing off."""
    sql, matched = OUTER[path]
    (orders,), = statement(url, "select count(*) from orders")[0]
    (pairs,), = statement(url, matched)[0]
    rows, summary = statement(url, sql)
    task = summary["phases"]["task"]
    outer = task["join_outer"]
    batches = math.ceil(orders / BATCH)
    assert outer["n"] == task["join_probe"]["n"] == batches
    assert outer["busy_s"] < 0.01 * task["join_probe"]["busy_s"]
    assert orders <= outer["items"] <= batches * BATCH
    if path == "single_match":
        assert "host_sync:join_total" not in task
        assert outer["items"] == task["join_emit"]["items"]
        assert rows == [[orders, pairs]]
    else:
        assert task["host_sync:join_total"]["n"] == batches
        # every order without such a line once, every line with its order
        (with_lines,), = statement(url, "select count(distinct l_orderkey) "
                                        "from lineitem where l_shipdate < "
                                        "date '1992-03-01'")[0]
        assert rows == [[orders - with_lines + pairs, pairs]]
    rows_off, none = statement(url, sql, tracing="false")
    assert rows_off == rows and none is None


def test_an_inner_join_records_no_outer_batch(url):
    _, summary = statement(url, FAN_OUT)
    _, q3 = statement(url, query_text("q3"))
    for doc in (summary, q3):
        assert "join_outer" not in all_phases(doc)


# planted tables for the sized chunks: `b` builds (500 rows; keys 0-99 three
# times, hot keys 1000-1009 twenty times, past the hash engine's match width
# of 8), `p` probes in four batches of BATCH rows that mostly miss: 60 rows of
# the first match, 30 of the second, 50 of the third match hot keys and 100
# others (1,300 rows, far past the 256 lanes its chunk 0 gets from the
# second's 90), 50 of the fourth
PLANTED_HITS = [(60, 0), (30, 0), (100, 50), (50, 0)]   # (plain, hot) a batch


def planted_tables():
    bk = np.concatenate([np.repeat(np.arange(100), 3),
                         np.repeat(np.arange(1000, 1010), 20)])
    pk = -1 - np.arange(4 * BATCH)
    for i, (plain, hot) in enumerate(PLANTED_HITS):
        at = i * BATCH + 7 * np.arange(plain + hot)
        pk[at] = np.concatenate([np.arange(plain) % 100,
                                 1000 + np.arange(hot) % 10])
    return (pd.DataFrame({"bk": bk, "bv": np.arange(len(bk)) * 3 + 1}),
            pd.DataFrame({"pk": pk, "id": np.arange(len(pk))}))


PLANTED_INNER = ("select count(*) as n, sum(bv) as s, sum(id * bv) as w "
                 "from p join b on pk = bk")
PLANTED_LEFT = ("select count(*) as n, count(bv) as m, sum(bv) as s, "
                "sum(case when bv is null then id else 0 end) as missed "
                "from p left join b on pk = bk")


@pytest.fixture(scope="module")
def planted():
    """(url, b, p): a one-worker cluster over the planted tables."""
    from presto_tpu.catalog.memory import MemoryConnector
    from presto_tpu.connector import Catalog
    from presto_tpu.server.coordinator import DistributedRunner

    b, p = planted_tables()
    conn = MemoryConnector()
    conn.add_table("b", b)
    conn.add_table("p", p)
    catalog = Catalog()
    catalog.register("m", conn, default=True)
    with DistributedRunner(catalog, n_workers=1) as dr:
        yield dr.coordinator.url, b, p


def planted_totals(b, p):
    return per_batch(p["pk"].to_numpy(), b["bk"].value_counts(), 4)


@pytest.mark.parametrize("engine", ["sort", "hash"])
def test_a_general_join_over_several_batches_answers_as_at_full_chunks(
        planted, engine):
    """Chunk 0 sized by the previous batch's total: the same rows as with
    `join_out_capacity` at the probe's capacity, as with every chunk at a
    cap of 128 lanes (the least bucket, so no chunk is sized), and as
    pandas."""
    url, b, p = planted
    rows, summary = statement(url, PLANTED_INNER, breaker_engine=engine)
    rows_cap, _ = statement(url, PLANTED_INNER, breaker_engine=engine,
                            join_out_capacity=str(BATCH))
    rows_least, least = statement(url, PLANTED_INNER, breaker_engine=engine,
                                  join_out_capacity="128")
    m = p.merge(b, left_on="pk", right_on="bk")
    assert rows == rows_cap == rows_least == [
        [len(m), int(m.bv.sum()), int((m.id * m.bv).sum())]]
    task = summary["phases"]["task"]
    assert task["host_sync:join_total"]["n"] == task["join_expand"]["n"] == 4
    assert task["join_expand"]["items"] == len(m) == sum(planted_totals(b, p))
    assert task["join_expand_sized"]["n"] == 3
    assert "join_expand_sized" not in least["phases"]["task"]


@pytest.mark.parametrize("engine", ["sort", "hash"])
def test_a_batch_past_its_sized_chunk_takes_more_chunks(planted, engine):
    """The third batch expands to 1,300 rows from a chunk 0 of 256 lanes
    (twice the second's 90 rows): the chunks after it cover the rest, each
    at the bucket of what remains. Every lane is accounted for
    and every row reaches the count."""
    url, b, p = planted
    totals = planted_totals(b, p)
    assert totals == [180, 90, 1300, 150]
    chunks = expand_chunks(totals, BATCH)
    assert chunks == [[BATCH], [512], [256, 2048], [4096]]
    rows, summary = statement(url, PLANTED_INNER, breaker_engine=engine)
    assert rows[0][0] == sum(totals)
    task = summary["phases"]["task"]
    assert task["join_probe"]["items"] == sum(map(len, chunks))
    assert task["join_expand_lanes"]["items"] == sum(map(sum, chunks))


def test_a_left_join_on_the_general_path_null_extends_the_same_rows(planted):
    """Every probe row without a match once with a NULL build side, every
    match once, whether chunk 0 was sized or at the probe's capacity."""
    url, b, p = planted
    m = p.merge(b, left_on="pk", right_on="bk", how="left")
    missed = m.bv.isna()
    want = [[len(m), int((~missed).sum()), int(m.bv.sum()),
             int(m.id[missed].sum())]]
    rows, summary = statement(url, PLANTED_LEFT, breaker_engine="sort")
    rows_cap, _ = statement(url, PLANTED_LEFT, breaker_engine="sort",
                            join_out_capacity=str(BATCH))
    assert rows == rows_cap == want
    task = summary["phases"]["task"]
    assert task["join_outer"]["n"] == task["join_expand"]["n"] == 4
    assert task["join_expand_sized"]["n"] == 3


def test_join_expand_sized_counts_the_batches_after_the_first(planted):
    """`join_expand_sized`: one occurrence a general batch whose chunk 0 was
    below `out_cap`, every batch of a prober but its first, `items` those
    chunks' lanes, no time of its own, and the process counter alike; with
    tracing off the answer is the same and nothing is recorded."""
    from presto_tpu.scan import metrics

    url, b, p = planted
    chunks = expand_chunks(planted_totals(b, p), BATCH)
    before = metrics.snapshot()["join_expand_sized"]
    rows, summary = statement(url, PLANTED_INNER, breaker_engine="sort")
    task = summary["phases"]["task"]
    sized = task["join_expand_sized"]
    assert sized["n"] == task["host_sync:join_total"]["n"] - 1 == 3
    assert sized["items"] == sum(c[0] for c in chunks[1:]) == 512 + 256 + 4096
    assert sized["busy_s"] < 0.01 * task["join_probe"]["busy_s"]
    assert metrics.snapshot()["join_expand_sized"] - before == sized["items"]
    before = metrics.snapshot()["join_expand_sized"]
    rows_off, none = statement(url, PLANTED_INNER, breaker_engine="sort",
                               tracing="false")
    assert rows_off == rows and none is None
    assert metrics.snapshot()["join_expand_sized"] == before


def test_the_hash_engines_overflow_redo_keeps_a_sized_chunk_whole(planted):
    """The third batch's hot keys hold 20 build rows, past the match matrix's
    8: the hash engine discards its chunk 0 of 256 lanes, widens, and redoes
    it at 256 lanes before the chunks after it; no row is lost or doubled."""
    url, b, p = planted
    rows, summary = statement(url, PLANTED_INNER, breaker_engine="hash")
    m = p.merge(b, left_on="pk", right_on="bk")
    assert rows == [[len(m), int(m.bv.sum()), int((m.id * m.bv).sum())]]
    task = summary["phases"]["task"]
    # the hot keys' 50 probe rows, in the one batch
    assert (task["join_fanout_overflow"]["n"],
            task["join_fanout_overflow"]["items"]) == (1, 50)
    assert task["join_expand_sized"]["n"] == 3
    # the overflow reads: one a batch, and the widenings' in the third
    assert task["host_sync:join_overflow"]["n"] > task["host_sync:join_total"]["n"]


@pytest.mark.parametrize("seed", [9, 2147483909])
def test_q9_answers_as_its_reference_with_its_two_key_join_general(seed):
    """Its own cluster a seed: the reference reads the arrays the catalog
    serves (`benchmark/data.py`), which the module's catalog did not get."""
    import sys

    from presto_tpu.server.__main__ import build_catalog
    from presto_tpu.server.coordinator import DistributedRunner

    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark import data as bdata, run as brun, traffic

    query = traffic.load_query("q9")
    data = bdata.generate(0.01, seed, sorted(query["tables"]))
    catalog = build_catalog(["tpch:sf=0.01"])
    bdata.install(catalog, 0.01, seed, data)
    sql = query_text("q9")
    with DistributedRunner(catalog, n_workers=1) as dr:
        joins = [line for line in dr.explain_distributed(sql).splitlines()
                 if "HashJoin" in line]
        rows, summary = statement(dr.coordinator.url, sql, breaker_engine="sort")
    # five joins, three of them on a unique build; orders' build fans out,
    # and so does the one on two keys that partsupp probes: lineitem's chain
    assert len(joins) == 5 and sum("unique" in j for j in joins) == 3
    (two_keys,) = [j for j in joins if "ps_partkey" in j]
    assert "['ps_suppkey', 'ps_partkey'] = ['l_suppkey', 'l_partkey']" in two_keys
    assert "unique" not in two_keys
    expected = brun.load_reference("q9")(data, query["params"]["fixed"])
    assert [[n, y, str(p)] for n, y, p in expected] == rows and len(rows) > 100
    task = summary["phases"]["task"]
    # orders' batches and partsupp's take the general path, lineitem's
    # (under part) and the two small joins' single batches the other
    general = sum(math.ceil(len(data[t][k]) / BATCH) for t, k in (
        ("orders", "o_orderkey"), ("partsupp", "ps_partkey")))
    assert task["host_sync:join_total"]["n"] == task["join_expand"]["n"] == general
    assert unique_batches(task) == math.ceil(
        len(data["lineitem"]["l_orderkey"]) / BATCH) + 2
    # orders' second batch expands at the bucket of twice its first's total;
    # partsupp's one batch at the probe's capacity
    green = np.char.find(bdata.strings(data["part"]["p_name"]).astype(str),
                         "green") >= 0
    green_lines = np.isin(data["lineitem"]["l_partkey"],
                          data["part"]["p_partkey"][green])
    totals = per_batch(
        data["orders"]["o_orderkey"],
        pd.Series(data["lineitem"]["l_orderkey"][green_lines]).value_counts(),
        math.ceil(len(data["orders"]["o_orderkey"]) / BATCH))
    chunks = expand_chunks(totals, BATCH) + expand_chunks([sum(totals)], BATCH)
    assert task["join_expand"]["items"] == 2 * sum(totals) > 0
    assert task["join_expand_lanes"]["items"] == sum(map(sum, chunks))
    assert task["join_expand"]["items"] < task["join_expand_lanes"]["items"]


@pytest.mark.parametrize("qid", ["q6", "q1"])
def test_a_statement_without_a_join_records_no_join_phase(url, qid):
    _, summary = statement(url, query_text(qid))
    phases = all_phases(summary)
    assert not set(JOIN_PHASES) & set(phases)
    assert not any(n.startswith("host_sync:join_") for n in phases)


def test_an_occurrence_left_before_a_yield_counts_once():
    tr = obs_trace.Tracer()

    def chunks():
        ph = tr.phase("join_probe")
        for _ in range(3):
            with ph:
                time.sleep(0.002)
                ph.items = 1
            yield                     # the consumer's time is not the probe's

    with tr.span("task", "task"):
        for _ in chunks():
            time.sleep(0.1)
        with tr.phase("join_build") as build:
            build.items = 5               # known only at the end
    (by_name,) = obs_trace.phases_by_role(tr.spans()).values()
    probe = by_name["join_probe"]
    assert probe["n"] == 1 and probe["items"] == 3
    assert 0.006 <= probe["busy_s"] < 0.15    # three stretches, not the waits
    assert probe["max_s"] < 0.1
    assert by_name["join_build"]["n"] == 1
    assert by_name["join_build"]["items"] == 5
    # with tracing off the same code talks to the no-op phase
    noop = obs_trace.NOOP.phase("join_probe")
    with noop:
        noop.items = 1
    with noop:
        pass
    assert noop.items == 0
