"""The join cell `sf1_q3` off the chip (CPU, SF 0.01, seeded data): its
rehearsal is judged correct traced and untraced, its float32 control is not,
a run of the harness with the join path broken underneath comes out not
correct - once for each thing a join cell can get wrong that a scan cell
cannot - and the four readers of the join's phases hold a hand's numbers."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import data as bdata, run as brun, traffic  # noqa: E402
from benchmark.control import control_verdict  # noqa: E402
from benchmark.refutil import day  # noqa: E402
from benchmark_shared import addition, declared  # noqa: E402,F401
from presto_tpu.obs import trace  # noqa: E402

CELL, SF = "sf1_q3", 0.01
JOIN_METRICS = ("join_build_s", "join_call_s", "join_sync_s",
                "join_probe_batches_per_stmt")


@pytest.fixture(scope="module")
def device():
    import jax

    return jax.devices()[0]


@pytest.fixture(autouse=True)
def short_warmup(monkeypatch):
    """The mix warms up for seconds; a test run need not."""
    load_mix = traffic.load_mix
    monkeypatch.setattr(
        traffic, "load_mix", lambda name: {**load_mix(name), "warmup_seconds": 0.0})


@pytest.fixture
def fresh_programs():
    """A fault planted inside a traced function reaches the run only if the
    program is traced again: drop the process's shared programs before the
    run, and after it so that no later test inherits a broken one."""
    from presto_tpu.exec import programs

    programs.reset(counters_only=False)
    yield
    programs.reset(counters_only=False)


def judged_wrong(res):
    assert res["correct"] is False and res["failed"] == 0
    assert res["compared"]["wrong_statements"]["value"] == res["attempted"] > 0


# -- the sound cell, and its control

@pytest.mark.parametrize("traced", [False, True])
def test_rehearsal_is_judged_correct(device, traced):
    res = brun.run_cell(CELL, 2147484011, 1.0, traced, device, sf_override=SF)
    assert res["correct"] is True and res["failed"] == 0 < res["attempted"]
    assert res["compared"] == {"wrong_statements": {"value": 0, "limit": 0}}
    got = res["metrics"]
    if not traced:
        assert set(got) == {"statement_s", "rows_per_s", "setup_s"}
        return
    for name in JOIN_METRICS:
        assert got[name]["value"] > 0, name
    # one batch of orders and one of lineitem probe at this scale
    assert got["join_probe_batches_per_stmt"]["value"] == 2
    assert got["join_sync_s"]["value"] <= got["host_sync_s"]["value"]
    assert got["join_call_s"]["value"] <= got["program_call_s"]["value"]
    # the metrics that every cell reports are read here as they stand: a
    # number (no window is stacked behind a join: 0), or left out off the chip
    for there in ("program_calls_per_stmt", "task_unattributed_pct",
                  "task_wait_s", "scan_read_s", "window_stack_s", "schedule_s",
                  "trace_collect_s", "trace_spans_per_stmt", "plan_s",
                  "first_response_s", "polls_per_stmt", "programs_minted",
                  "compiles_in_window", "statement_max_s"):
        assert there in got, there
    assert got["task_unattributed_pct"]["value"] <= 50
    assert got["compiles_in_window"]["value"] == 0
    for gone in ("statement_roofline", "device_idle_pct",
                 "device_launches_per_stmt", "first_text_s", "statement_p95_s",
                 "first_quarter_slowdown_pct"):
        assert gone not in got, gone


@pytest.mark.parametrize("seed", [11, 2147484002, 3000000019])
def test_float32_control_is_judged_not_correct(seed):
    v = control_verdict(CELL, seed, sf=SF)
    assert v["correct"] is False
    assert v["compared"]["wrong_statements"]["value"] == 1


def test_exact_reference_in_its_own_place_is_correct():
    assert control_verdict(CELL, 11, sf=SF, arith="exact")["correct"] is True


# -- faults, each under a whole run of the harness

def test_fault_a_probe_that_drops_matches(device, monkeypatch, fresh_programs):
    from presto_tpu.exec import runtime

    unique, expand = runtime.probe_unique, runtime.probe_expand

    def unique_dropping(*a, **kw):
        idx, matched = unique(*a, **kw)
        return idx, matched & (idx % 2 == 0)

    def expand_dropping(*a, **kw):
        probe_row, build_idx, live = expand(*a, **kw)
        return probe_row, build_idx, live & (build_idx % 2 == 0)

    monkeypatch.setattr(runtime, "probe_unique", unique_dropping)
    monkeypatch.setattr(runtime, "probe_expand", expand_dropping)
    judged_wrong(brun.run_cell(CELL, 21, 1.0, False, device, sf_override=SF))


def collide(monkeypatch):
    """Customers 2k and 2k+1 share a hash: every range the unique probe
    finds is two build rows wide."""
    from presto_tpu.exec import runtime
    from presto_tpu.ops import join as opsjoin

    monkeypatch.setattr(
        opsjoin, "join_hash",
        lambda batch, key_names: batch.column(key_names[0]).values.astype("int64") // 2)
    # jax keeps its trace of `build_side` by the function's identity: under
    # another identity the build is traced again, with the planted hash
    monkeypatch.setattr(
        runtime, "build_side",
        lambda batch, key_names: opsjoin.build_side(batch, key_names))


def test_a_planted_collision_is_told_apart_by_the_keys(device, monkeypatch,
                                                       fresh_programs):
    collide(monkeypatch)
    res = brun.run_cell(CELL, 22, 1.0, False, device, sf_override=SF)
    assert res["correct"] is True and res["attempted"] > 0


def test_fault_a_probe_that_matches_on_hash_alone(device, monkeypatch,
                                                  fresh_programs):
    import jax.numpy as jnp

    from presto_tpu.ops import join as opsjoin

    collide(monkeypatch)
    monkeypatch.setattr(
        opsjoin, "_keys_equal",
        lambda table, build_idx, *a: jnp.ones(build_idx.shape, dtype=bool))
    judged_wrong(brun.run_cell(CELL, 22, 1.0, False, device, sf_override=SF))


def test_fault_a_build_that_keeps_filtered_out_rows(device, monkeypatch):
    """The program's customer table says BUILDING in every row, the
    reference's does not: the first join's build side holds the customers
    its filter should have dropped."""
    install = bdata.install

    def unfiltered(catalog, sf, seed, data):
        cust = dict(data["customer"])
        cust["c_mktsegment"] = np.full_like(cust["c_mktsegment"], "BUILDING")
        install(catalog, sf, seed, {**data, "customer": cust})

    monkeypatch.setattr(bdata, "install", unfiltered)
    judged_wrong(brun.run_cell(CELL, 23, 1.0, False, device, sf_override=SF))


def tie_the_top_two(monkeypatch):
    """The two orders of highest revenue get the same revenue, so that
    `o_orderdate` alone decides which comes first."""
    generate = bdata.generate
    params = traffic.load_query("q3")["params"]["fixed"]

    def tied(sf, seed, tables):
        data = generate(sf, seed, tables)
        top = brun.load_reference("q3")(data, params)
        li, cutoff = data["lineitem"], day(params["date"])
        short, long_ = sorted(
            (np.flatnonzero((li["l_orderkey"] == row[0])
                            & (li["l_shipdate"] > cutoff)) for row in top[:2]),
            key=len)
        for col in ("l_extendedprice", "l_discount"):
            li[col][long_[:len(short)]] = li[col][short]
        li["l_extendedprice"][long_[len(short):]] = 0
        again = brun.load_reference("q3")(data, params)
        assert again[0][1] == again[1][1] and again[0][2] < again[1][2]
        return data

    monkeypatch.setattr(bdata, "generate", tied)


def test_a_tie_in_revenue_is_broken_by_the_order_date(device, monkeypatch):
    tie_the_top_two(monkeypatch)
    res = brun.run_cell(CELL, 24, 1.0, False, device, sf_override=SF)
    assert res["correct"] is True and res["attempted"] > 0


def test_fault_a_topn_that_breaks_the_tie_the_other_way(device, monkeypatch,
                                                        fresh_programs):
    from presto_tpu.exec import runtime

    sort_keys = runtime._sort_keys

    def last_key_reversed(node, b):
        keys = sort_keys(node, b)
        if len(node.keys) > 1:
            keys[-1] = keys[-1]._replace(descending=not keys[-1].descending)
        return keys

    tie_the_top_two(monkeypatch)
    monkeypatch.setattr(runtime, "_sort_keys", last_key_reversed)
    judged_wrong(brun.run_cell(CELL, 24, 1.0, False, device, sf_override=SF))


# -- the four readers, against hand-made summaries

def agg(n, busy, self_s=None, **more):
    return {"n": n, "busy_s": busy, "self_s": busy if self_s is None else self_s,
            "max_s": busy / n, **more}


def summary(query_id, k, joins=True):
    """One statement's summary, every number stretched by `k`."""
    task = {"exchange_wait": agg(3 * k, 0.8 * k, wait=True),
            "program_call:Aggregate": agg(5 * k, 0.2 * k),
            "host_sync:sink_serialize": agg(2 * k, 0.01 * k)}
    other = {"program_call:Project": agg(k, 0.002 * k)}
    if joins:
        task.update({
            "join_build": agg(2 * k, 1.5 * k, 0.1 * k, items=3 * k),
            "join_probe": agg(58 * k, 0.4 * k, 0.05 * k, items=58 * k),
            "program_call:HashJoin": agg(152 * k, 0.3 * k),
            "host_sync:join_build_rows": agg(2 * k, 0.02 * k),
            "host_sync:join_total": agg(46 * k, 6.0 * k),
            "host_sync:join_overflow": agg(46 * k, 0.03 * k),
            "host_sync:join_selectivity": agg(2 * k, 0.5 * k)})
        # a join's programs may be called from another thread too
        other["program_call:MultiwayJoin"] = agg(4 * k, 0.05 * k)
    return {"queryId": query_id, "wall_s": 9.0 * k, "tasks": 5,
            "task_wall_s": 12.0 * k, "exchange_wait_s": 8.0 * k,
            "spans": 70 * k, "dropped": 0,
            "phases": {"task": task, "fragment-window-producer": other}}


# the mean of the statements scaled 1 and 3 is the statement scaled 2
EXPECTED = {
    "join_build_s": 2 * 1.5,
    "join_call_s": 2 * (0.3 + 0.05),
    "join_sync_s": 2 * (0.02 + 6.0 + 0.03 + 0.5),
    "join_probe_batches_per_stmt": 2 * 58,
}


def a_run(ids_and_starts, profiler_stopped_at):
    return {"traced": {"t1": profiler_stopped_at},
            "completed": [{"query_id": q, "t0": t0, "t1": t0 + 1.0}
                          for q, t0 in ids_and_starts]}


@pytest.fixture
def planted(monkeypatch):
    docs = [summary("warmup", 7), summary("under_profiler", 5),
            summary("a", 1), summary("b", 3),
            summary("scan_only", 2, joins=False)]
    monkeypatch.setattr(trace, "summaries", lambda: list(docs))


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_holds_the_planted_number(name, planted):
    read = brun.load_reader("layer_metrics", name)
    run = a_run([("under_profiler", 10.0), ("a", 20.0), ("b", 30.0)], 15.0)
    assert read(run) == pytest.approx(EXPECTED[name], rel=1e-9)
    assert read(a_run([("a", 20.0), ("b", 30.0)], None)) == \
        pytest.approx(EXPECTED[name], rel=1e-9)
    # a statement with no join in it adds nothing to the mean, not a 0
    run = a_run([("a", 20.0), ("scan_only", 25.0), ("b", 30.0)], None)
    assert read(run) == pytest.approx(EXPECTED[name], rel=1e-9)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_has_nothing_to_read_without_the_phases(name, planted, monkeypatch):
    read = brun.load_reader("layer_metrics", name)
    # a program whose joins have no phases (the parent commit): None, never 0
    assert read(a_run([("scan_only", 20.0)], None)) is None
    assert read(a_run([("x", 20.0)], None)) is None
    assert read(a_run([], None)) is None
    monkeypatch.delattr(trace, "summaries")
    assert read(a_run([("a", 20.0), ("b", 30.0)], None)) is None


def test_the_join_metrics_are_declared_for_the_join_cell_alone(declared):
    bench, root = declared
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in JOIN_METRICS:
        m = by_name[name]
        assert CELL in m["workloads"] and m["moves"] == "statement_s"
        assert m["layer"] == "scheduler + operators" and m["better"] == "lower"
        assert os.path.isfile(
            os.path.join(root, "benchmark", "layer_metrics", name + ".py"))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("tpch_sf1_join", "q3_repeat", 1)
