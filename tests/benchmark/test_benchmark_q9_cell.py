"""The fan-out cell `sf1_q9` off the chip (CPU, seeded data, SF 0.01).

Its declaration: a configuration, a cell and five per-layer metrics appended
to `BENCHMARK.json`, every accepted entry as it was. Its five readers against
hand-made summaries. Its plain reference against a second formulation with
loops and dicts, and the float32 control coming out not correct. And three
whole runs of the harness at SF 0.01, at which `EXPLAIN` plans what it plans
at SF1 - orders' rows and partsupp's each probe a build that fans out, the
second on two keys (`test_the_plan_at_this_scale_is_the_cells`): one sound,
judged correct traced with the five metrics in its line; one whose expand
keeps only the first match of a probe row and one whose probe matches on
`ps_partkey` alone, both judged not correct.
"""

import collections
import hashlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import data as bdata, run as brun, traffic  # noqa: E402
from benchmark.control import control_verdict  # noqa: E402
from benchmark.refutil import date_str, dec  # noqa: E402
from benchmark_shared import a_run, addition, declared  # noqa: E402,F401
from presto_tpu.obs import trace  # noqa: E402

CELL, CONFIG, MIX, SF = "sf1_q9", "tpch_sf1_q9", "q9_repeat", 0.01
# name -> (unit, source, better), in the order they stand after
# `agg_spill_bytes_per_stmt`
GENERAL_METRICS = collections.OrderedDict([
    ("join_general_batches_per_stmt", ("count", "program_counter", "lower")),
    ("join_general_sync_s", ("s", "program_span", "lower")),
    ("join_expand_rows_per_stmt", ("count", "program_counter", "lower")),
    ("join_expand_fill_pct", ("%", "program_counter", "higher")),
    ("join_fanout_overflow_rows_per_stmt", ("count", "program_counter", "lower")),
])
# the benchmark as PR 33 left it (4 configurations, 5 cells, 36 per-layer
# metrics): sha256 of its canonical JSON. A `benchmark` PR that edits an
# accepted entry states the new digest here.
ACCEPTED = "39e6c8ca08819087e8c80f17b5dee0a974fddb3078a4d919a98a2069b1d25374"


# -- the declaration

def test_the_entries_are_appended_and_the_accepted_ones_untouched(declared):
    bench, root = declared
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("join_general_batches_per_stmt")
    assert names[at - 1] == "agg_spill_bytes_per_stmt" and at == 36
    assert names[at:at + 5] == list(GENERAL_METRICS)
    before = dict(bench, configs=bench["configs"][:4],
                  workloads=bench["workloads"][:5],
                  per_layer=bench["per_layer"][:at])
    doc = json.dumps(before, sort_keys=True, separators=(",", ":"))
    assert hashlib.sha256(doc.encode()).hexdigest() == ACCEPTED
    assert bench["configs"][4]["name"] == CONFIG
    assert bench["workloads"][5]["name"] == CELL


def test_the_metrics_are_the_new_cells_alone(declared):
    bench, root = declared
    for name, (unit, source, better) in GENERAL_METRICS.items():
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert m == {"name": name, "unit": unit, "better": better,
                     "source": source, "layer": "scheduler + operators",
                     "moves": "statement_s", "workloads": [CELL]}
        assert os.path.isfile(os.path.join(
            root, "benchmark", "layer_metrics", name + ".py"))
    for m in bench["per_layer"]:
        if m["name"] not in GENERAL_METRICS:
            assert CELL not in m.get("workloads", [])


def test_the_cell_and_its_configuration(declared):
    bench, root = declared
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    assert entry["reduced"] == config["reduced"] == ["scale_factor", "query_mix"]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert "2.4.9" in entry["source"] and "COLOR = green" in entry["source"]
    assert config["scale_factor"] in (1, 0.3)  # ISSUE 33's rule: `reduced_why`
    assert "L1" in config["reduced_why"] and "L2" in config["reduced_why"]
    assert config["exec_config"] == {} == config["session_properties"]
    assert (config["workers"], config["chips"]) == (1, 1)
    assert config["data_module"] == "data"
    with open(os.path.join(root, "benchmark/configs/tpch_sf1_join.json")) as f:
        assert config["guarantees"] == json.load(f)["guarantees"]
    assumed = " ".join(config["assumed"])
    assert "two of the 93 colours" in assumed and "2.2 %" in assumed
    assert "['ps_suppkey', 'ps_partkey'] = ['l_suppkey', 'l_partkey']" in assumed
    with open(os.path.join(root, "benchmark", "traffic", MIX + ".json")) as f:
        mix = json.load(f)  # the file: `short_warmup` stands in `load_mix`
    assert mix["queries"] == [{"id": "q9", "weight": 1}]
    assert (mix["streams"], mix["loop"], mix["params"]) == (1, "closed", "fixed")
    assert (mix["warmup"], mix["warmup_seconds"], mix["traced_seconds"],
            mix["traced_min_statements"]) == (2, 5.0, 4.0, 3)
    # what `run.py` selects for the cell: every unlisted metric and its own,
    # none of `sf1_q3`'s or `q18_highndv`'s
    mine = [m["name"] for m in bench["per_layer"]
            if "workloads" not in m or CELL in m["workloads"]]
    assert set(GENERAL_METRICS) <= set(mine) and "statement_roofline" in mine
    assert not [n for n in mine if n.startswith(("join_", "agg_"))
                and n not in GENERAL_METRICS]


def test_the_query_file():
    query = traffic.load_query("q9")
    assert query["tables"] == {
        "lineitem": ["l_orderkey", "l_partkey", "l_suppkey", "l_quantity",
                     "l_extendedprice", "l_discount"],
        "orders": ["o_orderkey", "o_orderdate"],
        "partsupp": ["ps_partkey", "ps_suppkey", "ps_supplycost"],
        "part": ["p_partkey", "p_name"],
        "supplier": ["s_suppkey", "s_nationkey"],
        "nation": ["n_nationkey", "n_name"]}
    text = query["template"]
    assert text.count("{") == 1 and "like '%{color}%'" in text
    assert "extract(year from o_orderdate) as o_year" in text
    assert "l_extendedprice * (1 - l_discount) - ps_supplycost * l_quantity" in text
    assert "order by nation, o_year desc" in text
    assert query["params"]["fixed"] == {"color": "green"}
    assert query["limits"] == {"wrong_statements": 0}
    assert [(c["name"], c["type"]) for c in query["result_columns"]] == [
        ("nation", "varchar"), ("o_year", "bigint"),
        ("sum_profit", "decimal(38,4)")]


def test_the_plan_at_this_scale_is_the_cells():
    """What the whole runs below rest on: at SF 0.01 `EXPLAIN` shows the
    join on two keys probed by partsupp against a build that is not
    `unique`, as at SF1 (the configuration's `assumed` holds that plan)."""
    from presto_tpu.exec import ExecConfig, LocalRunner
    from presto_tpu.server.__main__ import build_catalog

    query = traffic.load_query("q9")
    catalog = build_catalog([f"tpch:sf={SF:g}"])
    bdata.install(catalog, SF, 9, bdata.generate(SF, 9, sorted(query["tables"])))
    plan = LocalRunner(catalog, ExecConfig()).explain(
        query["template"].format(**query["params"]["fixed"]))
    joins = [line.strip().split("   ")[0] for line in plan.splitlines()
             if "HashJoin" in line]
    # partsupp probes last, on two keys; where supplier joins the chain
    # (before orders or after) follows the seed at this scale, not at SF1
    assert joins[0] == \
        "HashJoin[inner; ['ps_suppkey', 'ps_partkey'] = ['l_suppkey', 'l_partkey']]"
    assert sorted(joins[1:]) == [
        "HashJoin[inner; ['l_partkey'] = ['p_partkey']; unique]",
        "HashJoin[inner; ['l_suppkey'] = ['s_suppkey']; unique]",
        "HashJoin[inner; ['o_orderkey'] = ['l_orderkey']]",
        "HashJoin[inner; ['s_nationkey'] = ['n_nationkey']; unique]"]
    with open(os.path.join(ROOT, "benchmark", "configs", CONFIG + ".json")) as f:
        assumed = " ".join(json.load(f)["assumed"])
    for join in joins:
        assert join in assumed, join


# -- the reference against a second formulation, and its control

def by_hand(data, params):
    """Q9 with loops and dicts: no pandas, no merge."""
    li, part, ps = data["lineitem"], data["part"], data["partsupp"]
    supp, nation, orders = data["supplier"], data["nation"], data["orders"]
    green = {key for key, name in zip(part["p_partkey"].tolist(),
                                      bdata.strings(part["p_name"]))
             if params["color"] in name}
    cost = dict(zip(zip(ps["ps_partkey"].tolist(), ps["ps_suppkey"].tolist()),
                    ps["ps_supplycost"].tolist()))
    names = dict(zip(nation["n_nationkey"].tolist(),
                     bdata.strings(nation["n_name"])))
    nation_of = {s: names[n] for s, n in zip(supp["s_suppkey"].tolist(),
                                             supp["s_nationkey"].tolist())}
    year_of = {o: int(date_str(d)[:4]) for o, d in zip(
        orders["o_orderkey"].tolist(), orders["o_orderdate"].tolist())}
    profit = collections.Counter()
    for order, p, s, qty, price, disc in zip(*(li[c].tolist() for c in (
            "l_orderkey", "l_partkey", "l_suppkey", "l_quantity",
            "l_extendedprice", "l_discount"))):
        if p in green:
            profit[nation_of[s], year_of[order]] += \
                price * (100 - disc) - cost[p, s] * qty * 100
    return [[str(n), y, dec(v, 4)] for (n, y), v in
            sorted(profit.items(), key=lambda kv: (kv[0][0], -kv[0][1]))]


@pytest.mark.parametrize("seed", [9, 2147484009, 3000000009])
def test_reference_matches_a_second_formulation(seed):
    data = bdata.generate(SF, seed, sorted(traffic.load_query("q9")["tables"]))
    answer = brun.load_reference("q9")
    for color, least in (("green", 100), ("ghost", 100), ("nosuchcolour", 0)):
        got = answer(data, {"color": color})
        assert got == by_hand(data, {"color": color}) and least <= len(got) <= 175


@pytest.mark.parametrize("seed", [11, 2147484002, 3000000019])
def test_float32_control_is_judged_not_correct(seed):
    """A nation's year sums some 5e9 units of a ten-thousandth at this scale
    (5e11 at SF1); float32 holds integers to 2^24 = 1.7e7."""
    v = control_verdict(CELL, seed, sf=SF)
    assert v["correct"] is False
    assert v["compared"]["wrong_statements"]["value"] == 1
    assert "sum_profit" in v["first_difference"]


def test_exact_reference_in_its_own_place_is_correct():
    assert control_verdict(CELL, 11, sf=SF, arith="exact")["correct"] is True


# -- whole runs of the harness, sound and with a fault underneath

@pytest.fixture(scope="module")
def device():
    import jax

    return jax.devices()[0]


@pytest.fixture(autouse=True)
def short_warmup(monkeypatch):
    """The mix warms up for seconds; a test run need not."""
    load_mix = traffic.load_mix
    monkeypatch.setattr(traffic, "load_mix", lambda name: {
        **load_mix(name), "warmup": 1, "warmup_seconds": 0.0})


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


def test_rehearsal_is_judged_correct_with_the_five_metrics_in_its_line(device):
    res = brun.run_cell(CELL, 2147484031, 0.5, True, device, sf_override=SF)
    assert res["correct"] is True and res["failed"] == 0 < res["attempted"]
    assert res["compared"] == {"wrong_statements": {"value": 0, "limit": 0}}
    got = {name: m["value"] for name, m in res["metrics"].items()}
    assert set(GENERAL_METRICS) <= set(got)
    # one batch of orders and one of partsupp probe a build that fans out
    assert got["join_general_batches_per_stmt"] == 2.0
    # some 1,300 lineitems of green parts come out of each of the two joins,
    # in two chunks of 2^14 lanes (orders') and of 2^13 (partsupp's)
    assert 2 * 1000 < got["join_expand_rows_per_stmt"] < 2 * 1700
    assert got["join_expand_fill_pct"] == pytest.approx(
        100 * got["join_expand_rows_per_stmt"] / (16384 + 8192), rel=1e-9)
    # about 170 (part, supplier) pairs hold those rows, 7.5 to a pair
    assert 20 < got["join_fanout_overflow_rows_per_stmt"] < 170
    assert 0 < got["join_general_sync_s"] <= got["host_sync_s"]
    assert got["task_unattributed_pct"] <= 50
    assert got["compiles_in_window"] == 0
    for there in ("program_calls_per_stmt", "host_sync_s", "programs_minted",
                  "statement_max_s", "plan_s", "window_stack_s"):
        assert there in got, there
    for gone in ("join_build_s", "join_sync_s", "join_search_steps",
                 "join_unique_probe_pct", "agg_partition_s", "first_text_s",
                 "statement_p95_s", "first_quarter_slowdown_pct"):
        assert gone not in got, gone
    # the aggregate, behind the joins' exchanges, never went grace
    assert not [name for doc in trace.summaries()[-res["attempted"]:]
                for by_name in doc["phases"].values() for name in by_name
                if name.startswith("agg_")]


def test_fault_an_expand_that_keeps_only_the_first_match(device, monkeypatch,
                                                         fresh_programs):
    """A probe row's candidates stand next to each other in the expanded
    slots: every slot but the first of its row is dropped."""
    import jax.numpy as jnp

    from presto_tpu.exec import runtime

    expand = runtime.probe_expand

    def first_match_only(*a, **kw):
        probe_row, build_idx, live = expand(*a, **kw)
        first = jnp.concatenate([jnp.ones(1, bool), probe_row[1:] != probe_row[:-1]])
        return probe_row, build_idx, live & first

    monkeypatch.setattr(runtime, "probe_expand", first_match_only)
    judged_wrong(brun.run_cell(CELL, 35, 0.5, False, device, sf_override=SF))


def test_fault_a_probe_that_matches_on_ps_partkey_alone(device, monkeypatch,
                                                        fresh_programs):
    """The two-key join built, counted and expanded on its part key alone:
    a partsupp row meets the lines of its part's other three suppliers."""
    from presto_tpu.exec import runtime
    from presto_tpu.ops import join as opsjoin

    def part_alone(keys):
        return tuple(k for k in keys if "partkey" in k) if len(keys) == 2 \
            else tuple(keys)

    monkeypatch.setattr(runtime, "build_side", lambda batch, key_names:
                        opsjoin.build_side(batch, part_alone(key_names)))
    monkeypatch.setattr(runtime, "probe_counts", lambda t, p, pk, bk, **kw:
                        opsjoin.probe_counts(t, p, part_alone(pk), part_alone(bk), **kw))
    monkeypatch.setattr(runtime, "probe_expand", lambda t, p, pk, bk, *a:
                        opsjoin.probe_expand(t, p, part_alone(pk), part_alone(bk), *a))
    judged_wrong(brun.run_cell(CELL, 36, 0.5, False, device, sf_override=SF))


# -- the five readers, against hand-made summaries

def agg(n, busy, self_s=None, **more):
    return {"n": n, "busy_s": busy, "self_s": busy if self_s is None else self_s,
            "max_s": busy / n, **more}


def summary(query_id, k, general=True, expand=True, rows=130_000, overflow=6_000):
    """One statement's summary, every number stretched by `k`."""
    task = {"exchange_wait": agg(3 * k, 0.8 * k, wait=True),
            "join_build": agg(5, 0.5 * k, 0.1 * k, items=60 * k),
            "join_probe": agg(66 * k, 0.4 * k, 0.05 * k, items=66 * k),
            "join_emit": agg(46 * k, 0.01 * k, items=46 * 4096 * k),
            "host_sync:join_build_rows": agg(5, 0.02 * k),
            "host_sync:join_output_rows": agg(47 * k, 0.9 * k),
            "host_sync:sink_serialize": agg(2 * k, 0.01 * k)}
    other = {"program_call:Project": agg(k, 0.002 * k)}
    if general:
        task.update({"host_sync:join_total": agg(12 * k, 1.0 * k),
                     "host_sync:join_overflow": agg(12 * k, 0.004 * k)})
        # a general join may be probed on another task's thread too
        other.update({"host_sync:join_total": agg(7 * k, 0.5 * k),
                      "host_sync:join_overflow": agg(7 * k, 0.003 * k)})
    if general and expand:
        # a summary leaves `items` out where every occurrence counted nothing
        task.update({"join_expand": agg(12 * k, 1e-6, **({"items": rows * k} if rows else {})),
                     "join_expand_lanes": agg(12 * k, 1e-6, items=12 * 131072 * k)})
        other.update({"join_expand": agg(7 * k, 1e-6, **({"items": rows * k} if rows else {})),
                      "join_expand_lanes": agg(7 * k, 1e-6, items=7 * 131072 * k)})
        if overflow:
            other["join_fanout_overflow"] = agg(7 * k, 1e-6, items=overflow * k)
    return {"queryId": query_id, "wall_s": 2.9 * k, "tasks": 8,
            "task_wall_s": 4.0 * k, "exchange_wait_s": 2.0 * k,
            "spans": 190 * k, "dropped": 0,
            "phases": {"task": task, "fragment-window-producer": other}}


# the mean of the statements scaled 1 and 3 is the statement scaled 2
EXPECTED = {
    "join_general_batches_per_stmt": 2 * 19,
    "join_general_sync_s": 2 * (1.0 + 0.004 + 0.5 + 0.003),
    "join_expand_rows_per_stmt": 2 * 260_000,
    "join_expand_fill_pct": 100 * 260_000 / (19 * 131072),
    "join_fanout_overflow_rows_per_stmt": 2 * 6_000,
}
ONLY_WITH_EXPAND = sorted(set(EXPECTED) - {"join_general_batches_per_stmt",
                                           "join_general_sync_s"})


@pytest.fixture
def planted(monkeypatch):
    docs = [summary("warmup", 7), summary("under_profiler", 5),
            summary("a", 1), summary("b", 3),
            summary("no_overflow", 1, overflow=0),
            summary("no_row", 1, rows=0, overflow=0),
            summary("all_unique", 2, general=False),
            summary("parent", 2, expand=False)]
    monkeypatch.setattr(trace, "summaries", lambda: list(docs))


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_holds_the_planted_number(name, planted):
    read = brun.load_reader("layer_metrics", name)
    run_ = a_run([("under_profiler", 10.0), ("a", 20.0), ("b", 30.0)], 15.0)
    assert read(run_) == pytest.approx(EXPECTED[name], rel=1e-9)
    assert read(a_run([("a", 20.0), ("b", 30.0)], None)) == \
        pytest.approx(EXPECTED[name], rel=1e-9)
    # a statement whose builds are all unique adds nothing to the mean
    run_ = a_run([("a", 20.0), ("all_unique", 25.0), ("b", 30.0)], None)
    assert read(run_) == pytest.approx(EXPECTED[name], rel=1e-9)


def test_general_batches_that_overflowed_nothing_or_expanded_to_nothing_read_nought(planted):
    read = {name: brun.load_reader("layer_metrics", name) for name in EXPECTED}
    calm = a_run([("no_overflow", 20.0)], None)
    got = read["join_fanout_overflow_rows_per_stmt"](calm)
    assert got == 0.0 and isinstance(got, float)  # a number: it does not vanish
    assert read["join_expand_rows_per_stmt"](calm) == 260_000
    empty = a_run([("no_row", 20.0)], None)
    for name in ("join_expand_rows_per_stmt", "join_expand_fill_pct",
                 "join_fanout_overflow_rows_per_stmt"):
        got = read[name](empty)
        assert got == 0.0 and isinstance(got, float), name
    assert read["join_general_batches_per_stmt"](empty) == 19
    # half the statements overflowed: the mean says so
    assert read["join_fanout_overflow_rows_per_stmt"](
        a_run([("a", 20.0), ("no_overflow", 25.0)], None)) == 3_000


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_has_nothing_to_read_without_a_general_batch(name, planted, monkeypatch):
    read = brun.load_reader("layer_metrics", name)
    # every build unique, no join at all, or no statement: None, never 0
    assert read(a_run([("all_unique", 20.0)], None)) is None
    assert read(a_run([("x", 20.0)], None)) is None
    assert read(a_run([], None)) is None
    monkeypatch.delattr(trace, "summaries")
    assert read(a_run([("a", 20.0), ("b", 30.0)], None)) is None


@pytest.mark.parametrize("name", ONLY_WITH_EXPAND)
def test_an_engine_without_the_expand_phases_gives_their_readers_nothing(name, planted):
    """The parent of PR 35 runs the general path and records none of the
    three phases: the driver reads its line under this PR's readers."""
    run_ = a_run([("parent", 20.0)], None)
    assert brun.load_reader("layer_metrics", name)(run_) is None
    assert brun.load_reader("layer_metrics", "join_general_batches_per_stmt")(run_) == 38
    assert brun.load_reader("layer_metrics", "join_general_sync_s")(run_) == \
        pytest.approx(2 * 1.507, rel=1e-9)
