"""The TPC-DS cell `sf1_q67` off the chip (CPU, seeded data, SF 0.01).

Its declaration: a configuration, a cell and three per-layer metrics
appended to `BENCHMARK.json`, every accepted entry as it was. Its data
module, which makes store_sales the way the connector does. Q67 written with
explicit `JOIN ... ON` against the text as the specification writes it. The
float32 control and three planted faults, each judged not correct. The
three readers against hand-made summaries. And one whole run of the harness,
judged correct traced with the three metrics in its line.
"""

import collections
import copy
import hashlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compare, run as brun, traffic  # noqa: E402
from benchmark import data_tpcds_q67 as bdata  # noqa: E402
from benchmark.control import control_verdict  # noqa: E402
from benchmark_shared import a_run, addition, agg, declared  # noqa: E402,F401
from presto_tpu.obs import trace  # noqa: E402

CELL, CONFIG, MIX, SF = "sf1_q67", "tpcds_sf1_q67", "q67_repeat", 0.01
SEED = 2147484023
# name -> (unit, source), in the order they stand after `join_outer_lanes_per_stmt`
METRICS = collections.OrderedDict([
    ("window_s", ("s", "program_span")),
    ("window_lanes_per_stmt", ("count", "program_counter")),
    ("grouping_set_lanes_per_stmt", ("count", "program_counter")),
])
# the benchmark before this cell (6 configurations, 7 cells, 51 per-layer
# metrics): sha256 of its canonical JSON. A `benchmark` PR that edits an
# accepted entry states the new digest here.
ACCEPTED = "95f2f2b270be3caa521fe45fd749ddc1acf78bd40b458b971138486d2a45e54d"
PARAMS = {"dms": 1200}
KEYS = ("i_category", "i_class", "i_brand", "i_product_name", "d_year",
        "d_qoy", "d_moy", "s_store_id")


# -- the declaration

def test_the_entries_are_appended_and_the_accepted_ones_untouched(declared):
    bench, root = declared
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("window_s")
    assert names[at - 1] == "join_outer_lanes_per_stmt" and at == 51
    assert names[at:at + 3] == list(METRICS)
    before = dict(bench, configs=bench["configs"][:6],
                  workloads=bench["workloads"][:7],
                  per_layer=bench["per_layer"][:at])
    doc = json.dumps(before, sort_keys=True, separators=(",", ":"))
    assert hashlib.sha256(doc.encode()).hexdigest() == ACCEPTED
    assert bench["configs"][6]["name"] == CONFIG
    assert bench["workloads"][7]["name"] == CELL


def test_the_metrics_are_the_new_cells_alone(declared):
    bench, root = declared
    for name, (unit, source) in METRICS.items():
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert m == {"name": name, "unit": unit, "better": "lower",
                     "source": source, "layer": "scheduler + operators",
                     "moves": "statement_s", "workloads": [CELL]}
        assert os.path.isfile(os.path.join(
            root, "benchmark", "layer_metrics", name + ".py"))
    for m in bench["per_layer"]:
        if m["name"] not in METRICS:
            assert CELL not in m.get("workloads", [])


def test_the_cell_and_its_configuration(declared):
    bench, root = declared
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    assert len(cell["why"]) <= 200
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    assert entry["reduced"] == config["reduced"] == ["scale_factor", "query_mix"]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert "query 67" in entry["source"] and "DMS = 1200" in entry["source"]
    assert config["scale_factor"] in (1, 0.3)  # the cut rule: `reduced_why`
    assert "L1" in config["reduced_why"] and "L2" in config["reduced_why"]
    assert config["exec_config"] == {} == config["session_properties"]
    assert (config["workers"], config["chips"]) == (1, 1)
    assert config["data_module"] == "data_tpcds_q67"
    assert config["catalog"] == "tpcds:sf={scale_factor}"
    assert config["architecture"] is None
    with open(os.path.join(root, "benchmark/configs/tpcds_sf1_q72.json")) as f:
        assert config["guarantees"] == json.load(f)["guarantees"]
    assumed = " ".join(config["assumed"])
    for fact in ("d_month_seq", "i_class", "nine", "grace"):
        assert fact in assumed, fact
    with open(os.path.join(root, "benchmark", "traffic", MIX + ".json")) as f:
        mix = json.load(f)  # the file: `short_warmup` stands in `load_mix`
    assert mix["queries"] == [{"id": "q67", "weight": 1}]
    assert (mix["streams"], mix["loop"], mix["params"]) == (1, "closed", "fixed")
    assert (mix["warmup"], mix["warmup_seconds"], mix["traced_seconds"]) == (2, 5.0, 4.0)
    assert mix["traced_min_statements"] == 2  # three statements: 49 s of profiler
    mine = [m["name"] for m in bench["per_layer"]
            if "workloads" not in m or CELL in m["workloads"]]
    assert set(METRICS) <= set(mine) and "statement_roofline" in mine
    assert not [n for n in mine if n.startswith(("join_", "agg_"))]


def test_the_query_file():
    query = traffic.load_query("q67")
    assert query["tables"] == {
        "store_sales": ["ss_sold_date_sk", "ss_item_sk", "ss_store_sk",
                        "ss_quantity", "ss_sales_price"],
        "date_dim": ["d_date_sk", "d_month_seq", "d_year", "d_qoy", "d_moy"],
        "store": ["s_store_sk", "s_store_id"],
        "item": ["i_item_sk", "i_category", "i_class", "i_brand",
                 "i_product_name"]}
    text = query["template"]
    assert f"group by rollup({', '.join(KEYS)})" in text
    assert "rank() over (partition by i_category order by sumsales desc) rk" in text
    assert "d_month_seq between {dms} and {dms}+11" in text
    assert "where rk <= 100" in text and text.rstrip().endswith("limit 100")
    assert query["params"]["fixed"] == PARAMS
    assert query["limits"] == {"wrong_statements": 0}
    assert [(c["name"], c["type"]) for c in query["result_columns"]] == [
        *((k, "bigint" if k.startswith("d_") else "varchar") for k in KEYS),
        ("sumsales", "decimal(38,2)"), ("rk", "bigint")]


# -- the data module

@pytest.fixture(scope="module")
def data():
    return bdata.generate(SF, SEED, sorted(traffic.load_query("q67")["tables"]))


def test_rows_and_bytes_from_the_query_file(data):
    from presto_tpu.catalog.tpcds import TpcdsGenerator

    query = traffic.load_query("q67")
    n = {t: len(bdata.column_array(next(iter(cols.values()))))
         for t, cols in data.items()}
    gen = TpcdsGenerator(SF, seed=SEED)
    assert n == {"store_sales": gen.n_store_sales, "date_dim": 73_049,
                 "item": 18_000, "store": gen.n_store}
    # store_sales as the connector draws it
    sales, _ = gen.store_sales_and_returns()
    assert (data["store_sales"]["ss_item_sk"] == sales["ss_item_sk"]).all()
    assert bdata.scanned_rows(query, data) == sum(n.values())
    # strings reach the device as int32 codes, keys and counts as int64
    assert bdata.referenced_bytes(query, data) == (
        5 * 8 * n["store_sales"] + 5 * 8 * n["date_dim"]
        + (8 + 4) * n["store"] + (8 + 4 * 4) * n["item"])


def test_the_data_module_refuses_an_engine_without_the_columns(monkeypatch):
    import importlib

    from presto_tpu.catalog import tpcds

    date_dim = tpcds.TpcdsGenerator.date_dim
    monkeypatch.setattr(tpcds.TpcdsGenerator, "date_dim", lambda self: {
        k: v for k, v in date_dim(self).items() if k != "d_month_seq"})
    with pytest.raises(ImportError, match="d_month_seq"):
        importlib.reload(bdata)
    monkeypatch.undo()
    importlib.reload(bdata)


# -- Q67 with explicit joins, served

EXPLICIT = """
select * from (
  select i_category, i_class, i_brand, i_product_name, d_year, d_qoy, d_moy, s_store_id, sumsales,
         rank() over (partition by i_category order by sumsales desc) rk
  from (select i_category, i_class, i_brand, i_product_name, d_year, d_qoy, d_moy, s_store_id,
               sum(coalesce(ss_sales_price*ss_quantity,0)) sumsales
        from store_sales
        join date_dim on (ss_sold_date_sk = d_date_sk)
        join store on (ss_store_sk = s_store_sk)
        join item on (ss_item_sk = i_item_sk)
        where d_month_seq between {dms} and {dms}+11
        group by rollup(i_category, i_class, i_brand, i_product_name, d_year, d_qoy, d_moy, s_store_id)) dw1) dw2
where rk <= 100
order by i_category, i_class, i_brand, i_product_name, d_year, d_qoy, d_moy, s_store_id, sumsales, rk
limit 100"""


def test_explicit_joins_answer_as_the_specifications_text(data):
    from presto_tpu import client
    from presto_tpu.exec import ExecConfig
    from presto_tpu.server.__main__ import build_catalog
    from presto_tpu.server.coordinator import DistributedRunner

    catalog = build_catalog([f"tpcds:sf={SF:g}"])
    bdata.install(catalog, SF, SEED, data)
    runner = DistributedRunner(catalog, n_workers=1, config=ExecConfig())
    try:
        url, session = runner.coordinator.url, client.ClientSession(user="t")
        text = traffic.load_query("q67")["template"].format(**PARAMS)
        first = client.execute(url, text, session)
        second = client.execute(url, EXPLICIT.format(**PARAMS), session)
    finally:
        runner.close()
    assert first == second and len(first[1]) == 100
    want = brun.load_reference("q67")(data, PARAMS)
    assert [[None if v is None else str(v) for v in r] for r in first[1]] == \
        [[None if v is None else str(v) for v in r] for r in want]


# -- the control and three planted faults

@pytest.mark.parametrize("seed", [11, 2147484002, 3000000011])
def test_float32_control_is_judged_not_correct(seed):
    v = control_verdict(CELL, seed, sf=SF)
    assert v["correct"] is False and v["first_difference"]
    assert v["compared"]["wrong_statements"] == {"value": 1, "limit": 0}


def one_rollup_level_dropped(rows):
    """The rows of the brand level (three keys set, five NULL) gone, and
    the rows after them moved up."""
    return [r for r in rows if sum(v is None for v in r[:8]) != 5]


def rank_off_by_one(rows):
    rows = copy.deepcopy(rows)
    rows[-1][9] += 1
    return rows


def a_padded_key_filled_in(rows):
    """One row of a coarser set given a key it does not group by."""
    rows = copy.deepcopy(rows)
    r = next(r for r in rows if r[7] is None)
    r[7] = "AAAAAAAA00000001"
    return rows


@pytest.mark.parametrize("fault", [one_rollup_level_dropped, rank_off_by_one,
                                   a_padded_key_filled_in],
                         ids=lambda f: f.__name__)
def test_a_planted_fault_is_judged_not_correct(data, fault):
    query = traffic.load_query("q67")
    want = brun.load_reference("q67")(data, PARAMS)
    key = traffic.params_key(PARAMS)
    refs = {("q67", key): (want, query["result_columns"])}

    def window(rows):
        return [{"index": 0, "query": "q67", "params_key": key, "error": None,
                 "columns": query["result_columns"], "rows": rows}]

    limits = {"wrong_statements": 0, "double_rel_err_max": 0.0}
    assert compare.judge(window(want), refs, limits)["correct"] is True
    assert fault(want) != want
    v = compare.judge(window(fault(want)), refs, limits)
    assert v["correct"] is False
    assert v["compared"]["wrong_statements"]["value"] == 1


# -- a whole run of the harness

@pytest.fixture
def short_warmup(monkeypatch):
    """The mix warms up for seconds; a test run need not."""
    load_mix = traffic.load_mix
    monkeypatch.setattr(traffic, "load_mix", lambda name: {
        **load_mix(name), "warmup": 1, "warmup_seconds": 0.0})


def test_rehearsal_is_judged_correct_with_the_metrics_in_its_line(short_warmup):
    import jax

    res = brun.run_cell(CELL, SEED, 0.5, True, jax.devices()[0],
                        sf_override=SF)
    assert res["correct"] is True and res["failed"] == 0 < res["attempted"]
    assert res["compared"] == {"wrong_statements": {"value": 0, "limit": 0}}
    got = {name: m["value"] for name, m in res["metrics"].items()}
    assert got["window_s"] > 0 and got["compiles_in_window"] == 0
    # the window sorts what the nine sets hand on, padded to its
    # power-of-two bucket (one sort program whatever the seed's capacities)
    lanes = int(got["window_lanes_per_stmt"])
    assert lanes >= 128 and lanes & (lanes - 1) == 0
    assert got["grouping_set_lanes_per_stmt"] >= 9 * 128
    last = trace.summaries()[-1]["phases"]
    n = collections.Counter()
    for by_name in last.values():
        for name in ("grouping_set", "window_collect", "window_lanes"):
            n[name] += by_name.get(name, {}).get("n", 0)
    assert n == {"grouping_set": 9, "window_collect": 1, "window_lanes": 1}
    for gone in ("join_build_rows_per_stmt", "agg_partition_s", "first_text_s"):
        assert gone not in got, gone


# -- the three readers, against hand-made summaries

def summary(query_id, k, window=True, sets=True):
    """One statement's summary, every number stretched by `k`."""
    task = {"join_build": agg(3, 0.5 * k, items=20 * k),
            "program_call:Aggregate": agg(40 * k, 0.3 * k)}
    other = {"program_call:Project": agg(k, 0.002 * k)}
    if window:
        task["window_collect"] = agg(1, 2.0 * k, items=17 * k)
        task["window_lanes"] = agg(1, 1e-6, items=4096 * k)
        task["program_call:Window"] = agg(1, 0.25 * k)
    else:  # a program from before the phase still calls the window
        task["program_call:Window"] = agg(1, 0.25 * k)
    if sets:
        task["grouping_set"] = agg(7, 1e-6, items=1000 * k)
        other["grouping_set"] = agg(2, 1e-6, items=24 * k)
    return {"queryId": query_id, "wall_s": 9.0 * k, "tasks": 30,
            "task_wall_s": 12.0 * k, "exchange_wait_s": 3.0 * k,
            "spans": 900 * k, "dropped": 0,
            "phases": {"task": task, "fragment-window-producer": other}}


# the mean of the statements scaled 1 and 3 is the statement scaled 2
EXPECTED = {"window_s": 2 * 2.25, "window_lanes_per_stmt": 2 * 4096,
            "grouping_set_lanes_per_stmt": 2 * 1024}


@pytest.fixture
def planted(monkeypatch):
    docs = [summary("warmup", 7), summary("under_profiler", 5),
            summary("a", 1), summary("b", 3),
            summary("no_window", 1, window=False, sets=False)]
    monkeypatch.setattr(trace, "summaries", lambda: list(docs))


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_holds_the_planted_number(name, planted):
    read = brun.load_reader("layer_metrics", name)
    run_ = a_run([("under_profiler", 10.0), ("a", 20.0), ("b", 30.0)], 15.0)
    assert read(run_) == pytest.approx(EXPECTED[name], rel=1e-9)
    assert read(a_run([("a", 20.0), ("b", 30.0)], None)) == \
        pytest.approx(EXPECTED[name], rel=1e-9)
    # a statement without the phase adds nothing to the mean
    run_ = a_run([("a", 20.0), ("no_window", 25.0), ("b", 30.0)], None)
    assert read(run_) == pytest.approx(EXPECTED[name], rel=1e-9)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_has_nothing_to_read_without_the_phase(name, planted, monkeypatch):
    read = brun.load_reader("layer_metrics", name)
    # a statement without the phase, no statement, or a program from before
    # the phases (the parent of this cell): None, never 0
    assert read(a_run([("no_window", 20.0)], None)) is None
    assert read(a_run([("x", 20.0)], None)) is None
    assert read(a_run([], None)) is None
    monkeypatch.delattr(trace, "summaries")
    assert read(a_run([("a", 20.0), ("b", 30.0)], None)) is None
