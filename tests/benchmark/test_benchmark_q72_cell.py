"""The TPC-DS cell `sf1_q72` off the chip (CPU, seeded data, SF 0.1).

Its declaration: a configuration, a cell and two per-layer metrics appended
to `BENCHMARK.json`, every accepted entry as it was. Its data module over the
program's TPC-DS generator. Its plain reference against a second formulation
with loops and dicts on three seeds. The float32 control, which Q72 cannot
have: its answer is strings, a week number and counts, so the control comes
out exact, and three planted faults stand in for it, each judged not
correct. The two readers against hand-made summaries. And one whole run of
the harness at SF 0.1 (at SF 0.01 inventory holds 1 % of the items and the
answer is empty), judged correct traced with both metrics in its line.
"""

import collections
import copy
import hashlib
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compare, run as brun, traffic  # noqa: E402
from benchmark import data_tpcds as bdata  # noqa: E402
from benchmark.control import control_verdict  # noqa: E402
from benchmark_shared import a_run, addition, agg, declared  # noqa: E402,F401
from presto_tpu.obs import trace  # noqa: E402

CELL, CONFIG, MIX, SF = "sf1_q72", "tpcds_sf1_q72", "q72_repeat", 0.1
# name -> layer, in the order they stand after `page_fetch_bytes_per_stmt`
METRICS = collections.OrderedDict([
    ("join_build_rows_per_stmt", "lifecycle / planner"),
    ("join_outer_lanes_per_stmt", "scheduler + operators"),
])
# the benchmark before this cell (5 configurations, 6 cells, 49 per-layer
# metrics): sha256 of its canonical JSON. A `benchmark` PR that edits an
# accepted entry states the new digest here.
ACCEPTED = "c617f7767791a59519f91039b9534a9f5785099e164130f0d6f0f77090c7343d"
PARAMS = {"bp": ">10000", "ms": "D", "year": 1999}


# -- the declaration

def test_the_entries_are_appended_and_the_accepted_ones_untouched(declared):
    bench, root = declared
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("join_build_rows_per_stmt")
    assert names[at - 1] == "page_fetch_bytes_per_stmt" and at == 49
    assert names[at:at + 2] == list(METRICS)
    before = dict(bench, configs=bench["configs"][:5],
                  workloads=bench["workloads"][:6],
                  per_layer=bench["per_layer"][:at])
    doc = json.dumps(before, sort_keys=True, separators=(",", ":"))
    assert hashlib.sha256(doc.encode()).hexdigest() == ACCEPTED
    assert bench["configs"][5]["name"] == CONFIG
    assert bench["workloads"][6]["name"] == CELL


def test_the_metrics_are_the_new_cells_alone(declared):
    bench, root = declared
    for name, layer in METRICS.items():
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert m == {"name": name, "unit": "count", "better": "lower",
                     "source": "program_counter", "layer": layer,
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
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    assert entry["reduced"] == config["reduced"] == ["scale_factor", "query_mix"]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert "query 72" in entry["source"] and "BP = '>10000'" in entry["source"]
    assert config["scale_factor"] in (1, 0.3)  # the cut rule: `reduced_why`
    assert "L1" in config["reduced_why"] and "L2" in config["reduced_why"]
    assert config["exec_config"] == {} == config["session_properties"]
    assert (config["workers"], config["chips"]) == (1, 1)
    assert config["data_module"] == "data_tpcds"
    assert config["catalog"] == "tpcds:sf={scale_factor}"
    assert config["architecture"] is None
    with open(os.path.join(root, "benchmark/configs/tpch_sf1_join.json")) as f:
        assert config["guarantees"] == json.load(f)["guarantees"]
    assumed = " ".join(config["assumed"])
    for fact in ("no_promo is 0", "23,490,000", "11,745,000", "i_item_desc",
                 "cs_bill_cdemo_sk", "primary keys",
                 "['inv_item_sk', 'inv_date_sk'] = ['cs_item_sk', 'd_date_sk#1']"):
        assert fact in assumed, fact
    with open(os.path.join(root, "benchmark", "traffic", MIX + ".json")) as f:
        mix = json.load(f)  # the file: `short_warmup` stands in `load_mix`
    assert mix["queries"] == [{"id": "q72", "weight": 1}]
    assert (mix["streams"], mix["loop"], mix["params"]) == (1, "closed", "fixed")
    assert (mix["warmup"], mix["warmup_seconds"], mix["traced_seconds"]) == (2, 5.0, 4.0)
    assert mix["traced_min_statements"] in (2, 3)
    # what `run.py` selects for the cell: every unlisted metric and its own,
    # none of another cell's
    mine = [m["name"] for m in bench["per_layer"]
            if "workloads" not in m or CELL in m["workloads"]]
    assert set(METRICS) <= set(mine) and "statement_roofline" in mine
    assert not [n for n in mine if n.startswith(("join_", "agg_"))
                and n not in METRICS]


def test_the_query_file():
    query = traffic.load_query("q72")
    assert sorted(query["tables"]) == [
        "catalog_returns", "catalog_sales", "customer_demographics", "date_dim",
        "household_demographics", "inventory", "item", "promotion", "warehouse"]
    text = query["template"]
    assert text.count("join ") == 10 and text.count("left outer join") == 2
    assert "hd_buy_potential = '{bp}'" in text and "d1.d_year = {year}" in text
    assert "cd_marital_status = '{ms}'" in text
    assert "d3.d_date > d1.d_date + 5" in text
    assert "order by total_cnt desc, i_item_desc, w_warehouse_name, d_week_seq" in text
    assert query["params"]["fixed"] == PARAMS
    assert query["limits"] == {"wrong_statements": 0}
    assert [(c["name"], c["type"]) for c in query["result_columns"]] == [
        ("i_item_desc", "varchar"), ("w_warehouse_name", "varchar"),
        ("d_week_seq", "bigint"), ("no_promo", "bigint"), ("promo", "bigint"),
        ("total_cnt", "bigint")]


# -- the data module

@pytest.fixture(scope="module")
def data():
    return bdata.generate(SF, 2147484011, sorted(traffic.load_query("q72")["tables"]))


def test_rows_and_bytes_from_the_query_file(data):
    query = traffic.load_query("q72")
    n = {t: len(bdata.column_array(next(iter(cols.values()))))
         for t, cols in data.items()}
    # date_dim, named three times, is scanned once a statement
    assert bdata.scanned_rows(query, data) == sum(n.values())
    # inventory's items are sampled below SF1: 1,800 x 5 warehouses x 261 weeks
    assert n["inventory"] == 1_800 * 5 * 261 and n["item"] == 18_000
    # strings reach the device as int32 codes, keys and counts as int64
    assert bdata.referenced_bytes(query, data) == (
        8 * 8 * n["catalog_sales"] + 4 * 8 * n["inventory"]
        + (8 + 4) * (n["warehouse"] + n["item"] + n["customer_demographics"]
                     + n["household_demographics"])
        + 4 * 8 * n["date_dim"] + 8 * n["promotion"]
        + 2 * 8 * n["catalog_returns"])


def test_the_catalog_serves_the_arrays_the_reference_reads(data):
    from presto_tpu.server.__main__ import build_catalog

    catalog = build_catalog([f"tpcds:sf={SF:g}"])
    bdata.install(catalog, SF, 2147484011, data)
    table = catalog.connectors["tpcds"].tables["item"]
    d, codes = data["item"]["i_item_desc"]
    assert list(table.dicts["i_item_desc"].decode(table.arrays["i_item_desc"])) \
        == list(d.decode(codes))
    assert table.primary_key == ["i_item_sk"]


def test_an_engine_without_the_columns_is_refused_at_import(monkeypatch):
    import importlib

    from presto_tpu.catalog import tpcds

    item = tpcds.TpcdsGenerator.item
    monkeypatch.setattr(tpcds.TpcdsGenerator, "item", lambda self: {
        k: v for k, v in item(self).items() if k != "i_item_desc"})
    with pytest.raises(ImportError, match="i_item_desc"):
        importlib.reload(bdata)
    monkeypatch.undo()
    importlib.reload(bdata)


# -- the reference against a second formulation

def by_hand(data, params):
    """Q72 with loops and dicts: no pandas, no merge."""
    dd, inv, cs = data["date_dim"], data["inventory"], data["catalog_sales"]
    day = dict(zip(dd["d_date_sk"].tolist(), zip(
        dd["d_date"].tolist(), dd["d_year"].tolist(), dd["d_week_seq"].tolist())))
    hdemo = data["household_demographics"]
    cdemo = data["customer_demographics"]
    hd_ok = {k for k, bp in zip(hdemo["hd_demo_sk"].tolist(),
                                bdata.strings(hdemo["hd_buy_potential"]))
             if bp == params["bp"]}
    cd_ok = {k for k, ms in zip(cdemo["cd_demo_sk"].tolist(),
                                bdata.strings(cdemo["cd_marital_status"]))
             if ms == params["ms"]}
    stock = collections.defaultdict(list)   # (item, week) -> [(wh, on hand)]
    for d, i, w, q in zip(*(inv[c].tolist() for c in (
            "inv_date_sk", "inv_item_sk", "inv_warehouse_sk",
            "inv_quantity_on_hand"))):
        stock[i, day[d][2]].append((w, q))
    desc = dict(zip(data["item"]["i_item_sk"].tolist(),
                    bdata.strings(data["item"]["i_item_desc"])))
    wh = data["warehouse"]
    wname = dict(zip(wh["w_warehouse_sk"].tolist(),
                     bdata.strings(wh["w_warehouse_name"])))
    promos = set(data["promotion"]["p_promo_sk"].tolist())
    cr = data["catalog_returns"]
    returns = collections.Counter(zip(cr["cr_item_sk"].tolist(),
                                      cr["cr_order_number"].tolist()))
    groups = collections.defaultdict(lambda: [0, 0, 0])
    for sold, ship, item, order, qty, promo, cd, hd in zip(*(cs[c].tolist() for c in (
            "cs_sold_date_sk", "cs_ship_date_sk", "cs_item_sk",
            "cs_order_number", "cs_quantity", "cs_promo_sk",
            "cs_bill_cdemo_sk", "cs_bill_hdemo_sk"))):
        date, year, week = day[sold]
        if year != params["year"] or hd not in hd_ok or cd not in cd_ok \
                or not day[ship][0] > date + 5:
            continue
        for w, on_hand in stock.get((item, week), ()):
            if on_hand < qty:
                n = max(1, returns[item, order])   # a LEFT JOIN keeps the sale
                g = groups[desc[item], wname[w], week]
                g[1 if promo in promos else 0] += n
                g[2] += n
    rows = sorted(([d, w, wk, *c] for (d, w, wk), c in groups.items()),
                  key=lambda r: (-r[5], r[0], r[1], r[2]))
    return rows[:100]


@pytest.mark.parametrize("seed", [9, 2147484009, 3000000009])
def test_reference_matches_a_second_formulation(seed):
    data = bdata.generate(SF, seed, sorted(traffic.load_query("q72")["tables"]))
    answer = brun.load_reference("q72")
    got = answer(data, PARAMS)
    assert got == by_hand(data, PARAMS) and 10 <= len(got) <= 100
    # the generator's promotions cover every cs_promo_sk: no_promo is 0
    assert all(r[3] == 0 and r[4] == r[5] for r in got)
    other = {"bp": "Unknown", "ms": "M", "year": 2001}
    assert answer(data, other) == by_hand(data, other)


# -- the control, which Q72 cannot have, and the faults that stand in for it

@pytest.mark.parametrize("seed", [11, 2147484002])
def test_float32_control_is_the_exact_answer(seed):
    """Q72 computes nothing but counts of rows and compares integers, dates
    and strings: no value passes through a float or a decimal, so its
    reference in float32 is its exact answer and the control is judged
    correct. What it would catch is caught by the faults below."""
    v = control_verdict(CELL, seed, sf=SF)
    assert v["correct"] is True and v["first_difference"] is None
    assert v["compared"] == {"wrong_statements": {"value": 0, "limit": 0}}


def one_null_extended_row_dropped(rows):
    """The last group's count falls by one: one sale the LEFT JOINs kept
    (no return, its catalog_returns columns NULL) lost."""
    rows = copy.deepcopy(rows)
    last = rows[-1]
    if last[5] == 1:
        rows.pop()
    else:
        last[4] -= 1
        last[5] -= 1
    return rows


def total_cnt_off_by_one(rows):
    rows = copy.deepcopy(rows)
    rows[0][5] += 1
    return rows


def one_weeks_pairs_missing(rows):
    week = rows[0][2]
    return [r for r in rows if r[2] != week]


@pytest.mark.parametrize("fault", [one_null_extended_row_dropped,
                                   total_cnt_off_by_one,
                                   one_weeks_pairs_missing],
                         ids=lambda f: f.__name__)
def test_a_planted_fault_is_judged_not_correct(data, fault):
    query = traffic.load_query("q72")
    want = brun.load_reference("q72")(data, PARAMS)
    key = traffic.params_key(PARAMS)
    refs = {("q72", key): (want, query["result_columns"])}

    def window(rows):
        return [{"index": 0, "query": "q72", "params_key": key, "error": None,
                 "columns": query["result_columns"], "rows": rows}]

    limits = {"wrong_statements": 0, "double_rel_err_max": 0.0}
    assert compare.judge(window(want), refs, limits)["correct"] is True
    v = compare.judge(window(fault(want)), refs, limits)
    assert v["correct"] is False
    assert v["compared"]["wrong_statements"]["value"] == 1


# -- a whole run of the harness

@pytest.fixture(autouse=True)
def short_warmup(monkeypatch):
    """The mix warms up for seconds; a test run need not."""
    load_mix = traffic.load_mix
    monkeypatch.setattr(traffic, "load_mix", lambda name: {
        **load_mix(name), "warmup": 1, "warmup_seconds": 0.0})


def test_rehearsal_is_judged_correct_with_both_metrics_in_its_line():
    import jax

    res = brun.run_cell(CELL, 2147484031, 0.5, True, jax.devices()[0],
                        sf_override=SF)
    assert res["correct"] is True and res["failed"] == 0 < res["attempted"]
    assert res["compared"] == {"wrong_statements": {"value": 0, "limit": 0}}
    got = {name: m["value"] for name, m in res["metrics"].items()}
    # the sorted builds hold the sales and their dimensions, never inventory
    n_inventory = 1_800 * 5 * 261
    assert 1_000 < got["join_build_rows_per_stmt"] < n_inventory / 10
    # two LEFT JOINs hand on every batch that reaches them whole
    assert got["join_outer_lanes_per_stmt"] >= 2 * 128
    assert got["compiles_in_window"] == 0
    for gone in ("join_build_s", "join_general_batches_per_stmt",
                 "join_unique_probe_pct", "agg_partition_s", "first_text_s"):
        assert gone not in got, gone


# -- the two readers, against hand-made summaries

def summary(query_id, k, build=True, outer=True, rows=60_000, lanes=1024):
    """One statement's summary, every number stretched by `k`."""
    task = {"join_build": agg(9, 0.5 * k, items=20 * k),
            "join_probe": agg(200 * k, 4.0 * k, items=200 * k),
            "host_sync:join_build_rows": agg(9, 0.02 * k)}
    other = {"program_call:Project": agg(k, 0.002 * k)}
    if build:
        # a summary leaves `items` out where every occurrence counted nothing
        task["join_build_table"] = agg(7, 1e-6, **({"items": rows * k} if rows else {}))
        other["join_build_table"] = agg(1, 1e-6, items=5 * k)
    if outer:
        task["join_outer"] = agg(2 * k, 1e-6, items=lanes * k)
    return {"queryId": query_id, "wall_s": 9.0 * k, "tasks": 9,
            "task_wall_s": 12.0 * k, "exchange_wait_s": 3.0 * k,
            "spans": 900 * k, "dropped": 0,
            "phases": {"task": task, "fragment-window-producer": other}}


# the mean of the statements scaled 1 and 3 is the statement scaled 2
EXPECTED = {"join_build_rows_per_stmt": 2 * (60_000 + 5),
            "join_outer_lanes_per_stmt": 2 * 1024}


@pytest.fixture
def planted(monkeypatch):
    docs = [summary("warmup", 7), summary("under_profiler", 5),
            summary("a", 1), summary("b", 3),
            summary("no_row", 1, rows=0, lanes=0),
            summary("no_join", 1, build=False, outer=False)]
    monkeypatch.setattr(trace, "summaries", lambda: list(docs))


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_holds_the_planted_number(name, planted):
    read = brun.load_reader("layer_metrics", name)
    run_ = a_run([("under_profiler", 10.0), ("a", 20.0), ("b", 30.0)], 15.0)
    assert read(run_) == pytest.approx(EXPECTED[name], rel=1e-9)
    assert read(a_run([("a", 20.0), ("b", 30.0)], None)) == \
        pytest.approx(EXPECTED[name], rel=1e-9)
    # a statement without the phase adds nothing to the mean
    run_ = a_run([("a", 20.0), ("no_join", 25.0), ("b", 30.0)], None)
    assert read(run_) == pytest.approx(EXPECTED[name], rel=1e-9)
    got = read(a_run([("no_row", 20.0)], None))
    assert isinstance(got, float) and got == (5.0 if "build" in name else 0.0)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_has_nothing_to_read_without_the_phase(name, planted, monkeypatch):
    read = brun.load_reader("layer_metrics", name)
    # a statement without the phase, no statement, or a program from before
    # the phases (the parent of this cell): None, never 0
    assert read(a_run([("no_join", 20.0)], None)) is None
    assert read(a_run([("x", 20.0)], None)) is None
    assert read(a_run([], None)) is None
    monkeypatch.delattr(trace, "summaries")
    assert read(a_run([("a", 20.0), ("b", 30.0)], None)) is None
    assert np.isfinite(EXPECTED[name])
