"""The grace-aggregation cell `q18_highndv` off the chip (CPU, seeded data).

Its declaration: a configuration, a cell and six per-layer metrics appended
to `BENCHMARK.json`, every accepted entry as it was. Its six readers against
hand-made summaries. Its plain reference against a second formulation with
loops and dicts, and the float32 control coming out not correct. And two
whole rehearsals of the harness at SF 0.01, the served path's `ExecConfig`
given a ceiling of 2^12 groups and QUANTITY 250 so that the statement spills
and answers with rows at that scale (as shipped it starts to spill at SF
0.09, a minute of XLA:CPU): one sound, judged correct traced, every new
metric in its line and no spill file left; one with a leaf partition's pages
dropped on their way back, judged not correct.
"""

import collections
import hashlib
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import data as bdata, run as brun, traffic  # noqa: E402
from benchmark.control import control_verdict  # noqa: E402
from benchmark.refutil import date_str, dec  # noqa: E402
from benchmark_shared import a_run, addition, declared  # noqa: E402,F401
from presto_tpu.obs import trace  # noqa: E402

CELL, CONFIG, MIX = "q18_highndv", "tpch_q18", "q18_repeat"
TABLES = ["customer", "lineitem", "orders"]
# name -> (unit, source), in the order they stand after `join_unique_probe_pct`
AGG_METRICS = collections.OrderedDict([
    ("agg_partition_s", ("s", "program_span")),
    ("agg_replay_s", ("s", "program_span")),
    ("agg_leaf_partitions_per_stmt", ("count", "program_counter")),
    ("agg_replay_batches_per_stmt", ("count", "program_counter")),
    ("agg_replay_waves_per_stmt", ("count", "program_counter")),
    ("agg_spill_bytes_per_stmt", ("bytes", "program_counter")),
])
# the benchmark as PR 32 left it (3 configurations, 4 cells, 30 per-layer
# metrics): sha256 of its canonical JSON. A `benchmark` PR that edits an
# accepted entry states the new digest here.
ACCEPTED = "03d2f9e8b23f52529df26feff51e765caff78eaf0dcc34930334e024d9f85e92"


# -- the declaration

def test_the_entries_are_appended_and_the_accepted_ones_untouched(declared):
    bench, root = declared
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("agg_partition_s")
    assert names[at - 1] == "join_unique_probe_pct"
    assert names[at:at + 6] == list(AGG_METRICS)
    before = dict(bench, configs=bench["configs"][:3],
                  workloads=bench["workloads"][:4],
                  per_layer=bench["per_layer"][:at])
    doc = json.dumps(before, sort_keys=True, separators=(",", ":"))
    assert hashlib.sha256(doc.encode()).hexdigest() == ACCEPTED
    assert bench["configs"][3]["name"] == CONFIG
    assert bench["workloads"][4]["name"] == CELL


def test_the_metrics_are_the_new_cells_alone(declared):
    bench, root = declared
    for name, (unit, source) in AGG_METRICS.items():
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert m == {"name": name, "unit": unit, "better": "lower",
                     "source": source, "layer": "scheduler + operators",
                     "moves": "statement_s", "workloads": [CELL]}
        assert os.path.isfile(os.path.join(
            root, "benchmark", "layer_metrics", name + ".py"))
    for m in bench["per_layer"]:
        if m["name"] not in AGG_METRICS:
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
    assert "2.4.18" in entry["source"] and "QUANTITY = 300" in entry["source"]
    assert config["scale_factor"] in (1, 0.3)  # ISSUE 33's rule: `reduced_why`
    assert config["exec_config"] == {} == config["session_properties"]
    assert (config["workers"], config["chips"]) == (1, 1)
    assert "spill files are scratch" in config["guarantees"]["durability"]
    assert os.path.isfile(os.path.join(
        root, "benchmark", config["data_module"] + ".py"))
    mix = traffic.load_mix(MIX)
    assert mix["queries"] == [{"id": "q18", "weight": 1}]
    assert (mix["streams"], mix["loop"], mix["params"]) == (1, "closed", "fixed")
    # what `run.py` selects for the cell: every unlisted metric and its own
    mine = [m["name"] for m in bench["per_layer"]
            if "workloads" not in m or CELL in m["workloads"]]
    assert set(AGG_METRICS) <= set(mine) and "statement_roofline" in mine
    assert not [n for n in mine if n.startswith("join_")]


def test_the_query_file():
    query = traffic.load_query("q18")
    assert query["tables"] == {
        "customer": ["c_custkey", "c_name"],
        "orders": ["o_orderkey", "o_custkey", "o_totalprice", "o_orderdate"],
        "lineitem": ["l_orderkey", "l_quantity"]}  # read twice, counted once
    assert query["template"].count("{") == 1  # QUANTITY, the one placeholder
    assert "total_qty" in query["template"] and "limit 100" in query["template"]
    assert query["params"]["fixed"] == {"quantity": "300"}
    assert query["limits"] == {"wrong_statements": 0}
    assert [(c["name"], c["type"]) for c in query["result_columns"]] == [
        ("c_name", "varchar"), ("c_custkey", "bigint"), ("o_orderkey", "bigint"),
        ("o_orderdate", "date"), ("o_totalprice", "decimal(15,2)"),
        ("total_qty", "bigint")]


def test_the_data_module_refuses_an_engine_that_replays_page_by_page(monkeypatch):
    """The parent of PR 33 takes 1,100-1,600 s over its first run of this
    cell: `benchmark/data_grace.py` turns it away at import."""
    import importlib

    from presto_tpu.spiller import PartitioningSpiller

    sound = importlib.import_module("benchmark.data_grace")
    assert sound.generate is bdata.generate and sound.install is bdata.install
    monkeypatch.delattr(PartitioningSpiller, "read_batches")
    monkeypatch.delitem(sys.modules, "benchmark.data_grace")
    with pytest.raises(ImportError, match="page by page"):
        importlib.import_module("benchmark.data_grace")
    monkeypatch.undo()
    sys.modules["benchmark.data_grace"] = sound


# -- the reference against a second formulation, and its control

def by_hand(data, params):
    """Q18 with loops and dicts: no pandas, no merge."""
    cust, orders, li = data["customer"], data["orders"], data["lineitem"]
    qty = collections.Counter()
    for key, q in zip(li["l_orderkey"].tolist(), li["l_quantity"].tolist()):
        qty[key] += q
    names = dict(zip(cust["c_custkey"].tolist(), bdata.strings(cust["c_name"])))
    rows = []
    for key, custkey, date, price in zip(*(orders[c].tolist() for c in (
            "o_orderkey", "o_custkey", "o_orderdate", "o_totalprice"))):
        if qty[key] > int(params["quantity"]):
            rows.append((-price, date, names[custkey], custkey, key, qty[key]))
    rows.sort(key=lambda r: r[:2])
    return [[str(name), custkey, key, date_str(date), dec(-neg_price, 2), q]
            for neg_price, date, name, custkey, key, q in rows[:100]]


@pytest.mark.parametrize("seed", [18, 2147484018, 3000000018])
def test_reference_matches_a_second_formulation(seed):
    data = bdata.generate(0.01, seed, TABLES)
    answer = brun.load_reference("q18")
    for quantity, least in (("300", 0), ("250", 10), ("150", 100)):
        params = {"quantity": quantity}
        got = answer(data, params)
        assert got == by_hand(data, params) and least <= len(got) <= 100


@pytest.mark.parametrize("seed", [11, 2147484002, 3000000019])
def test_float32_control_is_judged_not_correct(seed):
    """Sums of integer quantities are exact in float32; cents are lost past
    2^24 = 167,772.16 and the orders that pass stand at three times that."""
    v = control_verdict(CELL, seed, sf=0.1)
    assert v["correct"] is False
    assert v["compared"]["wrong_statements"]["value"] == 1
    assert "o_totalprice" in v["first_difference"]


def test_exact_reference_in_its_own_place_is_correct():
    assert control_verdict(CELL, 11, sf=0.1, arith="exact")["correct"] is True


# -- whole rehearsals of the harness, sound and with a fault underneath

@pytest.fixture(scope="module")
def device():
    import jax

    return jax.devices()[0]


@pytest.fixture
def spilling_at_sf001(monkeypatch):
    """The served path lowers a session to `ExecConfig` with the shipped
    ceiling of 2^17 groups, which Q18's 15,000 at SF 0.01 never reach: give
    that one call a ceiling of 2^12. QUANTITY 250 for the 300 no order
    passes at this scale; one warm-up statement, not two and five seconds."""
    from presto_tpu.exec import ExecConfig
    from presto_tpu.server import session

    monkeypatch.setattr(session, "ExecConfig", lambda **kw: ExecConfig(
        **{**kw, "agg_cap_ceiling": 1 << 12}))
    load_mix, load_query = traffic.load_mix, traffic.load_query
    monkeypatch.setattr(traffic, "load_mix", lambda name: {
        **load_mix(name), "warmup": 1, "warmup_seconds": 0.0})

    def with_250(qid):
        query = load_query(qid)
        return {**query, "params": {"fixed": {"quantity": "250"}}}

    monkeypatch.setattr(traffic, "load_query", with_250)


@pytest.fixture
def spill_files(monkeypatch):
    """Paths of the spill files made while the fixture stands."""
    from presto_tpu.spiller import SpillFile

    made, init = [], SpillFile.__init__

    def init_noting(self, path, *a, **kw):
        made.append(path)
        init(self, path, *a, **kw)

    monkeypatch.setattr(SpillFile, "__init__", init_noting)
    return made


def test_rehearsal_spills_is_judged_correct_and_leaves_no_file(
        device, spilling_at_sf001, spill_files):
    res = brun.run_cell(CELL, 2147484031, 0.5, True, device, sf_override=0.01)
    assert res["correct"] is True and res["failed"] == 0 < res["attempted"]
    assert res["compared"] == {"wrong_statements": {"value": 0, "limit": 0}}
    got = {name: m["value"] for name, m in res["metrics"].items()}
    assert set(AGG_METRICS) <= set(got)
    # the inner aggregate's eight partitions, two of them with rows (the
    # content hash of a lone small integer is the integer, and the order
    # keys are 1 and 5 modulo 8: PERF.md, 7); their 7,500 groups each
    # outnumber the ceiling, so both split by eight and the children fit
    assert got["agg_leaf_partitions_per_stmt"] == 8.0 + 2 * 8.0
    assert 2.0 + 16.0 <= got["agg_replay_batches_per_stmt"] <= 2 * 2.0 + 16.0
    assert got["agg_replay_waves_per_stmt"] == 0.0
    assert got["agg_spill_bytes_per_stmt"] > 100_000
    assert got["agg_partition_s"] > 0 and got["agg_replay_s"] > 0
    assert got["task_unattributed_pct"] <= 50
    assert got["compiles_in_window"] == 0
    for there in ("program_calls_per_stmt", "host_sync_s",
                  "programs_minted", "statement_max_s", "plan_s"):
        assert there in got, there
    for gone in ("join_build_s", "join_search_steps", "join_unique_probe_pct",
                 "first_text_s", "statement_p95_s", "first_quarter_slowdown_pct"):
        assert gone not in got, gone
    # scratch: deleted with the statement that wrote them
    assert spill_files and not [p for p in spill_files if os.path.exists(p)]


def test_fault_a_leaf_partition_whose_pages_are_dropped(
        device, spilling_at_sf001, monkeypatch):
    """The leaf that holds the answer's first order comes back empty."""
    from presto_tpu.spiller import PartitioningSpiller, np_bucket_ids

    seed = 31
    data = bdata.generate(0.01, seed, TABLES)
    top_order = brun.load_reference("q18")(data, {"quantity": "250"})[0][2]
    spill, read_partition = PartitioningSpiller.spill, \
        PartitioningSpiller.read_partition
    doomed = set()

    def spill_noting(self, batch):
        if len(self.key_names) == 1:  # the inner aggregate: l_orderkey
            keys = np.asarray(batch.column(self.key_names[0]).values)
            here = np.asarray(batch.live) & (keys == top_order)
            pids = np_bucket_ids([(keys, None, None)], self.n_partitions,
                                 divisor=self.divisor)
            doomed.update((id(self), int(p)) for p in np.unique(pids[here]))
        return spill(self, batch)

    def read_dropping(self, p, host=False):
        if (id(self), p) in doomed and p not in self.children:
            return iter(())
        return read_partition(self, p, host)

    monkeypatch.setattr(PartitioningSpiller, "spill", spill_noting)
    monkeypatch.setattr(PartitioningSpiller, "read_partition", read_dropping)
    res = brun.run_cell(CELL, seed, 0.5, False, device, sf_override=0.01)
    assert doomed
    assert res["correct"] is False and res["failed"] == 0
    assert res["compared"]["wrong_statements"]["value"] == res["attempted"] > 0


# -- the six readers, against hand-made summaries

def agg(n, busy, self_s=None, **more):
    return {"n": n, "busy_s": busy, "self_s": busy if self_s is None else self_s,
            "max_s": busy / n, **more}


def summary(query_id, k, grace=True, waves=0):
    """One statement's summary, every number stretched by `k`."""
    task = {"exchange_wait": agg(3 * k, 0.8 * k, wait=True),
            "program_call:Aggregate": agg(70 * k, 0.2 * k),
            "host_sync:agg_confirm": agg(60 * k, 1.9 * k),
            "host_sync:sink_serialize": agg(2 * k, 0.01 * k)}
    other = {"program_call:Project": agg(k, 0.002 * k)}
    if grace:
        task.update({
            "agg_partition": agg(k, 0.35 * k, 0.02 * k, items=46 * k),
            "agg_replay": agg(50 * k, 2.5 * k, 0.9 * k, items=66 * k),
            "agg_repartition": agg(6 * k, 0.3 * k, 0.1 * k),
            "host_sync:agg_spill_rows": agg(46 * k, 0.5 * k),
            "agg_spill_write": agg(120 * k, 0.25 * k, items=3_000_000 * k),
            "agg_spill_read": agg(200 * k, 0.3 * k, items=5_000_000 * k)})
        # an aggregate may replay on another task's thread: its leaves are
        # empty, so their `items` are left out of the summary
        other.update({"agg_replay": agg(8 * k, 0.02 * k),
                      "agg_spill_write": agg(k, 0.001 * k, items=500 * k)})
        if waves:
            task["agg_replay_wave"] = agg(waves * k, 1e-6, items=waves * k)
    return {"queryId": query_id, "wall_s": 4.9 * k, "tasks": 7,
            "task_wall_s": 6.0 * k, "exchange_wait_s": 4.0 * k,
            "spans": 170 * k, "dropped": 0,
            "phases": {"task": task, "fragment-window-producer": other}}


# the mean of the statements scaled 1 and 3 is the statement scaled 2
EXPECTED = {
    "agg_partition_s": 2 * 0.35,
    "agg_replay_s": 2 * (2.5 + 0.3 + 0.02),
    "agg_leaf_partitions_per_stmt": 2 * (50 + 8),
    "agg_replay_batches_per_stmt": 2 * 66,
    "agg_replay_waves_per_stmt": 0.0,
    "agg_spill_bytes_per_stmt": 2 * (3_000_000 + 500),
}


@pytest.fixture
def planted(monkeypatch):
    docs = [summary("warmup", 7), summary("under_profiler", 5),
            summary("a", 1), summary("b", 3),
            summary("waves", 1, waves=4),
            summary("fits_one_table", 2, grace=False)]
    monkeypatch.setattr(trace, "summaries", lambda: list(docs))


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_holds_the_planted_number(name, planted):
    read = brun.load_reader("layer_metrics", name)
    run_ = a_run([("under_profiler", 10.0), ("a", 20.0), ("b", 30.0)], 15.0)
    assert read(run_) == pytest.approx(EXPECTED[name], rel=1e-9)
    assert read(a_run([("a", 20.0), ("b", 30.0)], None)) == \
        pytest.approx(EXPECTED[name], rel=1e-9)
    # a statement that never spilled adds nothing to the mean, not a 0
    run_ = a_run([("a", 20.0), ("fits_one_table", 25.0), ("b", 30.0)], None)
    assert read(run_) == pytest.approx(EXPECTED[name], rel=1e-9)


def test_a_replay_without_a_wave_reads_nought_and_one_with_waves_their_number(planted):
    read = brun.load_reader("layer_metrics", "agg_replay_waves_per_stmt")
    got = read(a_run([("a", 20.0)], None))
    assert got == 0.0 and isinstance(got, float)  # a number: it does not vanish
    assert read(a_run([("waves", 20.0)], None)) == 4.0
    assert read(a_run([("a", 20.0), ("waves", 25.0)], None)) == 2.0


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_has_nothing_to_read_without_a_replay(name, planted, monkeypatch):
    read = brun.load_reader("layer_metrics", name)
    # a program whose grace path has no phases (the parent commit), or a
    # statement whose aggregates fit one table: None, never 0
    assert read(a_run([("fits_one_table", 20.0)], None)) is None
    assert read(a_run([("x", 20.0)], None)) is None
    assert read(a_run([], None)) is None
    monkeypatch.delattr(trace, "summaries")
    assert read(a_run([("a", 20.0), ("b", 30.0)], None)) is None
