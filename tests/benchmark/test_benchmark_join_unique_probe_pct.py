"""`join_unique_probe_pct` off the chip: its reader holds a hand's numbers
over planted summaries - 100 where no probe batch read `join_total`, 0 (a
number, not nothing) where every one did, nothing only where a statement
recorded no `join_probe` - and `BENCHMARK.json` declares it for the join
cell. The cell's traced rehearsal (CPU, SF 0.01) asserts Q3's 100.0 in
`test_benchmark_join_search_steps.py`, which rehearses the cell already."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as brun  # noqa: E402
from benchmark_shared import a_run, addition, agg, declared, summary  # noqa: E402,F401
from presto_tpu.obs import trace  # noqa: E402

CELL, NAME = "sf1_q3", "join_unique_probe_pct"


@pytest.fixture
def plant(monkeypatch):
    """plant({query id: (probes, general, probes seen from another thread)})"""
    def plant(statements):
        docs = []
        for qid, (probes, general, elsewhere) in statements.items():
            doc = summary(qid, 1)
            if probes:
                doc["phases"]["task"]["join_probe"] = agg(probes, 0.4, items=probes)
            if general:
                doc["phases"]["task"]["host_sync:join_total"] = agg(general, 6.0)
            if elsewhere:
                doc["phases"]["fragment-window-producer"]["join_probe"] = \
                    agg(elsewhere, 0.1, items=elsewhere)
            docs.append(doc)
        monkeypatch.setattr(trace, "summaries", lambda: list(docs))
    return plant


@pytest.mark.parametrize("statements, ids, stopped_at, want", [
    # every batch on the single-match path: Q3 since PR 30
    ({"a": (58, 0, 0)}, ["a"], None, 100.0),
    # 12 of 58: Q3 on PR 29's tree
    ({"a": (58, 46, 0)}, ["a"], None, 100.0 * 12 / 58),
    # every batch on the general path: the worst case reads 0, it does not vanish
    ({"a": (58, 58, 0)}, ["a"], None, 0.0),
    # no `join_probe`: a scan statement, a program from before PR 27
    ({"a": (0, 0, 0)}, ["a"], None, None),
    # such a statement adds nothing to the mean of the others, not a 0
    ({"a": (58, 46, 0), "scan": (0, 0, 0)}, ["a", "scan"], None, 100.0 * 12 / 58),
    # probes seen from two thread roles are summed, not averaged: 24 of 70
    ({"a": (58, 46, 12)}, ["a"], None, 100.0 * 24 / 70),
    # the mean is over statements, each by its own batches
    ({"a": (58, 0, 0), "b": (10, 10, 0)}, ["a", "b"], None, 50.0),
    # statements sent before the profiler was stopped are left out
    ({"early": (58, 58, 0), "a": (58, 0, 0)}, ["early", "a"], 15.0, 100.0),
    # none was sent after it: all of them
    ({"early": (58, 58, 0)}, ["early"], 15.0, 0.0),
    # the run's statements left no summary
    ({"a": (58, 0, 0)}, ["x"], None, None),
])
def test_reader_holds_the_planted_number(statements, ids, stopped_at, want,
                                         plant):
    plant(statements)
    read = brun.load_reader("layer_metrics", NAME)
    got = read(a_run([(q, 10.0 * (i + 1)) for i, q in enumerate(ids)], stopped_at))
    if want is None:
        assert got is None
    else:
        assert got is not None and got == pytest.approx(want, abs=1e-9)


def test_reader_has_nothing_to_read_without_summaries(plant, monkeypatch):
    plant({"a": (58, 0, 0)})
    read = brun.load_reader("layer_metrics", NAME)
    assert read(a_run([], None)) is None
    monkeypatch.delattr(trace, "summaries")
    assert read(a_run([("a", 20.0)], None)) is None


def test_the_metric_is_declared_for_the_join_cell(declared):
    bench, root = declared
    m = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert CELL in m["workloads"]
    assert {k: v for k, v in m.items() if k != "workloads"} == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "lifecycle / planner",
        "moves": "statement_s"}
    assert os.path.isfile(
        os.path.join(root, "benchmark", "layer_metrics", NAME + ".py"))
