"""`join_verify_width` off the chip: its reader holds a hand's numbers over
planted summaries and has nothing to read where the program records no
`join_verify` (PR 36's parent), the join cell's traced rehearsal (CPU, SF
0.01) carries 1.0 in its line, and `BENCHMARK.json` declares it, as committed
and with an addition appended behind it."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as brun, traffic  # noqa: E402
from benchmark_shared import a_run, addition, agg, declared, summary  # noqa: E402,F401
from presto_tpu.obs import trace  # noqa: E402

CELL, SF, NAME = "sf1_q3", 0.01, "join_verify_width"


@pytest.fixture
def plant(monkeypatch):
    """plant({query id: [(role, builds, lanes)], ...}): no list, no phase."""
    def plant(statements):
        docs = []
        for qid, builds in statements.items():
            doc = summary(qid, 1, search=False)
            for role, n, lanes in builds or ():
                doc["phases"][role]["join_verify"] = agg(n, 1e-5, items=lanes)
            docs.append(doc)
        monkeypatch.setattr(trace, "summaries", lambda: list(docs))
    return plant


TASK, OTHER = "task", "fragment-window-producer"


@pytest.mark.parametrize("statements, ids, stopped_at, want", [
    # Q3's two builds of distinct keys: one lane each
    ({"a": [(TASK, 2, 2)]}, ["a"], None, 1.0),
    # builds observed from two thread roles are summed, not averaged
    ({"a": [(TASK, 1, 1), (OTHER, 1, 3)]}, ["a"], None, 2.0),
    # a set-op build of one key repeated 1,000 times beside a unique one
    ({"a": [(TASK, 2, 1001)]}, ["a"], None, 500.5),
    # the mean is over statements, each by its own builds
    ({"a": [(TASK, 2, 2)], "b": [(TASK, 1, 3)]}, ["a", "b"], None, 2.0),
    # a statement with no such phase (the parent, a scan) adds nothing, not a 0
    ({"a": [(TASK, 2, 2)], "parent": None}, ["a", "parent"], None, 1.0),
    # statements sent before the profiler was stopped are left out
    ({"early": [(TASK, 2, 8)], "a": [(TASK, 2, 2)]}, ["early", "a"], 15.0, 1.0),
    # builds with no live lane report no `items`
    ({"a": [(TASK, 2, 0)]}, ["a"], None, 0.0),
])
def test_reader_holds_the_planted_number(statements, ids, stopped_at, want,
                                         plant):
    plant(statements)
    if want == 0.0:
        for doc in trace.summaries():
            doc["phases"][TASK]["join_verify"].pop("items")
    read = brun.load_reader("layer_metrics", NAME)
    got = read(a_run([(q, 10.0 * (i + 1)) for i, q in enumerate(ids)], stopped_at))
    assert got == pytest.approx(want, abs=1e-9)


def test_reader_has_nothing_to_read_without_the_phase(plant, monkeypatch):
    plant({"a": [(TASK, 2, 2)], "parent": None})
    read = brun.load_reader("layer_metrics", NAME)
    # a program whose builds record no `join_verify` (the parent): None, never 0
    assert read(a_run([("parent", 20.0)], None)) is None
    assert read(a_run([("x", 20.0)], None)) is None
    assert read(a_run([], None)) is None
    monkeypatch.delattr(trace, "summaries")
    assert read(a_run([("a", 20.0)], None)) is None


def test_the_traced_rehearsal_reports_it(monkeypatch):
    import jax

    load_mix = traffic.load_mix
    monkeypatch.setattr(
        traffic, "load_mix", lambda name: {**load_mix(name), "warmup_seconds": 0.0})
    res = brun.run_cell(CELL, 2147484127, 1.0, True, jax.devices()[0],
                        sf_override=SF)
    assert res["correct"] is True and res["failed"] == 0 < res["attempted"]
    # both of Q3's builds hold distinct keys: one lane to verify
    assert res["metrics"][NAME]["value"] == 1.0


def test_the_metric_is_declared_for_the_join_cell(declared):
    bench, root = declared
    m = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert CELL in m["workloads"]
    assert {k: v for k, v in m.items() if k != "workloads"} == {
        "name": NAME, "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "kernels", "moves": "statement_s"}
    assert os.path.isfile(
        os.path.join(root, "benchmark", "layer_metrics", NAME + ".py"))
