"""`join_search_steps` off the chip: its reader holds a hand's numbers and has
nothing to read where the program records no `join_search` (the parent
commit), the cell's traced rehearsal (CPU, SF 0.01) carries it in its line,
and `BENCHMARK.json` declares it for the join cell, as committed and with an
addition appended behind it."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as brun, traffic  # noqa: E402
from benchmark_shared import a_run, addition, declared, summary  # noqa: E402,F401
from presto_tpu.obs import trace  # noqa: E402

CELL, SF, NAME = "sf1_q3", 0.01, "join_search_steps"


@pytest.fixture
def planted(monkeypatch):
    docs = [summary("warmup", 7, steps=40), summary("under_profiler", 5, steps=30),
            summary("a", 1, steps=7), summary("b", 3, steps=9),
            summary("parent", 2, search=False)]
    monkeypatch.setattr(trace, "summaries", lambda: list(docs))


@pytest.mark.parametrize("ids, stopped_at", [
    ([("under_profiler", 10.0), ("a", 20.0), ("b", 30.0)], 15.0),
    ([("a", 20.0), ("b", 30.0)], None),
    # a statement that recorded no search adds nothing to the mean, not a 0
    ([("a", 20.0), ("parent", 25.0), ("b", 30.0)], None),
])
def test_reader_holds_the_planted_number(ids, stopped_at, planted):
    read = brun.load_reader("layer_metrics", NAME)
    # steps a build, whatever the statement's size: the mean of 7/2 and 9/2
    assert read(a_run(ids, stopped_at)) == pytest.approx(4.0, rel=1e-9)


def test_reader_has_nothing_to_read_without_the_phase(planted, monkeypatch):
    read = brun.load_reader("layer_metrics", NAME)
    # a program whose builds record no `join_search` (the parent): None, never 0
    assert read(a_run([("parent", 20.0)], None)) is None
    assert read(a_run([("x", 20.0)], None)) is None
    assert read(a_run([], None)) is None
    monkeypatch.delattr(trace, "summaries")
    assert read(a_run([("a", 20.0), ("b", 30.0)], None)) is None


def test_a_build_of_empty_buckets_reads_zero_steps(monkeypatch):
    docs = [summary("a", 1)]
    for phases in docs[0]["phases"].values():
        phases["join_search"].pop("items")
    monkeypatch.setattr(trace, "summaries", lambda: docs)
    read = brun.load_reader("layer_metrics", NAME)
    assert read(a_run([("a", 20.0)], None)) == 0


def test_the_traced_rehearsal_reports_it(monkeypatch):
    import jax

    load_mix = traffic.load_mix
    monkeypatch.setattr(
        traffic, "load_mix", lambda name: {**load_mix(name), "warmup_seconds": 0.0})
    res = brun.run_cell(CELL, 2147484029, 1.0, True, jax.devices()[0],
                        sf_override=SF)
    assert res["correct"] is True and res["failed"] == 0 < res["attempted"]
    got = res["metrics"]
    # halving rounds inside a bucket, not log2 of a build's capacity
    assert 0 < got[NAME]["value"] <= 6
    assert got["join_probe_batches_per_stmt"]["value"] == 2
    # both of Q3's builds print `unique`: every batch on the single-match path
    assert got["join_unique_probe_pct"]["value"] == 100.0


def test_the_metric_is_declared_for_the_join_cell_alone(declared):
    bench, root = declared
    m = next(m for m in bench["per_layer"] if m["name"] == NAME)
    # found by name: where it stands, and which other cells read it, is not
    # what it is
    assert CELL in m["workloads"]
    assert {k: v for k, v in m.items() if k != "workloads"} == {
        "name": NAME, "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "kernels", "moves": "statement_s"}
    assert os.path.isfile(
        os.path.join(root, "benchmark", "layer_metrics", NAME + ".py"))
