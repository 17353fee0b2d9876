"""The seven readers of the page path's phases off the chip: each holds a
hand's number over planted summaries, has nothing to read where the program
records no such phase (a program from before the page path had phases), is
declared for every cell with its reader beside it, and is read by a cell an
addition brings; a traced rehearsal of a cell (CPU, SF 0.01) carries all
seven in its line."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as brun, traffic  # noqa: E402
from benchmark_shared import a_run, addition, agg, declared  # noqa: E402,F401
from presto_tpu.obs import trace  # noqa: E402

# metric -> (phase, field)
READS = {
    "page_ready_s": ("page_ready", "busy_s"),
    "page_fetch_s": ("page_fetch", "busy_s"),
    "page_encode_s": ("page_encode", "busy_s"),
    "page_serve_s": ("page_serve", "busy_s"),
    "page_decode_s": ("page_decode", "busy_s"),
    "page_upload_s": ("page_upload", "busy_s"),
    "page_fetch_bytes_per_stmt": ("page_fetch", "items"),
}
LAST_BEFORE = "join_verify_width"  # the metric the seven were appended behind


def summary(query_id, k, pages=True):
    """One statement's summary, stretched by `k`: a producing task, a
    consuming task, the coordinator's root stream, a request thread."""
    task = {"program_call:Aggregate": agg(3, 0.2 * k),
            "exchange_wait": agg(4, 0.5 * k, wait=True),
            "host_sync:sink_serialize": agg(3, 0.1 * k)}
    phases = {"task": task, "coordinator": {"schedule": agg(1, 0.01 * k)}}
    if pages:
        task.update(page_ready=agg(3, 0.05 * k),
                    page_fetch=agg(3, 0.02 * k, items=3000 * k),
                    page_encode=agg(3, 0.03 * k, items=900 * k),
                    page_decode=agg(2, 0.004 * k, items=600 * k),
                    page_upload=agg(2, 0.006 * k, items=2000 * k))
        phases["coordinator"].update(
            page_decode=agg(1, 0.001 * k, items=300 * k),
            page_upload=agg(1, 0.002 * k, items=1000 * k))
        phases["http"] = {"page_serve": agg(2, 0.008 * k, items=3)}
    return {"queryId": query_id, "wall_s": 2.0 * k, "tasks": 2,
            "task_wall_s": 3.0 * k, "spans": 40, "dropped": 0,
            "phases": phases}


# the mean of the statements stretched by 1 and 3 is the one stretched by 2;
# a phase on two roles is summed over them
EXPECTED = {
    "page_ready_s": 0.1,
    "page_fetch_s": 0.04,
    "page_encode_s": 0.06,
    "page_serve_s": 0.016,
    "page_decode_s": 2 * (0.004 + 0.001),
    "page_upload_s": 2 * (0.006 + 0.002),
    "page_fetch_bytes_per_stmt": 6000.0,
}


@pytest.fixture
def planted(monkeypatch):
    docs = [summary("under_profiler", 5), summary("a", 1), summary("b", 3),
            summary("parent", 1, pages=False)]
    monkeypatch.setattr(trace, "summaries", lambda: list(docs))


@pytest.mark.parametrize("name", sorted(READS))
def test_reader_holds_the_planted_number(name, planted):
    read = brun.load_reader("layer_metrics", name)
    run = a_run([("under_profiler", 10.0), ("a", 20.0), ("b", 30.0)], 15.0)
    assert read(run) == pytest.approx(EXPECTED[name], rel=1e-9)
    # a statement of a program without the phase adds nothing, not a 0
    run = a_run([("a", 20.0), ("b", 30.0), ("parent", 40.0)], None)
    assert read(run) == pytest.approx(EXPECTED[name], rel=1e-9)


@pytest.mark.parametrize("name", sorted(READS))
def test_reader_has_nothing_to_read_without_the_phase(name, planted,
                                                       monkeypatch):
    read = brun.load_reader("layer_metrics", name)
    # the parent records no page phase: None, never 0
    assert read(a_run([("parent", 20.0)], None)) is None
    assert read(a_run([("x", 20.0)], None)) is None
    assert read(a_run([], None)) is None
    monkeypatch.delattr(trace, "summaries")
    assert read(a_run([("a", 20.0)], None)) is None


def test_fetched_bytes_count_where_a_page_fetched_none(planted):
    # every plane already on the host: the phase is there, its items are not
    for doc in trace.summaries():
        doc["phases"]["task"].get("page_fetch", {}).pop("items", None)
    read = brun.load_reader("layer_metrics", "page_fetch_bytes_per_stmt")
    assert read(a_run([("a", 20.0), ("parent", 30.0)], None)) == 0.0


def test_the_seven_are_declared_for_every_cell(declared):
    bench, root = declared
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(LAST_BEFORE) + 1
    assert names[at:at + len(READS)] == list(READS)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, (_, field) in READS.items():
        unit, source = ("s", "program_span") if field == "busy_s" else \
            ("bytes", "program_counter")
        assert by_name[name] == {
            "name": name, "unit": unit, "better": "lower", "source": source,
            "layer": "scheduler + operators", "moves": "statement_s"}
        assert os.path.isfile(
            os.path.join(root, "benchmark", "layer_metrics", name + ".py"))


def test_a_cell_an_addition_brings_reads_them_too(addition, monkeypatch):
    monkeypatch.setattr(brun, "ROOT", addition.root)
    got = [m["name"] for m in brun.load_cell(addition.cell)["per_layer"]]
    assert set(READS) <= set(got) and addition.metric in got


def test_the_traced_rehearsal_reports_them(monkeypatch):
    import jax

    load_mix = traffic.load_mix
    monkeypatch.setattr(
        traffic, "load_mix", lambda name: {**load_mix(name), "warmup_seconds": 0.0})
    res = brun.run_cell("sf10_q6", 2147484127, 1.0, True, jax.devices()[0],
                        sf_override=0.01)
    assert res["correct"] is True and res["failed"] == 0 < res["attempted"]
    for name in READS:
        assert res["metrics"][name]["value"] > 0, name
