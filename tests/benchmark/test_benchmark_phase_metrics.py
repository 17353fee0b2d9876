"""The ten readers of the engine's per-statement phase summaries: each is fed
a small `run` with planted summaries and has to come out at the number a
hand computes; with no summary for the run's statements, or on a program
that has no `summaries()`, each has nothing to read."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as brun  # noqa: E402
from benchmark_shared import addition, declared  # noqa: E402,F401
from presto_tpu.obs import trace  # noqa: E402


def agg(n, busy, self_s=None, **more):
    return {"n": n, "busy_s": busy, "self_s": busy if self_s is None else self_s,
            "max_s": busy / n, **more}


def summary(query_id, scale):
    """One statement's summary; `scale` stretches every number, so that a
    mean over two statements differs from either."""
    k = scale
    return {
        "queryId": query_id, "wall_s": 1.0 * k, "tasks": 2,
        "task_wall_s": 2.0 * k, "exchange_wait_s": 0.9 * k,
        "spans": 30 * k, "dropped": 2 * k,
        "phases": {
            "scan-prefetch": {
                "scan_read": agg(400 * k, 0.04 * k),
                "scan_queue_full": agg(400 * k, 0.7 * k, wait=True)},
            "fragment-window-producer": {
                "scan_wait": agg(401 * k, 0.01 * k, wait=True),
                "window_stack": agg(50 * k, 0.6 * k, items=400 * k),
                "program_call:Project": agg(1 * k, 0.002 * k)},
            "task": {
                "window_wait": agg(51 * k, 0.5 * k, wait=True),
                "scan_wait": agg(2 * k, 0.1 * k, wait=True),
                "exchange_wait": agg(3 * k, 0.8 * k, wait=True),
                "program_call:Aggregate": agg(52 * k, 0.2 * k, 0.15 * k),
                "program_call:Sort": agg(4 * k, 0.01 * k),
                "host_sync:agg_confirm": agg(50 * k, 0.03 * k),
                "host_sync:sink_serialize": agg(2 * k, 0.01 * k)},
            "coordinator": {
                "schedule": agg(1, 0.02 * k),
                "trace_collect": agg(1, 0.005 * k)},
        },
    }


# the mean of the statements scaled 1 and 3 is the statement scaled 2
EXPECTED = {
    "scan_read_s": 0.08,
    "window_stack_s": 1.2,
    "program_call_s": 2 * (0.002 + 0.2 + 0.01),
    "program_calls_per_stmt": 2 * (1 + 52 + 4),
    "host_sync_s": 2 * (0.03 + 0.01),
    "task_wait_s": 2 * (0.5 + 0.1),
    "schedule_s": 0.04,
    "trace_collect_s": 0.01,
    # named: 0.5 + 0.1 + 0.8 + 0.15 + 0.01 + 0.03 + 0.01 = 1.6 of 2.0, at any scale
    "task_unattributed_pct": 20.0,
    "trace_spans_per_stmt": 2 * (30 + 2),
}


def a_run(ids_and_starts, profiler_stopped_at):
    return {"traced": {"t1": profiler_stopped_at},
            "completed": [{"query_id": q, "t0": t0, "t1": t0 + 1.0}
                          for q, t0 in ids_and_starts]}


@pytest.fixture
def planted(monkeypatch):
    docs = [summary("warmup", 7), summary("under_profiler", 5),
            summary("a", 1), summary("b", 3)]
    monkeypatch.setattr(trace, "summaries", lambda: list(docs))


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_holds_the_planted_number(name, planted):
    read = brun.load_reader("layer_metrics", name)
    # the statements sent after the profiler was stopped, and only those
    run = a_run([("under_profiler", 10.0), ("a", 20.0), ("b", 30.0)], 15.0)
    assert read(run) == pytest.approx(EXPECTED[name], rel=1e-9)
    # an untraced run: all of its statements
    run = a_run([("a", 20.0), ("b", 30.0)], None)
    assert read(run) == pytest.approx(EXPECTED[name], rel=1e-9)
    # none was sent after the profiler stopped: all of them, as statement_max_s
    run = a_run([("a", 1.0), ("b", 2.0)], 15.0)
    assert read(run) == pytest.approx(EXPECTED[name], rel=1e-9)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_has_nothing_to_read_without_summaries(name, planted, monkeypatch):
    read = brun.load_reader("layer_metrics", name)
    # the run's statements left no summary (tracing off, or pushed out)
    assert read(a_run([("x", 20.0), ("y", 30.0)], None)) is None
    assert read(a_run([], None)) is None
    # a program from before the engine had phases: no `summaries` at all
    monkeypatch.delattr(trace, "summaries")
    assert read(a_run([("a", 20.0), ("b", 30.0)], None)) is None


def test_every_new_metric_is_declared_for_every_cell(declared):
    bench, _ = declared
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in EXPECTED:
        m = by_name[name]
        assert "workloads" not in m and m["moves"] == "statement_s"
        assert m["layer"] == "scheduler + operators" and m["better"] == "lower"
