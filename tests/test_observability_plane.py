"""Observability plane: span tracer + trace-token propagation across the
in-process cluster, histogram metric families, exposition-format lint,
and the slow-query event sink.

Reference modules: airlift trace-token propagation, DistributionStat /
TimeStat metrics export, the EventListener SPI's QueryCompletedEvent."""

import glob
import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pandas as pd
import pytest

from presto_tpu.catalog.memory import MemoryConnector
from presto_tpu.connector import Catalog
from presto_tpu.exec import ExecConfig
from presto_tpu.obs import metrics as obs_metrics
from presto_tpu.obs import trace as obs_trace
from presto_tpu.obs.events import SlowQueryLogger
from presto_tpu.obs.exposition import lint_exposition
from presto_tpu.server.metrics import _fmt, render_metrics


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:  # the benchmark's trace reduction and query texts
    sys.path.insert(0, ROOT)


def _catalog():
    conn = MemoryConnector()
    rng = np.random.default_rng(7)
    conn.add_table("t", pd.DataFrame({"k": np.arange(400) % 7,
                                      "v": rng.normal(size=400)}))
    cat = Catalog()
    cat.register("m", conn, default=True)
    return cat


# -- metrics plane (unit) --------------------------------------------------


class TestHistograms:
    def test_log_buckets_shape(self):
        b = obs_metrics.log_buckets(0.01, 600.0)
        assert b == sorted(b)
        assert len(b) == len(set(b))
        assert b[0] == 0.01
        assert all(x > 0 for x in b)
        # last finite bound sits within one ratio step of hi (the +Inf
        # bucket covers the tail)
        assert b[-1] <= 600.0
        assert b[-1] >= 600.0 / (10.0 ** (1.0 / 3.0)) * 0.99

    def test_observe_render_and_plane_filter(self):
        h = obs_metrics.Histogram("test_obs_hist_seconds", "unit-test family",
                                  obs_metrics.log_buckets(0.001, 10.0))
        for v in (0.002, 0.002, 5.0):
            h.observe(v, plane="worker")
        h.observe(0.1, plane="coordinator")
        snap = h.snapshot("worker")
        assert len(snap) == 1
        (_, s), = snap.items()
        assert s["count"] == 3
        doc = "\n".join(h.render("worker")) + "\n"
        assert lint_exposition(doc) == []
        assert 'le="+Inf"' in doc
        assert "test_obs_hist_seconds_count" in doc
        # the coordinator observation never leaks into the worker plane
        assert 'plane="coordinator"' not in doc

    def test_empty_plane_renders_zeroed_family(self):
        h = obs_metrics.Histogram("test_obs_empty_seconds", "x",
                                  obs_metrics.log_buckets(0.001, 1.0))
        doc = "\n".join(h.render("worker")) + "\n"
        assert lint_exposition(doc) == []
        assert 'test_obs_empty_seconds_count{plane="worker"} 0' in doc

    def test_builtin_families_exist(self):
        names = {h.name for h in obs_metrics.ALL_HISTOGRAMS}
        assert len(names) >= 4
        doc = obs_metrics.render_histograms("coordinator")
        assert lint_exposition(doc) == []


class TestExpositionFormat:
    def test_label_escaping_roundtrip(self):
        line = _fmt("m", 1, {"q": 'a"b\\c\nd'})
        assert '\\"' in line and "\\\\" in line and "\\n" in line
        doc = "# HELP m x\n# TYPE m gauge\n" + line + "\n"
        assert lint_exposition(doc) == []

    def test_render_metrics_types_and_headers_once(self):
        doc = render_metrics([
            ("m_total", "monotone", 3, None),
            ("g", "by label", 1.5, {"a": "b"}),
            ("g", "by label", 2.5, {"a": "c"}),
            ("x", "explicit type wins", 7, None, "counter"),
        ])
        assert "# TYPE m_total counter" in doc
        assert "# TYPE g gauge" in doc
        assert doc.count("# TYPE g gauge") == 1
        assert doc.count("# HELP g") == 1
        assert "# TYPE x counter" in doc
        assert lint_exposition(doc) == []

    def test_lint_catches_duplicate_type(self):
        errs = lint_exposition("# TYPE m gauge\n# TYPE m gauge\nm 1\n")
        assert any("duplicate TYPE" in e for e in errs)

    def test_lint_catches_type_after_samples(self):
        errs = lint_exposition("# HELP m x\nm 1\n# TYPE m gauge\n")
        assert any("after its samples" in e for e in errs)

    def test_lint_catches_missing_type(self):
        errs = lint_exposition("m 1\n")
        assert any("no # TYPE" in e for e in errs)

    def test_lint_catches_bad_escape(self):
        errs = lint_exposition(
            '# HELP m x\n# TYPE m gauge\nm{a="b\\x"} 1\n')
        assert any("invalid escape" in e for e in errs)

    def test_lint_catches_histogram_defects(self):
        base = "# HELP h x\n# TYPE h histogram\n"
        errs = lint_exposition(
            base + 'h_bucket{le="1"} 1\nh_sum 1\nh_count 1\n')
        assert any("+Inf" in e for e in errs)
        errs = lint_exposition(
            base + 'h_bucket{le="1"} 5\nh_bucket{le="2"} 3\n'
            'h_bucket{le="+Inf"} 5\nh_sum 1\nh_count 5\n')
        assert any("monotone" in e for e in errs)
        errs = lint_exposition(
            base + 'h_bucket{le="+Inf"} 2\nh_sum 1\nh_count 3\n')
        assert any("_count" in e for e in errs)
        errs = lint_exposition(base + "h 1\n")
        assert any("invalid for histogram" in e for e in errs)

    def test_lint_catches_non_numeric_value(self):
        errs = lint_exposition("# HELP m x\n# TYPE m gauge\nm bogus\n")
        assert any("non-numeric" in e for e in errs)

    def test_cli(self, tmp_path):
        from presto_tpu.obs import exposition

        good = tmp_path / "good.prom"
        good.write_text("# HELP m x\n# TYPE m gauge\nm 1\n")
        assert exposition.main([str(good)]) == 0
        bad = tmp_path / "bad.prom"
        bad.write_text("m 1\n")
        assert exposition.main([str(bad)]) == 1


# -- tracer (unit) ---------------------------------------------------------


class TestTracer:
    def test_span_nesting_and_record_parenting(self):
        tr = obs_trace.Tracer()
        with tr.span("query", "query") as root:
            assert tr.root_id == root.span_id
            with tr.span("child", "operator") as ch:
                assert ch.parent_id == root.span_id
                sp = tr.record("compile", "compile", 1.0, 2.0)
                assert sp.parent_id == ch.span_id
        # spans append on close: inner-first
        assert [s.name for s in tr.spans()] == ["compile", "child", "query"]
        # off-stack records (producer threads) parent to the root
        late = tr.record("late", "operator", 1.0, 2.0)
        assert late.parent_id == tr.root_id

    def test_token_roundtrip(self):
        tr = obs_trace.Tracer(trace_id="t_x")
        with tr.span("query", "query") as root:
            tok = tr.token()
            assert obs_trace.parse_token(tok) == ("t_x", root.span_id)
        assert obs_trace.parse_token(
            obs_trace.format_token("t", None)) == ("t", None)

    def test_absorb_reparents_worker_dump(self):
        coord = obs_trace.Tracer(trace_id="T")
        with coord.span("query", "query"):
            stage = coord.record("stage-0", "stage", 0.0, 1.0)
        worker = obs_trace.Tracer(trace_id="T")
        with worker.span("task", "task"):
            worker.record("op", "operator", 0.0, 0.5)
        dump = worker.to_json()
        coord.absorb(dump["spans"], {dump["rootSpanId"]: stage.span_id})
        by_id = {s.span_id: s for s in coord.spans()}
        assert by_id[dump["rootSpanId"]].parent_id == stage.span_id
        tree = obs_trace.build_tree(coord.spans())
        assert len(tree) == 1  # one stitched root: the query span

    def test_max_spans_drops_and_counts(self):
        tr = obs_trace.Tracer(max_spans=2)
        for i in range(3):
            tr.record(f"s{i}", "operator", 0.0, 1.0)
        assert len(tr.spans()) == 2
        assert tr.dropped == 1
        assert tr.to_json()["dropped"] == 1

    def test_noop_tracer(self):
        n = obs_trace.NOOP
        assert n.enabled is False
        with n.span("a", "b") as sp:
            assert sp.duration_s == 0.0
        assert n.record("a", "b", 0, 1).span_id is None
        assert n.to_json()["spans"] == []
        assert n.token() == ""

    def test_thread_local_use(self):
        tr = obs_trace.Tracer()
        with obs_trace.use(tr):
            assert obs_trace.current() is tr
            with obs_trace.use(obs_trace.NOOP):
                assert obs_trace.current() is obs_trace.NOOP
            assert obs_trace.current() is tr
        assert obs_trace.current() is obs_trace.NOOP

    def test_registry_alias_get_latest_eviction(self):
        reg = obs_trace.TraceRegistry(max_traces=2)
        t1, t2, t3 = (obs_trace.Tracer() for _ in range(3))
        reg.register(t1, "a1")
        reg.register(t2)
        reg.register(t3)  # evicts t1 and its alias
        assert reg.get(t1.trace_id) is None
        assert reg.get("a1") is None
        assert reg.get(t2.trace_id) is t2
        assert reg.latest() is t3
        reg.alias("x", "never-registered")  # ignored, not an error
        assert reg.get("x") is None
        reg.alias("y", t3.trace_id)
        assert reg.get("y") is t3



class TestPhases:
    def test_nesting_gives_self_time_and_items_add_up(self):
        tr = obs_trace.Tracer()
        with tr.span("query", "query"):
            with tr.phase("outer"):
                time.sleep(0.01)
                for k in (3, 5):
                    with tr.phase("inner", items=k):
                        time.sleep(0.01)
        ph = tr.to_json()["phases"]["coordinator"]
        outer, inner = ph["outer"], ph["inner"]
        assert (outer["n"], inner["n"], inner["items"]) == (1, 2, 8)
        assert inner["busy_s"] == inner["self_s"] >= 0.02
        assert outer["busy_s"] >= 0.03
        # busy less what was opened inside it on the same thread
        assert outer["self_s"] == pytest.approx(
            outer["busy_s"] - inner["busy_s"], abs=1e-5)
        assert inner["max_s"] <= inner["busy_s"]
        # a dump carries one span of kind `phase` per aggregate, with the
        # same id every time, hung under the root
        spans = [s for s in tr.spans() if s.kind == "phase"]
        assert sorted(s.name for s in spans) == ["inner", "outer"]
        assert {s.parent_id for s in spans} == {tr.root_id}
        assert [s.span_id for s in spans] == \
            [s.span_id for s in tr.spans() if s.kind == "phase"]
        assert spans[0].attrs["role"] == "coordinator"

    def test_two_threads_give_two_roles(self):
        tr = obs_trace.Tracer()

        def work():
            with obs_trace.use(tr), obs_trace.current().phase("scan_read"):
                pass

        with tr.span("task", "task"):
            threads = [threading.Thread(target=work, name=n) for n in
                       ("scan-prefetch", "task-q7.0.0", "task-q7.0.1.r1")]
            for t in threads:
                t.start()
            for t in threads:
                t.join(10)
            assert not any(t.is_alive() for t in threads)
        ph = obs_trace.phases_by_role(tr.spans())
        assert sorted(ph) == ["scan-prefetch", "task"]
        assert ph["task"]["scan_read"]["n"] == 2     # the ids are cut off
        assert ph["scan-prefetch"]["scan_read"]["n"] == 1

    def test_noop_tracer_records_nothing(self):
        before = len(obs_trace.summaries())
        with obs_trace.NOOP.span("query", "query"):
            with obs_trace.NOOP.phase("outer", items=2):
                with obs_trace.NOOP.phase("w", wait=True):
                    pass
        assert obs_trace.NOOP.spans() == []
        assert obs_trace.NOOP.to_json()["phases"] == {}
        assert len(obs_trace.summaries()) == before

    def test_wait_phase_enters_no_annotation(self, monkeypatch):
        entered = []

        class Spy:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                entered.append(self.name)

            def __exit__(self, *exc):
                pass

        monkeypatch.setattr(obs_trace, "_Annotation", Spy)
        tr = obs_trace.Tracer()
        with tr.phase("window_stack"):
            with tr.phase("window_queue_full", wait=True):
                pass
        assert entered == ["engine:window_stack"]
        ph = obs_trace.phases_by_role(tr.spans())
        (by_name,) = ph.values()
        assert by_name["window_queue_full"]["wait"] is True
        assert "wait" not in by_name["window_stack"]

    def test_query_span_close_leaves_a_summary(self):
        coord = obs_trace.Tracer(trace_id="T_sum")
        with coord.span("query", "query") as root:
            with coord.phase("schedule"):
                pass
            coord.record("exchange_wait", "exchange_wait", 0.0, 1.0,
                         parent_id=root.span_id, wait_s=0.25)
            worker = obs_trace.Tracer(trace_id="T_sum")
            with worker.span("task", "task"):
                with worker.phase("program_call:Aggregate"):
                    pass
                # a worker's own exchange wait is not the coordinator's
                worker.record("exchange_wait", "exchange_wait", 0.0, 1.0,
                              wait_s=9.0)
            coord.absorb(worker.to_json()["spans"])
        doc = obs_trace.summaries()[-1]
        assert doc["queryId"] == "T_sum" and doc["tasks"] == 1
        # the exchange_wait spans stay in the trace, for the doctor; the
        # summary no longer sums them
        assert "exchange_wait_s" not in doc and doc["dropped"] == 0
        assert doc["spans"] == len(coord.spans())
        assert doc["phases"]["coordinator"]["schedule"]["n"] == 1
        role = obs_trace._thread_role(threading.current_thread().name)
        assert doc["phases"][role]["program_call:Aggregate"]["n"] == 1
        # a task tracer's root is no query span: it leaves none of its own
        assert [d["queryId"] for d in obs_trace.summaries()].count("T_sum") == 1


# -- slow-query sink (unit) ------------------------------------------------


def _qinfo(qid="q1", elapsed=1.0):
    from presto_tpu.server.querymanager import QueryInfo

    now = 1000.0
    return QueryInfo(query_id=qid, sql="select 1", state="FINISHED",
                     user="u", resource_group=None, create_time=now,
                     end_time=now + elapsed)


def test_slow_query_logger_threshold_and_topk(tmp_path):
    p = str(tmp_path / "slow.jsonl")
    lg = SlowQueryLogger(p, threshold_s=0.5, top_k=2)
    lg.log(_qinfo(elapsed=0.1))  # below threshold: not logged
    spans = [obs_trace.Span(f"s{i}", None, f"op{i}", "operator",
                            0.0, float(i))
             for i in range(1, 5)]
    lg.log(_qinfo(qid="q2", elapsed=2.0), spans)
    with open(p) as fh:
        recs = [json.loads(line) for line in fh]
    assert len(recs) == 1
    rec = recs[0]
    assert rec["event"] == "queryCompleted"
    assert rec["queryId"] == "q2"
    assert rec["elapsedS"] == 2.0
    # top-k most expensive spans, most expensive first
    assert [t["name"] for t in rec["topSpans"]] == ["op4", "op3"]


# -- cluster integration ---------------------------------------------------


@pytest.fixture(scope="module")
def cluster():
    from presto_tpu.server.coordinator import DistributedRunner

    with DistributedRunner(_catalog(), n_workers=2) as dr:
        yield dr


class TestClusterTracing:
    def test_trace_token_propagation_and_stitching(self, cluster):
        coord = cluster.coordinator
        session = coord.protocol.session_from_headers({})
        qe = coord.query_manager.create_query(
            session, "select k, sum(v) as s from t group by k")
        assert qe.wait(60)
        assert qe.state == "FINISHED", qe.error
        with urllib.request.urlopen(
                f"{coord.url}/v1/query/{qe.query_id}/trace", timeout=10) as r:
            doc = json.loads(r.read())
        assert doc["traceId"] == qe.query_id
        spans = doc["spans"]
        by_kind = {}
        for s in spans:
            by_kind.setdefault(s["kind"], []).append(s)
        # worker task spans traveled back over the token header and got
        # stitched under synthesized stage spans under the query root
        assert "query" in by_kind and "stage" in by_kind \
            and "task" in by_kind
        root = next(s for s in spans if s["spanId"] == doc["rootSpanId"])
        assert root["name"] == "query"
        stage_ids = {s["spanId"] for s in by_kind["stage"]}
        for st in by_kind["stage"]:
            assert st["parentId"] == doc["rootSpanId"]
        for t in by_kind["task"]:
            assert t["parentId"] in stage_ids
            assert (t.get("attrs") or {}).get("node", "").startswith(
                "worker-")
        # the root span covers >= 95% of the whole trace envelope
        starts = [s["start"] for s in spans]
        ends = [s["end"] for s in spans if s["end"] is not None]
        envelope = max(ends) - min(starts)
        assert envelope >= 0.0
        assert root["durationS"] >= 0.95 * envelope
        # one nested tree rooted at the query span
        assert len(doc["tree"]) == 1
        assert doc["tree"][0]["spanId"] == doc["rootSpanId"]

    def test_statement_results_carry_trace_uri(self, cluster):
        req = urllib.request.Request(
            f"{cluster.coordinator.url}/v1/statement",
            data=b"select 1 as x", method="POST")
        with urllib.request.urlopen(req, timeout=30) as r:
            out = json.loads(r.read())
        assert "traceUri" in out
        assert "/trace" in out["traceUri"]

    def test_explain_analyze_compile_execute_split(self, cluster):
        out = cluster.coordinator.explain_analyze_distributed(
            "select k, avg(v) as a, max(v) as mx from t "
            "group by k having max(v) > -1e9")
        assert "-- task execution profile --" in out
        assert "wall=" in out
        # a first execution jit-compiles at least one node: the profile
        # splits per-operator wall into compile vs execute
        assert "compile=" in out and "execute=" in out

    def test_tracing_disabled_is_noop(self, cluster):
        import dataclasses as dc

        coord = cluster.coordinator
        before = coord.trace_registry.latest()
        cfg = dc.replace(cluster.config, tracing=False)
        coord.run_batch("select min(v) as x from t", cfg)
        assert coord.trace_registry.latest() is before

    def test_metrics_exposition_lint_both_planes(self, cluster):
        cluster.run("select count(*) as n from t")  # ensure observations
        urls = ([("coordinator", cluster.coordinator.url)]
                + [(w.node_id, w.url) for w in cluster.workers])
        for name, u in urls:
            with urllib.request.urlopen(f"{u}/v1/metrics", timeout=10) as r:
                body = r.read().decode()
            assert lint_exposition(body) == [], (name, lint_exposition(body))
            hist_fams = [line for line in body.splitlines()
                         if line.startswith("# TYPE")
                         and line.endswith(" histogram")]
            assert len(hist_fams) >= 4, name

    def test_ui_query_drilldown_page(self, cluster):
        coord = cluster.coordinator
        session = coord.protocol.session_from_headers({})
        qe = coord.query_manager.create_query(
            session, "select max(v) as mx from t")
        assert qe.wait(60)
        with urllib.request.urlopen(
                f"{coord.url}/ui/query/{qe.query_id}", timeout=10) as r:
            html = r.read().decode()
        assert qe.query_id in html
        assert "query" in html  # root span row renders
        # unknown query id 404s
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{coord.url}/ui/query/nope", timeout=10)
        assert ei.value.code == 404


def test_slow_query_log_end_to_end(tmp_path):
    from presto_tpu.server.coordinator import Coordinator
    from presto_tpu.server.worker import Worker

    log = str(tmp_path / "slow.jsonl")
    cat = _catalog()
    coord = Coordinator(cat, min_workers=1, slow_query_log=log)
    w = Worker(cat, node_id="w0", coordinator_url=coord.url)
    try:
        deadline = time.time() + 10
        while time.time() < deadline and not coord.node_manager.active_nodes():
            time.sleep(0.05)
        qe = coord.query_manager.create_query(
            coord.protocol.session_from_headers({}),
            "select sum(v) as s from t")
        assert qe.wait(60)
        assert qe.state == "FINISHED", qe.error
        with open(log) as fh:
            recs = [json.loads(line) for line in fh]
        assert recs
        rec = recs[-1]
        assert rec["queryId"] == qe.query_id
        assert rec["state"] == "FINISHED"
        # the trace's top spans ride along inline
        assert rec["topSpans"]
        assert all("durationS" in t for t in rec["topSpans"])
    finally:
        w.close()
        coord.close()


def test_local_runner_trace_and_disable():
    from presto_tpu.exec.runner import LocalRunner

    cat = _catalog()
    r = LocalRunner(cat)
    r.run("select k, sum(v) as s from t group by k")
    tr = r.last_trace
    assert tr is not None
    kinds = {s.kind for s in tr.spans()}
    assert "query" in kinds
    assert "operator" in kinds
    root = next(s for s in tr.spans() if s.span_id == tr.root_id)
    assert root.name == "query"
    # tracing off: NOOP end to end, nothing recorded
    r2 = LocalRunner(cat, ExecConfig(tracing=False))
    r2.run("select count(*) as n from t")
    assert r2.last_trace is None


# -- engine phases through the cluster ---------------------------------------


def _lineitem_catalog(sf="0.01"):
    from presto_tpu.server.__main__ import build_catalog

    return build_catalog([f"tpch:sf={sf}"])


def _query_text(qid):
    with open(os.path.join(ROOT, "benchmark", "queries", qid + ".json")) as f:
        params = json.load(f)["params"]["fixed"]
    with open(os.path.join(ROOT, "benchmark", "queries", qid + ".sql")) as f:
        return f.read().format(**params).strip()


def _run_statement(coord, sql):
    # 60,000 lineitem rows in batches of 8,192, so that windows are stacked
    qe = coord.query_manager.create_query(
        coord.protocol.session_from_headers(
            {"X-Presto-Session": "batch_rows=8192"}), sql)
    assert qe.wait(120)
    assert qe.state == "FINISHED", qe.error
    return qe.query_id


def _counts(summary):
    # how a consumer's pulls group pages into responses follows the clock,
    # so the request threads' `n` is left out
    return {(role, name): agg["n"]
            for role, by_name in summary["phases"].items()
            for name, agg in by_name.items() if role != "http"}


def test_statements_leave_phase_summaries_that_outlive_the_cluster():
    from presto_tpu.server.coordinator import DistributedRunner

    ids = {}
    with DistributedRunner(_lineitem_catalog(), n_workers=1) as dr:
        coord = dr.coordinator
        for qid in ("q1", "q6"):          # grouped, ungrouped
            sql = _query_text(qid)
            ids[qid] = [_run_statement(coord, sql) for _ in range(3)][1:]
        with urllib.request.urlopen(
                f"{coord.url}/v1/query/{ids['q1'][-1]}/trace", timeout=10) as r:
            doc = json.loads(r.read())
        assert doc["phases"]["coordinator"]["schedule"]["n"] == 1
        assert any(s["kind"] == "phase" for s in doc["spans"])
        for gone in ("host_decode", "device_transfer", "fragment_step"):
            assert not any(s["kind"] == gone for s in doc["spans"])
    # the cluster is closed: the summaries are still there
    by_id = {d["queryId"]: d for d in obs_trace.summaries()}
    for qid, (second, third) in ids.items():
        a, b = by_id[second], by_id[third]
        assert _counts(a) == _counts(b), qid      # counts repeat exactly
        assert a["spans"] == b["spans"] and a["dropped"] == 0
        task = a["phases"]["task"]
        assert any(n.startswith("program_call:") for n in task), task
        assert any(n.startswith("host_sync:") for n in task), task
        assert task["window_wait"]["wait"] is True
        # the fused path ran: batches were stacked into windows
        stack = a["phases"]["fragment-window-producer"]["window_stack"]
        assert stack["n"] >= 1 and stack["items"] >= 2
        assert a["phases"]["scan-prefetch"]["scan_read"]["n"] >= 7
        assert a["phases"]["coordinator"]["trace_collect"]["n"] == 1
        assert a["tasks"] >= 2 and a["task_wall_s"] > 0
        named = sum(agg["self_s"] for agg in task.values())
        assert 0 < named <= a["task_wall_s"] * 1.01
    assert "host_sync:breaker_finish" in by_id[ids["q1"][0]]["phases"]["task"]


PAGE_PHASES = {"task": ("page_ready", "page_fetch", "page_encode"),
               "http": ("page_serve",),
               "coordinator": ("page_decode", "page_upload")}


def _each(summary):
    for role, by_name in summary["phases"].items():
        for name, agg in by_name.items():
            yield role, name, agg


def _pages(summary, field):
    return {(role, name): agg.get(field) for role, name, agg in _each(summary)
            if name.startswith("page_")}


def test_a_page_trip_is_six_phases():
    """Q3 through one worker: the tasks hand each other device batches
    (`page_handoff`), so the root stream alone is pages. Its sink
    serializes each page in three phases inside `host_sync:sink_serialize`,
    a request thread serves it, and the coordinator decodes and uploads it,
    with `exchange_wait` round the queue alone."""
    from presto_tpu.server.coordinator import DistributedRunner

    with DistributedRunner(_lineitem_catalog(), n_workers=1) as dr:
        sql = _query_text("q3")
        ids = [_run_statement(dr.coordinator, sql) for _ in range(3)][1:]
    by_id = {d["queryId"]: d for d in obs_trace.summaries()}
    a, b = by_id[ids[0]], by_id[ids[1]]
    for role, names in PAGE_PHASES.items():
        for name in names:
            assert a["phases"][role][name]["n"] >= 1, (role, name)
    assert a["phases"]["coordinator"]["exchange_wait"]["wait"] is True
    task = a["phases"]["task"]
    assert task["page_handoff"]["n"] >= 1 and task["page_handoff"]["items"]
    assert "page_decode" not in task and "page_upload" not in task
    # the counts and the bytes repeat exactly; the request threads' `n`
    # follows the clock (module `_counts`), the pages they served do not
    assert _counts(a) == _counts(b)
    assert _pages(a, "items") == _pages(b, "items")
    # every page serialized is decoded once, and is the root stream's
    n = {name: sum(agg["n"] for _, nm, agg in _each(a) if nm == name)
         for name in ("page_encode", "page_decode", "page_fetch")}
    root = a["phases"]["coordinator"]["page_decode"]["n"]
    assert n["page_encode"] == n["page_decode"] == n["page_fetch"] == root
    served = a["phases"]["http"]["page_serve"]
    assert served["items"] == n["page_encode"] >= served["n"]
    # the sink's three phases are inside its serialize, not beside it
    inside = sum(task[p]["busy_s"] for p in PAGE_PHASES["task"])
    assert inside <= task["host_sync:sink_serialize"]["busy_s"]
    named = sum(agg["self_s"] for agg in task.values())
    assert 0 < named <= a["task_wall_s"] * 1.01


def test_spilled_pages_are_no_exchange_pages():
    """A grace aggregation writes and reads its spill files through the
    same serde, and records them as `agg_spill_write|read` alone."""
    from presto_tpu.exec.runner import LocalRunner

    r = LocalRunner(_lineitem_catalog(), ExecConfig(
        batch_rows=1 << 13, agg_capacity=1 << 8, agg_cap_ceiling=1 << 11,
        spill_partitions=4))
    df = r.run("select l_orderkey, sum(l_quantity) as q from lineitem "
               "group by l_orderkey")
    assert len(df) > 1 << 11
    names = {name for by_name in obs_trace.phases_by_role(
        r.last_trace.spans()).values() for name in by_name}
    assert {"agg_spill_write", "agg_spill_read"} <= names, names
    assert not any(name.startswith("page_") for name in names), names


def test_tracing_off_makes_the_device_calls_it_made(monkeypatch):
    """With tracing off no phase is recorded and serde makes no wait of its
    own on the device; on, a page waits once, and its bytes are the same."""
    import jax
    import jax.numpy as jnp

    from presto_tpu import serde
    from presto_tpu.batch import Batch, Column
    from presto_tpu.server.coordinator import DistributedRunner
    from presto_tpu.types import BIGINT, DOUBLE

    waits = []
    block = jax.block_until_ready

    def counting(x):
        if sys._getframe(1).f_code.co_filename == serde.__file__:
            waits.append(1)
        return block(x)

    monkeypatch.setattr(jax, "block_until_ready", counting)
    b = Batch(["k", "v"], [BIGINT, DOUBLE],
              [Column(jnp.arange(8, dtype=jnp.int64),
                      jnp.arange(8) % 3 > 0),
               Column(jnp.linspace(0.0, 1.0, 8))],
              jnp.arange(8) % 2 == 0)
    plain = serde.serialize_batch(b)
    assert waits == []
    tr = obs_trace.Tracer()
    assert serde.serialize_batch(b, tracer=tr) == plain
    assert len(waits) == 1
    back = serde.deserialize_batch(plain, tracer=tr)
    assert np.asarray(back.columns[0].values)[:4].tolist() == [0, 2, 4, 6]
    ph = obs_trace.phases_by_role(tr.spans())
    (mine,) = ph.values()
    # the planes whole: live 8 B, k 64 B and its validity 8 B, v 64 B
    assert mine["page_fetch"]["items"] == 144
    assert mine["page_encode"]["items"] == len(plain)
    assert mine["page_decode"]["items"] == len(plain)
    # four rows padded to 2^k lanes: live, k, its validity, v
    cap = back.capacity
    assert mine["page_upload"]["items"] == cap * (1 + 8 + 1 + 8)

    before = len(obs_trace.summaries())
    with DistributedRunner(_catalog(), n_workers=1,
                           config=ExecConfig(tracing=False)) as dr:
        df = dr.run("select k, sum(v) as s from t group by k")
    assert len(df) == 7
    assert len(obs_trace.summaries()) == before
    assert len(waits) == 1


def test_engine_phases_share_the_profilers_clock(tmp_path):
    """Off the chip: under a profiler session the phases are events on the
    engine threads' lines of the xplane, inside the benchmark's window."""
    import jax.profiler

    from benchmark import trace_reduce
    from presto_tpu.server.coordinator import DistributedRunner

    with DistributedRunner(_lineitem_catalog(), n_workers=1) as dr:
        sql = _query_text("q6")
        _run_statement(dr.coordinator, sql)      # compile outside the trace
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
                _run_statement(dr.coordinator, sql)
        finally:
            jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    trace = trace_reduce.load_xplane(path)
    window, found, lines = None, {}, {}
    for p, plane in enumerate(trace["planes"]):
        for i, line in enumerate(plane["lines"]):   # one an OS thread id
            for name, start, dur in line["events"]:
                if name == trace_reduce.WINDOW_SPAN:
                    window = (start, start + dur)
                elif name.startswith("engine:") and \
                        line["name"].startswith(trace_reduce.PYTHON_LINE):
                    found.setdefault(name, []).append((start, start + dur))
                    lines.setdefault((p, i), []).append(
                        (name, start, start + dur))
    assert window is not None
    assert "engine:window_stack" in found, sorted(found)
    assert any(n.startswith("engine:program_call:") for n in found)
    for page in ("fetch", "encode", "decode", "upload"):
        assert "engine:page_" + page in found, sorted(found)
    # a worker's request thread serves the pages, not the sink that made
    # them: no page is served inside a serialize on its own line
    assert "engine:page_serve" in found, sorted(found)
    for events in lines.values():
        sinks = [(a, b) for n, a, b in events
                 if n == "engine:host_sync:sink_serialize"]
        for n, a, b in events:
            if n == "engine:page_serve":
                assert not any(s <= a and b <= e for s, e in sinks)
    assert not any("wait" in n or "queue_full" in n for n in found)
    for spans in found.values():
        for a, b in spans:
            assert window[0] <= a <= b <= window[1]


@pytest.mark.parametrize("qid", ["q6", "q1"])
def test_fragment_programs_carry_operator_names(qid, monkeypatch):
    """`jax.named_scope` at trace time: the fused fragment program's
    operations are located under the operator that made them."""
    from presto_tpu.exec import programs
    from presto_tpu.exec.runner import LocalRunner

    texts = {}
    wrap = programs.wrap

    def spying_wrap(entry, node_stats, node_kind, key):
        fn = wrap(entry, node_stats, node_kind, key)
        if "fragment_step" not in key:
            return fn

        def call(*args, **kw):
            if key not in texts:
                texts[key] = entry.jfn.lower(*args, **kw).as_text(
                    debug_info=True)
            return fn(*args, **kw)

        call._entry = entry
        return call

    monkeypatch.setattr(programs, "wrap", spying_wrap)
    LocalRunner(_lineitem_catalog(), ExecConfig(batch_rows=8192)).run(
        _query_text(qid))
    assert texts, "no fused fragment program ran"
    for key, text in texts.items():
        assert "breaker_step/scan_chain/" in text, key


# -- runtime statistics feedback plane (obs/runstats.py) -------------------


class TestRunstatsExposition:
    def test_drift_histogram_is_builtin(self):
        names = {h.name for h in obs_metrics.ALL_HISTOGRAMS}
        assert "presto_tpu_stats_drift_ratio" in names

    def test_hbo_families_on_metrics_endpoints(self, cluster):
        from presto_tpu.obs import runstats

        runstats.observe("fpT/cat", "agg_groups", "aggregate", 2.0, 8.0)
        for u in ([cluster.coordinator.url]
                  + [w.url for w in cluster.workers]):
            with urllib.request.urlopen(f"{u}/v1/metrics", timeout=10) as r:
                body = r.read().decode()
            assert lint_exposition(body) == []
            assert "presto_tpu_hbo_observations_total" in body
            assert "presto_tpu_hbo_history_entries" in body
            assert "presto_tpu_stats_drift_ratio_bucket" in body
            assert "presto_tpu_breaker_replay_waves_total" in body

    def test_mesh_emits_exchange_and_lane_spans(self):
        from presto_tpu.parallel.mesh import make_mesh
        from presto_tpu.parallel.mesh_exec import MeshExecutor

        cat = _catalog()
        mx = MeshExecutor(cat, make_mesh(8), ExecConfig())
        tr = obs_trace.Tracer()
        with obs_trace.use(tr):
            mx.run("select k, sum(v) as s from t group by k")
        kinds = {s.kind for s in tr.spans()}
        # PR 9's fused collectives bypass the tracer; the host-side
        # markers close that wall-time hole
        assert "mesh_program" in kinds
        assert "exchange_wait" in kinds
        assert "lane_pack" in kinds
        assert "breaker_engine" in kinds
        ew = next(s for s in tr.spans() if s.kind == "exchange_wait")
        assert {"fid", "bytes", "lanes_used", "lanes_total",
                "util"} <= set(ew.attrs)
        mp = next(s for s in tr.spans() if s.kind == "mesh_program")
        assert ew.parent_id == mp.span_id


def test_slow_query_logger_hbo_fields(tmp_path):
    p = str(tmp_path / "slow.jsonl")
    lg = SlowQueryLogger(p, threshold_s=0.0)
    spans = [
        obs_trace.Span("s1", None, "breaker_engine", "breaker_engine",
                       0.0, 0.0, {"node": "Aggregate", "engine": "sort",
                                  "why": "observed 6e+03 groups"}),
        obs_trace.Span("s2", None, "exchange f0", "exchange_wait",
                       0.0, 0.0, {"fid": 0, "lanes_used": 12,
                                  "lanes_total": 64, "util": 0.1875}),
        obs_trace.Span("s3", None, "overflow_replay", "overflow_replay",
                       0.0, 0.0, {"node": "Aggregate", "cap_to": 8192}),
        obs_trace.Span("s4", None, "overflow_replay", "overflow_replay",
                       0.0, 0.0, {"node": "HashJoin"}),
    ]
    lg.log(_qinfo(qid="q9", elapsed=1.0), spans)
    with open(p) as fh:
        rec = json.loads(fh.readlines()[-1])
    assert rec["breakerEngines"] == [
        {"node": "Aggregate", "engine": "sort",
         "why": "observed 6e+03 groups"}]
    assert rec["laneUtil"] == [
        {"fid": 0, "lanesUsed": 12, "lanesTotal": 64, "util": 0.1875}]
    assert rec["overflowReplays"] == 2
    assert rec["overflowBoosts"] == 1  # only the cap_to-carrying wave
