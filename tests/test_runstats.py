"""Runtime-statistics feedback plane (obs/runstats.py): drift math,
history persistence + keying, the hbo=off strict no-op contract, and the
two-run acceptance loop — a workload whose static NDV estimate is 10×
wrong flips to the correct breaker engine and presize on its second run,
with zero overflow-replay waves.

Reference analog: Presto's history-based optimizer (HBO) keyed on plan
canonical hashes; here the key is the PR 5 structural fingerprint plus a
catalog snapshot token.
"""

import json
import os

import numpy as np
import pandas as pd
import pytest

from presto_tpu.catalog.memory import MemoryConnector
from presto_tpu.connector import Catalog
from presto_tpu.exec import ExecConfig
from presto_tpu.exec.runner import LocalRunner
from presto_tpu.obs import metrics as obs_metrics
from presto_tpu.obs import runstats
from presto_tpu.obs.exposition import lint_exposition
from presto_tpu.ops.grouping import partition_skew
from presto_tpu.plan.stats import exchange_lane_rows
from presto_tpu.scan import metrics as scan_metrics
from presto_tpu.server.metrics import render_metrics


@pytest.fixture
def history_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("PRESTO_TPU_CACHE_DIR", str(tmp_path))
    runstats.reset()
    scan_metrics.reset()
    yield tmp_path
    runstats.reset()


@pytest.fixture
def no_history(monkeypatch):
    monkeypatch.delenv("PRESTO_TPU_CACHE_DIR", raising=False)
    runstats.reset()
    scan_metrics.reset()
    yield
    runstats.reset()


def _skewed_catalog(n=6000):
    """All-distinct keys grouped through an EXPRESSION: the memory
    connector's exact column NDV can't see through `k % 100000`, so the
    planner falls back to the rows*0.1 heuristic — a 10× underestimate."""
    conn = MemoryConnector()
    conn.add_table("t", pd.DataFrame({
        "k": np.arange(n, dtype=np.int64),
        "v": np.ones(n, dtype=np.int64)}))
    cat = Catalog()
    cat.register("m", conn, default=True)
    return cat


SKEW_SQL = "select k % 100000 as g, sum(v) from m.t group by 1"


# -- unit: store semantics -------------------------------------------------


class TestStore:
    def test_observe_max_merge_and_drift(self, no_history):
        e1 = runstats.observe("fp1/cat", "agg_groups", "aggregate",
                              est=100.0, actual=1000.0)
        assert e1["actual"] == 1000.0 and e1["n"] == 1
        # later smaller observation keeps the high-water mark (capacity
        # consumers need the worst case), but counts the observation
        e2 = runstats.observe("fp1/cat", "agg_groups", "aggregate",
                              est=100.0, actual=400.0)
        assert e2["actual"] == 1000.0 and e2["n"] == 2
        snap = obs_metrics.STATS_DRIFT.snapshot("worker")
        counts = [s["count"] for s in snap.values()]
        assert sum(counts) >= 2

    def test_extras_merge_and_note(self, no_history):
        runstats.observe("fp2/cat", "agg_groups", "aggregate",
                         est=10.0, actual=20.0, extra={"replays": 2})
        runstats.note("fp2/cat", "agg_groups", replays=1, why="x")
        ent = runstats.lookup("fp2/cat", "agg_groups")
        assert ent["replays"] == 2.0  # max-merge
        assert ent["why"] == "x"

    def test_none_fp_is_noop(self, no_history):
        assert runstats.observe(None, "s", "op", 1.0, 2.0) is None
        runstats.note(None, "s", x=1)
        assert runstats.lookup(None, "s") is None
        assert runstats.snapshot()["history"] == {}

    def test_generation_bumps_on_mutation(self, no_history):
        g0 = runstats.generation()
        runstats.observe("fp3/cat", "s", "op", 1.0, 2.0)
        assert runstats.generation() > g0

    def test_history_jsonl_round_trip(self, history_dir):
        runstats.observe("fpA/cat", "agg_groups", "aggregate",
                         est=5.0, actual=50.0, extra={"skew": 2.5})
        path = history_dir / "hbo_history.jsonl"
        assert path.exists()
        recs = [json.loads(x) for x in path.read_text().splitlines()]
        assert recs[-1]["fp"] == "fpA/cat"
        assert recs[-1]["actual"] == 50.0
        # a fresh process (reset forces reload) sees the persisted entry
        runstats.reset()
        ent = runstats.lookup("fpA/cat", "agg_groups")
        assert ent is not None and ent["actual"] == 50.0
        assert ent["skew"] == 2.5

    def test_last_line_wins_on_load(self, history_dir):
        path = history_dir / "hbo_history.jsonl"
        path.write_text(
            json.dumps({"fp": "f/c", "site": "s", "actual": 10.0, "n": 1})
            + "\n"
            + json.dumps({"fp": "f/c", "site": "s", "actual": 99.0, "n": 2})
            + "\n" + "not json\n")
        runstats.reset()
        assert runstats.lookup("f/c", "s")["actual"] == 99.0

    def test_no_cache_dir_stays_in_memory(self, no_history):
        assert runstats.history_path() is None
        runstats.observe("fpB/cat", "s", "op", 1.0, 2.0)
        assert runstats.lookup("fpB/cat", "s")["actual"] == 2.0


class TestFingerprint:
    def test_keying_structure_and_catalog(self, no_history):
        cat = _skewed_catalog(100)
        r = LocalRunner(cat)
        qp1 = r.plan("select k from m.t where k > 5")
        qp2 = r.plan("select k from m.t where k > 9")
        qp3 = r.plan(SKEW_SQL)
        fp1 = runstats.node_fingerprint(qp1.root.child, cat)
        fp2 = runstats.node_fingerprint(qp2.root.child, cat)
        fp3 = runstats.node_fingerprint(qp3.root.child, cat)
        # literals differ but the structure is the same shape-class only
        # when the structural fingerprint says so; distinct operators
        # must never collide
        assert fp1 != fp3 and fp2 != fp3
        # same node → memoized, stable
        assert runstats.node_fingerprint(qp1.root.child, cat) == fp1
        # data change flips the catalog token half of every key
        tok_before = runstats.catalog_token(cat)
        cat.connectors["m"].add_table("t2", pd.DataFrame({"x": [1, 2]}))
        assert runstats.catalog_token(cat) != tok_before

    def test_fingerprint_strips_config_suffix(self, no_history):
        # the history key is the config-free structural sha, whatever the
        # compile plane stamped: its `_program_ns` (program form plus the
        # config fingerprint) keys nothing here
        from presto_tpu.exec.programs import structural_fingerprint
        from presto_tpu.plan.nodes import RemoteSource

        cat = _skewed_catalog(100)
        node = LocalRunner(cat).plan("select k from m.t where k > 5").root.child
        node.__dict__["_program_ns"] = "a" * 40 + "f" * 16  # sha + config fp
        fp = runstats.node_fingerprint(node, cat)
        assert fp == structural_fingerprint(node)[:24] + "/" + \
            runstats.catalog_token(cat)
        # two subtrees alike but for the exchange that feeds them share a
        # program namespace and keep history keys of their own
        src = [RemoteSource(fragment_id=f, output=list(node.output))
               for f in (1, 2)]
        a, b = (structural_fingerprint(s, program=True) for s in src)
        assert a is not None and a == b
        assert runstats.node_fingerprint(src[0], cat) != \
            runstats.node_fingerprint(src[1], cat)


class TestMetricRows:
    def test_exposition_families_and_lint(self, no_history):
        runstats.observe("fpC/cat", "agg_groups", "aggregate", 1.0, 4.0)
        runstats.record_flip("breaker_engine")
        runstats.record_correction("agg_presize")
        rows = runstats.metric_rows({"plane": "worker"})
        doc = render_metrics(rows)
        assert lint_exposition(doc) == []
        assert 'presto_tpu_hbo_observations_total{plane="worker",' \
               'site="agg_groups"} 1' in doc
        assert 'presto_tpu_hbo_would_flip_total{plane="worker",' \
               'site="breaker_engine"} 1' in doc
        assert 'presto_tpu_hbo_corrections_total{plane="worker",' \
               'site="agg_presize"} 1' in doc
        assert "presto_tpu_hbo_history_entries" in doc

    def test_drift_histogram_family_renders(self, no_history):
        runstats.observe("fpD/cat", "scan_rows", "tablescan", 10.0, 20.0)
        doc = "\n".join(obs_metrics.STATS_DRIFT.render("worker")) + "\n"
        assert lint_exposition(doc) == []
        assert "presto_tpu_stats_drift_ratio_bucket" in doc


# -- unit: planner hooks ---------------------------------------------------


class TestPlannerHooks:
    def test_exchange_lane_rows_observed_override(self):
        static = exchange_lane_rows(10000.0, 100.0, 4)
        observed = exchange_lane_rows(10000.0, 100.0, 4,
                                      observed_lane_rows=40.0)
        assert observed == 50.0  # 40 × 1.25 headroom
        assert observed != static
        # None / 0 fall through to the static path
        assert exchange_lane_rows(10000.0, 100.0, 4,
                                  observed_lane_rows=None) == static
        assert exchange_lane_rows(10000.0, 100.0, 4,
                                  observed_lane_rows=0.0) == static

    def test_partition_skew(self):
        assert partition_skew([10, 10, 10, 10]) == 1.0
        assert partition_skew([40, 0, 0, 0]) == 1.0  # one live partition
        assert partition_skew([30, 10]) == pytest.approx(1.5)
        assert partition_skew([]) == 1.0


# -- acceptance: the two-run feedback loop ---------------------------------


class TestFeedbackLoop:
    def test_run1_observes_drift_run2_corrects(self, history_dir):
        cat = _skewed_catalog()
        r1 = LocalRunner(cat, ExecConfig(hbo="observe"))
        txt1 = r1.explain_analyze(SKEW_SQL)
        # run 1: static estimate 600 groups → hash engine, presize 4096;
        # actual 6000 distinct groups → ≥1 overflow-replay wave and a 10×
        # drift annotation
        assert "engine=hash" in txt1
        assert "drift=10x" in txt1
        assert r1.last_stats.get("breaker.replay_waves", 0) >= 1
        snap = runstats.snapshot()
        assert snap["observations"].get("agg_groups") == 1
        assert snap["would_flip"].get("breaker_engine") == 1
        ent = [e for k, e in snap["history"].items()
               if k.endswith("|agg_groups")]
        assert ent and ent[0]["actual"] == 6000.0 and ent[0]["est"] == 600.0

        # run 2 (fresh runner, same structure): history flips the engine
        # choice, presizes past the observed group count, zero waves
        r2 = LocalRunner(cat, ExecConfig(hbo="correct"))
        txt2 = r2.explain_analyze(SKEW_SQL)
        assert "(hbo: observed)" in txt2
        assert "engine=sort" in txt2
        assert r2.last_stats.get("breaker.replay_waves", 0) == 0
        corr = runstats.snapshot()["corrections"]
        assert corr.get("breaker_engine", 0) >= 1
        assert corr.get("agg_presize", 0) >= 1
        # same answer both runs (group-by output order is engine-defined)
        d1 = r1.run(SKEW_SQL).sort_values("g").reset_index(drop=True)
        d2 = r2.run(SKEW_SQL).sort_values("g").reset_index(drop=True)
        assert d1.equals(d2)

    def test_hbo_off_is_strict_noop(self, history_dir):
        cat = _skewed_catalog()
        r = LocalRunner(cat, ExecConfig(hbo="off"))
        txt = r.explain_analyze(SKEW_SQL)
        # pre-HBO behavior: static choice, no provenance, no drift marker,
        # nothing observed, nothing persisted
        assert "engine=hash" in txt
        assert "(hbo: observed)" not in txt
        assert "drift=" not in txt
        snap = runstats.snapshot()
        assert snap["history"] == {}
        assert snap["observations"] == {}
        assert not (history_dir / "hbo_history.jsonl").exists()
        # ...but replay-wave telemetry still counts (the wave happened)
        assert r.last_stats.get("breaker.replay_waves", 0) >= 1

    def test_observe_mode_never_changes_decisions(self, history_dir):
        cat = _skewed_catalog()
        r1 = LocalRunner(cat, ExecConfig(hbo="observe"))
        r1.run_batch(SKEW_SQL)
        # warm history, but observe-mode runs keep using static estimates
        r2 = LocalRunner(cat, ExecConfig(hbo="observe"))
        txt = r2.explain_analyze(SKEW_SQL)
        assert "engine=hash" in txt
        assert "(hbo: observed)" not in txt

    def test_session_property_plumbs_hbo(self):
        from presto_tpu.server.session import Session, SessionPropertyError

        s = Session()
        assert s.exec_config().hbo == "observe"
        s.set("hbo", "CORRECT")
        assert s.exec_config().hbo == "correct"
        with pytest.raises(SessionPropertyError):
            s.set("hbo", "sometimes")


class TestHistoryCompaction:
    """Satellite of the devprof PR: the history store ages out on load
    (TTL + entry cap) and `python -m presto_tpu.obs.runstats --compact`
    rewrites the append-only JSONL to one line per live entry."""

    def _write(self, path, records):
        with open(path, "a") as fh:
            for rec in records:
                fh.write(json.dumps(rec) + "\n")

    def test_ttl_drops_stale_entries_on_load(self, history_dir):
        import time as _time

        now = _time.time()
        path = history_dir / "hbo_history.jsonl"
        self._write(path, [
            {"fp": "old", "site": "s", "actual_rows": 1,
             "ts": now - 100 * 86400.0},
            {"fp": "fresh", "site": "s", "actual_rows": 2, "ts": now},
            # ts-less records predate the TTL stamp — kept, not dropped
            {"fp": "legacy", "site": "s", "actual_rows": 3},
        ])
        runstats.reset()  # force lazy reload with the default TTL
        assert runstats.lookup("old", "s") is None
        assert runstats.lookup("fresh", "s")["actual_rows"] == 2
        assert runstats.lookup("legacy", "s")["actual_rows"] == 3

    def test_entry_cap_keeps_newest(self, history_dir):
        import time as _time

        now = _time.time()
        path = history_dir / "hbo_history.jsonl"
        self._write(path, [
            {"fp": f"fp{i}", "site": "s", "actual_rows": i, "ts": now + i}
            for i in range(6)])
        res = runstats.compact(max_entries=2)
        assert res["lines_before"] == 6 and res["entries"] == 2
        assert runstats.lookup("fp5", "s") is not None
        assert runstats.lookup("fp4", "s") is not None
        assert runstats.lookup("fp0", "s") is None
        # the file itself was rewritten to the survivors
        assert len(path.read_text().splitlines()) == 2

    def test_compact_rewrites_superseded_lines(self, history_dir):
        # same (fp, site) observed repeatedly: append-only bloat, one
        # live entry
        for actual in (10, 20, 30):
            runstats.observe("fp", "s", "groupby", est=5, actual=actual)
        path = history_dir / "hbo_history.jsonl"
        assert len(path.read_text().splitlines()) == 3
        res = runstats.compact()
        assert res["lines_before"] == 3 and res["entries"] == 1
        assert len(path.read_text().splitlines()) == 1
        ent = runstats.lookup("fp", "s")
        assert ent["actual"] == 30.0  # the merged (latest/max) entry wins

    def test_cli_compact(self, history_dir, capsys):
        runstats.observe("fp", "s", "groupby", est=5, actual=7)
        runstats.observe("fp", "s", "groupby", est=5, actual=9)
        assert runstats.main(["--compact"]) == 0
        out = capsys.readouterr().out
        assert "compacted" in out and "2 lines -> 1 entries" in out

    def test_cli_without_cache_dir(self, no_history, capsys):
        assert runstats.main(["--compact"]) == 1
        assert "PRESTO_TPU_CACHE_DIR is not set" in capsys.readouterr().out
