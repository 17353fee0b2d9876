"""Mid-flight telemetry plane: publisher store, heartbeat merge,
stall/straggler/drift detection, the query doctor, and metric families."""

import json
import time

import pytest

from presto_tpu.obs import events as obs_events
from presto_tpu.obs import inflight
from presto_tpu.obs import lifecycle


@pytest.fixture(autouse=True)
def _reset():
    inflight.reset()
    lifecycle.reset()
    obs_events.EVENTS.clear()
    yield
    inflight.reset()
    lifecycle.reset()
    obs_events.EVENTS.clear()


def _wait_for(pred, timeout=3.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(0.01)
    return pred()


# ---------------------------------------------------------------------------
# publisher store


def test_publish_accumulates_counters_and_overwrites_gauges():
    t = inflight.TaskInflight("q1", "q1.0.0")
    t.publish("Aggregate", rows_in=10, rows_out=5, windows=1, batches=2,
              overflow=3, cap=64)
    t.publish("Aggregate", rows_in=7, rows_out=4, windows=1, batches=1,
              overflow=0, cap=128)
    d = t.ops["Aggregate"]
    assert d["rowsIn"] == 17 and d["rowsOut"] == 9
    assert d["windows"] == 2 and d["batches"] == 3
    # gauges overwrite: the doc reports the CURRENT overflow vector
    assert d["overflow"] == 0 and d["cap"] == 128
    assert d["seq"] == 2
    # unknown gauge keys are dropped, not stored
    t.publish("Aggregate", bogus_key=1)
    assert "bogus_key" not in t.ops["Aggregate"]


def test_snapshot_ring_bounded_to_depth():
    t = inflight.TaskInflight("q1", "q1.0.0")
    for i in range(inflight.SNAPSHOT_DEPTH + 5):
        t.publish("Sort", windows=1, stagedWindows=i)
    snaps = list(t.ops["Sort"]["snapshots"])
    assert len(snaps) == inflight.SNAPSHOT_DEPTH
    # ring keeps the most recent snapshots
    assert snaps[-1]["windows"] == inflight.SNAPSHOT_DEPTH + 5


def test_registry_register_alias_and_snapshot_doc():
    inflight.register("qs", group="global.adhoc", stall_threshold_s=60)
    inflight.alias("attempt1", "qs")
    t0 = inflight.task("attempt1", "attempt1.0.0", fragment=0)
    t1 = inflight.task("attempt1", "attempt1.1.0", fragment=1)
    t0.publish("TableScan", rows_out=100, windows=2)
    t1.publish("Aggregate", rows_in=100, rows_out=10, windows=1,
               repartitions=2, spillDepth=1)
    doc = inflight.snapshot_doc("qs")
    assert doc["queryId"] == "qs" and doc["group"] == "global.adhoc"
    assert doc["publishes"] == 2
    assert doc["fragments"]["0"]["rowsOut"] == 100
    assert doc["fragments"]["1"]["repartitions"] == 2
    assert doc["fragments"]["1"]["spillDepth"] == 1
    assert len(doc["tasks"]) == 2
    # alias resolves for the attempt id too
    assert inflight.snapshot_doc("attempt1")["queryId"] == "qs"
    assert inflight.snapshot_doc("q_unknown") is None


def test_merge_worker_seq_guarded_idempotent():
    e = inflight.register("qm", stall_threshold_s=60)
    t = inflight.task("qm", "qm.0.0")
    t.publish("Join", rows_out=50, windows=1)
    hb = {"qm": {"qm.0.0": t.doc()}}
    # in-process cluster: the heartbeat re-reports a publisher already in
    # the registry — merging it twice must not double-count
    inflight.merge_worker("w0", hb)
    inflight.merge_worker("w0", hb)
    assert e.total_rows_out() == 50
    # a NEWER doc from the wire replaces the held op state
    newer = json.loads(json.dumps(hb))  # deep copy
    od = newer["qm"]["qm.0.0"]["ops"]["Join"]
    od["seq"] = 5
    od["rowsOut"] = 80
    inflight.merge_worker("w0", newer)
    assert e.total_rows_out() == 80


def test_finish_marks_entry_and_metric_gauge_drops():
    inflight.register("qf", stall_threshold_s=60)
    rows = inflight.metric_rows({})
    assert ("presto_tpu_inflight_queries", rows[0][2]) == (rows[0][0], 1)
    inflight.finish("qf")
    rows = inflight.metric_rows({})
    assert rows[0][2] == 0
    assert inflight.snapshot_doc("qf")["finished"] is True


# ---------------------------------------------------------------------------
# stall / straggler / drift detection


def test_stall_detected_event_forensics_and_episode_close(tmp_path):
    inflight.configure(forensics_dir=str(tmp_path))
    e = inflight.register("q_stall", group="g", stall_threshold_s=0.1)
    t = inflight.task("q_stall", "q_stall.0.0")
    t.publish("Aggregate", windows=1, rows_out=5)
    t.publish("Aggregate", windows=1, rows_out=5)
    assert _wait_for(lambda: e.stalls >= 1)
    # the watcher counts the stall, then emits the event, then writes the
    # forensics: wait for the last of the three
    forensics = tmp_path / "inflight_forensics.jsonl"
    assert _wait_for(
        lambda: forensics.exists() and forensics.read_text().endswith("\n"))
    evs = obs_events.EVENTS.events(query_id="q_stall",
                                   kind="stall_detected")
    assert evs and evs[0]["operator"] == "Aggregate"
    assert evs[0]["taskId"] == "q_stall.0.0"
    assert evs[0]["stalledS"] > 0.1
    # forensic JSONL: last-N window snapshots per operator
    rec = json.loads(forensics.read_text().splitlines()[-1])
    assert rec["queryId"] == "q_stall" and rec["operator"] == "Aggregate"
    snaps = rec["ops"]["q_stall.0.0/Aggregate"]["snapshots"]
    assert len(snaps) >= 2
    # the next publish closes the episode, booking wall to the stuck op
    t.publish("Aggregate", windows=1)
    assert e._stall_since is None
    assert e.stall_seconds.get("Aggregate", 0.0) > 0.0
    # while stalled the watcher does not re-flag — exactly one episode
    assert e.stalls == 1


def test_straggler_detected_once_per_site():
    e = inflight.register("q_strag", stall_threshold_s=60,
                          straggler_factor=2.0)
    fast = inflight.task("q_strag", "q_strag.0.0", fragment=0)
    slow = inflight.task("q_strag", "q_strag.0.1", fragment=0)
    slow.publish("Scan", windows=1)
    for _ in range(10):
        fast.publish("Scan", windows=1)
    assert _wait_for(lambda: len(e.stragglers) >= 1)
    evs = obs_events.EVENTS.events(query_id="q_strag",
                                   kind="straggler_detected")
    assert len(evs) == 1
    assert evs[0]["taskId"] == "q_strag.0.1"
    assert evs[0]["leaderTaskId"] == "q_strag.0.0"
    assert evs[0]["leaderWindows"] == 10
    assert evs[0]["laggardWindows"] == 1
    # flagged once: more skew does not re-emit for the same site
    for _ in range(5):
        fast.publish("Scan", windows=1)
    time.sleep(0.1)
    assert len(obs_events.EVENTS.events(query_id="q_strag",
                                        kind="straggler_detected")) == 1


def test_straggler_floor_suppresses_start_of_run_skew():
    e = inflight.register("q_floor", stall_threshold_s=60,
                          straggler_factor=4.0)
    a = inflight.task("q_floor", "q_floor.0.0", fragment=0)
    inflight.task("q_floor", "q_floor.0.1", fragment=0)
    # 2-vs-0 windows is below the minimum-progress floor (max(2, factor))
    a.publish("Scan", windows=1)
    a.publish("Scan", windows=1)
    time.sleep(0.15)
    assert e.stragglers == []


def test_inflight_drift_throttled_doubling():
    lifecycle.register("q_drift")
    lc = lifecycle.get("q_drift")
    lc.predicted = {"sink_rows": 10, "rows": 10, "wall_s": 1.0}
    e = inflight.register("q_drift", stall_threshold_s=60)
    t = inflight.task("q_drift", "q_drift.0.0")
    t.publish("Scan", rows_out=25, windows=1)  # 2.5x predicted
    assert _wait_for(lambda: bool(obs_events.EVENTS.events(
        query_id="q_drift", kind="inflight_drift")))
    evs = obs_events.EVENTS.events(query_id="q_drift", kind="inflight_drift")
    assert evs[0]["ratio"] == pytest.approx(2.5)
    # throttle doubled past the observed ratio: staying at 2.5x is quiet
    assert e._next_drift_ratio >= 4.0
    time.sleep(0.1)
    assert len(obs_events.EVENTS.events(query_id="q_drift",
                                        kind="inflight_drift")) == 1


# ---------------------------------------------------------------------------
# query doctor


def test_doctor_stall_outranks_generic_exec():
    entry = lifecycle.register("q_doc")
    entry.timeline.mark("queued")
    entry.timeline.mark("admitted")
    entry.timeline.mark("planning")
    entry.timeline.mark("compiling")
    entry.timeline.mark("executing")
    e = inflight.register("q_doc", stall_threshold_s=60)
    # book a closed stall episode covering most of the wall by hand
    e.stall_seconds["Aggregate"] = 10.0
    time.sleep(0.02)
    doc = inflight.analyze("q_doc")
    assert doc is not None
    top = doc["causes"][0]
    assert top["cause"] == "stall" and top["operator"] == "Aggregate"
    assert "Aggregate" in doc["verdict"]
    assert doc["inflight"]["publishes"] == 0


def test_doctor_cache_hit_is_terminal_verdict():
    lifecycle.register("q_cache")
    lifecycle.note_cache("q_cache", {"key": "abc", "savedS": 1.2})
    doc = inflight.analyze("q_cache")
    assert doc["causes"][0]["cause"] == "result_cache"
    assert doc["causes"][0]["score"] == 1.0


def test_doctor_hbo_drift_cause():
    entry = lifecycle.register("q_hbo")
    entry.predicted = {"wall_s": 0.001, "rows": 1, "sink_rows": 1}
    entry.timeline.mark("executing")
    time.sleep(0.02)
    doc = inflight.analyze("q_hbo")
    drift = [c for c in doc["causes"] if c["cause"] == "hbo_drift"]
    assert drift and "under actual" in drift[0]["detail"]


def test_doctor_none_when_no_plane_saw_query():
    assert inflight.analyze("q_nothing") is None


def test_slow_log_annotation_carries_doctor_and_stragglers():
    e = inflight.register("q_slow", stall_threshold_s=60)
    e.stragglers.append({"fragment": 0, "taskId": "q_slow.0.1",
                         "leaderTaskId": "q_slow.0.0",
                         "leaderWindows": 10, "laggardWindows": 1,
                         "factor": 4.0, "ts": 0.0})
    ann = inflight.slow_log_annotation("q_slow")
    assert "doctor" in ann and "verdict" in ann["doctor"]
    assert ann["stragglers"][0]["taskId"] == "q_slow.0.1"
    assert inflight.slow_log_annotation("q_other") is None


# ---------------------------------------------------------------------------
# metric families + exposition


def test_metric_families_armed_gated_and_lint_clean():
    from presto_tpu.obs.exposition import lint_exposition
    from presto_tpu.server.metrics import render_metrics

    assert not inflight.armed()
    inflight.register("q_m", stall_threshold_s=60)
    assert inflight.armed()
    rows = inflight.metric_rows({"plane": "coordinator"})
    names = {r[0] for r in rows}
    assert names == {"presto_tpu_inflight_queries",
                     "presto_tpu_inflight_publishes_total",
                     "presto_tpu_stalls_total",
                     "presto_tpu_stragglers_total"}
    text = render_metrics(rows)
    assert lint_exposition(text) == []


def test_reset_disarms_and_clears():
    inflight.register("q_r", stall_threshold_s=60)
    inflight.task("q_r", "q_r.0.0").publish("Scan", windows=1)
    inflight.reset()
    assert not inflight.armed()
    assert inflight.get("q_r") is None
    assert inflight.metric_rows({})[1][2] == 0  # publishes zeroed
