"""The reduction from a profiler trace to busy/idle, launches, top
operations and charged gaps: on a hand-built trace whose answers are known,
and on the small trace recorded on the chip against a second formulation."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import trace_reduce as tr  # noqa: E402

S = 1_000_000_000


def hand_built():
    ops = [["a", 1 * S, 2 * S],            # 1..3
           ["b", 2 * S, 2 * S],            # 2..4 overlaps a
           ["b", 2 * S + S // 2, S // 2],  # nested inside
           ["c", 6 * S, 1 * S],            # 6..7
           ["d", 9 * S, 3 * S]]            # 9..12, clipped at 10
    modules = [["m1", 1 * S, 3 * S], ["m2", 6 * S, 1 * S], ["m3", 9 * S, 3 * S],
               ["before", -5 * S, 1 * S]]
    spans = [["bench:traced_window", 0, 10 * S], ["bench:post", 0, 1 * S],
             ["bench:poll", 1 * S, 7 * S], ["bench:post", 8 * S + S // 2, S]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": ops},
                                            {"name": "XLA Modules", "events": modules}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": spans},
            # two engine threads, both inside an eager op from 4.5 to 5.5 and
            # one of them again from 7.5 to 9.5 (the last half second busy)
            {"name": "python3", "events": [["PjitFunction(x)", 4 * S + S // 2, S]]},
            {"name": "python3", "events": [["PjitFunction(x)", 4 * S + S // 2, S],
                                           ["PjitFunction(x)", 7 * S + S // 2, 2 * S],
                                           ["DevicePut", 2 * S, S]]}]}]}


def test_hand_built_trace():
    r = tr.reduce(hand_built())
    assert r["window_s"] == 10.0
    assert r["busy_s"] == 3 + 1 + 1      # 1..4, 6..7, 9..10
    assert r["launches"] == 3            # the one before the window is out
    assert r["longest_gap_s"] == 2.0     # 4..6 and 7..9
    ops = dict(r["device_ops"])
    assert ops == {"a": 2.0, "b": 2.5, "c": 1.0, "d": 1.0}
    gaps = dict(r["idle_gaps"])
    # idle: 0..1 (post), 4..6 and 7..8 (poll), 8..8.5 (no span), 8.5..9 (post)
    assert {k: v for k, v in gaps.items() if not k.startswith("host:")} == {
        "post": 1.5, "poll": 3.0, "outside_spans": 0.5}
    assert sum(v for k, v in gaps.items() if not k.startswith("host:")) \
        == r["window_s"] - r["busy_s"]
    # idle time during which some Python thread was inside the event: union
    # over threads (4.5..5.5 once, 7.5..9); DevicePut ran while the device
    # was busy, so it is charged nothing
    assert gaps["host:PjitFunction(x)"] == 1.0 + 1.5
    assert "host:DevicePut" not in gaps


def test_nothing_to_read_gives_none():
    t = hand_built()
    t["planes"] = t["planes"][1:]           # no device plane: a CPU rehearsal
    assert tr.reduce(t) is None
    t = hand_built()
    t["planes"][1]["lines"][0]["events"].pop(0)   # no window annotation
    assert tr.reduce(t) is None


def test_short_name():
    hlo = ('%custom-call.2 = s64[1,131072]{1,0:T(1,128)} custom-call(u32[1,131072]'
           '{1,0:T(1,128)} %bitcast.2), custom_call_target="X64Combine"')
    assert tr.short_name(hlo) == "%custom-call.2 custom-call X64Combine"
    assert tr.short_name("%while.33 = (u32[]{:T(128)}, pred[]{:T(512)}) "
                         "while((u32[]{:T(128)}) %tuple.413), condition=%c") \
        == "%while.33 while"
    assert tr.short_name("already short") == "already short"


@pytest.fixture(scope="module")
def sample():
    with open(os.path.join(ROOT, "benchmark", "testdata", "trace_sample.json")) as f:
        return json.load(f)


def test_recorded_trace_against_a_second_formulation(sample):
    r = tr.reduce(sample)
    dev = next(p for p in sample["planes"] if p["name"] == "/device:TPU:0")
    lines = {ln["name"]: ln["events"] for ln in dev["lines"]}
    win = next(e for p in sample["planes"] for ln in p["lines"]
               for e in ln["events"] if e[0] == tr.WINDOW_SPAN)
    lo, hi = win[1], win[1] + win[2]
    # busy by coordinate compression: mark every elementary interval covered
    ev = np.array([[max(s, lo), min(s + d, hi)] for _, s, d in lines["XLA Ops"]])
    ev = ev[ev[:, 1] > ev[:, 0]]
    cuts = np.unique(ev)
    depth = np.zeros(len(cuts), dtype=np.int64)
    np.add.at(depth, np.searchsorted(cuts, ev[:, 0]), 1)
    np.add.at(depth, np.searchsorted(cuts, ev[:, 1]), -1)
    covered = np.cumsum(depth)[:-1] > 0
    busy = float((np.diff(cuts)[covered]).sum()) / 1e9
    assert r["busy_s"] == pytest.approx(busy, rel=1e-12)
    assert r["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert r["launches"] == sum(1 for _, s, _ in lines["XLA Modules"] if lo <= s < hi)
    # what the chip run showed: two statements of Q6 at SF1, 292 launches
    # each, the device idle for 97 % of the window
    assert r["launches"] == 584
    idle_pct = 100 * (1 - r["busy_s"] / r["window_s"])
    assert 96.5 < idle_pct < 97.5
    assert len(r["device_ops"]) == 10 and len(r["idle_gaps"]) == 10
    every = tr.reduce(sample, top=1000)["idle_gaps"]
    assert sum(s for n, s in every if not n.startswith("host:")) \
        == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-9)
    # the eager op that holds the worker's threads shows in the idle time
    assert dict(every)["host:PjitFunction(broadcast_in_dim)"] > 0.1
