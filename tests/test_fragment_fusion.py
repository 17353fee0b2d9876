"""Whole-fragment device residency (exec/fragment_jit.py): window
stacking/padding units, the async double-buffer producer, and
local-vs-fused verifier equality — the fused lax.scan ingest must be
result-identical to the per-batch path, decline the modes it cannot
cover (grace spill, grouped execution), and actually collapse the
dispatch count."""

import contextlib
import time

import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from presto_tpu.batch import Batch
from presto_tpu.catalog.memory import MemoryConnector
from presto_tpu.catalog.tpch import tpch_catalog
from presto_tpu.connector import Catalog
from presto_tpu.exec import ExecConfig, LocalRunner
from presto_tpu.exec import fragment_jit as fj
from presto_tpu.verifier import Verifier, report

from conftest import assert_frames_match


def _mkbatch(n=4, base=0, cap=8):
    vals = np.zeros(cap, np.int64)
    vals[:n] = np.arange(base, base + n)
    live = np.zeros(cap, bool)
    live[:n] = True
    from presto_tpu.batch import Column
    from presto_tpu.types import BIGINT

    return Batch(["x"], [BIGINT], [Column(jnp.asarray(vals), None)],
                 jnp.asarray(live), {})


# ---------------------------------------------------------------------------
# window assembly units: a window is references, never a copy


def test_iter_windows_groups_and_pads():
    bs = [_mkbatch(base=i) for i in range(6)]
    items = list(fj.iter_windows(iter(bs), width=4))
    # 6 same-struct batches at width 4 -> one full window + a 2-tail
    assert [type(i) for i in items] == [fj.Window, fj.Window]
    assert items[0].k == 4 and items[0].width == 4
    assert items[1].k == 2 and items[1].width == 2
    assert len(items[0].batches) == 4
    assert all(x is y for x, y in zip(items[0].batches, bs[:4]))
    assert all(x is y for x, y in zip(items[1].batches, bs[4:]))


def test_iter_windows_ragged_tail_pads_to_pow2_by_reference():
    bs = [_mkbatch(base=i) for i in range(7)]
    (w,) = list(fj.iter_windows(iter(bs), width=8))
    assert w.k == 7 and w.width == 8 and len(w.batches) == 8
    assert all(x is y for x, y in zip(w.batches[:7], bs))
    # the padding slot IS the last real batch (the step masks it dead)
    assert w.batches[7] is bs[-1]


def test_iter_windows_lone_batch_passes_through():
    bs = [_mkbatch()]
    items = list(fj.iter_windows(iter(bs), width=8))
    assert len(items) == 1 and items[0] is bs[0]


def test_iter_windows_flushes_on_structure_change():
    small = [_mkbatch(cap=8, base=i) for i in range(3)]
    big = [_mkbatch(cap=16, base=i) for i in range(2)]
    items = list(fj.iter_windows(iter(small + big), width=8))
    assert isinstance(items[0], fj.Window) and items[0].k == 3
    assert isinstance(items[1], fj.Window) and items[1].k == 2
    assert items[0].width == 4 and items[1].width == 2
    assert {b.live.shape for b in items[0].batches} == {(8,)}
    assert {b.live.shape for b in items[1].batches} == {(16,)}


def test_window_operands_and_footprint():
    """What the fused step is called with: the tuple itself and `k` as an
    int32 device scalar (an operand, so its value selects no program); the
    footprint is the step's stacked temporary, `width` batches."""
    from presto_tpu.memory import batch_device_bytes

    bs = [_mkbatch(base=i) for i in range(5)]
    (w,) = list(fj.iter_windows(iter(bs), width=8))
    batches, k = w.operands
    assert batches is w.batches
    assert k.shape == () and k.dtype == np.int32 and int(k) == 5
    # resident on the device, made once for each value: no transfer a call
    assert w.operands[1] is k
    assert fj.window_device_bytes(w) == 8 * batch_device_bytes(bs[0])
    # shape_bucketing pads every multi-batch flush to the full width
    (wb,) = list(fj.iter_windows(iter(bs[:2]), width=8, bucket=True))
    assert wb.k == 2 and wb.width == 8 and wb.batches[2:] == (bs[1],) * 6


@contextlib.contextmanager
def _no_dispatch(monkeypatch):
    """No array may move and no eager stack or padding op may run."""
    import jax

    def boom(*a, **k):
        raise AssertionError("eager jnp call on the window path")

    monkeypatch.setattr(jnp, "stack", boom)
    monkeypatch.setattr(jnp, "zeros_like", boom)
    # the config value, not jax.transfer_guard(): the producer is another
    # thread, and the context manager is thread-local
    prev = jax.config.jax_transfer_guard
    jax.config.update("jax_transfer_guard", "disallow")
    try:
        yield
    finally:
        jax.config.update("jax_transfer_guard", prev)


@pytest.mark.parametrize("source", ["iter_windows", "WindowSource"])
def test_window_assembly_makes_no_jax_dispatch(source, monkeypatch):
    bs = [_mkbatch(base=i) for i in range(7)]
    with _no_dispatch(monkeypatch):
        if source == "iter_windows":
            items = list(fj.iter_windows(iter(bs), width=8))
        else:
            src = fj.WindowSource(iter(bs), width=8)
            items = list(src)
            src.close()
    (w,) = items
    assert w.k == 7 and w.width == 8
    assert all(x is y for x, y in zip(w.batches, bs + bs[-1:]))


# ---------------------------------------------------------------------------
# the async producer


def test_window_source_preserves_order():
    bs = [_mkbatch(base=i) for i in range(20)]
    src = fj.WindowSource(iter(bs), width=4)
    got = []
    for item in src:
        if isinstance(item, fj.Window):
            got.extend(item.batches[:item.k])
        else:
            got.append(item)
    src.close()
    assert len(got) == 20
    assert all(x is y for x, y in zip(got, bs))


def test_window_source_drain_recovers_undelivered():
    """Consumer abandons mid-stream: drain() must hand back every batch
    the producer pulled but never delivered, in stream order."""
    bs = [_mkbatch(base=i) for i in range(32)]
    src = fj.WindowSource(iter(bs), width=4)
    it = iter(src)
    first = next(it)
    assert isinstance(first, fj.Window)
    consumed = list(first.batches[:first.k])
    rest = src.drain()
    # no duplicates, no gaps within what was pulled; prefix of the stream;
    # the very objects the stream yielded, not copies
    got = consumed + rest
    assert len(got) >= 8  # the window in hand and the staged one, at least
    assert all(x is y for x, y in zip(got, bs))


def test_window_source_drain_drops_the_ragged_tails_padding():
    """A staged ragged window gives back its k real batches only."""
    bs = [_mkbatch(base=i) for i in range(7)]
    src = fj.WindowSource(iter(bs), width=8)
    deadline = time.time() + 10.0
    while src._q.empty() and time.time() < deadline:
        time.sleep(0.01)
    rest = src.drain()
    assert len(rest) == 7 and all(x is y for x, y in zip(rest, bs))


def test_window_source_propagates_producer_exception():
    def stream():
        yield _mkbatch(base=0)
        raise RuntimeError("decode failed")

    src = fj.WindowSource(stream(), width=4)
    with pytest.raises(RuntimeError, match="decode failed"):
        list(src)
    src.close()


def test_window_source_close_does_not_hang_when_unconsumed():
    bs = [_mkbatch(base=i) for i in range(64)]
    src = fj.WindowSource(iter(bs), width=4)
    t0 = time.time()
    src.close()
    assert time.time() - t0 < 5.0
    assert not src._thread.is_alive()


# ---------------------------------------------------------------------------
# fused-vs-per-batch equality (memory connector, counters)


def _memory_catalog(n=3000, nulls=True):
    rng = np.random.default_rng(7)
    conn = MemoryConnector()
    g = rng.integers(0, 5, n)
    v = rng.normal(0.0, 10.0, n)
    vals = np.array([None if nulls and i % 17 == 0 else float(x)
                     for i, x in enumerate(v)], dtype=object)
    conn.add_table("t", pd.DataFrame({
        "g": g, "v": vals, "s": [f"s{int(x) % 3}" for x in g]}))
    cat = Catalog()
    cat.register("mem", conn, default=True)
    return cat


def _run_pair(sql, n=3000, **cfg):
    cat = _memory_catalog(n)
    on = LocalRunner(cat, ExecConfig(batch_rows=512, **cfg))
    off = LocalRunner(cat, ExecConfig(batch_rows=512,
                                      fragment_fusion=False, **cfg))
    return on, on.run(sql), off, off.run(sql)


# ---------------------------------------------------------------------------
# the fused steppers on ragged windows, against the per-batch fold


def _find(node, kind):
    if isinstance(node, kind):
        return node
    for c in node.children():
        hit = _find(c, kind)
        if hit is not None:
            return hit
    return None


@pytest.fixture(scope="module")
def steppers():
    """The engine's own merge closures and scan batches for a keyed
    aggregate and a TopN, with the fused and per-batch programs jitted
    once for the module (so a second trace shows in `_cache_size`)."""
    import jax

    from presto_tpu.exec import runtime as rt
    from presto_tpu.plan.nodes import Aggregate, Sort

    r = LocalRunner(_memory_catalog(4096, nulls=False),
                    ExecConfig(batch_rows=512))
    agg = _find(r.plan("select g, count(*) c, sum(v) s, min(s) m from t"
                       " where v < 12 group by g").root, Aggregate)
    srt = _find(r.plan("select g, v from t order by v desc limit 7").root,
                Sort)
    ms = rt._agg_steps(agg, "sort").merge_step
    ts = rt._topn_step(srt)
    env = {
        "agg": dict(
            batches=list(rt._fused_child(agg.child, r._new_ctx())[0]),
            step0=jax.jit(fj.scan_stepper(ms, True), static_argnums=(2,)),
            step=jax.jit(fj.scan_stepper(ms, False), static_argnums=(3,),
                         donate_argnums=(0,)),
            one0=jax.jit(lambda b, cap: ms(None, b, cap),
                         static_argnums=(1,)),
            one=jax.jit(lambda acc, b, cap: ms(acc, b, cap),
                        static_argnums=(2,)),
            cap=(64,)),
        "topn": dict(
            batches=list(rt._fused_child(srt.child, r._new_ctx())[0]),
            step0=jax.jit(fj.topn_stepper(ts, True)),
            step=jax.jit(fj.topn_stepper(ts, False), donate_argnums=(0,)),
            one0=jax.jit(lambda b: (ts(None, b), 0)),
            one=jax.jit(lambda acc, b: (ts(acc, b), 0)),
            cap=()),
    }
    assert all(len(e["batches"]) == 8 for e in env.values())
    return env


def _live_rows(acc):
    import jax

    live = np.asarray(acc.live)
    return [np.asarray(x)[live] for x in jax.tree_util.tree_leaves(acc)]


@pytest.mark.parametrize("k,width", [(2, 8), (5, 8), (6, 8), (7, 8), (3, 4)])
@pytest.mark.parametrize("kind", ["agg", "topn"])
def test_ragged_window_step_matches_per_batch_fold(steppers, kind, k, width):
    """`k` real batches padded to `width` by reference: the fused pair
    (first window, then a second one into the donated accumulator) must
    equal the per-batch fold over the real batches alone — dead slots add
    nothing — and must run the full window's program, not one per `k`."""
    import jax

    e = steppers[kind]
    bs, cap = e["batches"], e["cap"]
    full = next(fj.iter_windows(iter(bs[:width]), width))
    assert full.k == full.width == width
    acc = e["step0"](*full.operands, *cap)
    e["step"](acc if kind == "topn" else acc[0], *full.operands, *cap)
    traced = e["step0"]._cache_size(), e["step"]._cache_size()

    (w,) = fj.iter_windows(iter(bs[:k]), width, bucket=True)
    assert (w.k, w.width) == (k, width)
    out = e["step0"](*w.operands, *cap)
    acc, ng = out if kind == "agg" else (out, 0)
    out = e["step"](acc, *w.operands, *cap)
    acc, ng2 = out if kind == "agg" else (out, 0)
    assert (e["step0"]._cache_size(), e["step"]._cache_size()) == traced

    ref, ns = None, []
    for b in bs[:k] + bs[:k]:
        ref, n = (e["one0"](b, *cap) if ref is None
                  else e["one"](ref, b, *cap))
        ns.append(int(n))
    assert int(ng) == max(ns[:k]) and int(ng2) == max(ns[k:])
    np.testing.assert_array_equal(np.asarray(acc.live), np.asarray(ref.live))
    for got, want in zip(_live_rows(acc), _live_rows(ref)):
        np.testing.assert_array_equal(got, want)
    if kind == "agg":
        # the group table is equal slot for slot, dead slots included
        for got, want in zip(jax.tree_util.tree_leaves(acc),
                             jax.tree_util.tree_leaves(ref)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the scan's batches were operands of a donating program four times
    # over and are still there: only the accumulator is donated
    assert not any(x.is_deleted() for b in w.batches
                   for x in jax.tree_util.tree_leaves(b))
    assert int(np.asarray(w.batches[-1].live).sum()) > 0


def test_cached_batches_survive_donating_statements():
    """donate_stepping on (the default): a global aggregate's and a TopN's
    fused steps donate their accumulator and nothing else, so a second and
    third statement over the connector's cached split batches read them
    intact."""
    cat = _memory_catalog(4096, nulls=False)
    r = LocalRunner(cat, ExecConfig(batch_rows=512, donate_stepping=True))
    off = LocalRunner(cat, ExecConfig(batch_rows=512, fragment_fusion=False))
    for sql in ("select count(*) c, sum(v) s from t where v < 12",
                "select g, v from t order by v desc limit 7"):
        want = off.run(sql)
        for _ in range(3):
            assert_frames_match(r.run(sql), want)
        assert r.last_stats["fragment.dispatches"] >= 1


def test_fused_agg_matches_and_collapses_dispatches():
    on, d_on, off, d_off = _run_pair(
        "select g, count(*) c, sum(v) s, avg(v) a from t group by g")
    assert_frames_match(d_on, d_off)
    assert on.last_stats.get("fragment.batch_dispatches", 0) == 0
    assert off.last_stats.get("fragment.dispatches", 0) == 0
    # 3000 rows / 512-row batches = 6 batches -> one fused window (W=8)
    assert on.last_stats["fragment.dispatches"] <= 3
    assert on.last_stats["fragment.fused_batches"] == \
        off.last_stats["fragment.batch_dispatches"]


def test_fused_varchar_group_key_matches():
    on, d_on, off, d_off = _run_pair(
        "select s, count(*) c from t group by s order by s")
    assert_frames_match(d_on, d_off)
    assert on.last_stats.get("fragment.dispatches", 0) >= 1


def test_fused_topn_matches():
    on, d_on, off, d_off = _run_pair(
        "select g, v from t order by v desc limit 7")
    assert_frames_match(d_on, d_off)
    assert on.last_stats.get("fragment.dispatches", 0) >= 1
    assert on.last_stats.get("fragment.batch_dispatches", 0) == 0


def test_overflow_replay_matches():
    """A derived group key (no column stats, so the CBO can't pre-size)
    at tiny initial capacity forces the growth-replay ladder through the
    fused window path; results must still match bit-for-bit."""
    on, d_on, off, d_off = _run_pair(
        "select cast(v * 100 as bigint) k, count(*) c, sum(v) s"
        " from t group by cast(v * 100 as bigint)",
        agg_capacity=128, n=5000)
    assert_frames_match(d_on, d_off)
    # the ladder was climbed, on both paths, and the fused one stayed fused
    assert on.last_stats["breaker.replay_waves"] >= 1
    assert off.last_stats["breaker.replay_waves"] >= 1
    assert on.last_stats["fragment.fused_batches"] >= 1


def test_grace_spill_declines_fusion_and_matches():
    """A ceiling below the CBO presize forces grace-from-start: the fused
    path must decline (per-batch spill ingest) and still match."""
    cat = _memory_catalog(5000)
    base = dict(batch_rows=512, agg_capacity=128, agg_cap_ceiling=128,
                spill_enabled=True)
    on = LocalRunner(cat, ExecConfig(**base))
    off = LocalRunner(cat, ExecConfig(fragment_fusion=False, **base))
    sql = "select g, v, count(*) c from t group by g, v"
    d_on, d_off = on.run(sql), off.run(sql)
    assert_frames_match(d_on, d_off)
    assert on.last_stats.get("fragment.dispatches", 0) == 0


def test_fusion_off_is_default_behavior():
    """fragment_fusion=false must preserve the per-batch path bit-for-bit
    (no windows, no fused programs, batch counters only)."""
    cat = _memory_catalog(3000)
    off = LocalRunner(cat, ExecConfig(batch_rows=512,
                                      fragment_fusion=False))
    d = off.run("select g, sum(v) s from t group by g")
    assert off.last_stats.get("fragment.dispatches", 0) == 0
    assert off.last_stats["fragment.batch_dispatches"] > 0
    assert len(d) == 5


def test_explain_analyze_shows_fragment_marker():
    cat = _memory_catalog(3000)
    r = LocalRunner(cat, ExecConfig(batch_rows=512))
    out = r.explain_analyze("select g, count(*) c from t group by g")
    assert "fragment=fused" in out
    assert "fused(" in out


def test_dispatch_metrics_exposed():
    from presto_tpu.scan import metrics as scan_metrics

    cat = _memory_catalog(3000)
    r = LocalRunner(cat, ExecConfig(batch_rows=512))
    r.run("select g, count(*) c from t group by g")
    rows = scan_metrics.metric_rows()
    names = {row[0] for row in rows}
    assert "presto_tpu_fragment_dispatches_total" in names
    assert "presto_tpu_batch_dispatches_total" in names
    snap = scan_metrics.snapshot()
    assert snap["fragment_dispatches"] >= 1


def test_session_property_roundtrip():
    from presto_tpu.server.session import SYSTEM_PROPERTIES, Session

    s = Session(properties={"fragment_fusion": False, "fragment_window": 4})
    cfg = s.exec_config()
    assert cfg.fragment_fusion is False
    assert cfg.fragment_window == 4
    assert SYSTEM_PROPERTIES.default("fragment_fusion") is True
    with pytest.raises(Exception):
        SYSTEM_PROPERTIES.decode("fragment_window", "0")


# ---------------------------------------------------------------------------
# local-vs-fused verifier sweep over the TPC-H suite


@pytest.fixture(scope="module")
def tpch_engines():
    cat = tpch_catalog(0.01)
    # small batches force multi-batch fragments so fusion actually engages
    control = LocalRunner(cat, ExecConfig(batch_rows=1 << 13,
                                          fragment_fusion=False))
    test = LocalRunner(cat, ExecConfig(batch_rows=1 << 13))
    return control, test


def _tpch_queries():
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "tpch_queries", os.path.join(os.path.dirname(__file__),
                                     "test_tpch.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.QUERIES


@pytest.mark.parametrize("name", ["q1", "q6"])
def test_tpch_subset_fused_matches_unfused(tpch_engines, name):
    """The queries of the non-slow subset in which fusion engages at SF
    0.01: keyed agg (q1) and filter + global agg (q6), eight lineitem
    batches in one window. The former picks q2, q3 and q13 never fused
    (their breakers sit on joins or on one-batch scans, so both engines ran
    the same per-batch programs); the slow sweep still has them."""
    control, test = tpch_engines
    outcome = Verifier(control, test).verify(_tpch_queries()[name], name)
    assert outcome.ok, report([outcome])
    assert test.last_stats["fragment.fused_batches"] >= 8
    assert "fragment.fused_batches" not in control.last_stats


@pytest.mark.slow
def test_tpch_sweep_fused_matches_unfused(tpch_engines):
    control, test = tpch_engines
    queries = _tpch_queries()
    v = Verifier(control, test)
    outcomes = v.run_suite(sorted(queries.items(),
                                  key=lambda kv: int(kv[0][1:])))
    assert all(o.ok for o in outcomes), report(outcomes)


@pytest.fixture(scope="module")
def spill_engines():
    cat = tpch_catalog(0.01)
    cfg = dict(batch_rows=1 << 12, agg_capacity=256, agg_cap_ceiling=1024,
               spill_enabled=True)
    control = LocalRunner(cat, ExecConfig(fragment_fusion=False, **cfg))
    test = LocalRunner(cat, ExecConfig(**cfg))
    return control, test


@pytest.mark.parametrize("name", ["q3", "q13", "q18"])
def test_tpch_sweep_spill_configs_match(spill_engines, name):
    """Spill/overflow-replay shapes: tiny capacity + ceiling on the agg-
    heavy queries — fusion must decline into grace or replay correctly.
    q3 and q13 spill eight partitions, q18 repartitions too (its leaves
    replayed by overflow waves until PR 33 sized a leaf from its rows).
    The former picks q1 (four groups) and q6 (no key) overflow nothing
    under these knobs; test_tpch_subset_fused_matches_unfused has them."""
    control, test = spill_engines
    outcome = Verifier(control, test).verify(_tpch_queries()[name], name)
    assert outcome.ok, report([outcome])
    assert test.last_stats["spill.partitions"] >= 8
    if name == "q18":
        assert test.last_stats["spill.repartitions"] >= 1
