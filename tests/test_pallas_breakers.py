"""Pallas linear-probing breaker engine (ops/pallas_hash.py) and the
stats-driven hash-vs-sort CBO choice (plan/stats.choose_breaker_engine,
exec/runtime breaker_engine threading).

Kernel-level: insert/probe vs a numpy oracle across capacities,
collision-heavy and skew-adversarial key sets, int64 plane exactness,
overflow accounting. Engine-level: overflow→regrow replay end-to-end,
forced-hash TPC-H/TPC-DS verifier sweeps against the sort engine, the
CBO picking differently per breaker, EXPLAIN/metrics surfacing, and the
session property. Everything runs in interpret mode on CPU — bit-exact
with the compiled TPU kernels."""

import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from presto_tpu.catalog.memory import MemoryConnector
from presto_tpu.catalog.tpch import tpch_catalog
from presto_tpu.connector import Catalog
from presto_tpu.exec import ExecConfig, LocalRunner
from presto_tpu.ops import pallas_hash as ph
from presto_tpu.ops.hashing import hash_columns
from presto_tpu.ops.radix import slot_hash
from presto_tpu.verifier import Verifier, report

from conftest import assert_frames_match


# ---------------------------------------------------------------------------
# kernel oracle helpers


def _planes(*cols):
    return jnp.stack([ph.encode_plane(jnp.asarray(c)) for c in cols])


def _slot0(planes, tcap):
    return slot_hash(hash_columns(list(planes)), tcap)


def _group_oracle(rows, live):
    """row index -> oracle group label (first-seen order over live rows)."""
    seen = {}
    out = []
    for i, r in enumerate(rows):
        if not live[i]:
            out.append(None)
            continue
        out.append(seen.setdefault(r, len(seen)))
    return out, len(seen)


def _check_group_assignment(gid, rows, live, tcap):
    """gid must induce exactly the oracle partition: equal keys share a
    gid, distinct keys do not, dead rows park at tcap."""
    oracle, n_distinct = _group_oracle(rows, live)
    gid = np.asarray(gid)
    by_label = {}
    for i, lab in enumerate(oracle):
        if lab is None:
            assert gid[i] == tcap, f"dead row {i} got gid {gid[i]}"
            continue
        assert gid[i] < tcap, f"live row {i} unplaced"
        by_label.setdefault(lab, set()).add(int(gid[i]))
    assert all(len(s) == 1 for s in by_label.values()), \
        "one key split across gids"
    firsts = [next(iter(s)) for s in by_label.values()]
    assert len(set(firsts)) == n_distinct, "distinct keys collapsed"


# ---------------------------------------------------------------------------
# group insert vs oracle


@pytest.mark.parametrize("cap", [4, 16, 64, 256])
def test_group_insert_oracle_across_capacities(cap):
    rng = np.random.default_rng(cap)
    n = 4 * cap
    keys = rng.integers(0, cap, size=n).astype(np.int64)  # distinct <= cap
    live = rng.random(n) > 0.1
    planes = _planes(keys)
    tcap = 2 * cap
    gid, table, occ, ng, ovf = ph.group_insert(
        planes, _slot0(planes, tcap), jnp.asarray(live), cap,
        interpret=True)
    rows = [(int(k),) for k in keys]
    _check_group_assignment(gid, rows, live, tcap)
    _, n_distinct = _group_oracle(rows, live)
    assert int(ng) == n_distinct and int(ovf) == 0
    # the table's occupied slots reproduce exactly the distinct key set
    occ = np.asarray(occ)
    table = np.asarray(table)
    assert set(table[0][occ > 0]) == {k for k, l in zip(keys, live) if l}


def test_group_insert_collision_heavy_single_slot():
    """Every row lands on slot 0 — the worst probe chain the table can
    see; distinct keys must still separate via linear probing."""
    cap = 32
    keys = np.arange(24, dtype=np.int64) % 12
    live = np.ones(24, bool)
    planes = _planes(keys)
    gid, _, _, ng, ovf = ph.group_insert(
        planes, jnp.zeros(24, jnp.int32), jnp.asarray(live), cap,
        interpret=True)
    _check_group_assignment(gid, [(int(k),) for k in keys], live, 2 * cap)
    assert int(ng) == 12 and int(ovf) == 0


def test_group_insert_skew_adversarial():
    """90% one hot key + a long tail, nullable second key: the presto-ish
    skew shape radix alone does not fix."""
    rng = np.random.default_rng(7)
    n = 2048
    hot = rng.random(n) < 0.9
    k1 = np.where(hot, 42, rng.integers(0, 200, size=n)).astype(np.int64)
    k2 = rng.integers(0, 3, size=n).astype(np.int64)
    valid2 = rng.random(n) > 0.2
    live = rng.random(n) > 0.05
    planes, has_nulls = ph.encode_group_keys(
        [(jnp.asarray(k1), None), (jnp.asarray(k2), jnp.asarray(valid2))])
    assert has_nulls
    cap = 1024
    gid, _, _, ng, ovf = ph.group_insert(
        planes, _slot0(planes, 2 * cap), jnp.asarray(live), cap,
        interpret=True)
    rows = [(int(a), int(b) if v else None)
            for a, b, v in zip(k1, k2, valid2)]
    _check_group_assignment(gid, rows, live, 2 * cap)
    _, n_distinct = _group_oracle(rows, live)
    assert int(ng) == n_distinct and int(ovf) == 0


def test_group_insert_overflow_counts_unplaced_rows():
    cap = 8
    keys = np.arange(64, dtype=np.int64)  # 64 distinct >> cap
    planes = _planes(keys)
    gid, _, _, ng, ovf = ph.group_insert(
        planes, _slot0(planes, 2 * cap), jnp.ones(64, bool), cap,
        interpret=True)
    assert int(ng) == cap            # inserts stop at the logical budget
    assert int(ovf) == 64 - cap      # every unplaced row counted once
    assert int(np.sum(np.asarray(gid) == 2 * cap)) == 64 - cap


# ---------------------------------------------------------------------------
# plane encoding exactness


def test_encode_plane_int64_limbs_exact_near_2_62():
    vals = jnp.asarray([(1 << 62) - 1, -(1 << 62), (1 << 62) - 3,
                        (1 << 61) + 12345678901234567], jnp.int64)
    plane = ph.encode_plane(vals)
    np.testing.assert_array_equal(np.asarray(ph.decode_plane(
        plane, jnp.int64)), np.asarray(vals))
    # distinct giant values stay distinct groups
    gid, _, _, ng, ovf = ph.group_insert(
        jnp.stack([plane]), _slot0(jnp.stack([plane]), 16),
        jnp.ones(4, bool), 8, interpret=True)
    assert int(ng) == 4 and int(ovf) == 0


def test_encode_plane_float_identities():
    v = jnp.asarray([0.0, -0.0, 1.5, np.nan, np.nan], jnp.float64)
    p = np.asarray(ph.encode_plane(v))
    assert p[0] == p[1], "-0.0 must encode like +0.0"
    assert p[3] == p[4], "NaNs must canonicalize to one GROUP BY key"
    assert len({p[0], p[2], p[3]}) == 3
    # join planes keep NaN distinct-from-everything via the matchable
    # mask, not the plane; canonicalize_nan=False leaves bits alone
    q = np.asarray(ph.encode_plane(v, canonicalize_nan=False))
    assert q[0] == q[1]


# ---------------------------------------------------------------------------
# join insert/probe vs oracle


def _join_tables(bkeys, blive, tcap):
    planes = _planes(bkeys)
    slot0 = _slot0(planes, tcap)
    slot_row = ph.join_insert(slot0, jnp.asarray(blive), tcap,
                              interpret=True)
    return planes, slot_row


def _probe_oracle(bkeys, blive, pkeys, plive):
    out = {}
    for i, (k, l) in enumerate(zip(pkeys, plive)):
        if not l:
            out[i] = []
            continue
        out[i] = [j for j, (bk, bl) in enumerate(zip(bkeys, blive))
                  if bl and bk == k]
    return out


@pytest.mark.parametrize("tcap", [64, 256, 1024])
def test_join_probe_oracle_counts_exact(tcap):
    rng = np.random.default_rng(tcap)
    nb, np_ = tcap // 4, tcap // 2
    bkeys = rng.integers(0, nb // 2, size=nb).astype(np.int64)
    blive = rng.random(nb) > 0.15
    pkeys = rng.integers(0, nb, size=np_).astype(np.int64)
    plive = rng.random(np_) > 0.1
    bplanes, slot_row = _join_tables(bkeys, blive, tcap)
    pplanes = _planes(pkeys)
    mm, cnt, ovf = ph.join_probe(
        _slot0(pplanes, tcap), pplanes, jnp.asarray(plive), slot_row,
        bplanes, fanout=8, interpret=True)
    oracle = _probe_oracle(bkeys, blive, pkeys, plive)
    cnt, mm = np.asarray(cnt), np.asarray(mm)
    n_over = 0
    for i, want in oracle.items():
        assert cnt[i] == len(want), f"row {i}: count {cnt[i]} != {len(want)}"
        got = [x for x in mm[i] if x >= 0]
        assert set(got) <= set(want) and len(got) == min(len(want), 8)
        n_over += len(want) > 8
    assert int(ovf) == n_over


def test_join_probe_collision_heavy_all_one_slot():
    bkeys = np.array([5, 9, 5, 13, 9, 5], np.int64)
    blive = np.ones(6, bool)
    tcap = 16
    bplanes = _planes(bkeys)
    slot_row = ph.join_insert(jnp.zeros(6, jnp.int32), jnp.asarray(blive),
                              tcap, interpret=True)
    pkeys = np.array([5, 9, 13, 7], np.int64)
    pplanes = _planes(pkeys)
    mm, cnt, ovf = ph.join_probe(
        jnp.zeros(4, jnp.int32), pplanes, jnp.ones(4, bool), slot_row,
        bplanes, fanout=4, interpret=True)
    oracle = _probe_oracle(bkeys, blive, pkeys, np.ones(4, bool))
    for i in range(4):
        assert int(np.asarray(cnt)[i]) == len(oracle[i])
        assert set(int(x) for x in np.asarray(mm)[i] if x >= 0) \
            == set(oracle[i])
    assert int(ovf) == 0


def test_join_probe_fanout_overflow_exact_counts():
    """counts stay EXACT past the fanout — that is the widening-ladder
    contract the runtime's re-probe depends on."""
    bkeys = np.full(12, 3, np.int64)  # one key, 12 duplicates
    tcap = 32
    bplanes, slot_row = _join_tables(bkeys, np.ones(12, bool), tcap)
    pplanes = _planes(np.array([3, 4], np.int64))
    mm, cnt, ovf = ph.join_probe(
        _slot0(pplanes, tcap), pplanes, jnp.ones(2, bool), slot_row,
        bplanes, fanout=4, interpret=True)
    assert int(np.asarray(cnt)[0]) == 12 and int(np.asarray(cnt)[1]) == 0
    assert int(ovf) == 1
    assert sorted(x for x in np.asarray(mm)[0] if x >= 0).__len__() == 4


# ---------------------------------------------------------------------------
# engine end-to-end: regrow replay, CBO, EXPLAIN, metrics, property


def _memory_catalog(n=3000, n_keys=600, seed=3):
    rng = np.random.default_rng(seed)
    conn = MemoryConnector()
    g = rng.integers(0, n_keys, size=n)
    v = rng.normal(0.0, 10.0, n)
    conn.add_table("t", pd.DataFrame({
        "g": g, "v": v, "s": [f"s{int(x) % 5}" for x in g]}))
    conn.add_table("d", pd.DataFrame({
        "k": np.arange(n_keys), "name": [f"n{i}" for i in range(n_keys)]}))
    cat = Catalog()
    cat.register("mem", conn, default=True)
    return cat


def test_hash_agg_overflow_regrows_and_matches_sort():
    """600 distinct keys through a 64-slot initial table: the overflow
    counter must drive the regrow-replay ladder to the same answer the
    sort engine produces."""
    cat = _memory_catalog()
    sql = "select g, count(*) c, sum(v) s from t group by g"
    base = dict(batch_rows=512, agg_capacity=64)
    hash_r = LocalRunner(cat, ExecConfig(breaker_engine="hash", **base))
    sort_r = LocalRunner(cat, ExecConfig(breaker_engine="sort", **base))
    assert_frames_match(hash_r.run(sql), sort_r.run(sql))
    assert hash_r.last_stats.get("breaker.engine_hash", 0) >= 1
    assert hash_r.last_stats.get("breaker.engine_sort", 0) == 0


def test_hash_join_matches_sort_engine():
    cat = _memory_catalog()
    sql = ("select d.name, count(*) c, sum(t.v) s from t "
           "join d on t.g = d.k group by d.name")
    base = dict(batch_rows=512)
    hash_r = LocalRunner(cat, ExecConfig(breaker_engine="hash", **base))
    sort_r = LocalRunner(cat, ExecConfig(breaker_engine="sort", **base))
    assert_frames_match(hash_r.run(sql), sort_r.run(sql))
    assert hash_r.last_stats.get("breaker.engine_hash", 0) >= 2


def test_auto_mode_cbo_picks_both_engines():
    """Low-duplication breakers must go sort, high-duplication hash — in
    auto mode BOTH dispatch counters end up non-zero."""
    from presto_tpu.scan import metrics as sm

    cat = tpch_catalog(0.01)
    r = LocalRunner(cat, ExecConfig(batch_rows=1 << 13))
    before = sm.snapshot()
    r.run("select l_returnflag, count(*) c from lineitem "
          "group by l_returnflag")
    assert r.last_stats.get("breaker.engine_hash", 0) == 1
    r.run("select l_orderkey, count(*) c from lineitem "
          "group by l_orderkey")
    assert r.last_stats.get("breaker.engine_sort", 0) == 1
    after = sm.snapshot()
    assert after["breaker_dispatches_hash"] > before["breaker_dispatches_hash"]
    assert after["breaker_dispatches_sort"] > before["breaker_dispatches_sort"]


def test_explain_shows_engine_choice():
    cat = _memory_catalog()
    auto = LocalRunner(cat, ExecConfig(batch_rows=512))
    out = auto.explain_analyze("select g, count(*) c from t group by g")
    assert "engine=hash" in out or "engine=sort" in out
    forced = LocalRunner(cat, ExecConfig(batch_rows=512,
                                         breaker_engine="hash"))
    out2 = forced.explain_analyze("select g, count(*) c from t group by g")
    assert "engine=hash: session breaker_engine=hash" in out2


def test_breaker_engine_session_property():
    from presto_tpu.server.session import Session, SessionPropertyError

    s = Session()
    assert s.exec_config().breaker_engine == "auto"
    s.set("breaker_engine", "HASH")
    assert s.exec_config().breaker_engine == "hash"
    with pytest.raises(SessionPropertyError):
        s.set("breaker_engine", "quantum")


# ---------------------------------------------------------------------------
# forced-hash verifier sweeps vs the sort engine


@pytest.fixture(scope="module")
def tpch_engines():
    cat = tpch_catalog(0.01)
    control = LocalRunner(cat, ExecConfig(batch_rows=1 << 13,
                                          breaker_engine="sort"))
    test = LocalRunner(cat, ExecConfig(batch_rows=1 << 13,
                                       breaker_engine="hash"))
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


@pytest.mark.parametrize("name", ["q1", "q3", "q6", "q13", "q18"])
def test_tpch_subset_hash_matches_sort(tpch_engines, name):
    """Non-slow representative subset, a case a query: agg-only (q1),
    join-heavy (q3), filter+agg (q6), outer-join agg (q13), large-fanout
    agg (q18)."""
    control, test = tpch_engines
    outcome = Verifier(control, test).verify(_tpch_queries()[name], name)
    assert outcome.ok, report([outcome])
    assert test.last_stats["breaker.engine_hash"] >= 1
    assert "breaker.engine_hash" not in control.last_stats


@pytest.mark.slow
def test_tpch_sweep_hash_matches_sort(tpch_engines):
    control, test = tpch_engines
    queries = _tpch_queries()
    v = Verifier(control, test)
    outcomes = v.run_suite(sorted(queries.items(),
                                  key=lambda kv: int(kv[0][1:])))
    assert all(o.ok for o in outcomes), report(outcomes)


@pytest.mark.slow
def test_tpcds_sweep_hash_matches_sort():
    from presto_tpu.catalog.tpcds import tpcds_catalog

    from test_tpcds_answers import Q

    cat = tpcds_catalog(0.005)
    cfg = dict(batch_rows=1 << 13, agg_capacity=1 << 12)
    control = LocalRunner(cat, ExecConfig(breaker_engine="sort", **cfg))
    test = LocalRunner(cat, ExecConfig(breaker_engine="hash", **cfg))
    v = Verifier(control, test)
    outcomes = v.run_suite(list(Q.items()))
    assert all(o.ok for o in outcomes), report(outcomes)
