"""Compile plane (exec/programs.py): structural program-key stability,
process-wide sharing, locked compile accounting, buffer donation, and the
per-class recompile budgets + EXPLAIN headroom riding along with it.

Reference: the reference engine's ExpressionCompiler / PageFunctionCompiler
cache generated classes by expression structure and reuse them across every
execution of the same plan shape; these tests pin the analogous contract
for XLA programs — same structure, one compile — plus the invariants that
make it safe (runtime-state-free wire plans, per-node stats views, private
entries for data-capturing builders).
"""

import json
import threading

import jax.numpy as jnp
import pytest

from presto_tpu.catalog.tpch import tpch_catalog
from presto_tpu.exec import ExecConfig, LocalRunner
from presto_tpu.exec import programs
from presto_tpu.exec.runtime import ExecContext, _node_jit
from presto_tpu.plan.codec import (
    fragment_from_json,
    fragment_to_json,
    node_fingerprint,
)
from presto_tpu.plan.fragmenter import fragment_plan
from presto_tpu.plan.nodes import Output, plan_to_string
from presto_tpu.types import BIGINT


@pytest.fixture(scope="module")
def cat():
    return tpch_catalog(0.01)


def root_fragment(cat, sql):
    runner = LocalRunner(cat, ExecConfig())
    qp = runner.plan(sql)
    return fragment_plan(qp, cat).fragments


SQL_A = ("select l_orderkey, l_quantity * 2 as q2 from lineitem "
         "where l_discount > 0.05")
SQL_B = ("select l_orderkey, l_quantity * 3 as q3 from lineitem "
         "where l_discount > 0.01")


# ---------------------------------------------------------------------------
# program-key stability


def test_fingerprint_survives_codec_round_trip(cat):
    for f in root_fragment(cat, SQL_A).values():
        back = fragment_from_json(json.loads(json.dumps(fragment_to_json(f))))
        assert node_fingerprint(back.root) == node_fingerprint(f.root)


def test_fingerprint_identical_across_two_decodes(cat):
    for f in root_fragment(cat, SQL_A).values():
        wire = json.dumps(fragment_to_json(f))
        a = fragment_from_json(json.loads(wire))
        b = fragment_from_json(json.loads(wire))
        assert a.root is not b.root
        assert node_fingerprint(a.root) == node_fingerprint(b.root)


def test_fingerprint_distinct_for_different_chains(cat):
    fa = {node_fingerprint(f.root)
          for f in root_fragment(cat, SQL_A).values()}
    fb = {node_fingerprint(f.root)
          for f in root_fragment(cat, SQL_B).values()}
    assert not (fa & fb)


def test_config_fingerprint_volatile_vs_structural():
    base = programs.config_fingerprint(ExecConfig())
    # volatile knobs (observability, budgets) must not fork the cache
    assert programs.config_fingerprint(
        ExecConfig(collect_stats=True, tracing=False,
                   max_compiled_shapes=3, precompile_workers=4)) == base
    # knobs baked into traced closures must
    assert programs.config_fingerprint(
        ExecConfig(radix_partitions=4)) != base
    assert programs.config_fingerprint(
        ExecConfig(donate_stepping=False)) != base


# ---------------------------------------------------------------------------
# process-wide sharing


def decode_twice(cat, sql):
    frags = root_fragment(cat, sql)
    fid = next(iter(frags))
    wire = json.dumps(fragment_to_json(frags[fid]))
    return (fragment_from_json(json.loads(wire)).root,
            fragment_from_json(json.loads(wire)).root)


def test_two_decodes_share_one_program_entry(cat):
    cfg = ExecConfig()
    ra, rb = decode_twice(cat, SQL_A)
    ctx = ExecContext(cat, cfg)
    assert programs.install_plan(ra, cfg) > 0
    assert programs.install_plan(rb, cfg) > 0
    assert ra.__dict__["_program_ns"] == rb.__dict__["_program_ns"]
    fa = _node_jit(ra, "t_shared", lambda: (lambda x: x + 1))
    fb = _node_jit(rb, "t_shared", lambda: (lambda x: x + 1))
    assert fa._entry is fb._entry
    fa(jnp.zeros(8, jnp.int32))
    fb(jnp.zeros(8, jnp.int32))  # same shape through the other node
    assert fa._entry.compiles == 1
    # attribution stays per-node: only the triggering node's stats moved
    assert ra.__dict__["_jit_stats"]["t_shared"]["compiles"] == 1
    assert rb.__dict__["_jit_stats"]["t_shared"]["compiles"] == 0
    del ctx


def test_unstamped_node_keeps_private_entry(cat):
    ra, rb = decode_twice(cat, SQL_A)
    # no install_plan: builders may capture runtime data, sharing is opt-in
    fa = _node_jit(ra, "t_priv", lambda: (lambda x: x + 1))
    fb = _node_jit(rb, "t_priv", lambda: (lambda x: x + 1))
    assert fa._entry is not fb._entry


def test_shared_opt_out_keeps_private_entry(cat):
    cfg = ExecConfig()
    ra, rb = decode_twice(cat, SQL_A)
    programs.install_plan(ra, cfg)
    programs.install_plan(rb, cfg)
    fa = _node_jit(ra, "t_optout", lambda: (lambda x: x + 1),
                   _shared=False)
    fb = _node_jit(rb, "t_optout", lambda: (lambda x: x + 1),
                   _shared=False)
    assert fa._entry is not fb._entry


def test_jit_kwargs_key_distinct_entries(cat):
    cfg = ExecConfig()
    ra, rb = decode_twice(cat, SQL_A)
    programs.install_plan(ra, cfg)
    programs.install_plan(rb, cfg)
    fa = _node_jit(ra, "t_kw", lambda: (lambda x, n: x[:n]),
                   static_argnums=(1,))
    fb = _node_jit(rb, "t_kw", lambda: (lambda x, n: x + n))
    # same ns+key but different jit kwargs must not collide
    assert fa._entry is not fb._entry


# ---------------------------------------------------------------------------
# locked compile accounting (the _cache_size race fix)


def test_concurrent_compile_accounting_is_exact(cat):
    cfg = ExecConfig()
    ra, rb = decode_twice(cat, SQL_A)
    programs.install_plan(ra, cfg)
    programs.install_plan(rb, cfg)
    fns = [_node_jit(n, "t_race", lambda: (lambda x: x * 2))
           for n in (ra, rb)]
    assert fns[0]._entry is fns[1]._entry
    shapes = [3, 5, 7, 11]
    errors = []

    def worker(fn):
        try:
            for n in shapes:
                fn(jnp.zeros(n, jnp.int32))
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(fns[i % 2],))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    # every distinct shape compiled exactly once, claimed exactly once —
    # the before/after pattern double- or under-counted here
    assert fns[0]._entry.compiles == len(shapes)
    total = (ra.__dict__["_jit_stats"]["t_race"]["compiles"]
             + rb.__dict__["_jit_stats"]["t_race"]["compiles"])
    assert total == len(shapes)


# ---------------------------------------------------------------------------
# donated stepping buffers


def test_donated_argument_is_consumed(cat):
    cfg = ExecConfig()
    ra, _ = decode_twice(cat, SQL_A)
    programs.install_plan(ra, cfg)
    fn = _node_jit(ra, "t_donate", lambda: (lambda acc, b: acc + b),
                   donate_argnums=(0,))
    acc = jnp.arange(16, dtype=jnp.int64)
    out = fn(acc, jnp.ones(16, jnp.int64))
    assert int(out[1]) == 2
    # the donated input buffer is gone — proof donation is active (a
    # stepping loop that accidentally reused acc would fail loudly here,
    # which is exactly why only linearly-threaded programs donate)
    with pytest.raises(RuntimeError):
        jnp.asarray(acc) + 1


def test_topn_and_global_agg_results_with_donation(cat):
    # the two donated stepping programs produce correct results across
    # multiple batches (small batch_rows forces several stepping rounds)
    cfg = ExecConfig(batch_rows=1 << 10, donate_stepping=True)
    r = LocalRunner(cat, cfg)
    top = r.run("select l_orderkey, l_extendedprice from lineitem "
                "order by l_extendedprice desc limit 7")
    assert len(top) == 7
    prices = top["l_extendedprice"].tolist()
    assert prices == sorted(prices, reverse=True)
    agg = r.run("select count(*) as c, sum(l_quantity) as q from lineitem")
    ref = LocalRunner(cat, ExecConfig(donate_stepping=False)).run(
        "select count(*) as c, sum(l_quantity) as q from lineitem")
    assert int(agg["c"][0]) == int(ref["c"][0])
    assert float(agg["q"][0]) == pytest.approx(float(ref["q"][0]))


# ---------------------------------------------------------------------------
# same query twice, process-wide: zero new compiles


def test_second_runner_reuses_every_program(cat):
    sql = ("select l_returnflag as f, count(*) as c from lineitem "
           "where l_quantity < 30 group by l_returnflag order by f")
    LocalRunner(cat, ExecConfig()).run(sql)
    before = programs.snapshot()
    out = LocalRunner(cat, ExecConfig()).run(sql)  # fresh plan objects
    after = programs.snapshot()
    assert len(out) > 0
    assert after["compiles"] == before["compiles"]
    assert after["hits"] > before["hits"]


# ---------------------------------------------------------------------------
# ahead-of-stream precompilation


def test_precompile_warms_scan_chain(cat):
    cfg = ExecConfig(precompile_workers=2)
    runner = LocalRunner(cat, cfg)
    sql = ("select s_name from supplier join nation on s_nationkey = "
           "n_nationkey where s_acctbal > 0")
    out = runner.run(sql)
    programs.drain_warmers()
    assert len(out) > 0


def test_chain_warmers_target_scan_chains(cat):
    from presto_tpu.exec.runtime import _chain_warmers

    cfg = ExecConfig(precompile_workers=2)
    runner = LocalRunner(cat, cfg)
    # build side (supplier filter chain, numeric-only) is an execute_node
    # target → warmable; probe side is fused into the join and must NOT be
    qp = runner.plan("select o_orderkey from orders join customer on "
                     "o_custkey = c_custkey where c_acctbal > 100")
    ctx = ExecContext(cat, cfg)
    tasks = _chain_warmers(qp.root, ctx)
    assert len(tasks) >= 1
    for t in tasks:
        t()  # synchronous warm must succeed end-to-end


# ---------------------------------------------------------------------------
# per-class recompile budgets + EXPLAIN headroom


def make_churner(node, n_shapes):
    fn = _node_jit(node, "churn", lambda: (lambda x: x - 1))
    for n in range(1, n_shapes + 1):
        fn(jnp.zeros(n, jnp.int32))
    return node


def test_per_class_budgets(cat):
    from presto_tpu.analysis.recompile import (
        RecompileBudgetError,
        check_recompiles,
        enforce,
        node_class,
    )
    from presto_tpu.plan.nodes import Sort, TableScan

    scan = make_churner(TableScan("m", "t", {"a": "a"}, [("a", BIGINT)]), 5)
    srt = make_churner(Sort(scan, [], None), 5)
    assert node_class(scan) == "scan" and node_class(srt) == "breaker"
    # scan budget binds the scan-class node only
    f = check_recompiles(srt, scan_budget=3)
    assert len(f) == 1 and "scan budget 3" in f[0].message
    # breaker budget binds the sort only
    f = check_recompiles(srt, breaker_budget=2)
    assert len(f) == 1 and "breaker budget 2" in f[0].message
    # global budget still applies to both; class overrides win
    assert len(check_recompiles(srt, shape_budget=4)) == 2
    assert check_recompiles(srt, shape_budget=4, scan_budget=8,
                            breaker_budget=8) == []
    with pytest.raises(RecompileBudgetError):
        enforce(srt, scan_budget=3)


def test_explain_renders_shape_headroom():
    from presto_tpu.plan.nodes import TableScan

    node = make_churner(TableScan("m", "t", {"a": "a"}, [("a", BIGINT)]), 2)
    s = plan_to_string(Output(node, ["a"], ["a"]))
    assert "shapes=2/16" in s  # worst program vs DEFAULT_SHAPE_BUDGET
    s = plan_to_string(Output(node, ["a"], ["a"]),
                       shape_budgets=(None, 4, None))
    assert "shapes=2/4" in s


def test_budget_knobs_flow_through_session():
    from presto_tpu.server.session import Session

    s = Session()
    s.set("max_compiled_shapes_scan", "4")
    s.set("max_compiled_shapes_breaker", "32")
    s.set("precompile_workers", "2")
    s.set("donate_stepping", "false")
    cfg = s.exec_config()
    assert cfg.max_compiled_shapes_scan == 4
    assert cfg.max_compiled_shapes_breaker == 32
    assert cfg.precompile_workers == 2
    assert cfg.donate_stepping is False


# ---------------------------------------------------------------------------
# metrics exposure


def test_compile_counters_render():
    from presto_tpu.server.metrics import render_metrics

    doc = render_metrics(programs.metric_rows({"plane": "worker"}))
    assert "presto_tpu_compile_cache_hits_total" in doc
    assert "presto_tpu_compile_cache_misses_total" in doc
    assert 'plane="worker"' in doc


def test_trace_wall_histogram_in_families():
    from presto_tpu.obs.metrics import ALL_HISTOGRAMS, COMPILE_TRACE_WALL

    assert COMPILE_TRACE_WALL in ALL_HISTOGRAMS
    assert COMPILE_TRACE_WALL.name == "presto_tpu_compile_trace_wall_seconds"

# ---------------------------------------------------------------------------
# persisted programs (PRESTO_TPU_CACHE_DIR warm restart)


def test_program_persistence_restores_after_cold_cache(cat, tmp_path,
                                                       monkeypatch):
    # double gate: cache dir set AND persist flag on
    monkeypatch.setenv("PRESTO_TPU_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("PRESTO_TPU_PROGRAM_PERSIST", "1")
    sql = ("select l_returnflag as f, sum(l_quantity) as s from lineitem "
           "where l_discount > 0.02 group by l_returnflag order by f")
    exp = LocalRunner(cat, ExecConfig()).run(sql)
    pdir = tmp_path / "programs"
    arts = list(pdir.glob("*.jaxexp")) if pdir.exists() else []
    if not arts:
        pytest.skip("jax.export unavailable for these programs "
                    "(persistence is best-effort by contract)")
    # simulate a restart: drop the shared in-memory entries entirely
    programs.reset(counters_only=False)
    out = LocalRunner(cat, ExecConfig()).run(sql)
    snap = programs.snapshot()
    assert snap["restored"] > 0  # artifacts re-hydrated, re-trace skipped
    assert out.equals(exp)  # restored programs compute the same answer


def test_program_persistence_gate_defaults_off(cat, tmp_path, monkeypatch):
    # cache dir alone must NOT write artifacts (opt-in flag required)
    monkeypatch.setenv("PRESTO_TPU_CACHE_DIR", str(tmp_path))
    monkeypatch.delenv("PRESTO_TPU_PROGRAM_PERSIST", raising=False)
    LocalRunner(cat, ExecConfig()).run(
        "select count(*) as c from region")
    assert not (tmp_path / "programs").exists()


# ---------------------------------------------------------------------------
# compile-cache directory: one pure decision, settable from outside


@pytest.mark.parametrize("environ,expect", [
    ({"JAX_COMPILATION_CACHE_DIR": "/x"}, None),  # set → leave alone
    ({"JAX_COMPILATION_CACHE_DIR": "/x",
      "PRESTO_TPU_CACHE_DIR": "/y"}, None),
    ({}, "checkout"),
    ({"PRESTO_TPU_CACHE_DIR": "/y"}, "checkout"),  # umbrella ≠ XLA cache
])
def test_compile_cache_dir_is_a_pure_function_of_env(environ, expect):
    import os

    import presto_tpu

    got = presto_tpu.compile_cache_dir(environ)
    if expect is None:
        assert got is None
    else:
        checkout = os.path.dirname(os.path.dirname(
            os.path.abspath(presto_tpu.__file__)))
        assert got == os.path.join(checkout, ".jax_cache")


def test_persisted_query_does_not_repoint_compile_cache(cat, tmp_path,
                                                        monkeypatch):
    import jax

    monkeypatch.setenv("PRESTO_TPU_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("PRESTO_TPU_PROGRAM_PERSIST", "1")
    before = jax.config.jax_compilation_cache_dir
    programs.reset(counters_only=False)
    LocalRunner(cat, ExecConfig()).run(
        "select l_linestatus as f, sum(l_tax) as s from lineitem "
        "where l_discount > 0.03 group by l_linestatus order by f")
    assert jax.config.jax_compilation_cache_dir == before
    assert not (tmp_path / "xla_cache").exists()
    assert programs.enable_compilation_cache() == bool(before)
