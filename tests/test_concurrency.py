"""Concurrency-safety plane: the analyzer's four rules over synthetic
sources, the shipped tree staying clean, the CLI JSON schema, the
devprof race-fix regressions, and a thread-stress matrix that drives one
coordinator from many client threads and reconciles every shared-state
ledger exactly (program-cache counters, /v1/memory, the HBO JSONL).

Reference discipline: the reference engine's TestingPrestoServer
concurrency drills + error-prone's GuardedBy checker — here re-aimed at
the engine's process-wide singletons."""

import json
import os
import textwrap
import threading
import time
import urllib.request

import numpy as np
import pytest

import presto_tpu
from presto_tpu.analysis.concurrency import (
    CONCURRENCY_RULES,
    analyze_paths,
    analyze_source,
)
from presto_tpu.catalog.memory import MemoryConnector
from presto_tpu.connector import Catalog
from presto_tpu.exec import ExecConfig
from presto_tpu.exec import programs
from presto_tpu.obs import devprof
from presto_tpu.obs import runstats


def check(src, path="mod.py"):
    return analyze_source(textwrap.dedent(src), path)


def rules_of(findings):
    return {f.rule for f in findings}


# -- rule matrix: unguarded ------------------------------------------------


class TestUnguarded:
    def test_module_state_mutation_outside_lock(self):
        fs = check("""
            import threading
            _lock = threading.Lock()
            _cache = {}

            def put(k, v):
                _cache[k] = v
        """)
        assert rules_of(fs) == {"unguarded"}
        assert any("mod.py:7" in f.loc for f in fs)
        assert all(f.plane == "concurrency" for f in fs)

    def test_module_state_mutation_under_lock_is_clean(self):
        assert check("""
            import threading
            _lock = threading.Lock()
            _cache = {}

            def put(k, v):
                with _lock:
                    _cache[k] = v
        """) == []

    def test_annotation_pins_the_guard(self):
        # mutation under the WRONG lock: inference alone would accept any
        # held lock; the annotation names the one that counts
        fs = check("""
            import threading
            _a = threading.Lock()
            _b = threading.Lock()
            _cache = {}  # shared: guarded-by(_a)

            def put(k, v):
                with _b:
                    _cache[k] = v
        """)
        assert "unguarded" in rules_of(fs)

    def test_class_attr_annotation(self):
        fs = check("""
            import threading

            class Pool:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.items = []  # shared: guarded-by(self._lock)

                def add(self, x):
                    self.items.append(x)
        """)
        assert rules_of(fs) == {"unguarded"}

    def test_class_attr_guarded_is_clean(self):
        assert check("""
            import threading

            class Pool:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.items = []  # shared: guarded-by(self._lock)

                def add(self, x):
                    with self._lock:
                        self.items.append(x)
        """) == []

    def test_requires_annotation_covers_the_body(self):
        # the def-line annotation declares the caller holds the lock: the
        # body is one critical section, not a pile of unguarded writes
        assert check("""
            import threading
            _lock = threading.Lock()
            _cache = {}

            def flush():  # shared: requires(_lock)
                _cache.clear()
        """) == []

    def test_locked_suffix_checks_call_sites(self):
        fs = check("""
            import threading
            _lock = threading.Lock()
            _cache = {}

            def _flush_locked():
                _cache.clear()

            def careless():
                _flush_locked()
        """)
        assert rules_of(fs) == {"unguarded"}
        assert any("_flush_locked" in f.message for f in fs)

    def test_locked_suffix_call_under_lock_is_clean(self):
        assert check("""
            import threading
            _lock = threading.Lock()
            _cache = {}

            def _flush_locked():
                _cache.clear()

            def careful():
                with _lock:
                    _flush_locked()
        """) == []

    def test_suppression_is_line_scoped(self):
        assert check("""
            import threading
            _lock = threading.Lock()
            _cache = {}

            def put(k, v):
                _cache[k] = v  # lint: allow(unguarded)
        """) == []

    def test_init_is_exempt(self):
        # construction happens-before sharing: __init__ writes are free
        assert check("""
            import threading

            class Pool:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.items = []  # shared: guarded-by(self._lock)
                    self.items.append(0)
        """) == []


# -- rule matrix: check-then-act -------------------------------------------


def cta_src(suffix=""):
    return """
    import threading
    _lock = threading.Lock()
    _cache = {}

    def get_or_make(k):
        with _lock:
            v = _cache.get(k)
        if v is None:
            v = object()
            with _lock:
                _cache[k] = v%s
        return v
""" % suffix


class TestCheckThenAct:
    def test_split_critical_sections_fire(self):
        fs = check(cta_src())
        assert "check-then-act" in rules_of(fs)

    def test_single_critical_section_is_clean(self):
        assert check("""
            import threading
            _lock = threading.Lock()
            _cache = {}

            def get_or_make(k):
                with _lock:
                    v = _cache.get(k)
                    if v is None:
                        v = _cache[k] = object()
                return v
        """) == []

    def test_suppression(self):
        assert check(cta_src("  # lint: allow(check-then-act)")) == []

    def test_unguarded_read_does_not_pair(self):
        # double-checked locking: the unlocked probe is not a guarded
        # read, so only the (revalidated) locked section counts
        assert check("""
            import threading
            _lock = threading.Lock()
            _cache = {}

            def get_or_make(k):
                v = _cache.get(k)
                if v is None:
                    with _lock:
                        if k not in _cache:
                            _cache[k] = object()
                        v = _cache[k]
                return v
        """) == []


# -- rule matrix: lock-order -----------------------------------------------


class TestLockOrder:
    def test_cycle_fires(self):
        fs = check("""
            import threading
            _a = threading.Lock()
            _b = threading.Lock()

            def f():
                with _a:
                    with _b:
                        pass

            def g():
                with _b:
                    with _a:
                        pass
        """)
        assert "lock-order" in rules_of(fs)

    def test_consistent_order_is_clean(self):
        assert check("""
            import threading
            _a = threading.Lock()
            _b = threading.Lock()

            def f():
                with _a:
                    with _b:
                        pass

            def g():
                with _a:
                    with _b:
                        pass
        """) == []

    def test_interprocedural_self_deadlock(self):
        # outer holds the non-reentrant lock and calls inner, which
        # acquires it again: found through the may-acquire fixpoint, not
        # lexical nesting
        fs = check("""
            import threading
            _lock = threading.Lock()
            _c = {}

            def outer():
                with _lock:
                    inner()

            def inner():
                with _lock:
                    _c["x"] = 1
        """)
        assert "lock-order" in rules_of(fs)

    def test_rlock_reacquire_is_clean(self):
        assert check("""
            import threading
            _lock = threading.RLock()
            _c = {}

            def outer():
                with _lock:
                    inner()

            def inner():
                with _lock:
                    _c["x"] = 1
        """) == []


# -- rule matrix: lock-in-jit ----------------------------------------------


class TestLockInJit:
    def test_lock_in_traced_region_fires(self):
        fs = check("""
            import threading

            import jax

            _lock = threading.Lock()

            @jax.jit
            def kernel(x):
                with _lock:
                    return x + 1
        """)
        assert "lock-in-jit" in rules_of(fs)

    def test_lock_outside_traced_region_is_clean(self):
        assert check("""
            import threading

            import jax

            _lock = threading.Lock()

            @jax.jit
            def kernel(x):
                return x + 1

            def host(x):
                with _lock:
                    return kernel(x)
        """) == []


# -- the shipped tree and the CLI ------------------------------------------


class TestShippedTree:
    def test_package_is_clean(self):
        pkg = os.path.dirname(os.path.abspath(presto_tpu.__file__))
        assert analyze_paths([pkg]) == []

    def test_cli_json_schema(self, tmp_path, capsys):
        # exposition-style contract for CI consumers: the --json document
        # is {findings: [{rule, loc, message, plane}], count, planes, timings}
        from presto_tpu.analysis.__main__ import main

        bad = tmp_path / "bad_mod.py"
        bad.write_text(textwrap.dedent("""
            import threading
            _lock = threading.Lock()
            _cache = {}

            def put(k, v):
                _cache[k] = v
        """))
        rc = main(["--no-lint", "--concurrency", "--json", str(bad)])
        doc = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert set(doc) == {"findings", "count", "planes", "timings"}
        assert doc["count"] == len(doc["findings"]) >= 1
        assert any("concurrency" in p for p in doc["planes"])
        for f in doc["findings"]:
            assert set(f) == {"rule", "loc", "message", "plane"}
            assert f["rule"] in CONCURRENCY_RULES
            assert f["plane"] == "concurrency"
            # loc anchors to file:line
            path, _, line = f["loc"].rpartition(":")
            assert path.endswith("bad_mod.py") and int(line) > 0

    def test_cli_rules_subset(self, tmp_path, capsys):
        from presto_tpu.analysis.__main__ import main

        bad = tmp_path / "bad_mod.py"
        bad.write_text(textwrap.dedent("""
            import threading
            _lock = threading.Lock()
            _cache = {}

            def put(k, v):
                _cache[k] = v
        """))
        rc = main(["--no-lint", "--concurrency", "--json",
                   "--rules", "lock-order", str(bad)])
        doc = json.loads(capsys.readouterr().out)
        assert rc == 0 and doc["count"] == 0


# -- devprof race-fix regressions ------------------------------------------


class TestDevprofRaces:
    @pytest.fixture(autouse=True)
    def _clean(self):
        devprof.reset()
        yield
        devprof.deactivate()
        devprof.set_provider(None)
        devprof.reset()

    def test_default_provider_records_platform(self):
        # the platform label is written by the provider OUTSIDE
        # sample_hbm's critical section (so a slow backend can't stall
        # readers) — it must still land, lock-correctly, in the doc
        doc = devprof.sample_hbm()
        assert doc.get("platform") == "cpu"
        assert devprof.device_memory_doc()["platform"] == "cpu"

    def test_inflight_claim_lowers_exactly_once(self):
        devprof.activate()
        n = 8
        lowered = [0]
        llock = threading.Lock()
        barrier = threading.Barrier(n)

        class FakeLowered:
            def cost_analysis(self):
                return {"flops": 7.0}

            def compile(self):
                raise RuntimeError("no memory analysis in this fake")

        class FakeJfn:
            def lower(self, *a, **k):
                with llock:
                    lowered[0] += 1
                time.sleep(0.05)  # hold the window open for the race
                return FakeLowered()

        class FakeEntry:
            fp = "test|claim|once"
            jfn = FakeJfn()

        errors = []

        def hammer():
            try:
                barrier.wait(10)
                devprof.on_call(FakeEntry(), "agg", "k")
            except Exception as e:  # pragma: no cover - failure detail
                errors.append(e)

        threads = [threading.Thread(target=hammer) for _ in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        assert not errors
        # the in-flight claim admits exactly one lowering; every racer
        # that lost the claim returned without duplicating the work
        assert lowered[0] == 1
        progs = devprof.snapshot()["programs"]
        assert progs["test|claim|once"]["flops"] == 7.0

    def test_failed_analysis_is_never_retried(self):
        devprof.activate()
        lowered = [0]

        class FakeJfn:
            def lower(self, *a, **k):
                lowered[0] += 1
                raise RuntimeError("lowering exploded")

        class FakeEntry:
            fp = "test|claim|fail"
            jfn = FakeJfn()

        for _ in range(3):
            devprof.on_call(FakeEntry(), "agg", "k")
        assert lowered[0] == 1
        assert "test|claim|fail" not in devprof.snapshot()["programs"]


# -- HBO JSONL cross-process safety ----------------------------------------


class TestHBOCrossProcess:
    @pytest.fixture(autouse=True)
    def _hbo_dir(self, tmp_path, monkeypatch):
        monkeypatch.setenv("PRESTO_TPU_CACHE_DIR", str(tmp_path))
        runstats.reset()
        yield
        runstats.reset()

    def test_appends_are_whole_lines(self):
        # 8 threads × 20 observes: every line in the file must parse —
        # single O_APPEND os.write per record, no torn interleavings
        n, per = 8, 20
        barrier = threading.Barrier(n)

        def writer(tid):
            barrier.wait(10)
            for i in range(per):
                runstats.observe(f"fp{tid}/cat", f"site{i % 5}", "agg",
                                 10.0, float(i))

        threads = [threading.Thread(target=writer, args=(t,))
                   for t in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        path = runstats.history_path()
        recs = [json.loads(line) for line in open(path)]
        assert len(recs) == n * per
        assert all({"fp", "site", "actual", "n"} <= set(r) for r in recs)

    def test_compaction_carries_foreign_entries(self):
        # an entry appended by ANOTHER process (simulated: not in this
        # process's in-memory store) must survive the compaction rewrite
        runstats.observe("fp1/cat", "siteA", "agg", 10.0, 25.0)
        path = runstats.history_path()
        with open(path, "a") as fh:
            fh.write(json.dumps({"fp": "fpX/cat", "site": "siteC",
                                 "actual": 3.0, "n": 1}) + "\n")
        runstats.compact()
        keys = {(r["fp"], r["site"])
                for r in (json.loads(line) for line in open(path))}
        assert ("fpX/cat", "siteC") in keys
        assert ("fp1/cat", "siteA") in keys
        # and the foreign entry is now loadable by this process too
        runstats.reset()
        assert runstats.lookup("fpX/cat", "siteC")["actual"] == 3.0

    def test_lock_file_lifecycle(self):
        runstats.observe("fp1/cat", "siteA", "agg", 1.0, 2.0)
        path = runstats.history_path()
        # the flock sidecar exists next to the history file
        assert os.path.exists(path + ".lock")


# -- thread-stress: one coordinator, many client threads -------------------


STRESS_QUERIES = [
    "select k, sum(v) as s from t group by k",
    "select count(*) as n from t where v > 0.5",
    "select max(v) as m, min(v) as lo from t",
    "select k, count(*) as c from t where k < 20 group by k",
]


def _stress_catalog(rows):
    conn = MemoryConnector()
    rng = np.random.default_rng(11)
    conn.add_table("t", {"k": np.arange(rows, dtype=np.int64) % 37,
                         "v": rng.normal(size=rows)})
    cat = Catalog()
    cat.register("m", conn, default=True)
    return cat


def _run_stress(tmp_path, monkeypatch, n_threads, per_thread, rows,
                n_shapes):
    """Drive one coordinator from n_threads client threads and reconcile
    every shared ledger exactly: program-cache hits+misses == lookups,
    /v1/memory drains to zero, and no HBO entry is lost between the
    in-memory store and the JSONL file."""
    from presto_tpu.server.coordinator import DistributedRunner

    monkeypatch.setenv("PRESTO_TPU_CACHE_DIR", str(tmp_path))
    runstats.reset()

    # count every shared program-cache lookup racing through entry_for
    lookups = [0]
    llock = threading.Lock()
    orig_entry_for = programs.entry_for

    def counting_entry_for(ns, *a, **k):
        if ns is not None:
            with llock:
                lookups[0] += 1
        return orig_entry_for(ns, *a, **k)

    monkeypatch.setattr(programs, "entry_for", counting_entry_for)
    base = programs.snapshot()

    queries = STRESS_QUERIES[:n_shapes]
    results = []
    rlock = threading.Lock()
    barrier = threading.Barrier(n_threads)

    with DistributedRunner(_stress_catalog(rows), n_workers=2,
                           config=ExecConfig(batch_rows=1 << 12)) as dr:
        coord = dr.coordinator

        def client(tid):
            try:
                barrier.wait(30)
                for i in range(per_thread):
                    sql = queries[(tid + i) % len(queries)]
                    session = coord.protocol.session_from_headers({})
                    qe = coord.query_manager.create_query(session, sql)
                    ok = qe.wait(120)
                    with rlock:
                        results.append((tid, sql, ok, qe.state, qe.error))
            except Exception as e:  # pragma: no cover - failure detail
                with rlock:
                    results.append((tid, "?", False, "EXCEPTION", str(e)))

        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        assert not any(t.is_alive() for t in threads)

        # every query finished — no state-machine corruption under load
        assert len(results) == n_threads * per_thread
        bad = [r for r in results if r[3] != "FINISHED"]
        assert not bad, bad

        # ledger 1: the program cache counted every lookup exactly once
        snap = programs.snapshot()
        hits = snap["hits"] - base["hits"]
        misses = snap["misses"] - base["misses"]
        assert hits + misses == lookups[0]
        assert hits >= 0 and misses >= 0

        # ledger 2: /v1/memory reconciles to zero once the dust settles
        deadline = time.time() + 30
        doc = {}
        while time.time() < deadline:
            doc = json.load(urllib.request.urlopen(
                coord.url + "/v1/memory", timeout=10))
            if (doc["cluster"]["totalReservedBytes"] == 0
                    and all(n["reservedBytes"] == 0
                            for n in doc["nodes"].values())):
                break
            time.sleep(0.2)
        assert doc["cluster"]["totalReservedBytes"] == 0
        assert all(n["reservedBytes"] == 0 for n in doc["nodes"].values())
        assert doc["cluster"]["lowMemoryKills"] == 0

    # ledger 3: every in-memory HBO entry made it to the JSONL file
    # (each observe appends the merged entry under the flock discipline)
    mem_keys = set(runstats.snapshot()["history"])
    assert mem_keys, "stress produced no HBO observations"
    path = runstats.history_path()
    file_keys = set()
    with open(path) as fh:
        for line in fh:
            rec = json.loads(line)
            file_keys.add(f"{rec['fp']}|{rec['site']}")
    assert mem_keys <= file_keys


def test_thread_stress_fast(tmp_path, monkeypatch):
    _run_stress(tmp_path, monkeypatch, n_threads=8, per_thread=2,
                rows=400, n_shapes=3)


@pytest.mark.slow
def test_thread_stress_matrix(tmp_path, monkeypatch):
    _run_stress(tmp_path, monkeypatch, n_threads=16, per_thread=4,
                rows=20000, n_shapes=4)
