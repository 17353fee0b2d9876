"""Ahead-of-traffic compile farm: boot-time program pre-arming, a
persisted plan corpus, inflight compile claims, and speculative
queue-wait precompilation.

Reference: the reference engine never shows a user its codegen cost —
ExpressionCompiler / PageFunctionCompiler classes live in a process-wide
generated-bytecode cache that is warm by the time traffic arrives, and a
restarted coordinator re-fills it from the steady drizzle of production
queries long before any latency-sensitive tenant notices. Our XLA analog
(exec/programs.py) made programs *shareable*; this module moves their
compilation off the query's critical path entirely:

- **plan corpus** (``farm_corpus.jsonl`` under ``PRESTO_TPU_CACHE_DIR``):
  structural fingerprints are one-way hashes, so pre-arming needs the
  plans themselves. Every installed plan (LocalRunner roots, worker
  fragment roots) appends its codec canonical JSON once, keyed by the
  root's structural sha; a ``sql`` record maps each statement's digest to
  its fragment fingerprints for queue-wait speculation. Same append +
  ``fcntl.flock`` discipline as the HBO history file; corrupt or
  tombstoned lines are skipped, never fatal.
- **boot farm**: a bounded worker pool decodes the corpus (HBO-observed
  fingerprints first — ``hbo_history.jsonl`` is the traffic oracle),
  stamps program namespaces, and runs the SAME chain warmers the live
  path uses, so trace + backend compile happen before the coordinator
  reports ready. Persisted ``jax.export`` artifacts and the XLA
  persistent compilation cache are picked up through the ordinary
  ``entry_for`` restore path.
- **inflight claims**: every warm task claims ``(program namespace,
  warmer)`` in a process-wide map before compiling; a concurrent farm
  worker or live-query warmer that loses the claim WAITS on the winner
  instead of double-compiling (the PR 12 check-then-act discipline,
  applied to compilation).
- **speculative queue-wait precompile**: while a query sits in its
  resource-group queue, the farm compiles the corpus plans recorded for
  its statement digest; the compile delta is charged to the group's
  compile budget (never to the query's own terminal delta — the query
  manager nets farm-attributed compiles out).

Everything is gated: ``PRESTO_TPU_FARM=1`` arms the process (boot), the
``compile_farm`` session property arms recording/speculation per query.
Off means off — no corpus IO, no claims, no metric families.
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

_CORPUS_FILE = "farm_corpus.jsonl"
# bound the number of corpus plans one boot will arm (a runaway corpus
# must not turn boot into an unbounded compile storm)
_DEFAULT_BOOT_LIMIT = 256
_DEFAULT_WORKERS = 2
# a claim loser waits for the winner's compile this long before giving
# up and compiling anyway (correctness never depends on the claim)
_CLAIM_WAIT_S = 120.0

_lock = threading.Lock()
_counters: Dict[str, int] = {  # shared: guarded-by(_lock)
    # corpus plans appended by this process
    "recorded": 0,
    # corpus plans armed (decoded + warmers ran) at boot
    "boot_armed": 0,
    # corpus lines skipped at load (corrupt / tombstoned / undecodable)
    "skipped": 0,
    # speculative precompile launches (one per queued query with a
    # corpus match)
    "speculations": 0,
    # speculations skipped because the group's compile budget was dry
    "speculations_budget_denied": 0,
    # warm tasks that lost an inflight claim and waited on the winner
    "claims_contended": 0,
    # XLA compile events attributed to farm work (boot + speculation);
    # the query manager subtracts these from live-query budget deltas
    "farm_compiles": 0,
}
_boot_wall_s = [0.0]  # shared: guarded-by(_lock)
# fp24 → "armed" (boot) | "live" (queue-wait speculation)
_status: Dict[str, str] = {}  # shared: guarded-by(_lock)
# inflight compile claims: claim key → Event set when the winner finished
_claims: Dict[str, threading.Event] = {}  # shared: guarded-by(_lock)
# root fingerprints already appended by this process (dedups corpus IO)
_recorded_fps: set = set()  # shared: guarded-by(_lock)
_recorded_sqls: set = set()  # shared: guarded-by(_lock)
# parsed corpus cache: (mtime, size) → {"plans": {...}, "sql": {...}}
_corpus_cache: List[Any] = [None, None]  # shared: guarded-by(_lock)
_pool = None  # shared: guarded-by(_lock)
_futures: List[Any] = []  # shared: guarded-by(_lock)


def enabled(config=None) -> bool:
    """Process-level arming (PRESTO_TPU_FARM=1) or per-session arming
    (compile_farm=on). config=None asks only about the process."""
    if os.environ.get("PRESTO_TPU_FARM") == "1":
        return True
    return (config is not None
            and getattr(config, "compile_farm", "off") == "on")


def corpus_path() -> Optional[str]:
    d = os.environ.get("PRESTO_TPU_CACHE_DIR")
    if not d:
        return None
    return os.path.join(d, _CORPUS_FILE)


def _fp24(root) -> Optional[str]:  # fp: key(farm-corpus) covers(plan-structure, config)
    """Config-free structural fingerprint of a plan root — the farm's
    status/corpus key (matches the HBO fingerprint's structural half).
    The key covers config even though the sha is config-free because
    every corpus record CARRIES the recording process's non-volatile
    config (`cfg`, see record_plan) and the armers warm under it —
    programs land in the same `_program_ns` the recorded traffic used,
    not whatever config the booting process happens to hold."""
    from presto_tpu.exec.programs import structural_fingerprint

    fp = structural_fingerprint(root)
    return fp[:24] if fp else None


def _cfg_doc(config) -> Dict[str, Any]:
    """JSON-safe dump of the non-volatile (program-relevant) ExecConfig
    fields — exactly the set config_fingerprint hashes, so a corpus
    record pins the program identity its plan compiled under."""
    import dataclasses

    from presto_tpu.exec.programs import _VOLATILE_CONFIG_FIELDS

    out: Dict[str, Any] = {}
    for f in dataclasses.fields(config):
        if f.name in _VOLATILE_CONFIG_FIELDS:
            continue
        v = getattr(config, f.name, None)
        if isinstance(v, tuple):
            v = list(v)
        if v is None or isinstance(v, (bool, int, float, str, list)):
            out[f.name] = v
    return out


def _cfg_restore(config, doc) -> Any:
    """The recorded config, reconstructed over the ambient one: known
    fields are replaced (JSON lists back to tuples — JSON has no
    tuples, so any list in a cfg doc started as one), unknown fields
    (older/newer writer) are ignored."""
    import dataclasses

    if not isinstance(doc, dict) or not doc:
        return config
    known = {f.name for f in dataclasses.fields(config)}
    fixed = {k: (tuple(v) if isinstance(v, list) else v)
             for k, v in doc.items() if k in known}
    try:
        return dataclasses.replace(config, **fixed)
    except (TypeError, ValueError):
        return config


def _sql_sha(sql: str) -> str:
    return hashlib.sha256(sql.strip().encode()).hexdigest()[:16]


# -- corpus -------------------------------------------------------------------


def _append(rec: Dict[str, Any]) -> bool:
    """One O_APPEND JSONL write under the cross-process flock (same
    discipline as obs/runstats.py — one line is one atomic record)."""
    path = corpus_path()
    if path is None:
        return False
    from presto_tpu.obs.runstats import _flock, _funlock

    data = (json.dumps(rec, sort_keys=True) + "\n").encode()
    lk = _flock(path, exclusive=True)
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
        try:
            os.write(fd, data)
        finally:
            os.close(fd)
        return True
    except OSError:
        return False
    finally:
        _funlock(lk)


def record_plan(root, ctx) -> bool:
    """Append this root's codec JSON to the corpus (once per process per
    fingerprint). Called from install_plan_programs — LocalRunner plan
    roots and worker fragment roots both land here, so the corpus holds
    exactly the trees whose programs actually compiled."""
    from presto_tpu.plan.codec import CodecError, canonical_node_json

    fp = _fp24(root)
    if fp is None:
        return False
    with _lock:
        if fp in _recorded_fps:
            return False
        _recorded_fps.add(fp)
    try:
        doc = json.loads(canonical_node_json(root))
    except (CodecError, TypeError, ValueError):
        return False
    ok = _append({"v": 1, "kind": "plan", "fp": fp, "plan": doc,
                  "cfg": _cfg_doc(ctx.config),
                  "ts": round(time.time(), 3)})
    if ok:
        with _lock:
            _counters["recorded"] += 1
    return ok


def record_sql(sql: str, roots) -> bool:
    """Map a statement digest to its plan fingerprints (queue-wait
    speculation resolves future submissions of the same SQL through
    this record — the raw SQL itself never touches the cache dir)."""
    if not sql:
        return False
    sha = _sql_sha(sql)
    with _lock:
        if sha in _recorded_sqls:
            return False
        _recorded_sqls.add(sha)
    fps = [fp for fp in (_fp24(r) for r in roots) if fp]
    if not fps:
        return False
    return _append({"v": 1, "kind": "sql", "sql": sha, "fps": fps,
                    "ts": round(time.time(), 3)})


def load_corpus() -> Dict[str, Dict[str, Any]]:
    """Parse the corpus (last line wins per key; corrupt lines counted
    and skipped; ``deleted`` tombstones drop their key). Cached on the
    file's (mtime, size) so queue-wait speculation stays cheap."""
    path = corpus_path()
    empty: Dict[str, Dict[str, Any]] = {"plans": {}, "sql": {},
                                        "cfgs": {}}
    if path is None or not os.path.exists(path):
        return empty
    try:
        st = os.stat(path)
        stamp = (st.st_mtime_ns, st.st_size)
    except OSError:
        return empty
    with _lock:
        if _corpus_cache[0] == stamp and _corpus_cache[1] is not None:
            return _corpus_cache[1]
    from presto_tpu.obs.runstats import _flock, _funlock

    plans: Dict[str, Any] = {}
    sqls: Dict[str, Any] = {}
    cfgs: Dict[str, Any] = {}
    skipped = 0
    lk = _flock(path, exclusive=False)
    try:
        with open(path, "r", encoding="utf-8", errors="replace") as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                    kind = rec["kind"]
                    if kind == "plan":
                        fp = str(rec["fp"])
                        if rec.get("deleted"):
                            plans.pop(fp, None)
                            cfgs.pop(fp, None)
                        else:
                            plans[fp] = rec["plan"]
                            # pre-cfg records (older writers) arm with
                            # the ambient config, same as before
                            cfgs[fp] = rec.get("cfg") or {}
                    elif kind == "sql":
                        sqls[str(rec["sql"])] = [str(f)
                                                 for f in rec["fps"]]
                    else:
                        skipped += 1
                except (KeyError, TypeError, ValueError):
                    skipped += 1
    except OSError:
        return empty
    finally:
        _funlock(lk)
    corpus = {"plans": plans, "sql": sqls, "cfgs": cfgs}
    with _lock:
        # stamp-keyed memo: racing parsers store (stamp, corpus) as an
        # atomic pair, so a stale pair self-heals on the next stat probe
        _corpus_cache[0] = stamp  # lint: allow(check-then-act)
        _corpus_cache[1] = corpus  # lint: allow(check-then-act)
        _counters["skipped"] += skipped
    return corpus


def _hbo_observed_fps() -> set:
    """Structural fp24 prefixes present in the HBO history — the farm's
    arming priority (observed traffic compiles first)."""
    from presto_tpu.obs import runstats as _runstats

    path = _runstats.history_path()
    out: set = set()
    if path is None or not os.path.exists(path):
        return out
    try:
        with open(path, "r", encoding="utf-8", errors="replace") as fh:
            for line in fh:
                try:
                    fp = json.loads(line).get("fp")
                except (TypeError, ValueError):
                    continue
                if isinstance(fp, str) and len(fp) >= 24:
                    out.add(fp[:24])
    except OSError:
        pass
    return out


def artifact_count() -> int:
    """Persisted jax.export artifacts under the cache dir (boot report)."""
    d = os.environ.get("PRESTO_TPU_CACHE_DIR")
    if not d:
        return 0
    try:
        return sum(1 for fn in os.listdir(os.path.join(d, "programs"))
                   if fn.endswith(".jaxexp"))
    except OSError:
        return 0


# -- inflight claims ----------------------------------------------------------


def _claim(key: str) -> Tuple[bool, threading.Event]:
    with _lock:
        ev = _claims.get(key)
        if ev is not None:
            return False, ev
        ev = _claims[key] = threading.Event()
        return True, ev


def _run_claimed(key: Optional[str], fn: Callable[[], None]) -> bool:
    """Run `fn` under the inflight claim for `key`: the winner compiles,
    losers wait for it (bounded) and skip. Returns True when this caller
    actually ran `fn`."""
    if key is None:
        fn()
        return True
    won, ev = _claim(key)
    if not won:
        with _lock:
            _counters["claims_contended"] += 1
        ev.wait(_CLAIM_WAIT_S)
        return False
    try:
        fn()
    finally:
        ev.set()
    return True


def _task_claim_key(task) -> Optional[str]:
    """Claim key for one chain-warmer task (a functools.partial whose
    first arg is the plan node): program namespace + warmer identity.
    Unstamped nodes (no namespace) warm unclaimed — their programs are
    private, so there is nothing shared to double-compile."""
    try:
        node = task.args[0]
        ns = node.__dict__.get("_program_ns")
        name = getattr(task.func, "__name__", "warm")
    except (AttributeError, IndexError):
        return None
    if not ns:
        return None
    return f"{ns}|{name}"


def wrap_claims(tasks: List[Callable]) -> List[Callable]:
    """Wrap live-path warm tasks in the farm's inflight claims, so
    concurrent queries (and a booting farm) compile each shared program
    exactly once."""
    out = []
    for t in tasks:
        key = _task_claim_key(t)
        out.append(lambda t=t, key=key: _run_claimed(key, t))
    return out


# -- farm pool ----------------------------------------------------------------


def _get_pool(workers: int):
    from concurrent.futures import ThreadPoolExecutor

    global _pool
    with _lock:
        if _pool is None:
            _pool = ThreadPoolExecutor(
                max_workers=max(1, workers),
                thread_name_prefix="compile-farm")
        return _pool


def _submit(fn: Callable[[], None], workers: int):
    pool = _get_pool(workers)

    def safe():
        try:
            fn()
        except Exception:
            pass  # farm work is best-effort by contract

    fut = pool.submit(safe)
    with _lock:
        _futures.append(fut)
        del _futures[:-1024]
    return fut


def drain() -> None:
    """Block until every outstanding farm task finished (boot block=True,
    tests, benches)."""
    while True:
        with _lock:
            pending = [f for f in _futures if not f.done()]
        if not pending:
            return
        for f in pending:
            try:
                f.result(timeout=600.0)
            except Exception:
                pass


# -- arming -------------------------------------------------------------------


def _warm_tasks_for(root, catalog, config) -> List[Callable]:
    """Decode-side mirror of the live install path: stamp namespaces,
    then build the SAME chain-warmer tasks execute_node would jit."""
    from presto_tpu.exec import programs as _programs
    from presto_tpu.exec.runtime import ExecContext, _chain_warmers

    ctx = ExecContext(catalog, config)
    _programs.install_plan(root, config)
    return _chain_warmers(root, ctx)


def _run_entry(fp: str, doc, catalog, config, status: str,
               cfg=None) -> int:  # fp: uses-key(farm-corpus)
    """Arm one corpus plan: decode, install under the RECORDED config
    (`cfg`, falling back to the ambient one for pre-cfg records), run
    its warmers under inflight claims, attribute the compile delta to
    the farm. Returns warm tasks run (≥0), or -1 when the plan was
    skipped (undecodable / uninstallable) — skips never count as
    armed."""
    from presto_tpu.exec import programs as _programs
    from presto_tpu.obs import metrics as _obs_metrics
    from presto_tpu.plan.codec import CodecError, node_from_json

    try:
        root = node_from_json(doc)
    except (CodecError, KeyError, TypeError, ValueError):
        with _lock:
            _counters["skipped"] += 1
        return -1
    try:
        tasks = _warm_tasks_for(root, catalog, _cfg_restore(config, cfg))
    except Exception:
        with _lock:
            _counters["skipped"] += 1
        return -1
    ran = 0
    for t in tasks:
        key = _task_claim_key(t)
        t0 = time.perf_counter()
        c0 = _programs.snapshot()["compiles"]

        def run(t=t):
            t()

        try:
            if _run_claimed(key, run):
                ran += 1
                delta = _programs.snapshot()["compiles"] - c0
                wall = time.perf_counter() - t0
                with _lock:
                    # process-counter delta over-attributes under
                    # concurrency (a neighbor's compile lands in the
                    # window) — same documented tolerance as the group
                    # budget charge in querymanager._charge_compiles
                    if delta > 0:
                        _counters["farm_compiles"] += delta
                try:
                    _obs_metrics.FARM_WARM_WALL.observe(
                        wall, plane="worker")
                except Exception:
                    pass
        except Exception:
            pass
    with _lock:
        cur = _status.get(fp)
        if status == "armed" and cur is None:
            _status[fp] = "armed"
        elif status == "live":
            _status[fp] = "live"
    return ran


def boot(catalog, config=None, workers: Optional[int] = None,
         block: bool = True,
         limit: Optional[int] = None) -> int:  # fp: uses-key(farm-corpus)
    """Pre-arm the process-wide program cache from the persisted corpus.
    Returns the number of corpus plans armed. block=True (coordinator
    boot) waits for the pool — "ready" means warm."""
    if not enabled(config) or corpus_path() is None:
        return 0
    from presto_tpu.exec import programs as _programs
    from presto_tpu.exec.runtime import ExecConfig
    from presto_tpu.obs import events as _obs_events

    config = config or ExecConfig()
    workers = workers or int(
        os.environ.get("PRESTO_TPU_FARM_WORKERS", _DEFAULT_WORKERS))
    limit = limit or int(
        os.environ.get("PRESTO_TPU_FARM_LIMIT", _DEFAULT_BOOT_LIMIT))
    # register pytree serialization on THIS thread, before workers exist:
    # a worker registering mid-boot can lose an import race against
    # another worker's lazy ops import, and artifact restore would
    # silently downgrade to a re-compile for the affected types
    _programs._ensure_pytree_serialization()
    corpus = load_corpus()
    plans = corpus["plans"]
    if not plans:
        return 0
    observed = _hbo_observed_fps()
    # traffic-observed structures arm first; the rest in corpus order
    order = sorted(plans, key=lambda fp: (fp not in observed,))[:limit]
    t0 = time.perf_counter()
    c0 = _programs.snapshot()["compiles"]
    # artifact prewarm FIRST: every persisted program deserializes and
    # backend-compiles now, so (a) the warm pass below restores from the
    # shared artifact cache instead of re-tracing, and (b) traffic-path
    # entries created lazily later (fragment/final/sort variants the
    # fabricated warm pass never reaches) dispatch onto already-compiled
    # executables instead of paying XLA on the first live call
    prewarmed = 0
    try:
        prewarmed = _programs.prewarm_artifacts(threads=workers,
                                                limit=4 * limit)
    except Exception:
        pass
    armed = [0]
    armed_lock = threading.Lock()

    def arm(fp):
        if _run_entry(fp, plans[fp], catalog, config, "armed",
                      cfg=corpus["cfgs"].get(fp)) >= 0:
            with armed_lock:
                armed[0] += 1

    futs = [_submit(lambda fp=fp: arm(fp), workers) for fp in order]
    if block:
        for f in futs:
            try:
                f.result(timeout=600.0)
            except Exception:
                pass
    wall = time.perf_counter() - t0
    with _lock:
        _counters["boot_armed"] += armed[0]
        _boot_wall_s[0] += wall
    try:
        _obs_events.EVENTS.emit(
            "precompile_boot", armed=armed[0],
            corpus=len(plans), observed=len(observed),
            artifacts=artifact_count(), prewarmed=prewarmed,
            compiles=_programs.snapshot()["compiles"] - c0,
            wall_s=round(wall, 4), blocking=bool(block))
    except Exception:
        pass
    return armed[0]


def speculate(sql: str, catalog, config, group: Optional[str] = None,
              charge_fn: Optional[Callable[[int], None]] = None,
              budget_fn: Optional[Callable[[], Optional[int]]] = None,
              query_id: Optional[str] = None,
              workers: Optional[int] = None):  # fp: uses-key(farm-corpus)
    """Queue-wait precompile: while the query queues, compile the corpus
    plans recorded for its statement digest. The compile delta is charged
    to the resource group via `charge_fn`; a dry budget (`budget_fn`
    returning 0) skips the speculation — speculative warmth must not
    starve the group's live queries. Non-blocking; returns the submitted
    future (None = nothing to do)."""
    if not enabled(config) or not sql:
        return None
    corpus = load_corpus()
    fps = corpus["sql"].get(_sql_sha(sql)) or []
    plans = corpus["plans"]
    cfgs = corpus["cfgs"]
    todo = [(fp, plans[fp]) for fp in fps if fp in plans]
    if not todo:
        return None
    if budget_fn is not None:
        try:
            remaining = budget_fn()
        except Exception:
            remaining = None
        if remaining is not None and remaining <= 0:
            with _lock:
                _counters["speculations_budget_denied"] += 1
            return None
    from presto_tpu.exec import programs as _programs
    from presto_tpu.obs import events as _obs_events

    with _lock:
        _counters["speculations"] += 1
    workers = workers or int(
        os.environ.get("PRESTO_TPU_FARM_WORKERS", _DEFAULT_WORKERS))

    def run():
        c0 = _programs.snapshot()["compiles"]
        ran = 0
        for fp, doc in todo:
            ran += max(0, _run_entry(fp, doc, catalog, config, "live",
                                     cfg=cfgs.get(fp)))
        delta = _programs.snapshot()["compiles"] - c0
        if delta > 0 and charge_fn is not None:
            try:
                charge_fn(delta)
            except Exception:
                pass
        try:
            _obs_events.EVENTS.emit(
                "precompile_speculative", query_id=query_id, group=group,
                plans=len(todo), warmed=ran, compiles=max(0, delta))
        except Exception:
            pass

    return _submit(run, workers)


# -- status / introspection ---------------------------------------------------


def status_fp(fp: Optional[str]) -> str:
    """"armed" (boot pre-armed) | "live" (queue-wait speculation) |
    "miss" for one structural fingerprint."""
    if not fp:
        return "miss"
    with _lock:
        return _status.get(fp[:24], "miss")


def status_for(root) -> str:
    return status_fp(_fp24(root))


def mark_live(root) -> None:
    """Promote a root's status to "live" (its programs were warmed for a
    specific queued query, not just at boot)."""
    fp = _fp24(root)
    if fp:
        with _lock:
            _status[fp] = "live"


def farm_compiles() -> int:
    """Compile events attributed to farm work — the query manager nets
    these out of live-query budget deltas so boot/speculative compiles
    are never double-charged to an unlucky concurrent query."""
    with _lock:
        return _counters["farm_compiles"]


def armed() -> bool:
    """Any farm activity this process (metric families render only once
    armed, keeping default scrapes bit-for-bit)."""
    with _lock:
        return bool(_status) or any(_counters.values())


def snapshot() -> Dict[str, Any]:
    with _lock:
        return {**_counters, "boot_wall_s": round(_boot_wall_s[0], 6),
                "statuses": len(_status),
                "corpus_path": corpus_path() or ""}


def reset() -> None:
    """Test/CI hook: drop claims, statuses, counters and the corpus
    cache (the corpus FILE is the caller's to manage)."""
    global _pool
    with _lock:
        for k in _counters:
            _counters[k] = 0
        _boot_wall_s[0] = 0.0
        _status.clear()
        _claims.clear()
        _recorded_fps.clear()
        _recorded_sqls.clear()
        _corpus_cache[0] = _corpus_cache[1] = None
        _futures.clear()
        pool, _pool = _pool, None
    if pool is not None:
        pool.shutdown(wait=False)


def metric_rows(labels: Optional[Dict[str, str]] = None) -> List[Tuple]:
    """Counter rows for both metric planes — rendered only once the farm
    has done anything, so an unarmed scrape stays bit-for-bit."""
    if not armed():
        return []
    snap = snapshot()
    return [
        ("presto_tpu_farm_corpus_recorded_total",
         "plan-corpus entries appended by this process",
         snap["recorded"], labels, "counter"),
        ("presto_tpu_farm_boot_armed_total",
         "corpus plans pre-armed at farm boot",
         snap["boot_armed"], labels, "counter"),
        ("presto_tpu_farm_skipped_total",
         "corpus lines skipped (corrupt, tombstoned, undecodable)",
         snap["skipped"], labels, "counter"),
        ("presto_tpu_farm_speculations_total",
         "queue-wait speculative precompile launches",
         snap["speculations"], labels, "counter"),
        ("presto_tpu_farm_speculations_budget_denied_total",
         "speculations skipped because the group compile budget was dry",
         snap["speculations_budget_denied"], labels, "counter"),
        ("presto_tpu_farm_claims_contended_total",
         "warm tasks that lost an inflight compile claim and waited",
         snap["claims_contended"], labels, "counter"),
        ("presto_tpu_farm_compiles_total",
         "XLA compile events attributed to farm work (boot + speculation)",
         snap["farm_compiles"], labels, "counter"),
        ("presto_tpu_farm_boot_wall_seconds",
         "cumulative wall spent arming the program cache at boot",
         snap["boot_wall_s"], labels, "gauge"),
    ]
