"""Device cost & HBM accounting plane (devprof).

Everything the engine knew about memory was self-reported (memory.py
pool reservations) and everything it knew about hardware efficiency was
hand-derived offline. This module is the
device-side truth plane:

  * **per-program cost/memory analysis** — every program the structural
    cache (exec/programs.py) compiles is lowered once more and asked for
    its XLA ``cost_analysis()`` (FLOPs, bytes accessed) and
    ``memory_analysis()`` (argument / output / temp / generated-code
    bytes), recorded here keyed on the PR 5 structural fingerprint. Span
    wall times from the tracer turn those into achieved-FLOP/s,
    achieved-bytes/s and arithmetic intensity (roofline) per operator
    and per query;
  * **HBM watermark sampling** — ``device.memory_stats()`` at span
    boundaries plus a background cadence, with honest ``unavailable``
    labeling when the backend has no device memory introspection (the
    CPU backend of the tests);
  * **ledger-vs-device reconciliation** — the sampled device watermark
    against the MemoryPool ledger's own peak, exported as the
    ``presto_tpu_memory_ledger_drift_ratio`` histogram: it catches
    accounting bugs the way the stats-drift histogram catches
    cardinality bugs;
  * **on-demand ``jax.profiler`` captures** — a per-query registry of
    profile dumps (the ``profile`` session property), surfaced as
    ``profileUri`` next to ``traceUri`` on ``/v1/statement``.

Process-global like the compile plane it mirrors, and strictly opt-in:
until :func:`activate` runs (the ``devprof`` ExecConfig field /
session property is ``"on"``), every hook is a single boolean check and
the engine behaves bit-for-bit as if this module did not exist. The
latch is sticky for the process once requested — same lifecycle as the
program cache — and :func:`deactivate` is the test hook that re-arms
the strict no-op contract. The provider behind HBM sampling is
pluggable (:func:`set_provider`) so reconciliation is unit-testable
off-device.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from presto_tpu.obs import metrics as _obs_metrics
from presto_tpu.obs import trace as _obs_trace

_LOCK = threading.Lock()
_ACTIVE = False

# structural fingerprint -> one program's device profile:
# {"flops", "bytes_accessed", "argument_bytes", "output_bytes",
#  "temp_bytes", "generated_code_bytes", "footprint_bytes", "calls",
#  "kind", "key"}  (numeric fields max-merge across recompiles — the
#  worst compiled shape is the capacity-relevant one)
_programs: Dict[str, Dict[str, Any]] = {}

_counters: Dict[str, int] = {
    # programs whose lowering yielded at least a cost or memory analysis
    "programs_analyzed": 0,
    # lowering/analysis attempts the backend could not answer
    "analysis_unavailable": 0,
    # HBM watermark samples taken (background cadence + span boundaries)
    "hbm_samples": 0,
    # samples answered with "no device memory introspection here"
    "hbm_unavailable": 0,
    # ledger-vs-device reconciliations performed
    "reconciliations": 0,
    # fused-window stagings accounted through note_staging()
    "staging_windows": 0,
}

# device watermark state (high-water across samples since activate/reset)
_hbm: Dict[str, Any] = {
    "available": None,          # None = never sampled, False = no device
    "reason": None,             # why unavailable, honest label
    "platform": None,
    "bytes_in_use": 0,
    "peak_bytes_in_use": 0,
    "bytes_limit": 0,
}

# fused-window device staging (fragment_jit) high-water accounting
_staging: Dict[str, float] = {"bytes_total": 0.0, "peak_window_bytes": 0.0}

# fingerprints whose lazy analysis came back empty — never retried (a
# backend that can't answer once won't answer on the next dispatch either,
# and the lowering attempt is not free)
_analysis_failed: set = set()

# fingerprints whose lazy analysis is running RIGHT NOW on some thread:
# on_call claims the fingerprint under _LOCK before lowering, so N
# concurrent dispatches of a never-seen program lower it exactly once
# instead of N times (lowering is the expensive step)
_analysis_inflight: set = set()

# query_id -> jax.profiler dump directory (profile session property)
_query_profiles: Dict[str, str] = {}

# pluggable memory_stats source: () -> Optional[dict]; None = default
_provider: Optional[Callable[[], Optional[dict]]] = None

_sampler_thread: Optional[threading.Thread] = None
_sampler_stop = threading.Event()
_SAMPLE_PERIOD_S = float(os.environ.get("PRESTO_TPU_DEVPROF_SAMPLE_S",
                                        "0.5"))


def active() -> bool:
    """The one check every hot-path hook performs. False = strict no-op."""
    return _ACTIVE


def activate() -> None:
    """Arm the plane (devprof=on saw a plan install). Sticky for the
    process, like the program cache; starts the background HBM sampler."""
    global _ACTIVE
    with _LOCK:
        if _ACTIVE:
            return
        _ACTIVE = True
    _start_sampler()


def deactivate() -> None:
    """Test hook: disarm and stop the sampler so a later devprof=off run
    can assert the strict no-op contract."""
    global _ACTIVE
    with _LOCK:
        _ACTIVE = False
    _stop_sampler()


def reset() -> None:
    """Test hook: deactivate and clear all recorded state."""
    deactivate()
    with _LOCK:
        _programs.clear()
        _analysis_failed.clear()
        _analysis_inflight.clear()
        _query_profiles.clear()
        for k in _counters:
            _counters[k] = 0
        _hbm.update(available=None, reason=None, platform=None,
                    bytes_in_use=0, peak_bytes_in_use=0, bytes_limit=0)
        _staging.update(bytes_total=0.0, peak_window_bytes=0.0)


# -- HBM watermark sampling ---------------------------------------------------


def set_provider(fn: Optional[Callable[[], Optional[dict]]]) -> None:
    """Override the device memory_stats source (tests: a fake provider
    makes reconciliation deterministic off-device). None restores the
    real ``jax.local_devices()[0].memory_stats()``."""
    global _provider
    with _LOCK:
        _provider = fn
        # a new source invalidates the old watermark + availability label
        _hbm.update(available=None, reason=None,
                    bytes_in_use=0, peak_bytes_in_use=0, bytes_limit=0)


def _default_provider() -> Optional[dict]:
    import jax

    dev = jax.local_devices()[0]
    platform = getattr(dev, "platform", None)
    # runs outside sample_hbm's critical section (providers are called
    # unlocked so a slow backend can't stall readers), so the label
    # write takes the lock itself
    with _LOCK:
        _hbm["platform"] = platform
    return dev.memory_stats()


def sample_hbm(tag: Optional[str] = None) -> Dict[str, Any]:
    """Take one device memory sample, fold it into the watermark, and —
    when a tracer is live and a tag names the boundary — record an
    ``hbm_sample`` span so the sample lands in the query timeline.
    Honest on CPU: a backend without memory_stats() yields an
    ``available: false`` doc with the reason, never fabricated zeros."""
    now = time.time()
    prov = _provider or _default_provider
    try:
        stats = prov()
        err = None
    except Exception as e:  # no devices / backend without introspection
        stats, err = None, f"{type(e).__name__}: {e}"
    with _LOCK:
        _counters["hbm_samples"] += 1
        if not stats:
            _counters["hbm_unavailable"] += 1
            if _hbm["available"] is None:
                _hbm["available"] = False
                _hbm["reason"] = (err or "backend reports no memory_stats "
                                  "(CPU fallback)")
        else:
            in_use = int(stats.get("bytes_in_use", 0) or 0)
            peak = int(stats.get("peak_bytes_in_use", in_use) or in_use)
            _hbm["available"] = True
            _hbm["reason"] = None
            _hbm["bytes_in_use"] = in_use
            _hbm["peak_bytes_in_use"] = max(
                int(_hbm["peak_bytes_in_use"]), peak, in_use)
            _hbm["bytes_limit"] = int(stats.get(
                "bytes_limit", _hbm["bytes_limit"]) or _hbm["bytes_limit"])
        doc = _hbm_doc_locked()
    if tag is not None:
        tr = _obs_trace.current()
        if tr.enabled:
            tr.record("hbm_sample", "hbm_sample", now, now, tag=tag, **{
                k: v for k, v in doc.items() if v is not None})
    return doc


def _hbm_doc_locked() -> Dict[str, Any]:
    if _hbm["available"]:
        return {"available": True, "platform": _hbm["platform"],
                "bytesInUse": _hbm["bytes_in_use"],
                "peakBytesInUse": _hbm["peak_bytes_in_use"],
                "bytesLimit": _hbm["bytes_limit"] or None}
    return {"available": False, "platform": _hbm["platform"],
            "reason": _hbm["reason"] or "never sampled"}


def device_memory_doc() -> Dict[str, Any]:
    """The current device memory document for status/heartbeat payloads
    (worker /v1/status → cluster heartbeat → /v1/memory rollup)."""
    with _LOCK:
        return _hbm_doc_locked()


def _start_sampler() -> None:
    global _sampler_thread
    if _SAMPLE_PERIOD_S <= 0:
        return
    _sampler_stop.clear()

    def loop():
        while not _sampler_stop.wait(_SAMPLE_PERIOD_S):
            if not _ACTIVE:
                break
            doc = sample_hbm()
            if not doc.get("available"):
                # no introspection on this backend: one honest sample is
                # the whole story, polling it again is pure overhead
                break

    t = threading.Thread(target=loop, daemon=True, name="devprof-hbm")
    with _LOCK:
        if _sampler_thread is not None and _sampler_thread.is_alive():
            return
        _sampler_thread = t
    t.start()


def _stop_sampler() -> None:
    global _sampler_thread
    _sampler_stop.set()
    with _LOCK:
        _sampler_thread = None


# -- per-program XLA cost / memory analysis ----------------------------------


def analyze_lowered(lowered) -> Dict[str, Any]:
    """Cost + memory analysis of one jax Lowered. The cost side is free;
    the memory side pays one ``.compile()`` (served by the persistent
    XLA cache on a TPU backend) — acceptable because the
    whole plane is opt-in. Missing pieces are recorded as absent, never
    guessed."""
    rec: Dict[str, Any] = {}
    try:
        ca = lowered.cost_analysis()
        if ca:
            if ca.get("flops") is not None:
                rec["flops"] = float(ca["flops"])
            if ca.get("bytes accessed") is not None:
                rec["bytes_accessed"] = float(ca["bytes accessed"])
    except Exception:
        pass
    try:
        ma = lowered.compile().memory_analysis()
        if ma is not None:
            arg = float(getattr(ma, "argument_size_in_bytes", 0) or 0)
            out = float(getattr(ma, "output_size_in_bytes", 0) or 0)
            tmp = float(getattr(ma, "temp_size_in_bytes", 0) or 0)
            rec["argument_bytes"] = arg
            rec["output_bytes"] = out
            rec["temp_bytes"] = tmp
            rec["generated_code_bytes"] = float(
                getattr(ma, "generated_code_size_in_bytes", 0) or 0)
            # the program's device-resident footprint while it runs
            rec["footprint_bytes"] = arg + out + tmp
    except Exception:
        pass
    return rec


def record_program(fp: str, rec: Dict[str, Any], kind: str = "",
                   key: str = "") -> Optional[Dict[str, Any]]:
    """Merge one program's analysis into the store (numerics max-merge:
    across recompiles the worst shape is the one capacity planning must
    survive). Returns the merged record, or None for an empty analysis."""
    if not rec:
        with _LOCK:
            _counters["analysis_unavailable"] += 1
        return None
    with _LOCK:
        ent = _programs.get(fp)
        if ent is None:
            ent = _programs[fp] = {"kind": kind, "key": key, "calls": 0}
            _counters["programs_analyzed"] += 1
        for k, v in rec.items():
            if isinstance(v, (int, float)):
                ent[k] = max(float(ent.get(k) or 0.0), float(v))
            else:
                ent[k] = v
        return dict(ent)


def on_compile(entry, node_kind: str, key: str, args, kw,
               node_stats: Optional[Dict[str, float]] = None) -> None:
    """Compile-plane hook (exec/programs.wrap, delta>0 branch): the
    program just compiled for these concrete args — lower it once more
    and record its XLA cost/memory analysis. Also stamps the calling
    node's ``_jit_stats`` view so EXPLAIN ANALYZE and the worker stats
    rows can attribute device numbers per operator."""
    if not _ACTIVE:
        return
    fp = getattr(entry, "fp", None) or f"private|{node_kind}|{key}"
    try:
        rec = analyze_lowered(entry.jfn.lower(*args, **kw))
    except Exception:
        rec = {}
    merged = record_program(fp, rec, kind=node_kind, key=key)
    if merged and node_stats is not None:
        for k in ("flops", "bytes_accessed", "footprint_bytes"):
            if merged.get(k) is not None:
                node_stats[k] = max(float(node_stats.get(k) or 0.0),
                                    float(merged[k]))


def on_call(entry, node_kind: str = "", key: str = "", args=(), kw=None,
            node_stats: Optional[Dict[str, float]] = None) -> None:
    """Per-call hook (every wrapped dispatch while active): count calls
    per program so roofline totals weight each program by how often it
    actually ran. A fingerprint never seen before is analyzed lazily —
    the program may have compiled before the plane activated (the cache
    deliberately does not fork on the devprof knob), and its analysis
    must not be lost to activation order."""
    if not _ACTIVE:
        return
    fp = getattr(entry, "fp", None) or (f"private|{node_kind}|{key}"
                                        if node_kind else None)
    if fp is None:
        return
    with _LOCK:
        ent = _programs.get(fp)
        if ent is not None:
            ent["calls"] = int(ent.get("calls") or 0) + 1
            merged = dict(ent)
        elif fp in _analysis_failed or fp in _analysis_inflight:
            # failed: never retried. inflight: another dispatch claimed
            # the lowering in this same critical section — its record
            # (or failure mark) will land; duplicating the work here is
            # exactly the check-then-act race this claim closes
            return
        else:
            _analysis_inflight.add(fp)
            merged = None
    if merged is None:
        try:
            try:
                rec = analyze_lowered(entry.jfn.lower(*args, **(kw or {})))
            except Exception:
                rec = {}
            merged = record_program(fp, rec, kind=node_kind, key=key)
        finally:
            with _LOCK:
                # only the thread that claimed fp in the first critical
                # section reaches this discard — the claim protocol, not
                # the lock scope, closes the window
                _analysis_inflight.discard(fp)  # lint: allow(check-then-act)
        if merged is None:
            with _LOCK:
                # safe outside the claiming section: only the thread
                # holding the in-flight claim for fp can reach this add
                _analysis_failed.add(fp)  # lint: allow(check-then-act)
            return
        with _LOCK:
            ent = _programs.get(fp)
            if ent is not None:
                ent["calls"] = int(ent.get("calls") or 0) + 1
    if node_stats is not None:
        # stamp the calling node's stats view every dispatch, not only on
        # first analysis — EXPLAIN ANALYZE task nodes are fresh instances
        # per run while the program record is process-wide
        for k in ("flops", "bytes_accessed", "footprint_bytes"):
            if merged.get(k) is not None:
                node_stats[k] = max(float(node_stats.get(k) or 0.0),
                                    float(merged[k]))


def note_staging(window_bytes: float) -> None:
    """fragment_jit hook: one fused window's stacked batches are about to
    stage onto the device — account the bytes (total shipped + worst
    single window, the fused path's device-residency high-water)."""
    if not _ACTIVE:
        return
    with _LOCK:
        _counters["staging_windows"] += 1
        _staging["bytes_total"] += float(window_bytes)
        _staging["peak_window_bytes"] = max(
            _staging["peak_window_bytes"], float(window_bytes))


# -- ledger-vs-device reconciliation -----------------------------------------


def reconcile(pool, plane: str = "worker",
              site: str = "query") -> Optional[Dict[str, Any]]:
    """Compare the device HBM watermark against the MemoryPool ledger's
    self-reported peak and feed the drift histogram. Returns the
    reconciliation doc, or None when either side has nothing to say
    (no device introspection, or a ledger that never reserved)."""
    if not _ACTIVE or pool is None:
        return None
    doc = sample_hbm()
    ledger_peak = float(getattr(pool, "peak", 0) or 0)
    if not doc.get("available") or ledger_peak <= 0:
        return None
    device_peak = float(doc.get("peakBytesInUse") or 0)
    if device_peak <= 0:
        return None
    ratio = device_peak / ledger_peak
    with _LOCK:
        _counters["reconciliations"] += 1
    _obs_metrics.LEDGER_DRIFT.observe(ratio, plane=plane, site=site)
    return {"devicePeakBytes": device_peak, "ledgerPeakBytes": ledger_peak,
            "driftRatio": ratio}


# -- per-query jax.profiler captures -----------------------------------------


def register_profile(query_id: str, path: str) -> None:
    with _LOCK:
        _query_profiles[query_id] = path
        # bounded like the trace registry — oldest captures age out
        while len(_query_profiles) > 200:
            _query_profiles.pop(next(iter(_query_profiles)))


def profile_for(query_id: str) -> Optional[str]:
    with _LOCK:
        return _query_profiles.get(query_id)


# -- exposure: summaries, metrics, rollups -----------------------------------


def programs_profile() -> Dict[str, Dict[str, Any]]:
    """Copy of the per-fingerprint program store (tests/bench)."""
    with _LOCK:
        return {fp: dict(ent) for fp, ent in _programs.items()}


def snapshot() -> Dict[str, Any]:
    with _LOCK:
        return {"active": _ACTIVE, "counters": dict(_counters),
                "hbm": _hbm_doc_locked(), "staging": dict(_staging),
                "programs": {fp: dict(e) for fp, e in _programs.items()}}


def summary(wall_s: Optional[float] = None) -> Dict[str, Any]:
    """Roofline rollup over every analyzed program, call-weighted: total
    device FLOPs and bytes actually dispatched, arithmetic intensity,
    and — given a wall time — achieved FLOP/s and bytes/s. This is what
    bench.py emits instead of hand-derived utilization numbers."""
    with _LOCK:
        n = len(_programs)
        flops = sum((e.get("flops") or 0.0) * max(int(e.get("calls") or 0), 1)
                    for e in _programs.values())
        byts = sum((e.get("bytes_accessed") or 0.0)
                   * max(int(e.get("calls") or 0), 1)
                   for e in _programs.values())
        peak_fp = max((e.get("footprint_bytes") or 0.0
                       for e in _programs.values()), default=0.0)
        calls = sum(int(e.get("calls") or 0) for e in _programs.values())
        hbm = _hbm_doc_locked()
        staging = dict(_staging)
        counters = dict(_counters)
    out: Dict[str, Any] = {
        "programs": n, "calls": calls,
        "total_flops": flops, "total_bytes_accessed": byts,
        "arithmetic_intensity": (flops / byts) if byts else None,
        "peak_program_footprint_bytes": peak_fp,
        "staging": staging, "device": hbm,
        "analysis_unavailable": counters["analysis_unavailable"],
    }
    if wall_s and wall_s > 0:
        out["achieved_flops_per_s"] = flops / wall_s
        out["achieved_bytes_per_s"] = byts / wall_s
    return out


_HELP = {
    "presto_tpu_devprof_programs_analyzed":
        "compiled programs with a recorded XLA cost/memory analysis",
    "presto_tpu_devprof_analysis_unavailable_total":
        "program analyses the backend could not answer",
    "presto_tpu_devprof_hbm_samples_total":
        "device memory_stats() watermark samples taken",
    "presto_tpu_devprof_hbm_unavailable_total":
        "samples where the backend had no device memory introspection",
    "presto_tpu_devprof_reconciliations_total":
        "ledger-vs-device peak reconciliations performed",
    "presto_tpu_devprof_total_flops":
        "call-weighted XLA-analyzed FLOPs across all recorded programs",
    "presto_tpu_devprof_total_bytes_accessed":
        "call-weighted XLA-analyzed bytes accessed across all programs",
    "presto_tpu_devprof_peak_program_footprint_bytes":
        "largest single-program device footprint (args+outputs+temps)",
    "presto_tpu_devprof_hbm_peak_bytes":
        "device-reported peak bytes in use (0 when unavailable)",
}


def metric_rows(labels: Dict[str, str]) -> List[Tuple]:
    """Rows for server.metrics.render_metrics on both /v1/metrics planes.
    Empty until the plane activates — the families appear only once
    devprof=on has run, keeping devprof=off scrapes byte-identical."""
    with _LOCK:
        if not _ACTIVE and not _counters["programs_analyzed"] \
                and not _counters["hbm_samples"]:
            return []
        c = dict(_counters)
    s = summary()
    rows: List[Tuple] = [
        ("presto_tpu_devprof_programs_analyzed",
         _HELP["presto_tpu_devprof_programs_analyzed"],
         s["programs"], dict(labels), "gauge"),
        ("presto_tpu_devprof_analysis_unavailable_total",
         _HELP["presto_tpu_devprof_analysis_unavailable_total"],
         c["analysis_unavailable"], dict(labels), "counter"),
        ("presto_tpu_devprof_hbm_samples_total",
         _HELP["presto_tpu_devprof_hbm_samples_total"],
         c["hbm_samples"], dict(labels), "counter"),
        ("presto_tpu_devprof_hbm_unavailable_total",
         _HELP["presto_tpu_devprof_hbm_unavailable_total"],
         c["hbm_unavailable"], dict(labels), "counter"),
        ("presto_tpu_devprof_reconciliations_total",
         _HELP["presto_tpu_devprof_reconciliations_total"],
         c["reconciliations"], dict(labels), "counter"),
        ("presto_tpu_devprof_total_flops",
         _HELP["presto_tpu_devprof_total_flops"],
         s["total_flops"], dict(labels), "gauge"),
        ("presto_tpu_devprof_total_bytes_accessed",
         _HELP["presto_tpu_devprof_total_bytes_accessed"],
         s["total_bytes_accessed"], dict(labels), "gauge"),
        ("presto_tpu_devprof_peak_program_footprint_bytes",
         _HELP["presto_tpu_devprof_peak_program_footprint_bytes"],
         s["peak_program_footprint_bytes"], dict(labels), "gauge"),
        ("presto_tpu_devprof_hbm_peak_bytes",
         _HELP["presto_tpu_devprof_hbm_peak_bytes"],
         (s["device"].get("peakBytesInUse") or 0)
         if s["device"].get("available") else 0,
         {**labels, "available": str(bool(
             s["device"].get("available"))).lower()}, "gauge"),
    ]
    return rows
