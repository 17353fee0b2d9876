"""Query-lifecycle span tracing.

Analog of airlift's trace-token propagation + the reference's per-operator
OperatorStats tree: the coordinator mints one trace per query, every
coordinator↔worker HTTP call carries the token in the `X-Presto-Tpu-Trace`
header, and each worker records its task's spans locally. After the result
stream completes the coordinator pulls every task's span dump and stitches
one query → stage → task → operator tree, served at
`/v1/query/{id}/trace`.

Span kinds:
  query            the coordinator-side root (covers plan + execute + merge)
  stage            synthesized per fragment (envelope of its task spans)
  task             one worker task execution
  operator         one plan node's aggregate batch-production wall
  compile          one XLA compile event inside a jitted program
  phase            one engine phase of one thread role, aggregated over
                   the trace: attrs carry role, n, busy_s, self_s, max_s
                   (and items, wait) — see "Engine phases" below
  exchange_wait    time a consumer spent blocked on a pull exchange; on
                   the mesh path, one per fused-collective exchange site
                   with lane occupancy attrs (fid/bytes/lanes_used/util)
  lane_pack        zero-width marker describing a mesh exchange's packed
                   lane layout (dtype buckets, collectives, payload bytes)
  mesh_program     wall time of one fused mesh device program dispatch
                   (covers every exchange + breaker inside the shard_map)
  breaker_engine   zero-width marker: the CBO's hash-vs-sort verdict for
                   one breaker (attrs carry engine + why, incl. HBO
                   provenance)
  overflow_replay  zero-width marker: one capacity-regrow / fanout-widen
                   replay wave a breaker executed (the runtime cost of
                   estimate error; obs/runstats drives it to zero)
  memory_revoke    one memory-pressure event: a pool reserve() crossed
                   the revoke threshold and drove revokers toward the
                   target (attrs: reserved before/after, request, limit)
  memory_kill      zero-width marker on the victim query's trace: the
                   cluster low-memory killer failed it with
                   CLUSTER_OUT_OF_MEMORY (attrs point at the forensics
                   snapshot dumped by server/cluster_memory.py)
  hbm_sample       zero-width device memory watermark sample at a span
                   boundary (obs/devprof.py; attrs carry bytes_in_use /
                   peak or an honest available=false reason on CPU)

Engine phases. What a thread does per batch or per window (read a split,
stack a window, call a compiled program, read a scalar back from the
device, wait on a queue) is bracketed with `tracer.phase(name)`. A phase
makes no `Span` per occurrence: it adds to an aggregate keyed by (thread
role, name) — n, busy_s, self_s (busy less the phases opened inside it on
the same thread), max_s, first start, last end — and, unless `wait=True`,
enters `jax.profiler.TraceAnnotation("engine:" + name)`, so that under an
open profiler session the phase is an event on that thread's line of the
xplane, on the profiler's clock (with no session open the annotation is a
no-op inside jax). Wait phases stay out of the xplane: a thread blocked on
a queue is not what the host was doing. The thread role is the thread's
name with the task id cut off (`task`, `scan-prefetch`,
`fragment-window-producer`); the thread that opens a trace's `query` span
is `coordinator`, and a caller may name the role itself (`http`: a
worker's request threads, whose names say nothing). A dump carries each
aggregate as one span of kind `phase`; when the `query` span closes, the
phases (the absorbed tasks' too), the task and query walls and the span
count are folded into one small summary per statement (13 KB in memory for
a three-table join's 31 phases over four roles, so about 50 MB when all
are kept), kept for the last 4,096 statements of the process and read with
`summaries()` — after the cluster is gone too.

Everything is allocation-light: tracing disabled means every call site
talks to the module NOOP singleton (`enabled=False` short-circuits before
any work), so `ExecConfig.tracing=False` costs one attribute check.

Correlation with the serving-plane telemetry (obs/lifecycle.py): the
trace id IS the serving query id, so every record on the cluster event
stream (`/v1/events`) carries it as `traceToken` — a lifecycle
transition, admission rejection, SLO violation, or latency-regression
flag joins back to this span tree by token equality. obs/lifecycle's
`complete()` also walks the finished tree's span kinds
(overflow_replay / memory_revoke / memory_kill) to republish those
incidents on the event stream with the same token.
"""

from __future__ import annotations

import contextlib
import itertools
import os
import threading
import time
from collections import OrderedDict, deque
from typing import Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation as _Annotation

# header carried on every coordinator↔worker HTTP call; value is
# "{trace_id}:{parent_span_id}" (parent = the coordinator's root span)
TRACE_HEADER = "X-Presto-Tpu-Trace"

_span_seq = itertools.count(1)
_trace_seq = itertools.count(1)
_PID = f"{os.getpid() & 0xFFFF:04x}"


def _new_span_id() -> str:
    return f"{_PID}-{next(_span_seq):x}"


def new_trace_id() -> str:
    return f"trace_{_PID}_{next(_trace_seq)}"


def format_token(trace_id: str, parent_span_id: Optional[str]) -> str:
    return f"{trace_id}:{parent_span_id or ''}"


def parse_token(token: str) -> Tuple[str, Optional[str]]:
    trace_id, _, parent = token.partition(":")
    return trace_id, (parent or None)


class Span:
    """One timed event. `end is None` means still open (never serialized
    that way by Tracer — spans are appended on close)."""

    __slots__ = ("span_id", "parent_id", "name", "kind", "start", "end",
                 "attrs")

    def __init__(self, span_id: str, parent_id: Optional[str], name: str,
                 kind: str, start: float, end: Optional[float] = None,
                 attrs: Optional[dict] = None):
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.kind = kind
        self.start = start
        self.end = end
        self.attrs = attrs

    @property
    def duration_s(self) -> float:
        return max(0.0, (self.end if self.end is not None else self.start)
                   - self.start)

    def to_dict(self) -> dict:
        d = {"spanId": self.span_id, "parentId": self.parent_id,
             "name": self.name, "kind": self.kind,
             "start": self.start, "end": self.end,
             "durationS": round(self.duration_s, 6)}
        if self.attrs:
            d["attrs"] = self.attrs
        return d


class _NoopSpan:
    span_id = None
    parent_id = None
    name = kind = ""
    start = end = 0.0
    duration_s = 0.0
    attrs = None


_NOOP_SPAN = _NoopSpan()


def _thread_role(name: str) -> str:
    """`task-q7.0.0` -> `task`, `scan-prefetch` -> `scan-prefetch`: the
    leading all-letter parts of a thread's name, so the ids go."""
    keep = []
    for part in name.split("-"):
        if not part.isalpha():
            break
        keep.append(part)
    return "-".join(keep) or name


class _ThreadPhases:
    """One thread's phase aggregates within one tracer. Written by that
    thread alone, so the hot path takes no lock."""

    __slots__ = ("role", "aggs", "child_s")

    def __init__(self, role: str):
        self.role = role
        # name -> [n, busy_s, self_s, max_s, first_start, last_end, items,
        # wait]
        self.aggs: Dict[str, list] = {}
        self.child_s = 0.0  # busy_s of the phases closed inside the open one


class _Phase:
    """Context for one occurrence of a phase; see Tracer.phase(). A
    generator leaves it before each `yield` and enters it again after
    (`with ph:` more than once): the time adds up and the occurrence counts
    once. `items` may be set or added to while it is open, where the count
    is known only at the end; it is added at each exit and starts again at
    0."""

    __slots__ = ("_st", "_name", "_annotate", "_ann", "items", "_t0",
                 "_outer_child_s", "_counted")

    def __init__(self, st: _ThreadPhases, name: str, annotate: bool,
                 items: int):
        self._st = st
        self._name = name
        self._annotate = annotate
        self.items = items
        self._counted = False

    def __enter__(self):
        st = self._st
        self._outer_child_s = st.child_s
        st.child_s = 0.0
        if self._annotate:  # it starts when it is made: one to each entry
            self._ann = _Annotation("engine:" + self._name)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._annotate:
            self._ann.__exit__(None, None, None)
        st = self._st
        dt = t1 - self._t0
        a = st.aggs.get(self._name)
        if a is None:
            a = st.aggs[self._name] = [0, 0.0, 0.0, 0.0, self._t0, t1, 0,
                                       not self._annotate]
        if not self._counted:
            a[0] += 1
            self._counted = True
        a[1] += dt
        a[2] += dt - st.child_s
        if dt > a[3]:
            a[3] = dt
        a[5] = t1
        a[6] += self.items
        self.items = 0
        st.child_s = self._outer_child_s + dt
        return False


class _NoopPhase:
    __slots__ = ()
    items = property(lambda self: 0, lambda self, n: None)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP_PHASE = _NoopPhase()


class Tracer:
    """Thread-safe span sink for one trace. A per-thread span stack gives
    `span()` contexts their default parent; threads that never opened a
    span (prefetch producers, exchange pullers) parent to the trace root."""

    enabled = True

    def __init__(self, trace_id: Optional[str] = None, max_spans: int = 8192):
        self.trace_id = trace_id or new_trace_id()
        self.max_spans = max_spans
        self.root_id: Optional[str] = None
        self.dropped = 0
        self._spans: List[Span] = []
        self._lock = threading.Lock()
        self._tls = threading.local()
        # every thread's phase aggregates, registered once per thread
        self._phase_threads: List[_ThreadPhases] = []
        self._phase_ids: Dict[Tuple[str, str], str] = {}

    def _phases_here(self, role: Optional[str] = None) -> _ThreadPhases:
        st = getattr(self._tls, "phases", None)
        if st is None:
            st = self._tls.phases = _ThreadPhases(
                role or _thread_role(threading.current_thread().name))
            with self._lock:
                self._phase_threads.append(st)
        return st

    def phase(self, name: str, wait: bool = False, items: int = 0,
              role: Optional[str] = None) -> _Phase:
        """`with tracer.phase(name):` round one occurrence of an engine
        phase on this thread (module docstring). `wait=True` marks time
        spent blocked: aggregated, never annotated. `items` counts what
        the occurrence handled (batches stacked). `role` names the
        thread's role where its name does not, on its first phase."""
        return _Phase(self._phases_here(role), name, not wait, items)

    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def current_parent(self) -> Optional[str]:
        st = self._stack()
        return st[-1] if st else self.root_id

    @contextlib.contextmanager
    def span(self, name: str, kind: str, parent_id: Optional[str] = None,
             **attrs):
        sid = _new_span_id()
        st = self._stack()
        pid = parent_id if parent_id is not None else (
            st[-1] if st else self.root_id)
        root = self.root_id is None
        if root:
            self.root_id = sid
            if kind == "query":
                self._phases_here().role = "coordinator"
        sp = Span(sid, pid, name, kind, time.time(), None, attrs or None)
        st.append(sid)
        try:
            yield sp
        finally:
            st.pop()
            sp.end = time.time()
            self._add(sp)
            if root and kind == "query":
                _keep_summary(self.summary())

    def record(self, name: str, kind: str, start: float, end: float,
               parent_id: Optional[str] = None, **attrs) -> Span:
        """Append an already-completed span (no stack interaction beyond
        default parenting)."""
        pid = parent_id if parent_id is not None else self.current_parent()
        sp = Span(_new_span_id(), pid, name, kind, start, end, attrs or None)
        self._add(sp)
        return sp

    def _add(self, sp: Span):
        with self._lock:
            if len(self._spans) >= self.max_spans:
                self.dropped += 1
                return
            self._spans.append(sp)

    def absorb(self, span_dicts: List[dict],
               parent_map: Optional[Dict[str, str]] = None):
        """Adopt spans serialized by another tracer (a worker task's dump).
        `parent_map` re-parents specific spans by their own span id —
        the coordinator uses it to hang task roots under synthesized
        stage spans."""
        for d in span_dicts or []:
            pid = d.get("parentId")
            sid = d.get("spanId") or _new_span_id()
            if parent_map and sid in parent_map:
                pid = parent_map[sid]
            self._add(Span(sid, pid, d.get("name") or "?",
                           d.get("kind") or "?",
                           float(d.get("start") or 0.0), d.get("end"),
                           d.get("attrs")))

    def spans(self) -> List[Span]:
        """Recorded and absorbed spans, then this tracer's own phase
        aggregates as one `phase` span each (same ids on every call)."""
        with self._lock:
            out = list(self._spans)
            threads = list(self._phase_threads)
        merged: Dict[Tuple[str, str], list] = {}
        for st in threads:
            for name, a in list(st.aggs.items()):
                m = merged.get((st.role, name))
                if m is None:
                    merged[(st.role, name)] = list(a)
                else:
                    m[0] += a[0]
                    m[1] += a[1]
                    m[2] += a[2]
                    m[3] = max(m[3], a[3])
                    m[4] = min(m[4], a[4])
                    m[5] = max(m[5], a[5])
                    m[6] += a[6]
        with self._lock:
            for key in merged:
                if key not in self._phase_ids:
                    self._phase_ids[key] = _new_span_id()
        # spans are on time.time(); phases take perf_counter() and are moved
        # onto the spans' clock as they leave
        shift = time.time() - time.perf_counter()
        for key in sorted(merged):
            n, busy, self_s, max_s, first, last, items, wait = merged[key]
            attrs = {"role": key[0], "n": n, "busy_s": round(busy, 6),
                     "self_s": round(self_s, 6), "max_s": round(max_s, 6)}
            if items:
                attrs["items"] = items
            if wait:
                attrs["wait"] = True
            out.append(Span(self._phase_ids[key], self.root_id, key[1], "phase",
                            first + shift, last + shift, attrs))
        return out

    def summary(self) -> dict:
        """What one statement's trace comes to: its phases by thread role
        (the absorbed tasks' included), the query and task walls, and what
        the tracer itself made. Small enough to keep after the trace is
        gone."""
        spans = self.spans()
        root = next((s for s in spans if s.span_id == self.root_id), None)
        tasks = [s.duration_s for s in spans if s.kind == "task"]
        return {
            "queryId": self.trace_id,
            "wall_s": round(root.duration_s, 6) if root is not None else None,
            "tasks": len(tasks),
            "task_wall_s": round(sum(tasks), 6),
            "spans": len(spans),
            "dropped": self.dropped,
            "phases": phases_by_role(spans),
        }

    def token(self, parent_id: Optional[str] = None) -> str:
        return format_token(self.trace_id,
                            parent_id if parent_id is not None
                            else self.current_parent())

    def to_json(self) -> dict:
        spans = self.spans()
        return {
            "traceId": self.trace_id,
            "rootSpanId": self.root_id,
            "dropped": self.dropped,
            "spans": [s.to_dict() for s in spans],
            "tree": build_tree(spans),
            "phases": phases_by_role(spans),
        }


class NoopTracer:
    """`enabled=False` lets hot paths skip instrumentation entirely; the
    methods still exist so cold call sites need no branches."""

    enabled = False
    trace_id = ""
    root_id = None
    dropped = 0

    @contextlib.contextmanager
    def span(self, name, kind, parent_id=None, **attrs):
        yield _NOOP_SPAN

    def record(self, name, kind, start, end, parent_id=None, **attrs):
        return _NOOP_SPAN

    def phase(self, name, wait=False, items=0, role=None):
        return _NOOP_PHASE

    def absorb(self, span_dicts, parent_map=None):
        pass

    def current_parent(self):
        return None

    def spans(self):
        return []

    def token(self, parent_id=None):
        return ""

    def to_json(self):
        return {"traceId": "", "rootSpanId": None, "dropped": 0,
                "spans": [], "tree": [], "phases": {}}


NOOP = NoopTracer()

# thread-local "current tracer" — lets deeply-buried code (jit compile
# detection, the selective-scan cascade) record spans without threading a
# tracer through every signature
_current = threading.local()


def current():
    return getattr(_current, "tracer", None) or NOOP


def set_current(tracer) -> None:
    _current.tracer = tracer


@contextlib.contextmanager
def use(tracer):
    prev = getattr(_current, "tracer", None)
    _current.tracer = tracer
    try:
        yield tracer
    finally:
        _current.tracer = prev


def phases_by_role(spans: List[Span]) -> Dict[str, Dict[str, dict]]:
    """{role: {phase: {n, busy_s, self_s, max_s[, items][, wait]}}} over the
    `phase` spans of a trace; the same phase from several tasks adds up."""
    out: Dict[str, Dict[str, dict]] = {}
    for s in spans:
        if s.kind != "phase" or not s.attrs:
            continue
        a = s.attrs
        d = out.setdefault(a.get("role") or "?", {}).setdefault(
            s.name, {"n": 0, "busy_s": 0.0, "self_s": 0.0, "max_s": 0.0})
        d["n"] += int(a.get("n") or 0)
        d["busy_s"] = round(d["busy_s"] + float(a.get("busy_s") or 0.0), 6)
        d["self_s"] = round(d["self_s"] + float(a.get("self_s") or 0.0), 6)
        d["max_s"] = max(d["max_s"], float(a.get("max_s") or 0.0))
        if a.get("items"):
            d["items"] = d.get("items", 0) + int(a["items"])
        if a.get("wait"):
            d["wait"] = True
    return out


# per-statement summaries of the last statements traced in this process:
# what the benchmark reads after the cluster is closed (the counterpart of
# exec/programs.snapshot())
_summaries: "deque[dict]" = deque(maxlen=4096)  # shared: guarded-by(_summaries_lock)
_summaries_lock = threading.Lock()


def _keep_summary(doc: dict) -> None:
    with _summaries_lock:
        _summaries.append(doc)


def summaries() -> List[dict]:
    """Tracer.summary() of the last 4,096 statements whose `query` span
    closed in this process, oldest first."""
    with _summaries_lock:
        return list(_summaries)


def build_tree(spans: List[Span]) -> List[dict]:
    """Nest spans by parent id; spans whose parent is unknown (foreign
    coordinator ids inside a worker dump, or None) become roots. Children
    sort by start time."""
    dicts = [s.to_dict() for s in spans]
    by_id = {d["spanId"]: d for d in dicts}
    roots: List[dict] = []
    for d in dicts:
        d.setdefault("children", [])
    for d in dicts:
        parent = by_id.get(d.get("parentId"))
        if parent is not None and parent is not d:
            parent["children"].append(d)
        else:
            roots.append(d)
    for d in dicts:
        d["children"].sort(key=lambda c: c["start"])
    roots.sort(key=lambda c: c["start"])
    return roots


class TraceRegistry:
    """Bounded query-id → Tracer map on the coordinator. Aliases let the
    session-level query id (what /v1/query serves) and the scheduler's
    internal per-attempt id (what task ids embed) resolve to one trace."""

    def __init__(self, max_traces: int = 200):
        self.max_traces = max_traces
        self._by_id: "OrderedDict[str, Tracer]" = OrderedDict()
        self._alias: Dict[str, str] = {}
        self._lock = threading.Lock()

    def register(self, tracer: Tracer, *aliases: str) -> None:
        with self._lock:
            self._by_id[tracer.trace_id] = tracer
            for a in aliases:
                self._alias[a] = tracer.trace_id
            while len(self._by_id) > self.max_traces:
                old, _ = self._by_id.popitem(last=False)
                self._alias = {a: t for a, t in self._alias.items()
                               if t != old}

    def alias(self, alias_id: str, trace_id: str) -> None:
        with self._lock:
            if trace_id in self._by_id:
                self._alias[alias_id] = trace_id

    def get(self, query_id: str) -> Optional[Tracer]:
        with self._lock:
            t = self._by_id.get(query_id)
            if t is not None:
                return t
            target = self._alias.get(query_id)
            return self._by_id.get(target) if target else None

    def latest(self) -> Optional[Tracer]:
        with self._lock:
            return next(reversed(self._by_id.values()), None) \
                if self._by_id else None
