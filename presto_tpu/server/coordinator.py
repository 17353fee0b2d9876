"""Coordinator — control plane: discovery, node health, distributed
scheduling, result collection.

Reference surface:
- metadata/DiscoveryNodeManager.java + embedded airlift discovery: workers
  announce themselves; the coordinator tracks active nodes
- failureDetector/HeartbeatFailureDetector.java:77,225,360: periodic pings
  with a decaying failure-rate gate; failed nodes are excluded from
  scheduling
- execution/scheduler/SqlQueryScheduler.java:640,657 + SqlStageExecution +
  server/remotetask/HttpRemoteTask.java:336: stage-by-stage task creation
  over HTTP
- ClusterSizeMonitor: gate query start on minimum workers

TPU-native shape: fragments are scheduled one-task-per-worker (HASH/SOURCE)
or single-task (SINGLE); producers are created before consumers (ascending
fragment id = topological order), everything runs concurrently and streams
through the pull exchange.
"""

from __future__ import annotations

import json
import os
import re
import threading
import time
import urllib.error
import urllib.request
from typing import Dict, List, Optional

from presto_tpu.batch import Batch
from presto_tpu.connector import Catalog
from presto_tpu.exec import farm as _farm
from presto_tpu.exec.runtime import ExecConfig
from presto_tpu.obs import events as _obs_events
from presto_tpu.obs import inflight as _obs_inflight
from presto_tpu.obs import lifecycle as _obs_lifecycle
from presto_tpu.obs import trace as _obs_trace
from presto_tpu.plan.fragmenter import (
    HASH,
    OUT_BROADCAST,
    SINGLE,
    SOURCE,
    DistributedPlan,
    strip_runtime_state,
)
from presto_tpu.server.exchange import ExchangeClient, ExchangeFailure
from presto_tpu.server.worker import TaskUpdate


class NodeInfo:
    def __init__(self, node_id: str, uri: str):
        self.node_id = node_id
        self.uri = uri
        self.last_seen = time.monotonic()
        # decayed failure counter (HeartbeatFailureDetector's
        # DecayCounter(0.1) moral equivalent)
        self.failure_score = 0.0
        self.state = "active"

    def record_success(self):
        self.last_seen = time.monotonic()
        self.failure_score *= 0.5

    def record_failure(self):
        self.failure_score = self.failure_score * 0.8 + 1.0

    @property
    def failed(self) -> bool:
        return self.failure_score > 4.0


class NodeManager:
    """Registry of announced worker nodes (DiscoveryNodeManager analog)."""

    def __init__(self, expire_s: float = 30.0):
        self.nodes: Dict[str, NodeInfo] = {}
        self._lock = threading.Lock()
        self.expire_s = expire_s

    def announce(self, node_id: str, uri: str, state: str = "active"):
        with self._lock:
            n = self.nodes.get(node_id)
            if n is None or n.uri != uri:
                n = NodeInfo(node_id, uri)
                self.nodes[node_id] = n
            else:
                n.record_success()
            # the worker's own announcement is authoritative for its state —
            # a restarted worker reusing node_id/uri returns to rotation
            n.state = "active" if state == "active" else "draining"

    def active_nodes(self) -> List[NodeInfo]:
        now = time.monotonic()
        with self._lock:
            return [
                n for n in self.nodes.values()
                if not n.failed and n.state == "active"
                and now - n.last_seen < self.expire_s
            ]

    def remove(self, node_id: str):
        with self._lock:
            self.nodes.pop(node_id, None)


class HeartbeatFailureDetector:
    """Background prober: GET /v1/status on every known node; nodes whose
    decayed failure score crosses the threshold are excluded from
    scheduling (HeartbeatFailureDetector.java:360 ping loop)."""

    def __init__(self, node_manager: NodeManager, interval_s: float = 2.0,
                 cluster_memory=None, query_manager=None):
        self.node_manager = node_manager
        self.interval_s = interval_s
        self.cluster_memory = cluster_memory
        self.query_manager = query_manager
        self._stop = threading.Event()
        self.thread = threading.Thread(target=self._loop, daemon=True,
                                       name="failure-detector")

    def start(self):
        self.thread.start()

    def _probe(self, n: NodeInfo):
        try:
            with urllib.request.urlopen(f"{n.uri}/v1/status", timeout=5) as r:
                status = json.loads(r.read())
            if status.get("state") in ("shutting_down", "shut_down"):
                n.state = "draining"
            else:
                n.record_success()
            if self.cluster_memory is not None:
                self.cluster_memory.update_node(n.node_id, status)
            progress = status.get("queryProgress")
            if progress:
                # lifecycle plane: fold the worker's live per-query row
                # counts into the progress registry (attempt ids resolve
                # through the registry's alias map)
                _obs_lifecycle.merge_worker_progress(n.node_id, progress)
            inflight = status.get("queryInflight")
            if inflight:
                # inflight plane: per-task operator watermarks, merged
                # per fragment (seq-guarded — in-process clusters whose
                # publishers already live in the registry are idempotent)
                _obs_inflight.merge_worker(n.node_id, inflight)
        except Exception:
            n.record_failure()

    def _loop(self):
        while not self._stop.wait(self.interval_s):
            # concurrent probes: one hung worker must not stall detection of
            # the others (reference pings asynchronously per service)
            probes = [
                threading.Thread(target=self._probe, args=(n,), daemon=True)
                for n in list(self.node_manager.nodes.values())
            ]
            for t in probes:
                t.start()
            for t in probes:
                t.join(timeout=6)
            # cluster OOM enforcement rides the heartbeat cadence
            # (ClusterMemoryManager.process runs on its executor likewise)
            if self.cluster_memory is not None and self.query_manager is not None:
                try:
                    self.cluster_memory.enforce(self.query_manager)
                except Exception:
                    pass

    def stop(self):
        self._stop.set()


class ClusterSizeMonitor:
    def __init__(self, node_manager: NodeManager, min_workers: int = 1):
        self.node_manager = node_manager
        self.min_workers = min_workers

    def wait_for_minimum(self, timeout_s: float = 30.0):
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if len(self.node_manager.active_nodes()) >= self.min_workers:
                return
            time.sleep(0.05)
        raise RuntimeError(
            f"insufficient active workers "
            f"({len(self.node_manager.active_nodes())} < {self.min_workers})"
        )


class QueryFailed(RuntimeError):
    """`retryable=True` marks failures caused by worker/transport loss
    (worth re-running on the surviving cluster); deterministic task errors
    stay non-retryable — the reference's RetryPolicy.QUERY makes the same
    distinction."""

    def __init__(self, msg: str, retryable: bool = False):
        super().__init__(msg)
        self.retryable = retryable


def compute_phases(frags) -> Dict[int, int]:
    """PhasedExecutionSchedule analog (execution/scheduler/
    PhasedExecutionSchedule.java): fragments feeding a join BUILD side get
    an earlier phase than the probe's fragment, so probe-side scans don't
    hold memory while the build is still assembling. Streaming producers
    (exchanges that pipeline: partial→final agg, sort inputs) share their
    consumer's phase. Returns fid → 0-based phase (ascending start order)."""
    from presto_tpu.plan.nodes import (
        HashJoin,
        NestedLoopJoin,
        RemoteSource,
        SemiJoin,
    )

    build_deps: Dict[int, set] = {fid: set() for fid in frags}
    stream_deps: Dict[int, set] = {fid: set() for fid in frags}

    def walk(n, fid, in_build):
        if isinstance(n, RemoteSource):
            (build_deps if in_build else stream_deps)[fid].add(n.fragment_id)
            return
        if isinstance(n, (HashJoin, SemiJoin, NestedLoopJoin)):
            walk(n.left, fid, in_build)
            walk(n.right, fid, True)  # build side
            return
        for c in n.children():
            walk(c, fid, in_build)

    for fid, f in frags.items():
        walk(f.root, fid, False)
    # consumers first (producers have lower fids — fragmenter numbers
    # topologically), so each fragment's phase is final before its deps'
    phase: Dict[int, int] = {}
    for fid in sorted(frags, reverse=True):
        phase.setdefault(fid, 0)
        for dep in stream_deps[fid]:
            phase[dep] = min(phase.get(dep, phase[fid]), phase[fid])
        for dep in build_deps[fid]:
            phase[dep] = min(phase.get(dep, phase[fid] - 1), phase[fid] - 1)
    lo = min(phase.values())
    return {fid: p - lo for fid, p in phase.items()}


def _fragment_scans(root) -> list:
    """All TableScan nodes of a fragment (split-placement candidates)."""
    from presto_tpu.plan.nodes import TableScan

    out = []

    def walk(n):
        if isinstance(n, TableScan):
            out.append(n)
        for c in n.children():
            walk(c)

    walk(root)
    return out


def _affinity_assign(table: str, n_splits: int,
                     worker_keys: List[str]) -> List[List[int]]:
    """Rendezvous-hash split placement with a balance cap (reference:
    scheduler/NodeScheduler.java + SimpleNodeSelector and the
    SOFT_AFFINITY NodeSelectionStrategy of connector split sources).

    Each split ranks every worker by fnv64(table:ordinal:worker) and
    lands on its best-ranked worker that still has capacity
    (cap = ⌈splits/workers⌉, the maxSplitsPerNode analog). The mapping is
    deterministic across queries AND coordinator restarts, so a worker
    keeps seeing the same splits — its device split cache turns that
    stability into scan locality. When a worker joins/leaves, only the
    splits hashed to it move (rendezvous minimal-disruption property)."""
    from presto_tpu.dictionary import fnv64

    k = len(worker_keys)
    cap = -(-n_splits // k) if n_splits else 0
    counts = [0] * k
    out: List[List[int]] = [[] for _ in range(k)]
    for j in range(n_splits):
        ranked = sorted(
            range(k),
            key=lambda w: fnv64(f"{table}:{j}:{worker_keys[w]}"),
            reverse=True)
        for w in ranked:
            if counts[w] < cap:
                out[w].append(j)
                counts[w] += 1
                break
    return out


class DistributedScheduler:
    """Schedules a DistributedPlan onto workers and streams the result
    (SqlQueryScheduler.schedule:657 analog). Policies
    (SystemSessionProperties EXECUTION_POLICY): "all-at-once" starts every
    stage immediately; "phased" creates each phase's tasks only after the
    previous phase's (join-build) tasks finished — see compute_phases."""

    def __init__(self, config: Optional[ExecConfig] = None,
                 cluster_secret: Optional[str] = None,
                 on_worker_lost=None, catalog=None):
        self.config = config or ExecConfig()
        self.cluster_secret = cluster_secret
        # notified with the NodeInfo of a worker found dead during task
        # placement/phase waits (the coordinator excludes it from rotation
        # immediately, like the pre-retry reprobe does)
        self.on_worker_lost = on_worker_lost
        # catalog access enables coordinator-side split placement
        # (soft-affinity scheduling); without it tasks fall back to the
        # static task_index::n_tasks striding
        self.catalog = catalog

    def _headers(self, extra: Optional[dict] = None) -> dict:
        h = dict(extra or {})
        if self.cluster_secret is not None:
            h["X-Presto-Cluster-Secret"] = self.cluster_secret
        return h

    def _create_tasks(self, query_id: str, dplan: DistributedPlan,
                      workers: List[NodeInfo], config: ExecConfig,
                      trace_hdrs: dict, created: list) -> Dict[int, List[str]]:
        """Place every fragment's tasks and POST them, phase by phase.
        Every task created is appended to `created` as (task_id, worker),
        so that the caller can abort them whatever happens here; returns
        the task URLs by fragment."""
        if not workers:
            raise QueryFailed("no active workers")
        frags = dplan.fragments
        # task counts per fragment (FIXED_HASH → one per worker; SINGLE → 1)
        n_tasks = {
            fid: 1 if f.partitioning == SINGLE else len(workers)
            for fid, f in frags.items()
        }
        # Recoverable grouped execution (reference:
        # SystemSessionProperties.java:69 recoverable_grouped_execution +
        # StageExecutionDescriptor + FixedSourcePartitionedScheduler):
        # a grouped SOURCE fragment (colocated bucketed join) is scheduled
        # ONE TASK PER LIFESPAN (task_index=b, n_tasks=B sweeps exactly
        # bucket b) in its own phase with spooled output; a worker lost
        # mid-phase re-runs only its UNFINISHED bucket tasks on survivors —
        # finished lifespans are never redone. Consumers launch only after
        # the gate, so a dead producer has contributed nothing downstream.
        grouped: Dict[int, int] = {}
        if getattr(config, "recoverable_grouped_execution", False):
            for fid, f in frags.items():
                # only fully self-contained fragments qualify: one with a
                # remote source would be forced into phase 0 BEFORE its
                # producers (broadcast build feeding the colocated join)
                if (f.partitioning == SOURCE and fid != dplan.root_fid
                        and not f.remote_sources()):
                    B = _fragment_lifespans(f.root)
                    if B:
                        grouped[fid] = B
                        n_tasks[fid] = B
        # consumer fragment of each producer (tree: exactly one consumer)
        consumer: Dict[int, int] = {}
        for fid, f in frags.items():
            for rs in f.remote_sources():
                consumer[rs.fragment_id] = fid
        # output partition count = consumer's task count
        n_out = {
            fid: n_tasks[consumer[fid]] if fid in consumer else 1
            for fid in frags
        }
        # soft-affinity split placement (NodeScheduler analog): for each
        # single-scan SOURCE fragment, enumerate the connector's splits
        # HERE and pin each ordinal to a worker by rendezvous hash. A
        # rescheduled task keeps its index → its ordinals, so coverage
        # survives worker loss. Multi-scan fragments (colocated bucket
        # joins) keep aligned task_index striding.
        # fid → per-task (ordinals-by-table, enumeration-count-by-table)
        split_assignments: Dict[int, List[tuple]] = {}
        if self.catalog is not None and getattr(config, "split_affinity",
                                                True):
            wkeys = [w.uri for w in workers]
            for fid, f in frags.items():
                if f.partitioning != SOURCE or fid in grouped:
                    continue
                scans = _fragment_scans(f.root)
                if len(scans) != 1:
                    continue
                scan = scans[0]
                try:
                    conn = self.catalog.connectors[scan.catalog]
                    handle = conn.get_table(scan.table)
                    nrows = int(handle.row_count or 0)
                    nsplits = max(1, -(-nrows // config.batch_rows))
                    n = len(conn.splits(handle, nsplits))
                except Exception:
                    continue  # non-enumerable here → static striding
                per_worker = _affinity_assign(scan.table, n, wkeys)
                split_assignments[fid] = [
                    ({scan.table: per_worker[i % len(workers)]},
                     {scan.table: n})
                    for i in range(n_tasks[fid])
                ]
        phased = getattr(config, "execution_policy",
                         "all-at-once") == "phased"
        phases = (compute_phases(frags) if phased
                  else {fid: 0 for fid in frags})
        if grouped:
            # grouped fragments run (and gate) first; everything else keeps
            # its relative phasing shifted after them
            phases = {fid: (0 if fid in grouped else phases[fid] + 1)
                      for fid in frags}
        last_phase = max(phases.values())

        task_urls: Dict[int, List[str]] = {}
        assignments = []  # (task_id, worker, fragment id, index, phase)
        for fid in sorted(frags):
            cnt = n_tasks[fid]
            urls = []
            for i in range(cnt):
                w = workers[i % len(workers)]
                tid = f"{query_id}.{fid}.{i}"
                assignments.append((tid, w, fid, i, phases[fid]))
                urls.append(f"{w.uri}/v1/task/{tid}")
            task_urls[fid] = urls

        def post_task(tid, w, fid, i):
            """Create the task on `w`, resolving upstream buffer URLs from
            the CURRENT task_urls (rescheduled producers re-point them)."""
            from presto_tpu.plan.codec import task_update_to_json

            f = frags[fid]
            upstreams = {
                rs.fragment_id: [
                    f"{u}/results/{i}" for u in task_urls[rs.fragment_id]
                ]
                for rs in f.remote_sources()
            }
            strip_runtime_state(f.root)
            sa = split_assignments.get(fid)
            update = TaskUpdate(
                fragment=f,
                task_index=i,
                n_tasks=n_tasks[fid],
                n_out_partitions=n_out[fid],
                upstreams=upstreams,
                config=_config_dict(config),
                # a build-phase task's consumers don't exist yet:
                # spool its output instead of blocking on back-pressure
                spool=phases[fid] < last_phase,
                split_assignment=None if sa is None else sa[i][0],
                split_counts=None if sa is None else sa[i][1],
            )
            body = json.dumps(task_update_to_json(update)).encode()
            req = urllib.request.Request(
                f"{w.uri}/v1/task/{tid}", data=body, method="POST",
                headers=self._headers({"Content-Type": "application/json",
                                       **trace_hdrs}),
            )
            with urllib.request.urlopen(req, timeout=30) as r:
                info = json.loads(r.read())
            if info.get("state") == "failed":
                raise QueryFailed(info.get("error") or "task failed")

        dead: set = set()

        def mark_dead(x):
            dead.add(id(x))
            x.record_failure()
            if self.on_worker_lost is not None:
                try:
                    self.on_worker_lost(x)
                except Exception:
                    pass

        def reschedule(tid, w, fid, i):
            """Re-run ONE lost task on a surviving worker, walking past
            survivors that also turn out dead."""
            mark_dead(w)
            attempt = int(tid.rsplit(".r", 1)[1]) + 1 if ".r" in tid else 1
            while True:
                survivors = [x for x in workers if id(x) not in dead]
                if not survivors:
                    # retryable: the query-level loop re-probes the cluster
                    # (pruning truly-dead nodes) before giving up
                    raise QueryFailed(
                        "no surviving workers to re-place lost tasks on",
                        retryable=True)
                if attempt > len(workers):
                    raise QueryFailed(f"task {tid} exhausted re-placement "
                                      f"retries")
                nw = survivors[i % len(survivors)]
                ntid = f"{query_id}.{fid}.{i}.r{attempt}"
                try:
                    post_task(ntid, nw, fid, i)
                except (urllib.error.URLError, OSError):
                    mark_dead(nw)
                    attempt += 1
                    continue
                task_urls[fid][i] = f"{nw.uri}/v1/task/{ntid}"
                created.append((ntid, nw))
                return ntid, nw

        # phase by phase; within a phase producers first (ascending fid
        # = topological order). All-at-once has exactly one phase.
        for ph in range(last_phase + 1):
            phase_tids = []
            for tid, w, fid, i, p in assignments:
                if p != ph:
                    continue
                try:
                    if id(w) in dead:
                        raise urllib.error.URLError("worker known dead")
                    post_task(tid, w, fid, i)
                    created.append((tid, w))
                    phase_tids.append((tid, w, fid, i))
                except (urllib.error.URLError, OSError):
                    # creation-time loss: any task is re-placeable on a
                    # survivor BEFORE its consumers wire upstreams
                    # (producers post first — ascending fid order)
                    ntid, nw = reschedule(tid, w, fid, i)
                    phase_tids.append((ntid, nw, fid, i))
            if ph < last_phase:
                # gate the next phase on this (build) phase finishing
                self._wait_finished(
                    phase_tids,
                    timeout_s=getattr(config, "phase_wait_timeout_s",
                                      600.0),
                    on_lost=(reschedule if ph == 0 and grouped
                             else None),
                    extra_headers=trace_hdrs)
        return task_urls

    def execute(self, query_id: str, dplan: DistributedPlan,
                workers: List[NodeInfo],
                config: Optional[ExecConfig] = None,
                stats_out: Optional[list] = None,
                tracer=None):
        """`stats_out`, when given, is filled with one
        (task_id, fragment_id, task_info_dict) per task after the result
        stream completes — the per-task stats rollup EXPLAIN ANALYZE
        renders (QueryStats/TaskStats introspection analog).

        `tracer` (obs.trace.Tracer) makes every task-create POST carry the
        query's trace token; after the stream completes the scheduler pulls
        each task's span dump and stitches query → stage → task."""
        config = config or self.config
        tracer = tracer or _obs_trace.NOOP
        trace_parent = tracer.current_parent()
        trace_hdrs = ({_obs_trace.TRACE_HEADER: tracer.token(trace_parent)}
                      if tracer.enabled else {})
        created = []
        completed = False
        try:
            with tracer.phase("schedule"):
                task_urls = self._create_tasks(query_id, dplan, workers,
                                               config, trace_hdrs, created)
            # stream the root fragment's single output buffer
            root_urls = [f"{u}/results/0" for u in task_urls[dplan.root_fid]]
            client = ExchangeClient(root_urls)
            if tracer.enabled:
                from presto_tpu.obs import metrics as _obs_metrics
            stream_w0 = time.time()
            waited = 0.0
            try:
                it = client.pages()
                while True:
                    w0 = time.monotonic()
                    try:
                        with tracer.phase("exchange_wait", wait=True):
                            page = next(it)
                    except StopIteration:
                        break
                    dt = time.monotonic() - w0
                    waited += dt
                    if tracer.enabled:
                        _obs_metrics.EXCHANGE_WAIT.observe(
                            dt, plane="coordinator")
                    yield client.decode(page, tracer)
                completed = True
            finally:
                client.close()
                if tracer.enabled:
                    tracer.record("exchange_wait", "exchange_wait",
                                  stream_w0, time.time(),
                                  parent_id=trace_parent,
                                  fragment=dplan.root_fid,
                                  wait_s=round(waited, 6))
            if stats_out is not None:
                for tid, w in created:
                    try:
                        req = urllib.request.Request(
                            f"{w.uri}/v1/task/{tid}/status",
                            headers=self._headers(trace_hdrs))
                        with urllib.request.urlopen(req, timeout=10) as r:
                            info = json.loads(r.read())
                        m = _TID_RE.match(tid)
                        fid = int(m.group(2)) if m else -1
                        stats_out.append((tid, fid, info))
                    except Exception:
                        pass
            if tracer.enabled:
                with tracer.phase("trace_collect"):
                    self._collect_task_traces(tracer, created, trace_parent,
                                              trace_hdrs)
        except ExchangeFailure as e:
            raise QueryFailed(str(e), retryable=not e.task_error) from e
        finally:
            # abort on ANY early exit — including GeneratorExit when the
            # consumer abandons the stream (client disconnect / LIMIT) —
            # so worker tasks and buffers are always released
            if not completed:
                self._abort(created)

    def _collect_task_traces(self, tracer, created, trace_parent,
                             extra_headers):
        """Stitch the distributed trace: pull every created task's span
        dump (GET /v1/task/{id}/trace), group by fragment, synthesize one
        `stage` span per fragment (the envelope of its tasks' spans) hung
        off the query root, and re-parent each worker task root onto its
        stage span. Unreachable workers just leave a hole — the trace is
        best-effort by design."""
        by_fid: Dict[int, list] = {}
        for tid, w in created:
            m = _TID_RE.match(tid)
            fid = int(m.group(2)) if m else -1
            try:
                req = urllib.request.Request(
                    f"{w.uri}/v1/task/{tid}/trace",
                    headers=self._headers(extra_headers))
                with urllib.request.urlopen(req, timeout=10) as r:
                    doc = json.loads(r.read())
            except Exception:
                continue
            if doc.get("spans"):
                by_fid.setdefault(fid, []).append(doc)
        for fid in sorted(by_fid):
            docs = by_fid[fid]
            starts = [s["start"] for d in docs for s in d["spans"]]
            ends = [(s["end"] if s["end"] is not None else s["start"])
                    for d in docs for s in d["spans"]]
            stage = tracer.record(
                f"stage-{fid}", "stage", min(starts), max(ends),
                parent_id=trace_parent, fragment=fid, tasks=len(docs))
            for d in docs:
                parent_map = {}
                root = d.get("rootSpanId")
                if root:
                    parent_map[root] = stage.span_id
                tracer.absorb(d["spans"], parent_map)

    def _wait_finished(self, tasks, timeout_s: float = 600.0,
                       poll_s: float = 0.1, on_lost=None,
                       extra_headers: Optional[dict] = None):
        """Block until every (tid, worker, fid, index) task reached a
        terminal state (phased scheduling's stage-completion gate). A
        failed task fails the query immediately. With `on_lost` (recoverable
        grouped execution), a task whose worker stopped answering is handed
        back — on_lost re-runs that lifespan on a survivor and returns the
        replacement (tid, worker) to keep waiting on; deterministic task
        FAILURES still fail the query (they would fail identically on
        any node)."""
        deadline = time.monotonic() + timeout_s
        pending = list(tasks)
        while pending:
            still = []
            for tid, w, fid, i in pending:
                try:
                    req = urllib.request.Request(
                        f"{w.uri}/v1/task/{tid}/status",
                        headers=self._headers(extra_headers))
                    with urllib.request.urlopen(req, timeout=10) as r:
                        info = json.loads(r.read())
                except Exception as e:
                    if on_lost is not None:
                        ntid, nw = on_lost(tid, w, fid, i)
                        still.append((ntid, nw, fid, i))
                        continue
                    raise QueryFailed(
                        f"lost task {tid} while awaiting phase completion: "
                        f"{e}", retryable=True) from e
                state = info.get("state")
                if state == "failed":
                    raise QueryFailed(info.get("error") or f"task {tid} failed")
                if state not in ("finished", "aborted"):
                    still.append((tid, w, fid, i))
            pending = still
            if pending:
                if time.monotonic() > deadline:
                    raise QueryFailed(
                        f"phase did not complete within {timeout_s}s "
                        f"({len(pending)} tasks still running)")
                time.sleep(poll_s)

    def _abort(self, created):
        for tid, w in created:
            try:
                req = urllib.request.Request(
                    f"{w.uri}/v1/task/{tid}", method="DELETE",
                    headers=self._headers(),
                )
                urllib.request.urlopen(req, timeout=5).read()
            except Exception:
                pass


# task ids are "{query_id}.{fragment}.{index}" with an optional ".r{n}"
# retry suffix (reschedule) — rsplit misparses retried ids, this doesn't
_TID_RE = re.compile(r"^(.+)\.(\d+)\.(\d+)(?:\.r\d+)?$")


def _fragment_lifespans(node) -> int:
    """Bucket count of a grouped (colocated-join) fragment, else 0
    (StageExecutionDescriptor.isStageGroupedExecution analog)."""
    from presto_tpu.plan.nodes import HashJoin

    if isinstance(node, HashJoin) and node.colocated:
        return node.colocated
    for c in node.children():
        b = _fragment_lifespans(c)
        if b:
            return b
    return 0


def _config_dict(cfg: ExecConfig) -> dict:
    import dataclasses

    return dataclasses.asdict(cfg)


class Coordinator:
    """Discovery + health + scheduling service. Exposes the announcement
    endpoint over HTTP; the statement protocol lives in
    presto_tpu.server.protocol (mounted on the same server)."""

    def __init__(self, catalog: Catalog, port: int = 0,
                 config: Optional[ExecConfig] = None, min_workers: int = 1,
                 broadcast_threshold_rows: float = 1_000_000,
                 cluster_secret: Optional[str] = None,
                 authenticator=None, session_property_manager=None,
                 query_event_log: Optional[str] = None,
                 cluster_memory_limit_bytes: Optional[int] = None,
                 low_memory_killer: str = "total-reservation-on-blocked",
                 low_memory_kill_delay_s: float = 1.0,
                 blocked_node_threshold: float = 0.95,
                 access_control=None, tls=None,
                 slow_query_log: Optional[str] = None,
                 slow_query_threshold_s: float = 0.0,
                 events_log: Optional[str] = None):
        from presto_tpu.server.cluster_memory import ClusterMemoryManager
        from presto_tpu.server.protocol import StatementProtocol
        from presto_tpu.server.querymanager import (
            QueryManager,
            batch_to_result,
        )

        self.catalog = catalog
        self.config = config or ExecConfig()
        self.broadcast_threshold_rows = broadcast_threshold_rows
        # column-level authorization consulted on every execution
        # (security/AccessControlManager.java analog; None = allow all)
        self.access_control = access_control
        self.tls = tls
        self.node_manager = NodeManager()
        self.cluster_memory = ClusterMemoryManager(
            cluster_memory_limit_bytes, policy=low_memory_killer,
            kill_delay_s=low_memory_kill_delay_s,
            blocked_node_threshold=blocked_node_threshold)
        # semantic result cache (server/result_cache.py): process-wide;
        # its bytes ride the cluster memory ledger and are revoked under
        # pressure before any query is killed
        from presto_tpu.server import result_cache as _result_cache

        self.result_cache = _result_cache.CACHE
        self.cluster_memory.result_cache = self.result_cache
        # revoke-before-kill ladder, second rung: under sustained pressure
        # the manager asks every active worker to revoke spillable operator
        # state (join builds / agg accumulators spill at their next batch
        # boundary) before killing anything
        self.cluster_memory.spill_revoker = self._revoke_spillable_state
        # adaptive rung tried BEFORE whole-operator revoke: shed only the
        # largest partitions of partition-granular owners (adaptive radix
        # aggregations) so hot state stays resident under pressure
        self.cluster_memory.partial_revoker = self._revoke_partial_state
        self._cluster_secret = cluster_secret
        self.failure_detector = HeartbeatFailureDetector(
            self.node_manager, cluster_memory=self.cluster_memory)
        self.size_monitor = ClusterSizeMonitor(self.node_manager, min_workers)
        self.scheduler = DistributedScheduler(
            self.config, cluster_secret=cluster_secret,
            on_worker_lost=lambda n: self._probe_and_exclude(n),
            catalog=catalog)
        self._query_seq = 0
        self._lock = threading.Lock()
        # keyed by (sql, plan-affecting session property values)
        self._dplan_cache: Dict[tuple, DistributedPlan] = {}
        self._cached_sqls: set = set()  # sqls with any cached plan (non-DDL)
        self._http = None

        def execute_fn(session, sql):
            cfg = session.exec_config()
            return batch_to_result(self.run_batch(sql, cfg, session))

        self.query_manager = QueryManager(execute_fn)
        self.failure_detector.query_manager = self.query_manager
        # query-id → Tracer; /v1/query/{id}/trace and the UI drill-down
        # resolve from here (scheduler attempt ids alias to the same trace)
        self.trace_registry = _obs_trace.TraceRegistry()
        # a low-memory kill stamps a memory_kill span onto the victim's
        # trace (registry exists only now — created after the manager)
        self.cluster_memory.trace_registry = self.trace_registry
        # inflight plane: stall forensics get the victim's open span stack
        # and pool reservations; configure() never arms, so off sessions
        # stay bit-for-bit
        _obs_inflight.configure(
            span_provider=lambda qid: (
                tr.spans() if (tr := self.trace_registry.get(qid))
                is not None else None),
            pool_provider=lambda qid: (
                (self.cluster_memory.memory_rollup().get("queryMemory")
                 or {}).get(qid)))

        if events_log:
            # unified cluster event stream JSONL sink (/v1/events mirrors
            # the in-memory ring regardless)
            _obs_events.EVENTS.configure(path=events_log)

        def _lifecycle_complete(event: str, info):
            # FIRST in the listener chain: SLO histograms, objective
            # violations, and the latency-regression flag must exist
            # before _log_slow reads the annotation
            if event != "queryCompleted":
                return
            try:
                tr = self.trace_registry.get(info.query_id)
                _obs_lifecycle.complete(
                    info, spans=tr.spans() if tr is not None else None)
            except Exception:
                pass

        self.query_manager.listeners.append(_lifecycle_complete)

        def _record_latency(event: str, info):
            if event != "queryCompleted":
                return
            try:
                from presto_tpu.obs import metrics as _obs_metrics

                _obs_metrics.QUERY_LATENCY.observe(
                    max(0.0, (info.end_time or time.time())
                        - info.create_time),
                    plane="coordinator", state=info.state)
            except Exception:
                pass

        self.query_manager.listeners.append(_record_latency)
        if slow_query_log:
            from presto_tpu.obs.events import SlowQueryLogger

            slow = SlowQueryLogger(slow_query_log,
                                   threshold_s=slow_query_threshold_s)

            def _log_slow(event: str, info, _s=slow):
                if event != "queryCompleted":
                    return
                tr = self.trace_registry.get(info.query_id)
                mem = None
                try:
                    # devprof plane: fold the query's memory picture into
                    # the slow-query record — its cluster-ledger slice plus
                    # the device's own numbers when the plane is on
                    from presto_tpu.obs import devprof as _devprof

                    doc = {}
                    roll = self.cluster_memory.memory_rollup()
                    qb = (roll.get("queryMemory") or {}).get(info.query_id)
                    if qb:
                        doc["reservedBytes"] = qb
                    if _devprof.active():
                        doc["device"] = _devprof.device_memory_doc()
                        s = _devprof.summary()
                        if s.get("peak_program_footprint_bytes"):
                            doc["peakProgramFootprintBytes"] = \
                                s["peak_program_footprint_bytes"]
                    mem = doc or None
                except Exception:
                    mem = None
                extra = _obs_lifecycle.slow_log_annotation(info.query_id)
                try:
                    # inflight plane: doctor verdict + straggler docs ride
                    # the slow-query record when the plane saw the query
                    inf = _obs_inflight.slow_log_annotation(info.query_id)
                    if inf:
                        extra = {**(extra or {}), **inf}
                except Exception:
                    pass
                _s.log(info, tr.spans() if tr is not None else None,
                       memory=mem, extra=extra)

            self.query_manager.listeners.append(_log_slow)
        if query_event_log:
            # query-completion audit stream (reference: the EventListener
            # SPI's QueryCompletedEvent, commonly shipped to an audit log)
            import dataclasses as _dc

            self._event_log_lock = threading.Lock()

            def log_event(event: str, info, path=query_event_log):
                rec = {"event": event, "ts": time.time(),
                       **_dc.asdict(info)}
                line = json.dumps(rec, default=str)
                with self._event_log_lock:
                    with open(path, "a") as fh:
                        fh.write(line + "\n")

            self.query_manager.listeners.append(log_event)

        def _speculate(qe):
            # queue-wait precompile: farm-compile the statement's recorded
            # plans while the query waits for admission, spending (and
            # respecting) the group's compile budget
            group = qe.resource_group or ""
            user = qe.session.user
            _farm.speculate(
                qe.sql, self.catalog, qe.session.exec_config(),
                group=group, query_id=qe.query_id,
                charge_fn=lambda n: self.query_manager.resource_groups
                .charge_compiles(group, n, user),
                budget_fn=lambda: self.query_manager.resource_groups
                .compile_budget_remaining(group, user))

        self.query_manager.speculate_fn = _speculate
        # ahead-of-traffic farm boot: arm the program cache from the
        # persisted corpus BEFORE serving starts, so "coordinator ready"
        # means "known programs warm" (blocking by design; gated on
        # PRESTO_TPU_FARM=1 + PRESTO_TPU_CACHE_DIR, else a no-op)
        try:
            self._farm_armed = _farm.boot(self.catalog, self.config,
                                          block=True)
        except Exception:
            self._farm_armed = 0
        # bind the socket first (determines self.url), wire the protocol,
        # THEN start serving — no request can observe a half-built coordinator
        self._bind_http(port)
        self.protocol = StatementProtocol(
            self.query_manager, catalog, self.url,
            explain_fn=self._explain,
            authenticator=authenticator,
            session_property_manager=session_property_manager,
        )
        from presto_tpu.server.querymanager import batch_to_result as _btr

        self.protocol.execute_stmt_fn = (
            lambda session, stmt: _btr(self.run_batch(
                "", session.exec_config(), session, stmt=stmt)))
        threading.Thread(target=self._http.serve_forever, daemon=True,
                         name="coordinator-http").start()
        self.failure_detector.start()

    def _explain(self, sql: str, analyze: bool, session,
                 etype: Optional[str] = None) -> str:
        if etype not in (None, "distributed", "logical", "validate"):
            raise ValueError(
                f"unknown EXPLAIN type {etype!r} "
                "(supported: DISTRIBUTED, LOGICAL, VALIDATE)")
        if analyze:
            if etype not in (None, "distributed"):
                raise ValueError(
                    "EXPLAIN ANALYZE only supports TYPE DISTRIBUTED")
            return self.explain_analyze_distributed(sql, session)
        if etype == "validate":
            from presto_tpu.plan.builder import plan_query

            plan_query(sql, self.catalog)  # raises on invalid queries
            return "VALID"
        if etype == "logical":
            from presto_tpu.plan.builder import plan_query
            from presto_tpu.plan.nodes import plan_to_string
            from presto_tpu.plan.optimizer import optimize

            return plan_to_string(optimize(plan_query(sql, self.catalog), self.catalog).root)
        # default / TYPE DISTRIBUTED
        return self.plan_distributed(sql, session).to_string()

    def explain_analyze_distributed(self, sql: str, session=None) -> str:
        """Run the query on the cluster with per-operator accounting and
        render a per-fragment, per-task stats rollup (the QueryStats/
        OperatorStats view of the reference's EXPLAIN ANALYZE)."""
        import dataclasses as _dc

        dplan = self.plan_distributed(sql, session)
        cfg = _dc.replace(
            session.exec_config() if session else self.config,
            collect_stats=True)
        # result-cache header: what a NON-explain run of this statement
        # would see right now. peek() is non-mutating — rendering the
        # header neither counts a hit/miss nor refreshes the entry.
        rc_line = None
        rc_mode = (getattr(cfg, "result_cache", "off") or "off").lower()
        if rc_mode != "off":
            if dplan.__dict__.get("_rc_cacheable"):
                from presto_tpu.server import result_cache as _rc_mod2

                rc_key = _rc_mod2.query_key(
                    dplan, self.catalog,
                    getattr(session, "catalog", "") or "",
                    getattr(session, "schema", "") or "")
                rc_state = ("hit" if self.result_cache.peek(rc_key)
                            else "miss")
            else:
                rc_state = "bypass"
            rc_line = f"[cache: {rc_state}]"
        # farm header: would a first-seen run of this structure land on a
        # warm program cache? armed = boot pre-armed, live = queue-wait
        # speculation warmed it, miss = cold. Rendered only when the farm
        # is in play (process or session arming) — off stays bit-for-bit.
        farm_line = None
        if _farm.enabled(cfg):
            farm_line = ("[farm: "
                         + _farm.status_for(dplan.fragments[dplan.root_fid]
                                            .root) + "]")
        stats: list = []
        self.size_monitor.wait_for_minimum()
        qid = self.next_query_id()
        workers = self.node_manager.active_nodes()
        # lifecycle plane: EXPLAIN ANALYZE serves through a QueryExecution
        # (the _immediate path), so the session query id already has a
        # registered timeline when lifecycle=on
        session_qid = getattr(session, "query_id", "") or ""
        entry = _obs_lifecycle.get(session_qid) if session_qid else None
        if entry is not None:
            _obs_lifecycle.mark(session_qid, "compiling")
            _obs_lifecycle.alias(qid, entry.query_id)
        if session_qid and _obs_inflight.get(session_qid) is not None:
            # task publishers key by the scheduler attempt id; route them
            # to the session's inflight entry
            _obs_inflight.alias(qid, session_qid)
        tracer = _obs_trace.NOOP
        if getattr(cfg, "tracing", True):
            tracer = _obs_trace.Tracer(
                trace_id=getattr(session, "query_id", "") or None)
            self.trace_registry.register(tracer)
            self.trace_registry.alias(qid, tracer.trace_id)
        with _obs_trace.use(tracer), tracer.span("query", "query",
                                                 sql=sql[:200]):
            first = True
            for _ in self.scheduler.execute(qid, dplan, workers, cfg,
                                            stats_out=stats, tracer=tracer):
                if first and entry is not None:
                    _obs_lifecycle.mark(session_qid, "executing")
                    first = False
        lines = []
        if rc_line is not None:
            lines += [rc_line, ""]
        if farm_line is not None:
            lines += [farm_line, ""]
        if entry is not None:
            seg = entry.timeline.segments()
            lines += [
                "-- lifecycle --",
                "  " + "  ".join(
                    f"{k}={seg[k]:.3f}s"
                    for k in ("queue_wait", "plan", "compile", "exec",
                              "drain", "e2e")),
                "",
            ]
        tr_spans = tracer.spans() if tracer is not _obs_trace.NOOP else None
        try:
            # query doctor: ranked bottleneck attribution over lifecycle +
            # inflight telemetry (present only when a plane saw the query)
            doctor = _obs_inflight.analyze(session_qid or qid,
                                           spans=tr_spans)
            if doctor is not None and doctor.get("verdict"):
                lines += ["-- doctor --", "  " + doctor["verdict"]]
                for c in doctor.get("causes", [])[1:3]:
                    lines.append(
                        f"    also: {c['cause']}"
                        f" ({c['score']:.0%}) {c.get('detail', '')}".rstrip())
                lines.append("")
        except Exception:
            pass
        lines += [dplan.to_string(), ""]
        # the engine each breaker took and why (the tasks' breaker_engine
        # trace markers): where the platform refused the hash engine, the
        # why-string says so here
        verdicts = sorted({
            (s.attrs.get("node"), s.attrs.get("engine"), s.attrs.get("why"))
            for s in (tr_spans or ())
            if s.kind == "breaker_engine" and s.attrs})
        if verdicts:
            lines.append("-- breaker engines --")
            lines += [f"  {n} [engine={e}: {w}]" for n, e, w in verdicts]
            lines.append("")
        lines.append("-- task execution profile --")
        by_fid: Dict[int, list] = {}
        for tid, fid, info in stats:
            by_fid.setdefault(fid, []).append((tid, info))
        for fid in sorted(by_fid):
            lines.append(f"fragment {fid}:")
            for tid, info in sorted(by_fid[fid]):
                lines.append(f"  task {tid} [{info.get('state')}]")
                for row in info.get("stats") or []:
                    line = (
                        f"    {row['node']:<16} rows={int(row['rows']):>12,}"
                        f" batches={int(row['batches']):>6}"
                        f" wall={row['wall_s']:.3f}s")
                    if row.get("bytes"):
                        line += f" bytes={int(row['bytes']):,}"
                    if "compiles" in row:
                        # compile wall comes out of the operator's measured
                        # wall: the split shows where the time actually went
                        cw = float(row.get("compile_wall_s") or 0.0)
                        line += (f" compiles={int(row['compiles'])}"
                                 f" compile={cw:.3f}s"
                                 f" execute="
                                 f"{max(0.0, row['wall_s'] - cw):.3f}s")
                    fl = float(row.get("flops") or 0.0)
                    ba = float(row.get("bytes_accessed") or 0.0)
                    pk = float(row.get("peak_bytes") or 0.0)
                    if fl or ba or pk:
                        # devprof plane: XLA's own cost/memory analysis of
                        # the operator's compiled programs (ai = flops per
                        # byte moved — the roofline x-axis)
                        parts = []
                        if pk:
                            parts.append(f"peak={int(pk):,}")
                        if fl:
                            parts.append(f"flops={fl:.4g}")
                        if ba:
                            parts.append(f"bytes={ba:.4g}")
                        if fl and ba:
                            parts.append(f"ai={fl / ba:.2f}")
                        line += " [" + " ".join(parts) + "]"
                    lines.append(line)
        return "\n".join(lines)

    # -- http -------------------------------------------------------------

    def _bind_http(self, port: int):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        coord = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):
                pass

            def _json(self, obj, code=200, extra_headers=None):
                data = json.dumps(obj).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(data)))
                for k, v in (extra_headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(data)

            def _text(self, body: str, content_type: str, code=200):
                data = body.encode()
                self.send_response(code)
                self.send_header("Content-Type", content_type)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def do_POST(self):
                if self.path == "/v1/statement":
                    from presto_tpu.server.security import AuthenticationError

                    n = int(self.headers.get("Content-Length", 0))
                    sql = self.rfile.read(n).decode()
                    try:
                        out, extra = coord.protocol.create(sql, self.headers)
                        return self._json(out, extra_headers=extra)
                    except AuthenticationError as e:
                        return self._json(
                            {"error": {"message": str(e),
                                       "errorName": "AUTHENTICATION_FAILED",
                                       "errorType": "USER_ERROR"}},
                            code=401,
                            extra_headers={
                                "WWW-Authenticate": 'Basic realm="presto-tpu"'
                            })
                    except Exception as e:
                        return self._json(
                            {"error": {"message": str(e),
                                       "errorName": type(e).__name__,
                                       "errorType": "USER_ERROR"},
                             "id": "", "stats": {"state": "FAILED"}})
                self._json({"error": "not found"}, 404)

            def do_PUT(self):
                if self.path.startswith("/v1/announcement/"):
                    node_id = self.path.rsplit("/", 1)[1]
                    n = int(self.headers.get("Content-Length", 0))
                    body = json.loads(self.rfile.read(n))
                    coord.node_manager.announce(
                        node_id, body["uri"], body.get("state", "active")
                    )
                    return self._json({"ok": True})
                self._json({"error": "not found"}, 404)

            def do_GET(self):
                m = re.match(r"^/v1/statement/([^/]+)/(\d+)$", self.path)
                if m:
                    try:
                        return self._json(
                            coord.protocol.poll(m.group(1), int(m.group(2)))
                        )
                    except KeyError:
                        return self._json({"error": "unknown query"}, 404)
                m = re.match(r"^/v1/query/([^/]+)/trace$", self.path)
                if m:
                    tr = coord.trace_registry.get(m.group(1))
                    if tr is None:
                        return self._json({"error": "no trace for query"},
                                          404)
                    return self._json(tr.to_json())
                m = re.match(r"^/v1/query/([^/]+)/progress$", self.path)
                if m:
                    qid = m.group(1)
                    state = None
                    try:
                        state = coord.query_manager.get(qid).state
                    except KeyError:
                        pass
                    doc = _obs_lifecycle.progress_doc(qid, state=state)
                    if doc is None:
                        return self._json(
                            {"error": "no lifecycle for query "
                                      "(unknown id or lifecycle=off)"}, 404)
                    return self._json(doc)
                m = re.match(r"^/v1/query/([^/]+)/inflight$", self.path)
                if m:
                    doc = _obs_inflight.snapshot_doc(m.group(1))
                    if doc is None:
                        return self._json(
                            {"error": "no inflight telemetry for query "
                                      "(unknown id or inflight=off)"}, 404)
                    return self._json(doc)
                m = re.match(r"^/v1/query/([^/]+)/doctor$", self.path)
                if m:
                    qid = m.group(1)
                    state = None
                    try:
                        state = coord.query_manager.get(qid).state
                    except KeyError:
                        pass
                    tr = coord.trace_registry.get(qid)
                    doc = _obs_inflight.analyze(
                        qid, spans=tr.spans() if tr is not None else None,
                        state=state)
                    if doc is None:
                        return self._json(
                            {"error": "no telemetry for query (unknown id "
                                      "or lifecycle+inflight off)"}, 404)
                    return self._json(doc)
                m = re.match(r"^/v1/events(?:\?(.*))?$", self.path)
                if m:
                    import urllib.parse as _up

                    q = _up.parse_qs(m.group(1) or "")

                    def _one(name, cast=str, default=None):
                        vals = q.get(name)
                        try:
                            return cast(vals[0]) if vals else default
                        except (TypeError, ValueError):
                            return default

                    return self._json({
                        "lastSeq": _obs_events.EVENTS.last_seq(),
                        "events": _obs_events.EVENTS.events(
                            since=_one("since", int, 0),
                            query_id=_one("queryId"),
                            kind=_one("kind"),
                            limit=_one("limit", int, 1000)),
                    })
                m = re.match(r"^/ui/query/([^/]+)$", self.path)
                if m:
                    from presto_tpu.server.metrics import render_query_page

                    page = render_query_page(coord, m.group(1))
                    if page is None:
                        return self._json({"error": "unknown query"}, 404)
                    return self._text(page, "text/html")
                m = re.match(r"^/v1/query/([^/]+)$", self.path)
                if m:
                    try:
                        qe = coord.query_manager.get(m.group(1))
                    except KeyError:
                        return self._json({"error": "unknown query"}, 404)
                    import dataclasses as _dc

                    return self._json(_dc.asdict(qe.info()))
                if self.path == "/v1/query":
                    import dataclasses as _dc

                    return self._json(
                        [_dc.asdict(i) for i in coord.query_manager.queries()]
                    )
                if self.path == "/v1/info":
                    return self._json({
                        "nodeId": "coordinator", "coordinator": True,
                        "uri": coord.url,
                    })
                if self.path == "/v1/node":
                    return self._json([
                        {"nodeId": n.node_id, "uri": n.uri,
                         "failureScore": n.failure_score, "state": n.state}
                        for n in coord.node_manager.nodes.values()
                    ])
                if self.path == "/v1/cluster":
                    qs = coord.query_manager.queries()
                    return self._json({
                        "activeWorkers": len(coord.node_manager.active_nodes()),
                        "runningQueries": sum(1 for q in qs if q.state == "RUNNING"),
                        "queuedQueries": sum(1 for q in qs if q.state == "QUEUED"),
                        "totalQueries": len(qs),
                        "memory": coord.cluster_memory.info(),
                    })
                if self.path == "/v1/memory":
                    # cluster memory rollup (MemoryPoolInfo REST analog):
                    # per-node reserved/peak/limit + device stats + the
                    # per-query slices the low-memory killer ranks on
                    return self._json(coord.cluster_memory.memory_rollup())
                if self.path == "/v1/metrics":
                    from presto_tpu.server.metrics import coordinator_metrics

                    return self._text(coordinator_metrics(coord),
                                      "text/plain; version=0.0.4")
                if self.path in ("/", "/ui", "/ui/"):
                    from presto_tpu.server.metrics import render_ui

                    return self._text(render_ui(coord), "text/html")
                self._json({"error": "not found"}, 404)

            def do_DELETE(self):
                m = re.match(r"^/v1/statement/([^/]+)(?:/\d+)?$", self.path)
                if m:
                    coord.protocol.cancel(m.group(1))
                    return self._json({"ok": True})
                if self.path == "/v1/cache":
                    # explicit operator flush of the semantic result cache
                    n = coord.result_cache.flush()
                    return self._json({"ok": True, "flushed": n})
                self._json({"error": "not found"}, 404)

        self._http = ThreadingHTTPServer(("127.0.0.1", port), Handler)
        if self.tls is not None:
            from presto_tpu.server.tls import install_client_context, wrap_server

            wrap_server(self._http, self.tls)
            install_client_context(self.tls)
        self.port = self._http.server_address[1]
        scheme = "https" if self.tls is not None else "http"
        self.url = f"{scheme}://127.0.0.1:{self.port}"

    # -- queries ----------------------------------------------------------

    def next_query_id(self) -> str:
        with self._lock:
            self._query_seq += 1
            return f"q{self._query_seq}"

    def execute_distributed(self, dplan: DistributedPlan,
                            config: Optional[ExecConfig] = None,
                            tracer=None):
        self.size_monitor.wait_for_minimum()
        qid = self.next_query_id()
        workers = self.node_manager.active_nodes()
        if tracer is None:
            tracer = _obs_trace.current()
        if tracer.enabled:
            # task ids embed this scheduler attempt id — make it resolve
            # to the query's trace too
            self.trace_registry.alias(qid, tracer.trace_id)
            # ... and to the lifecycle progress entry (trace ids are
            # minted as the serving query id), so worker heartbeats keyed
            # by this attempt reach the right registry slot
            _obs_lifecycle.alias(qid, tracer.trace_id)
            # ... and to the inflight entry, so task publishers keyed by
            # this attempt heartbeat into the serving query's telemetry
            _obs_inflight.alias(qid, tracer.trace_id)
        entry = _obs_lifecycle.get(qid)
        if entry is None:
            yield from self.scheduler.execute(qid, dplan, workers, config,
                                              tracer=tracer)
            return
        # lifecycle plane: the first root-stream batch is the
        # compiling->executing boundary; every batch feeds the live
        # progress counts
        import numpy as _np
        first = True
        for b in self.scheduler.execute(qid, dplan, workers, config,
                                        tracer=tracer):
            if first:
                _obs_lifecycle.mark(entry.query_id, "executing")
                first = False
            entry.observe_batch(int(_np.asarray(b.live).sum()))
            yield b
        if first:
            # zero-batch stream (e.g. empty scan): still crossed into
            # execution before draining
            _obs_lifecycle.mark(entry.query_id, "executing")

    def _try_scaled_write(self, stmt, config, session) -> Optional[Batch]:
        """Scaled writers (SCALED_WRITER_DISTRIBUTION): CTAS into a
        connector that supports part tables plans the source query
        distributed and wraps each root task with a TableWriter — every
        task writes its own part concurrently; counts gather and sum; the
        staging directory commits atomically (TableFinish). Returns None
        when the statement doesn't qualify (the gathered single-writer
        path handles it)."""
        import uuid

        from presto_tpu.exec.runtime import _collect_concat
        from presto_tpu.plan.builder import plan_query
        from presto_tpu.plan.fragmenter import fragment_plan
        from presto_tpu.plan.nodes import Output, TableWriter
        from presto_tpu.plan.optimizer import optimize
        from presto_tpu.sql import ast as _ast

        if not isinstance(stmt, _ast.CreateTableAs):
            return None
        if stmt.properties:
            # partitioned CTAS groups rows by partition value — the
            # single-writer path owns that layout
            return None
        conn, tname = self.catalog.connector_for(stmt.name)
        if not getattr(conn, "supports_scaled_writes", lambda: False)():
            return None
        qp = optimize(plan_query(stmt.query, self.catalog), self.catalog)
        self._enforce_access([qp.root], session)
        if qp.scalar_subqueries:
            return None  # binding protocol stays on the gathered path
        write_id = uuid.uuid4().hex[:8]
        inner = qp.root.child
        writer = TableWriter(inner, conn.name, tname, write_id)
        qp.root = Output(writer, ["rows"], ["rows"])
        if not conn.begin_scaled_create(tname,
                                        if_not_exists=stmt.if_not_exists):
            return self._count_batch(0)
        try:
            dplan = fragment_plan(
                qp, self.catalog,
                broadcast_threshold_rows=self.broadcast_threshold_rows)
            # NO query-level retry here: a retry with a different worker
            # count would leave stale parts from the first attempt in the
            # staging dir (duplicated rows); failures abort the staging
            batches = list(self.execute_distributed(dplan, config))
            merged = _collect_concat(iter(batches))
            total = 0
            if merged is not None:
                rows = merged.to_pydict(decode_strings=False)["rows"]
                total = int(sum(int(v) for v in rows))
            conn.finish_scaled_create(tname)
        except BaseException:
            conn.abort_scaled_create(tname)
            raise
        return self._count_batch(total)

    @staticmethod
    def _count_batch(rows: int) -> Batch:
        import jax.numpy as jnp
        import numpy as np

        from presto_tpu.batch import Column
        from presto_tpu.types import BIGINT

        vals = np.zeros(128, np.int64)
        vals[0] = rows
        live = np.zeros(128, bool)
        live[0] = True
        return Batch(["rows"], [BIGINT],
                     [Column(jnp.asarray(vals), None)],
                     jnp.asarray(live), {})

    def _revoke_spillable_state(self) -> int:
        """POST /v1/memory/revoke on every active worker: spillable
        operator state (hybrid hash join builds, grace-agg accumulators)
        flags itself and spills at the next batch boundary. Returns how
        many revokers were signaled cluster-wide."""
        signaled = 0
        for n in self.node_manager.active_nodes():
            try:
                req = urllib.request.Request(
                    f"{n.uri}/v1/memory/revoke", data=b"{}", method="POST")
                if self._cluster_secret is not None:
                    req.add_header("X-Presto-Cluster-Secret",
                                   self._cluster_secret)
                with urllib.request.urlopen(req, timeout=3) as r:
                    doc = json.loads(r.read())
                signaled += int(doc.get("revokersSignaled") or 0)
            except Exception:
                continue
        return signaled

    def _revoke_partial_state(self) -> int:
        """POST /v1/memory/revoke {"partial": true} on every active
        worker: partition-granular owners (adaptive radix aggregations)
        shed only their LARGEST partitions at the next batch boundary.
        Returns partitions revoked cluster-wide — 0 means no partial
        owner anywhere, and the enforce ladder falls through to the
        whole-operator rung."""
        revoked = 0
        for n in self.node_manager.active_nodes():
            try:
                req = urllib.request.Request(
                    f"{n.uri}/v1/memory/revoke",
                    data=b'{"partial": true}', method="POST")
                if self._cluster_secret is not None:
                    req.add_header("X-Presto-Cluster-Secret",
                                   self._cluster_secret)
                with urllib.request.urlopen(req, timeout=3) as r:
                    doc = json.loads(r.read())
                revoked += int(doc.get("partitionsRevoked") or 0)
            except Exception:
                continue
        return revoked

    def _probe_and_exclude(self, n: NodeInfo):
        """One-node version of _reprobe_workers, called when task placement
        found the node dead: confirm with a direct probe and exclude it
        from rotation immediately if it really is gone."""
        try:
            with urllib.request.urlopen(f"{n.uri}/v1/status", timeout=3) as r:
                json.loads(r.read())
            n.record_success()
        except Exception:
            n.failure_score = 5.0  # past NodeInfo.failed threshold

    def _reprobe_workers(self):
        """Synchronous cluster probe before a retry: a node that fails its
        probe is excluded IMMEDIATELY (score jump past the threshold) —
        the background detector's decayed counter is deliberately slow for
        flaky networks, but a retry must not re-schedule onto a node that
        just killed the query."""
        def probe(n):
            try:
                with urllib.request.urlopen(f"{n.uri}/v1/status",
                                            timeout=3) as r:
                    json.loads(r.read())
                n.record_success()
            except Exception:
                n.failure_score = 5.0  # past NodeInfo.failed threshold

        threads = [threading.Thread(target=probe, args=(n,), daemon=True)
                   for n in list(self.node_manager.nodes.values())]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=4)

    def _execute_with_retry(self, dplan: DistributedPlan,
                            config: Optional[ExecConfig] = None) -> list:
        """Query-level elastic retry (reference: RetryPolicy.QUERY /
        recoverable execution's coarse form): any task failure or worker
        transport error re-probes the cluster and re-runs the whole query
        on the surviving nodes."""
        retries = (config or self.config).query_retry_count
        attempt = 0
        while True:
            try:
                return list(self.execute_distributed(dplan, config))
            except (QueryFailed, urllib.error.URLError, OSError) as e:
                # deterministic task errors re-fail identically: don't
                # burn a full re-execution on them
                retryable = (e.retryable if isinstance(e, QueryFailed)
                             else True)
                if attempt >= retries or not retryable:
                    raise (e if isinstance(e, QueryFailed)
                           else QueryFailed(str(e), retryable=True))
                attempt += 1
                self._reprobe_workers()
                if not self.node_manager.active_nodes():
                    raise QueryFailed(
                        "no active workers after failure probe") from e

    def plan_distributed(self, sql: str, session=None,
                         stmt=None) -> DistributedPlan:
        from presto_tpu.exec.runtime import ExecContext
        from presto_tpu.plan.builder import plan_query
        from presto_tpu.plan.fragmenter import fragment_plan
        from presto_tpu.plan.optimizer import optimize

        # session properties that change the PLAN feed the cache key
        # (join_distribution_type — SystemSessionProperties.java:59)
        jdt = (session.get("join_distribution_type") if session else "AUTOMATIC") or "AUTOMATIC"
        jdt = jdt.upper()
        threshold = {
            "BROADCAST": float("inf"),
            "PARTITIONED": 0.0,
        }.get(jdt, self.broadcast_threshold_rows)
        jm = ((session.get("join_mode") if session else None) or
              getattr(self.config, "join_mode", "auto")).lower()
        cache_key = (sql, jdt, jm)
        hit = self._dplan_cache.get(cache_key) if sql else None
        if hit is not None:
            return hit
        qp = optimize(plan_query(stmt if stmt is not None else sql,
                                 self.catalog), self.catalog)
        if jm != "off":
            from presto_tpu.plan.multiway import apply_join_mode

            cfg = session.exec_config() if session else self.config
            apply_join_mode(qp, self.catalog, cfg)
        cacheable = bool(sql) and not qp.scalar_subqueries and qp.cacheable
        if qp.scalar_subqueries:
            # bind uncorrelated scalar subqueries coordinator-side first
            # (the reference runs them as separate plan stages). They
            # EXECUTE here, before run_batch's fragment walk can see them —
            # authorize their scans now or a subquery smuggles denied data
            from presto_tpu.exec.runtime import bind_scalar_subqueries

            self._enforce_access(
                (s.root for s in qp.scalar_subqueries.values()), session)
            bind_scalar_subqueries(qp, ExecContext(self.catalog, self.config))
        dplan = fragment_plan(
            qp, self.catalog,
            broadcast_threshold_rows=threshold,
        )
        # result-cache eligibility rides on the plan object: only plans
        # with no scalar subqueries and a cacheable (deterministic) tree
        # may consult/populate the semantic result cache
        dplan.__dict__["_rc_cacheable"] = cacheable
        if cacheable:
            # concurrent submissions of the same sql both plan (the get
            # above is a lock-free fast path) but the insert keeps the
            # cache + membership set consistent; last writer wins with an
            # equivalent plan
            with self._lock:
                self._dplan_cache[cache_key] = dplan
                self._cached_sqls.add(sql)
        return dplan

    def _enforce_access(self, roots, session) -> None:
        """Column-level authorization over every table the (cached or
        fresh) plan touches — enforced per EXECUTION, so plan caching
        can't bypass a rule change (AccessControlManager.checkCanSelect
        FromColumns analog). `roots` is an iterable of plan roots."""
        if self.access_control is None:
            return
        from presto_tpu.plan.nodes import IndexJoin as _IdxJ
        from presto_tpu.plan.nodes import TableScan as _TS

        user = getattr(session, "user", None) or "user"

        def walk(n):
            if isinstance(n, _TS):
                self.access_control.check_can_select(
                    user, n.catalog, n.table,
                    set(n.assignments.values()) | set(n.constraints or ()))
            elif isinstance(n, _IdxJ):
                self.access_control.check_can_select(
                    user, n.catalog, n.table,
                    set(n.assignments.values()))
            for c in n.children():
                walk(c)

        for r in roots:
            walk(r)

    # -- result cache ------------------------------------------------------

    def _invalidate_result_cache(self):
        """Snapshot-token barrier after DDL/DML: reclaim every cached
        result whose token no longer matches the live catalog."""
        rc = self.result_cache
        if rc is None or not rc.armed():
            return
        try:
            from presto_tpu.obs.runstats import catalog_token

            rc.flush_stale(catalog_token(self.catalog))
        except Exception:
            pass

    def _rc_connector(self):
        """The private memory connector holding materialized subplan
        results. Underscore-prefixed, so `catalog_token` skips it — its
        churn must never invalidate the cache keyed on that token."""
        conn = self.catalog.connectors.get("_rc")
        if conn is None:
            from presto_tpu.catalog.memory import MemoryConnector

            conn = MemoryConnector("_rc")
            # direct registration (not Catalog.register): the splice
            # connector must never become the session default
            conn.name = "_rc"
            self.catalog.connectors["_rc"] = conn
        return conn

    @staticmethod
    def _rc_table_name(skey: str) -> str:
        """Splice table name for a subplan cache key — derived from the
        KEY (stable across plan objects and processes), never from plan
        node identity."""
        import hashlib as _hashlib

        return "rc_" + _hashlib.sha256(skey.encode()).hexdigest()[:16]

    def _materialize_subplan(self, node, skey, config):
        """Execute one breaker subtree as its own distributed query and
        land the result as a `_rc` memory table. Returns (table_name,
        batch, wall_s) or None on any failure (the caller falls back to
        executing the unspliced plan)."""
        from presto_tpu.exec.runtime import _JIT_COMPACT, _collect_concat
        from presto_tpu.plan.fragmenter import fragment_plan
        from presto_tpu.plan.nodes import Output, QueryPlan

        try:
            names = [s for s, _ in node.output]
            sub_qp = QueryPlan(Output(node, names, names))
            sub_dplan = fragment_plan(
                sub_qp, self.catalog,
                broadcast_threshold_rows=self.broadcast_threshold_rows)
            t0 = time.perf_counter()
            batches = list(self.execute_distributed(sub_dplan, config))
            merged = _collect_concat(iter(batches))
            if merged is None:
                return None
            merged = _JIT_COMPACT(merged)
            wall = time.perf_counter() - t0
            tname = self._rc_table_name(skey)
            conn = self._rc_connector()
            conn.drop_table(tname, if_exists=True)
            conn.create_table_from(tname, [merged])
            return tname, merged, wall
        except Exception:
            return None

    def _run_with_subplan_reuse(self, sql, stmt, config, session):
        """result_cache=subplan: replan FRESH (the shared-plan-cache copy
        must never be mutated), look up each topmost grouped-Aggregate
        breaker in the subplan cache, splice hits in as `_rc` table
        scans (materializing misses first), and execute the spliced
        plan. Returns the merged batch, or None when nothing spliced —
        the caller falls back to the normal path."""
        from presto_tpu.exec.runtime import _collect_concat
        from presto_tpu.plan.builder import plan_query
        from presto_tpu.plan.fragmenter import fragment_plan
        from presto_tpu.plan.nodes import TableScan
        from presto_tpu.plan.optimizer import optimize
        from presto_tpu.server import result_cache as _rc_mod

        try:
            qp = optimize(plan_query(
                stmt if stmt is not None else sql, self.catalog),
                self.catalog)
        except Exception:
            return None
        if qp.scalar_subqueries or not qp.cacheable:
            return None
        # authorization runs against the PRE-splice plan: splicing only
        # replaces subtrees the user was just cleared to read
        self._enforce_access([qp.root], session)
        candidates = _rc_mod.find_breaker_subplans(qp.root)
        if not candidates:
            return None
        spliced = 0
        for node in candidates:
            skey = _rc_mod.subplan_key(node, self.catalog)
            if skey is None:
                continue
            cached = self.result_cache.lookup(skey)
            if cached is None:
                made = self._materialize_subplan(node, skey, config)
                if made is None:
                    continue
                tname, batch, wall = made
                conn = self._rc_connector()
                if not self.result_cache.admit(
                        skey, "subplan", batch, wall_s=wall,
                        token=skey.rsplit("/", 2)[1],
                        on_evict=(lambda c=conn, t=tname:
                                  c.drop_table(t, if_exists=True))):
                    conn.drop_table(tname, if_exists=True)
                    continue
            else:
                # entry present ⇒ its backing table is still registered
                # (the entry's on_evict is what drops it)
                tname = self._rc_table_name(skey)
                if tname not in self._rc_connector().tables:
                    continue
            scan = TableScan(
                catalog="_rc", table=tname,
                assignments={s: s for s, _ in node.output},
                output=list(node.output))
            if _rc_mod.replace_child(qp.root, node, scan):
                spliced += 1
        if not spliced:
            return None
        dplan = fragment_plan(
            qp, self.catalog,
            broadcast_threshold_rows=self.broadcast_threshold_rows)
        batches = self._execute_with_retry(dplan, config)
        return _collect_concat(iter(batches))

    def _profile_capture(self, session):
        """Context manager for the `profile` session property: a
        jax.profiler trace per query under PRESTO_TPU_CACHE_DIR/profiles/
        <query_id>, surfaced as profileUri in the statement response.
        No-op with a warning when the profiler or cache dir is
        unavailable — the query still runs."""
        import contextlib
        import warnings

        qid = getattr(session, "query_id", "") or "adhoc"
        base = os.environ.get("PRESTO_TPU_CACHE_DIR")
        cm = None
        pdir = None
        if not base:
            warnings.warn("profile=true is a no-op: PRESTO_TPU_CACHE_DIR "
                          "is not set", stacklevel=3)
        else:
            try:
                import jax.profiler as _prof

                pdir = os.path.join(base, "profiles", qid)
                os.makedirs(pdir, exist_ok=True)
                cm = _prof.trace(pdir)
            except Exception as e:
                warnings.warn("profile=true is a no-op: jax profiler "
                              f"unavailable ({e})", stacklevel=3)
                cm = None

        @contextlib.contextmanager
        def run():
            if cm is None:
                yield
                return
            try:
                with cm:
                    yield
            finally:
                try:
                    from presto_tpu.obs import devprof as _devprof

                    _devprof.register_profile(qid, pdir)
                except Exception:
                    pass

        return run()

    def run_batch(self, sql: str, config: Optional[ExecConfig] = None,
                  session=None, stmt=None) -> Batch:
        """`stmt` overrides parsing — the bound AST of a prepared
        statement (EXECUTE path; no SQL re-rendering)."""
        cfg = config or self.config
        if getattr(cfg, "profile", False):
            with self._profile_capture(session):
                return self._run_batch_traced(sql, config, session, stmt)
        return self._run_batch_traced(sql, config, session, stmt)

    def _run_batch_traced(self, sql: str,
                          config: Optional[ExecConfig] = None,
                          session=None, stmt=None) -> Batch:
        cfg = config or self.config
        if not getattr(cfg, "tracing", True):
            return self._run_batch_inner(sql, config, session, stmt)
        # trace id = the session query id when there is one, so
        # /v1/query/{id}/trace resolves directly; the root span covers
        # planning + scheduling + result merge (≥95% of query wall)
        tracer = _obs_trace.Tracer(
            trace_id=getattr(session, "query_id", "") or None)
        self.trace_registry.register(tracer)
        with _obs_trace.use(tracer), tracer.span(
                "query", "query", sql=(sql or "")[:200],
                user=getattr(session, "user", None) or "user"):
            return self._run_batch_inner(sql, config, session, stmt)

    def _run_batch_inner(self, sql: str, config: Optional[ExecConfig] = None,
                         session=None, stmt=None) -> Batch:
        import jax.numpy as jnp

        from presto_tpu.batch import Column
        from presto_tpu.exec.runtime import _JIT_COMPACT, _collect_concat
        from presto_tpu.sql import ast as _ast
        from presto_tpu.sql.parser import parse_sql

        if stmt is None:
            # cached distributed plans are never DDL — skip the parse probe
            # (O(1) membership; the parsed stmt is reused by plan_distributed)
            cached = sql in self._cached_sqls
            stmt = None if cached else parse_sql(sql)
        from presto_tpu.exec.runner import is_ddl

        if stmt is not None and is_ddl(stmt):
            try:
                scaled = self._try_scaled_write(stmt, config, session)
                if scaled is not None:
                    return scaled
                # DDL/DML executes coordinator-side; the source query
                # still runs distributed (reference:
                # DataDefinitionExecution on the coordinator + a
                # distributed TableWriter source)
                from presto_tpu.exec.runner import execute_data_definition
                from presto_tpu.plan.builder import plan_query as _pq

                def run_query_fn(q):
                    from presto_tpu.plan.fragmenter import fragment_plan
                    from presto_tpu.plan.optimizer import optimize as _opt

                    qp = _opt(_pq(q, self.catalog), self.catalog)
                    self._enforce_access([qp.root], session)
                    d = fragment_plan(qp, self.catalog,
                                      broadcast_threshold_rows=self.broadcast_threshold_rows)
                    batches = list(self.execute_distributed(d, config))
                    merged = _collect_concat(iter(batches))
                    if merged is None:
                        root = d.fragments[d.root_fid].root
                        types = dict(root.output)
                        merged = Batch(
                            d.output_names,
                            [types[n] for n in d.output_names],
                            [Column(jnp.zeros(128, types[n].dtype), None)
                             for n in d.output_names],
                            jnp.zeros(128, bool), {},
                        )
                    return _JIT_COMPACT(merged)

                return execute_data_definition(stmt, self.catalog,
                                               run_query_fn)
            finally:
                # DDL/DML is the snapshot-token barrier: reclaim every
                # cached result whose token no longer matches (a no-op on
                # an unarmed cache — result_cache=off stays bit-for-bit)
                self._invalidate_result_cache()

        dplan = self.plan_distributed(sql, session, stmt=stmt)
        self._enforce_access(
            (f.root for f in dplan.fragments.values()), session)
        session_qid = getattr(session, "query_id", "") or ""
        lifecycle_on = bool(
            session_qid and _obs_lifecycle.get(session_qid) is not None)

        def _stamp_fingerprint():
            # stamp the structural fingerprint so progress gets its HBO
            # prediction and completion its regression baseline
            if not lifecycle_on:
                return
            try:
                from presto_tpu.obs import runstats as _runstats

                _obs_lifecycle.set_fingerprint(
                    session_qid, _runstats.node_fingerprint(
                        dplan.fragments[dplan.root_fid].root, self.catalog))
            except Exception:
                pass

        # result cache consult: after plan install + authorization,
        # BEFORE fragment scheduling. mode=off touches nothing (no key
        # computation, no arming — the pre-cache path bit-for-bit).
        cfg = config or self.config
        mode = (getattr(cfg, "result_cache", "off") or "off").lower()
        rc_key = rc_token = None
        if mode != "off" and dplan.__dict__.get("_rc_cacheable"):
            from presto_tpu.obs.runstats import catalog_token as _ctok
            from presto_tpu.server import result_cache as _rc_mod

            rc_token = _ctok(self.catalog)
            rc_key = _rc_mod.query_key(
                dplan, self.catalog,
                getattr(session, "catalog", "") or "",
                getattr(session, "schema", "") or "")
            if rc_key is not None:
                hit = self.result_cache.lookup(
                    rc_key, query_id=session_qid or None)
                if hit is not None:
                    # a hit short-circuits scheduling entirely: the
                    # timeline jumps straight to draining with a cache
                    # provenance mark (compile and exec segments resolve
                    # to exactly zero — segments() fills unstamped
                    # boundaries rightward)
                    _stamp_fingerprint()
                    if lifecycle_on:
                        _obs_lifecycle.mark(session_qid, "draining",
                                            provenance="cache")
                    _obs_lifecycle.note_cache(session_qid, {
                        "kind": "query", "key": rc_key[:24],
                        "bytes": _rc_mod.batch_nbytes(hit)})
                    return hit
        _stamp_fingerprint()
        if _farm.enabled(cfg):
            # corpus feed + status attribution: record this statement's
            # plans for future boots/speculation, and stamp whether THIS
            # run lands on a farm-warmed cache (armed/live) or cold (miss)
            try:
                froot = dplan.fragments[dplan.root_fid].root
                _farm.record_sql(
                    sql, [f.root for f in dplan.fragments.values()])
                fstatus = _farm.status_for(froot)
                if session_qid:
                    _obs_lifecycle.note_farm(session_qid, {
                        "status": fstatus})
                if fstatus != "miss":
                    _obs_events.EVENTS.emit(
                        "precompile_hit", query_id=session_qid or None,
                        status=fstatus)
            except Exception:
                pass
        if lifecycle_on:
            # lifecycle plane: plan ready = plan->compile boundary
            _obs_lifecycle.mark(session_qid, "compiling")
        t_exec0 = time.perf_counter()
        merged = None
        if mode == "subplan":
            merged = self._run_with_subplan_reuse(sql, stmt, config, session)
        if merged is None:
            batches = self._execute_with_retry(dplan, config)
            merged = _collect_concat(iter(batches))
        if merged is None:
            root = dplan.fragments[dplan.root_fid].root
            types = dict(root.output)
            merged = Batch(
                dplan.output_names,
                [types[n] for n in dplan.output_names],
                [Column(jnp.zeros(128, types[n].dtype), None)
                 for n in dplan.output_names],
                jnp.zeros(128, bool),
                {},
            )
        out = _JIT_COMPACT(merged)
        if rc_key is not None:
            # cost-aware admission: observed exec wall, floored by the
            # HBO baseline for this structure (a lucky fast run must not
            # undervalue a historically expensive query)
            wall = time.perf_counter() - t_exec0
            try:
                from presto_tpu.obs import runstats as _runstats

                ent = _runstats.lookup_node(
                    dplan.fragments[dplan.root_fid].root, self.catalog,
                    _runstats.QUERY_SITE)
                if ent and ent.get("wall_s"):
                    wall = max(wall, float(ent["wall_s"]))
            except Exception:
                pass
            self.result_cache.admit(rc_key, "query", out, wall_s=wall,
                                    token=rc_token,
                                    query_id=session_qid or None)
        return out

    def close(self):
        self.failure_detector.stop()
        self.query_manager.close()
        if self._http is not None:
            self._http.shutdown()
            self._http.server_close()


class DistributedRunner:
    """In-process cluster: coordinator + N workers over real localhost HTTP
    (DistributedQueryRunner.java:78 analog — multi-node without a cluster).

    Every worker shares the same Catalog object (connectors are
    deterministic; in a real deployment each worker constructs its own from
    catalog properties)."""

    def __init__(self, catalog: Catalog, n_workers: int = 2,
                 config: Optional[ExecConfig] = None,
                 broadcast_threshold_rows: float = 1_000_000,
                 access_control=None, tls=None,
                 coordinator_kwargs: Optional[dict] = None):
        import secrets as _secrets

        from presto_tpu.server.worker import Worker

        self.catalog = catalog
        self.config = config or ExecConfig()
        cluster_secret = _secrets.token_hex(16)
        self.coordinator = Coordinator(
            catalog, config=self.config, min_workers=n_workers,
            broadcast_threshold_rows=broadcast_threshold_rows,
            cluster_secret=cluster_secret,
            access_control=access_control, tls=tls,
            # extra Coordinator knobs (slow_query_log, events_log, ...)
            # without re-plumbing every parameter through the runner
            **(coordinator_kwargs or {}),
        )
        self.workers = [
            Worker(catalog, node_id=f"worker-{i}",
                   coordinator_url=self.coordinator.url,
                   memory_pool_bytes=self.config.memory_pool_bytes,
                   spill_dir=self.config.spill_dir,
                   revoke_threshold=self.config.memory_revoking_threshold,
                   revoke_target=self.config.memory_revoking_target,
                   cluster_secret=cluster_secret, tls=tls)
            for i in range(n_workers)
        ]

    def plan_distributed(self, sql: str) -> DistributedPlan:
        return self.coordinator.plan_distributed(sql)

    def explain_distributed(self, sql: str) -> str:
        return self.coordinator.plan_distributed(sql).to_string()

    def run_batch(self, sql: str) -> Batch:
        return self.coordinator.run_batch(sql)

    def run(self, sql: str):
        return self.run_batch(sql).to_pandas()

    def close(self):
        for w in self.workers:
            w.close()
        self.coordinator.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
