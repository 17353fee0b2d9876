"""Batch-streaming execution of logical plans.

The analog of the reference's worker data plane — LocalExecutionPlanner
(operator factory construction), Driver.processInternal:347 (the page loop)
and the operator implementations (HashAggregationOperator,
HashBuilderOperator/LookupJoinOperator, OrderByOperator, ...) — re-shaped
for XLA:

- every *stateless* chain (Filter/Project) between pipeline breakers is
  collapsed into one traced function, so scan→filter→project→partial-agg is
  ONE XLA program per batch (the fusion Presto gets from
  ScanFilterAndProjectOperator + generated PageProcessors, here done by the
  compiler);
- pipeline breakers (Aggregate, Join build, Sort) accumulate fixed-capacity
  device state and grow it geometrically on overflow (detected via returned
  group counts — the recompile-on-growth discipline replaces rehashing);
- streams are python generators of Batches — the Driver loop, at batch not
  page granularity.
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from functools import partial
from types import SimpleNamespace
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu.batch import (
    Batch,
    Column,
    concat_columns,
    round_up_capacity,
    slice_column,
)
from presto_tpu.connector import Catalog
from presto_tpu.exec import farm as _farm
from presto_tpu.exec import fragment_jit as _fragment_jit
from presto_tpu.exec import programs as _programs
from presto_tpu.expr.compile import compile_expr, compile_predicate
from presto_tpu.obs import trace as _obs_trace
from presto_tpu.expr.ir import Constant, InputRef, substitute_params
from presto_tpu.expr.structural import StructVal
from presto_tpu.ops.grouping import (KeyCol, StateCol, grouped_merge,
                                     partition_skew)
from presto_tpu.ops.join import (
    BuildTable,
    MwSpec,
    align_probe_strings,
    build_side,
    gather_join_output,
    hash_build_side,
    hash_probe_counts,
    hash_probe_expand,
    hash_probe_unique,
    join_compare_dtypes,
    multiway_counts,
    multiway_expand,
    multiway_probe_unique,
    probe_counts,
    probe_expand,
    probe_unique,
    table_stats,
)
from presto_tpu.ops.sort import (
    SortKey,
    compact,
    compact_permutation,
    lex_sort,
    limit_batch,
    permute_batch,
    sort_batch,
    sort_permutation,
)
from presto_tpu.plan.agg_states import (
    agg_state_layout,
    limb_pairs,
    sum_state_type,
)
from presto_tpu.plan.nodes import (
    Aggregate,
    AggSpec,
    Filter,
    HashJoin,
    IndexJoin,
    Limit,
    MultiwayJoin,
    NestedLoopJoin,
    OneRow,
    Output,
    PlanNode,
    Project,
    QueryPlan,
    RemoteSource,
    SemiJoin,
    SetOp,
    Sort,
    TableScan,
    Unnest,
    Window,
)
from presto_tpu.types import BIGINT, DOUBLE, DecimalType, Type


@dataclasses.dataclass
class ExecConfig:
    """Session knobs (reference: SystemSessionProperties / TaskManagerConfig)."""

    batch_rows: int = 1 << 17  # rows per scan batch
    agg_capacity: int = 1 << 12  # initial group-table capacity
    # High-NDV group tables are the wrong tool on XLA: every merge step
    # sorts (capacity + batch) rows, so a CBO-pre-sized multi-million-slot
    # table makes each batch pay a mostly-dead mega-sort (measured: q3 SF1
    # RUN went 68.7s -> small-cap partitioned in seconds on CPU). Above
    # this ceiling the aggregation goes GRACE: raw input hash-partitions to
    # spill (host-side, dynamic shapes are free there) and each partition
    # merges independently at small capacity — the reference's
    # SpillableHashAggregationBuilder / grouped-execution shape.
    agg_cap_ceiling: int = 1 << 17
    # how many aggregate merge steps may be in flight before their group
    # counts are confirmed on the host. A device→host sync costs a full
    # round trip (not measured on the v5e yet), so the driver dispatches
    # optimistically and replays from a held checkpoint on the rare
    # capacity overflow (reference analog: none — the JVM has no dispatch
    # latency; this is TPU-native pipelining)
    agg_pipeline_depth: int = 3
    topn_slack: int = 4
    join_out_capacity: Optional[int] = None  # default: probe batch capacity
    # coalesce sparse join output batches before downstream operators
    # (MergingPageOutput analog; see _merging_output)
    merge_sparse_output: bool = True
    max_growth_retries: int = 24
    # EXPLAIN ANALYZE: per-operator wall/rows/batches accounting (forces a
    # device sync per batch — off in production, like Presto's verbose stats)
    collect_stats: bool = False
    # query-lifecycle span tracing (obs/trace.py): operator, compile and
    # exchange_wait spans, and the per-thread engine phases. Cheap enough
    # to stay on (no per-batch device sync); False makes every span site a
    # single attribute check on the NOOP tracer
    tracing: bool = True
    # memory + spill (reference: MemoryPool / spiller; None = unlimited)
    memory_pool_bytes: Optional[int] = None
    spill_enabled: bool = True
    spill_dir: Optional[str] = None
    spill_partitions: int = 8
    # dynamic hybrid hash spill (spiller.py): how many times a spill
    # partition may split by the next hash bits — mid-build when it blows
    # past its byte budget, or at replay when the partition still doesn't
    # fit the memory budget (recursive repartitioning). A partition that
    # exceeds the budget at max depth fails with SPILL_LIMIT_EXCEEDED
    # (identical keys share every hash bit and can never split).
    spill_max_depth: int = 4
    # spill directory byte budget: a spill write that would push the
    # directory's live footprint past this fails the spilling query with
    # SPILL_LIMIT_EXCEEDED instead of filling the disk. None = unlimited.
    spill_dir_budget_bytes: Optional[int] = None
    memory_revoking_threshold: float = 0.9
    memory_revoking_target: float = 0.5
    # Aria selective scan (scan/ package): constrained scans on connectors
    # with a read_split_selective path filter rows DURING host decode and
    # upload only survivors. Off → decode-everything + device-side filter
    # (the pre-Aria shape; also the oracle for bit-identical-result tests)
    selective_scan: bool = True
    # background split prefetch depth: decode/stage split i+1..i+depth on a
    # host thread while the device computes split i (the IO/compute overlap
    # of the reference's async split loading — PageSourceProvider readers
    # run ahead of the driver). 0 disables.
    scan_prefetch: int = 2
    # query-level elastic retry (the reference's RetryPolicy.QUERY): on a
    # failed/unreachable worker the coordinator re-probes the cluster,
    # drops dead nodes, and re-executes the whole query this many times
    query_retry_count: int = 1
    # stage scheduling policy (reference: execution/scheduler/
    # AllAtOnceExecutionPolicy vs PhasedExecutionSchedule): "phased" defers
    # probe-side stages until their join build stages finish, cutting peak
    # cluster memory on multi-join plans
    execution_policy: str = "all-at-once"
    # recoverable grouped execution (SystemSessionProperties.java:69): a
    # colocated-join fragment schedules one task per lifespan (bucket) in
    # a gated phase; a worker lost mid-phase re-runs only its unfinished
    # buckets on survivors instead of retrying the whole query
    recoverable_grouped_execution: bool = False
    # phased mode: how long one build phase may run before the query fails
    phase_wait_timeout_s: float = 600.0
    # coordinator-side split placement with rendezvous-hash soft affinity
    # (reference: scheduler/NodeScheduler + SimpleNodeSelector and the
    # SOFT_AFFINITY NodeSelectionStrategy): a split lands on the same
    # worker across queries, so the worker's device split cache turns
    # placement stability into real scan locality. Off → static
    # task_index::n_tasks striding.
    split_affinity: bool = True
    # within-worker radix partitioning for pipeline breakers (ops/radix.py):
    # joins and keyed aggregations split both sides by the top bits of the
    # content hash and run each partition's build/probe (or group merge) at
    # a small bounded capacity — the same handful of compiled shapes
    # regardless of input size. Must be a power of two; 0/1 = off (the
    # classic single-table path).
    radix_partitions: int = 0
    # hybrid spill: a radix partition whose build side exceeds this byte
    # budget serializes its batches to host spill (serde page format) and is
    # processed after the in-memory partitions. None = never (partitions
    # stay resident); the reference analog is the dynamic hybrid hash
    # join's per-partition memory budget.
    join_spill_budget_bytes: Optional[int] = None
    # bounded-recompile guard (analysis/recompile.py): fail the query when
    # any single node program compiled more than this many distinct shapes
    # — the "bounded compiled shapes" promise of the radix/bucketing work
    # enforced, not just rendered by EXPLAIN ANALYZE. None = off.
    max_compiled_shapes: Optional[int] = None
    # per-operator-CLASS overrides of the guard: streaming scan-chain
    # nodes emit one padded capacity and should stay near 1-2 shapes,
    # while pipeline breakers legitimately see pow2 growth ladders. None
    # = fall back to max_compiled_shapes.
    max_compiled_shapes_scan: Optional[int] = None
    max_compiled_shapes_breaker: Optional[int] = None
    # donate accumulator buffers on linearly-threaded stepping programs
    # (TopN step, global-aggregate step): the caller never reuses the
    # input accumulator, so XLA may update it in place instead of
    # double-buffering accumulator HBM. Keyed-agg steps are NOT donated —
    # the optimistic dispatch window holds acc_before for overflow replay.
    donate_stepping: bool = True
    # ahead-of-stream precompilation: trace+compile scan-side fused chain
    # programs on this many background threads at plan install, so
    # compilation overlaps host scan decode instead of serializing in
    # front of batch 0. 0 disables.
    precompile_workers: int = 0
    # whole-fragment device residency (exec/fragment_jit.py): stack up to
    # fragment_window consecutive same-structure scan batches and fold the
    # breaker step over the window inside ONE compiled program (lax.scan),
    # collapsing O(batches) per-batch dispatches to O(batches / window).
    # Applies to scan-rooted leaf fragments feeding a decomposable
    # aggregate or a TopN sort; everything else (unnest, host projections,
    # spill replay, grouped execution, radix) keeps the per-batch path.
    # fragment_fusion=False preserves the per-batch path everywhere.
    fragment_fusion: bool = True
    fragment_window: int = 8
    # breaker engine selection (ops/pallas_hash vs the sorted-primitive
    # engine): "auto" lets the CBO (plan/stats.choose_breaker_engine) pick
    # per breaker from derived NDV/row-count/payload-width stats; "sort" /
    # "hash" force one engine everywhere (the hash side of the forcing is
    # what the engine-equivalence verifier sweeps run)
    breaker_engine: str = "auto"
    # multiway join collapse (plan/multiway.py): "auto" lets the CBO
    # (plan/stats.choose_join_mode) fold eligible star-schema join chains
    # into one MultiwayJoin probe program per HBO-corrected build sizes
    # and selectivities; "multiway" forces every eligible chain;
    # "binary" runs the pass but always declines (stamping the verdict
    # in EXPLAIN); "off" skips the pass — the pre-collapse plan
    # bit-for-bit.
    join_mode: str = "auto"
    # history-based optimization (obs/runstats.py): "observe" (default)
    # records estimate-vs-actual drift at every stats-driven decision site
    # keyed on structural fingerprints; "correct" additionally feeds
    # observed values back into engine choice / presize / lane sizing on a
    # repeat of the same structure; "off" is a strict no-op — the pre-HBO
    # engine bit-for-bit (no observation syncs, no history writes).
    hbo: str = "observe"
    # device cost & HBM accounting plane (obs/devprof.py): "on" records
    # XLA cost_analysis/memory_analysis per compiled program, samples
    # device.memory_stats() watermarks, and reconciles them against the
    # MemoryPool ledger; "off" (default) is a strict no-op — no extra
    # lowering, no sampler thread, today's engine bit-for-bit.
    devprof: str = "off"
    # on-demand jax.profiler capture for this query's execution, dumped
    # under PRESTO_TPU_CACHE_DIR (no-op with a warning when the profiler
    # or the cache dir is unavailable)
    profile: bool = False
    # serving-plane SLO telemetry (obs/lifecycle.py): "on" makes worker
    # task sinks count emitted rows/batches so heartbeats carry live
    # query progress; "off" is a strict no-op — pre-lifecycle task path
    # and heartbeat doc bit-for-bit.
    lifecycle: str = "on"
    # semantic result cache (server/result_cache.py): "query" memoizes
    # final results keyed on (structural plan sha, catalog snapshot token,
    # session catalog.schema); "subplan" additionally materializes and
    # reuses breaker-subplan results; "off" (default) is a strict no-op —
    # no cache consult, no metric families, no events, today's engine
    # bit-for-bit.
    result_cache: str = "off"
    # pow2 shape bucketing (exec/farm.py subsystem): "pow2" pads
    # merging-output flushes and partial jit windows up to their
    # power-of-two bucket (capped at the stream's target capacity), so the
    # distinct-aval set reaching _node_jit collapses to one shape per
    # stream instead of a per-flush ladder — fewer avals, fewer compiles,
    # charged once per bucket against the recompile budgets. "off"
    # (default) is a strict no-op — today's flush/window shapes
    # bit-for-bit. Padding only adds dead lanes (live=False), which every
    # kernel already masks, so results are identical either way.
    shape_bucketing: str = "off"
    # ahead-of-traffic compile farm (exec/farm.py): "on" records every
    # installed plan into the persistent farm corpus under
    # PRESTO_TPU_CACHE_DIR and lets server planes boot-arm the program
    # cache / speculatively precompile during queue wait; "off" (default)
    # is a strict no-op — no corpus writes, no claims, no metric families.
    compile_farm: str = "off"
    # mid-flight telemetry plane (obs/inflight.py): "on" makes drivers
    # publish operator watermarks (windows dispatched, rows in/out, spill
    # depth/repartitions, replay caps, lane util) into the per-query
    # inflight store at wave/window boundaries — host-held counts only,
    # never a fresh device sync; "off" (default) is a strict no-op — no
    # publishes, no watcher, no metric families, today's engine
    # bit-for-bit.
    inflight: str = "off"
    # stall detector bound: row watermarks frozen for this many seconds
    # while the query executes → stall_detected event + forensics dump
    stall_threshold_s: float = 2.0
    # straggler detector bound: a fragment site > factor x behind its
    # siblings' window watermark → straggler_detected event + slow-log doc
    straggler_factor: float = 4.0
    # in-run adaptation (exec/adaptive.py): "off" (default) is a strict
    # no-op — pre-adaptive engine bit-for-bit; "observe" evaluates every
    # decision point and logs what it WOULD do (events, EXPLAIN, doctor)
    # without acting; "on" acts — engine flips between replay waves,
    # forward-propagating presize/lane sizing, device-radix partition
    # growth, largest-partition-first partial revocation. Cache-volatile:
    # a flipped engine forks program keys via the existing @h suffix, so
    # the knob itself never changes what any one program computes.
    adaptive: str = "off"


def _node_jit(node: PlanNode, key: str, builder, _shared=True, **jit_kwargs):
    """Node-facing jit memoization, delegating to the process-wide
    structural program cache (exec/programs.py — the analog of Presto's
    codegen class cache: ExpressionCompiler's generated classes are keyed
    by expression structure and reused across every execution of the same
    plan shape). Nodes stamped by ``programs.install_plan`` share one
    compiled program per (structural namespace, key, jit kwargs) across
    plans, fragments, concurrent tasks and queries; unstamped nodes (and
    ``_shared=False`` call sites, whose builders close over runtime data
    such as a materialized build table) keep a private entry.

    Compile events (count + wall, detected via jit cache-size growth) are
    claimed under the entry's lock — exact under concurrency — and mirrored
    into node._jit_stats[key] for EXPLAIN ANALYZE and the recompile guard."""
    cache = node.__dict__.setdefault("_jit_cache", {})
    fn = cache.get(key)
    if fn is None:
        stats = node.__dict__.setdefault("_jit_stats", {}).setdefault(
            key, {"compiles": 0, "compile_wall_s": 0.0})
        ns = node.__dict__.get("_program_ns") if _shared else None
        entry = _programs.entry_for(
            ns, type(node).__name__, key, jit_kwargs,
            lambda: jax.jit(builder(), **jit_kwargs))
        fn = cache[key] = _programs.wrap(entry, stats,
                                         type(node).__name__, key)
    return fn


class ExecContext:
    def __init__(self, catalog: Catalog, config: ExecConfig,
                 memory_pool=None, spill_manager=None):
        from presto_tpu.memory import MemoryPool
        from presto_tpu.spiller import SpillManager

        self.catalog = catalog
        self.config = config
        self.stats: Dict[str, float] = {}
        # per-plan-node OperatorStats analog (keyed by id(node)):
        # {"rows": ..., "batches": ..., "wall_s": ..., "bytes": ...}
        self.node_stats: Dict[int, Dict[str, float]] = {}
        # span tracer (obs/trace.py). NOOP unless a server plane (worker
        # task / coordinator run) or the LocalRunner installs a real one —
        # config.tracing only matters where a tracer gets installed
        self.tracer = _obs_trace.NOOP
        # distributed task context (set by the worker; None for LocalRunner):
        # this task reads splits[task_index::n_tasks] of every scanned table
        # (SOURCE_DISTRIBUTION split placement, statically assigned)
        self.task_index: int = 0
        self.n_tasks: int = 1
        # coordinator-assigned split ordinals per table (soft-affinity
        # placement — scheduler/NodeScheduler analog). None → static
        # task_index::n_tasks striding; ordinals index the connector's
        # deterministic unpruned split enumeration; split_counts carries
        # the coordinator's enumeration size per table (mismatch at scan
        # time = the table changed underneath the plan → loud failure)
        self.split_assignment: Optional[Dict[str, List[int]]] = None
        self.split_counts: Optional[Dict[str, int]] = None
        # grouped (lifespan) execution: when set, scans of bucketed tables
        # read ONLY this bucket's splits (Lifespan.java:26-38 — the driver
        # group id); the colocated-join executor sweeps it over the task's
        # assigned buckets
        self.lifespan: Optional[int] = None
        # total lifespans of the active grouped-execution sweep (None when
        # not sweeping): lets operators size per-bucket state (a bucket
        # holds ~1/lifespans of the groups) and run memory-tight
        self.lifespans: Optional[int] = None
        # fragment_id -> callable returning an iterator of Batches pulled
        # from the exchange (the ExchangeOperator's client)
        self.remote_sources = None
        # memory + spill: worker-shared when provided, else per-context
        # (QueryContext → MemoryPool; SpillSpaceTracker)
        self.memory_pool = memory_pool or MemoryPool(
            config.memory_pool_bytes,
            revoke_threshold=config.memory_revoking_threshold,
            revoke_target=config.memory_revoking_target,
        )
        self.spill_manager = spill_manager or SpillManager(config.spill_dir)
        if (config.spill_dir_budget_bytes is not None
                and self.spill_manager.budget_bytes is None):
            self.spill_manager.budget_bytes = config.spill_dir_budget_bytes
        # every spiller/spill-file an operator opens registers here so task
        # teardown can close+unlink them even when the operator generator
        # died mid-spill (failed or canceled query) — close() is idempotent
        self.spill_resources: List = []
        # mid-flight telemetry publisher (obs/inflight.TaskInflight) —
        # installed by the worker task when the `inflight` session
        # property is on; None = every publish hook is a no-op
        self.inflight = None
        # in-run adaptation controller (exec/adaptive.AdaptiveState) —
        # None when the `adaptive` session property is off, which keeps
        # every decision site a single attribute check (strict no-op)
        self.adaptive = None
        if getattr(config, "adaptive", "off") != "off":
            from presto_tpu.exec.adaptive import AdaptiveState

            self.adaptive = AdaptiveState(config.adaptive)

    def track_spill(self, resource) -> None:
        self.spill_resources.append(resource)

    def cleanup_spill(self) -> None:
        """Leak guard: close (and unlink) every spill resource this context
        ever opened. Safe to call repeatedly and after normal closes."""
        for r in self.spill_resources:
            try:
                r.close()
            except Exception:
                pass
        self.spill_resources = []

    def should_spill(self, projected_delta_bytes: int) -> bool:
        """Would adding this reservation cross the revoke threshold?"""
        pool = self.memory_pool
        if pool.limit is None or not self.config.spill_enabled:
            return False
        return (pool.reserved + projected_delta_bytes
                > pool.limit * pool.revoke_threshold)

    def record(self, node, rows: int, wall_s: float, bytes_: int = 0):
        s = self.node_stats.setdefault(
            id(node), {"rows": 0, "batches": 0, "wall_s": 0.0, "bytes": 0}
        )
        s["rows"] += rows
        s["batches"] += 1
        s["wall_s"] += wall_s
        s["bytes"] += bytes_


# ---------------------------------------------------------------------------
# stateless chain fusion


def collapse_chain(node: PlanNode) -> Tuple[PlanNode, Callable[[Batch], Batch]]:
    """Peel Filter/Project off `node` until a breaker; return (base, fn)
    where fn applies the whole chain at trace time (so it fuses into
    whatever jit program calls it). Memoized per node so repeated
    executions of a cached plan reuse the same function objects (and hence
    every jit trace)."""
    memo = node.__dict__.get("_collapsed")
    if memo is not None:
        return memo
    steps: List[Callable[[Batch], Batch]] = []
    cur = node
    while True:
        if isinstance(cur, Filter):
            pred = compile_predicate(cur.predicate)

            def step(b: Batch, pred=pred) -> Batch:
                return b.with_live(b.live & pred(b))

            steps.append(step)
            cur = cur.child
        elif isinstance(cur, Project):
            compiled = [(s, e.type, compile_expr(e), e) for s, e in cur.exprs]

            def step(b: Batch, compiled=compiled) -> Batch:
                names, types, cols = [], [], []
                dicts = {}
                for s, t, fn, e in compiled:
                    if isinstance(e, InputRef):
                        # identity projection: reuse the column object —
                        # cheaper, and preserves long-decimal limbs that a
                        # re-evaluation through the expression compiler
                        # would truncate to int64
                        names.append(s)
                        types.append(t)
                        cols.append(b.column(e.name))
                        if e.name in b.dicts:
                            dicts[s] = b.dicts[e.name]
                        if e.name + "#keys" in b.dicts:
                            dicts[s + "#keys"] = b.dicts[e.name + "#keys"]
                        continue
                    v, valid = fn(b)
                    if isinstance(v, StructVal):
                        # structural (ARRAY/MAP) expression result
                        names.append(s)
                        types.append(t)
                        cols.append(Column(v.values, valid, sizes=v.sizes,
                                           evalid=v.evalid, keys=v.keys))
                        ed, kd = fn.sdicts(b)
                        if ed is not None:
                            dicts[s] = ed
                        if kd is not None:
                            dicts[s + "#keys"] = kd
                        continue
                    v = jnp.broadcast_to(v, (b.capacity,)).astype(t.dtype)
                    if valid is not None and getattr(valid, "ndim", 1) == 0:
                        # scalar validity (e.g. divide-by-constant guard)
                        # must widen with the values: downstream gathers
                        # index it per row
                        valid = jnp.broadcast_to(valid, (b.capacity,))
                    names.append(s)
                    types.append(t)
                    cols.append(Column(v, valid))
                    # identity projections keep their dictionary; computed
                    # string expressions carry their synthesized one
                    if isinstance(e, InputRef) and e.name in b.dicts:
                        dicts[s] = b.dicts[e.name]
                    elif getattr(fn, "out_dict", None) is not None:
                        dicts[s] = fn.out_dict
                    elif getattr(fn, "dyn_dict", None) is not None:
                        d = fn.dyn_dict(b)
                        if d is not None:
                            dicts[s] = d
                return Batch(names, types, cols, b.live, dicts)

            steps.append(step)
            cur = cur.child
        else:
            break

    if not steps:
        result = (cur, None)
    else:
        steps.reverse()

        @jax.named_scope("scan_chain")
        def chain(b: Batch) -> Batch:
            for s in steps:
                b = s(b)
            return b

        result = (cur, chain)
    node.__dict__["_collapsed"] = result
    return result


# ---------------------------------------------------------------------------
# node executors


def execute_node(node: PlanNode, ctx: ExecContext) -> Iterator[Batch]:
    """Execute a plan node to a stream of batches. Any Filter/Project chain
    sitting on top of a breaker is applied per output batch (jitted once);
    breakers fuse the chain *below* them into their own stepping programs
    via _fused_child."""
    base, down = collapse_chain(node)
    stream = _operator_stream(base, ctx)
    if down is not None:
        jfn = _node_jit(node, "down", lambda: down)
        # the chain needs a real batch: a join's pending output is gathered
        # at the probe's capacity first, and counted after the chain
        stream = (jfn(_gathered(b)) for b in stream)
    yield from _merged(stream, base, ctx)


def _operator_stream(base: PlanNode, ctx: ExecContext) -> Iterator:
    """A chain's base as a stream, under the statistics and the span that
    are switched on. A join with a unique build hands on
    `_PendingJoinOutput`s where it would a `Batch`: `_merging_output`
    gathers them once their live count is read, anything else takes
    `_gathered(b)`."""
    stream = _execute_base(base, ctx)
    if ctx.config.collect_stats:
        stream = _instrumented(stream, base, ctx)
    if ctx.tracer.enabled:
        stream = _traced(stream, base, ctx)
    return stream


def _merged(stream: Iterator, base: PlanNode, ctx: ExecContext) -> Iterator:
    """Selective operators emit batches at probe CAPACITY whose live
    occupancy can be ~1%; every downstream per-batch cost (sorts, merges,
    probes) is capacity-shaped, so coalesce before fanning out (reference:
    operator/project/MergingPageOutput.java). Any other base's stream is
    handed on as it is."""
    if not isinstance(base, (HashJoin, MultiwayJoin, SemiJoin,
                             NestedLoopJoin, IndexJoin)):
        return stream
    if not ctx.config.merge_sparse_output:
        return map(_gathered, stream)  # nobody reads a count
    return _merging_output(stream, ctx.config.batch_rows,
                           bucket=ctx.config.shape_bucketing != "off",
                           tracer=ctx.tracer)


def _pad_batch(b: Batch, cap: int) -> Batch:
    """Pad rows with dead lanes up to cap (keeps capacities power-of-two
    so downstream per-shape jit caches stay bounded)."""
    extra = cap - b.capacity
    if extra <= 0:
        return b

    def padp(p, fill=0):
        if p is None:
            return None
        widths = [(0, extra)] + [(0, 0)] * (p.ndim - 1)
        return jnp.pad(p, widths, constant_values=fill)

    cols = [
        Column(padp(c.values),
               padp(c.validity, False),
               padp(c.hi), padp(c.sizes), padp(c.evalid, False),
               padp(c.keys))
        for c in b.columns
    ]
    return Batch(b.names, b.types, cols, padp(b.live, False), b.dicts)


def _merging_output(stream: Iterator, target_cap: int,
                    bucket: bool = False,
                    tracer=_obs_trace.NOOP) -> Iterator[Batch]:
    """MergingPageOutput analog: bring each sparse batch down to the
    power-of-two bucket of its live count `n` (live rows to the front,
    `round_up_capacity(n)` lanes) and concatenate until a full batch
    accumulates. Dense batches (`2 n >= capacity`) pass through untouched;
    empty batches are dropped. Costs one host sync per input batch (its
    live count: the phase `host_sync:join_output_rows`) — repaid many times
    over by the capacity-shaped work it removes downstream on selective
    multi-join plans — and the count it pays for sizes the work: a sparse
    `Batch` is compacted by a program that gathers `round_up_capacity(n)`
    lanes of every plane, not the batch's capacity, and a
    `_PendingJoinOutput` (a unique probe's matches, nothing gathered yet)
    is gathered at that size, dense or not at all (`emit(n)`); what it
    hands back compacted is neither compacted nor counted again. Every
    batch so materialised is one `join_emit` occurrence, `items` its lanes.

    ``bucket`` (shape_bucketing=pow2) additionally pads every flush —
    including the single-batch passthrough — up to the stream's pow2
    target capacity, so downstream programs see ONE flush shape instead
    of a per-flush pow2 ladder; padding adds only dead lanes (live=False),
    which every kernel masks, so results are unchanged."""
    pending: List[Batch] = []
    pending_live = 0
    bucket_cap = round_up_capacity(max(int(target_cap), 1)) if bucket else 0

    def flush():
        nonlocal pending, pending_live
        if len(pending) == 1:
            out = pending[0]
        else:
            out = _collect_concat(iter(pending))
            # concat of mixed pow2 slices is no longer pow2 itself —
            # re-bucket so downstream programs see a bounded shape set
            out = _pad_batch(out, round_up_capacity(out.capacity))
        if bucket:
            out = _pad_batch(
                out, max(round_up_capacity(out.capacity), bucket_cap))
        pending, pending_live = [], 0
        return out

    def consume(b, n):
        nonlocal pending_live
        if n == 0:
            return None
        compacted = False
        if isinstance(b, _PendingJoinOutput):
            b, compacted = b.emit(n)
        if not compacted:
            if 2 * n >= b.capacity:
                return b  # dense: pass through (flushing pending first)
            out_cap = round_up_capacity(n)
            with _emit_phase(tracer, min(out_cap, b.capacity)):
                b = _JIT_COMPACT(b, out_cap=out_cap)
        pending.append(b)
        pending_live += n
        return None

    # one-batch lookahead: the live count is dispatched and fetched
    # asynchronously while the NEXT batch computes, so dense streams don't
    # pay a blocking device→host sync per batch (same optimistic pattern
    # as the aggregate's dispatch window)
    window: List[Tuple[Batch, "jnp.ndarray"]] = []

    def drain(block_all: bool):
        while window and (block_all or len(window) > 1):
            b, cnt = window.pop(0)
            with tracer.phase("host_sync:join_output_rows"):
                n = int(cnt)
            dense = consume(b, n)
            if dense is not None:
                if pending:
                    yield flush()
                yield dense
            elif pending_live >= target_cap:
                yield flush()

    for b in stream:
        # a pending join output brings its count from the probe's program
        cnt = (b.count if isinstance(b, _PendingJoinOutput)
               else jnp.sum(b.live))
        try:
            cnt.copy_to_host_async()
        except Exception:
            pass
        window.append((b, cnt))
        yield from drain(block_all=False)
    yield from drain(block_all=True)
    if pending:
        yield flush()


def _emit_phase(tracer, lanes: int):
    """One `join_emit` occurrence: a selective operator's output batch
    materialised at `lanes` lanes (a pending join output gathered, a sparse
    batch compacted). `items` and the process counter `join_emit_lanes`
    carry the lanes: over the rows out they say how often the output was
    sized by what matched."""
    from presto_tpu.scan import metrics as _scan_metrics

    _scan_metrics.record("join_emit_lanes", lanes)
    return tracer.phase("join_emit", items=lanes)


def _outer_phase(tracer, lanes: int) -> None:
    """One `join_outer` occurrence, no time of its own: a batch a LEFT or
    FULL join hands on gathered whole, at `lanes` lanes, because every
    probe row stays (a single-match probe's emit, the general path's
    NULL-extended rows)."""
    with tracer.phase("join_outer", items=lanes):
        pass


def _expand_phases(tracer, rows: int, lanes: int, overflow: int,
                   sized: int = 0) -> None:
    """What one probe batch on the join's general path expanded to, from
    numbers the host has already read: `join_expand` (`items` = the batch's
    `total`, its output rows), `join_expand_lanes` (`items` = the lanes its
    chunks gathered, rows or not), where chunk 0 was sized below `out_cap`
    by the previous batch's total, `join_expand_sized` (`items` = that
    chunk's lanes) and, where a probe row's candidates passed the counting
    scan, `join_fanout_overflow` (`items` = those rows). One occurrence
    each, no time of their own; the process counter `join_expand_rows`
    carries the rows and, with tracing on, `join_expand_sized` the sized
    chunk's lanes."""
    from presto_tpu.scan import metrics as _scan_metrics

    _scan_metrics.record("join_expand_rows", rows)
    named = [("join_expand", rows), ("join_expand_lanes", lanes)]
    if sized and tracer.enabled:
        _scan_metrics.record("join_expand_sized", sized)
        named.append(("join_expand_sized", sized))
    if overflow:
        named.append(("join_fanout_overflow", overflow))
    for name, items in named:
        with tracer.phase(name, items=items):
            pass


def _instrumented(stream: Iterator[Batch], node: PlanNode, ctx: ExecContext):
    """OperatorStats collection (reference: OperationTimer stamping every
    addInput/getOutput into OperatorStats, Driver.java:277)."""
    import time as _time

    from presto_tpu.memory import batch_device_bytes

    while True:
        t0 = _time.perf_counter()
        try:
            b = _gathered(next(stream))
        except StopIteration:
            return
        rows = int(jnp.sum(b.live))  # forces device sync
        ctx.record(node, rows, _time.perf_counter() - t0,
                   bytes_=batch_device_bytes(b))
        yield b


def _traced(stream: Iterator[Batch], node: PlanNode, ctx: ExecContext):
    """Span wrapper: one aggregate `operator` span per plan node (total
    span = first pull to exhaustion; busy_s = time actually spent inside
    next()), plus a kernel-wall histogram observation per batch. No device
    syncs — this stays on in production, unlike _instrumented."""
    import time as _time

    from presto_tpu.obs import metrics as _obs_metrics

    tracer = ctx.tracer
    parent = tracer.current_parent()
    start = _time.time()
    busy = 0.0
    batches = 0
    try:
        while True:
            t0 = _time.perf_counter()
            try:
                b = next(stream)
            except StopIteration:
                return
            dt = _time.perf_counter() - t0
            busy += dt
            batches += 1
            _obs_metrics.BATCH_KERNEL_WALL.observe(dt, plane="worker")
            yield b
    finally:
        tracer.record(type(node).__name__, "operator", start, _time.time(),
                      parent_id=parent, busy_s=round(busy, 6),
                      batches=batches)


def _fused_child(node: PlanNode, ctx: ExecContext):
    """(raw input stream, chain-to-apply-inside-your-jit) for a breaker's
    child — the ScanFilterAndProject fusion point."""
    base, up = collapse_chain(node)
    # breakers pull children through here, not execute_node — apply the
    # same sparse-output coalescing before the consumer's chain
    stream = _merged(_operator_stream(base, ctx), base, ctx)
    return stream, (up or (lambda b: b))


def _execute_base(base: PlanNode, ctx: ExecContext) -> Iterator[Batch]:
    if isinstance(base, TableScan):
        yield from _scan_batches(base, ctx)
        return
    if isinstance(base, Aggregate):
        yield from _execute_aggregate(base, ctx)
        return
    if isinstance(base, HashJoin):
        yield from _execute_join(base, ctx)
        return
    if isinstance(base, MultiwayJoin):
        yield from _execute_multiway_join(base, ctx)
        return
    if isinstance(base, IndexJoin):
        yield from _execute_index_join(base, ctx)
        return
    if isinstance(base, NestedLoopJoin):
        yield from _execute_nljoin(base, ctx)
        return
    if isinstance(base, SemiJoin):
        yield from _execute_semijoin(base, ctx)
        return
    if isinstance(base, SetOp):
        yield from _execute_setop(base, ctx)
        return
    if isinstance(base, Unnest):
        yield from _execute_unnest(base, ctx)
        return
    if isinstance(base, OneRow):
        cap = 128
        live = np.zeros(cap, bool)
        live[0] = True
        yield Batch([], [], [], jnp.asarray(live), {})
        return
    from presto_tpu.plan.nodes import HostProject as _HP

    if isinstance(base, _HP):
        yield from _execute_host_project(base, ctx)
        return
    from presto_tpu.plan.nodes import TableWriter as _TW

    if isinstance(base, _TW):
        # scaled writer: this task writes its stream as one part and
        # emits its row count (TableWriterOperator analog)
        conn = ctx.catalog.connectors[base.catalog]
        batches = list(execute_node(base.child, ctx))
        n = conn.write_part(base.table,
                            f"{base.write_id}-{ctx.task_index:04d}",
                            batches) if batches else 0
        vals = np.zeros(128, np.int64)
        vals[0] = n
        live = np.zeros(128, bool)
        live[0] = True
        yield Batch(["rows"], [BIGINT],
                    [Column(jnp.asarray(vals), None)],
                    jnp.asarray(live), {})
        return
    if isinstance(base, Sort):
        yield from _execute_sort(base, ctx)
        return
    if isinstance(base, Window):
        yield from _execute_window(base, ctx)
        return
    if isinstance(base, Limit):
        remaining = base.count
        jlimit = _JIT_LIMIT  # `n` traced: one compile per shape
        for b in execute_node(base.child, ctx):
            out = jlimit(b, remaining)
            n = out.num_live()
            remaining -= n
            yield out
            if remaining <= 0:
                return
        return
    if isinstance(base, Output):
        # project to the user-facing schema (worker-side in distributed
        # plans; run_plan applies the same projection for local plans)
        for b in execute_node(base.child, ctx):
            yield b.select(base.symbols).rename(base.names)
        return
    if isinstance(base, RemoteSource):
        if ctx.remote_sources is None:
            raise RuntimeError("RemoteSource outside a distributed task")
        yield from ctx.remote_sources(base.fragment_id)
        return
    raise NotImplementedError(f"no executor for {type(base).__name__}")


# -- scan -------------------------------------------------------------------


def _scan_batches(scan: TableScan, ctx: ExecContext) -> Iterator[Batch]:
    conn = ctx.catalog.connectors[scan.catalog]
    handle = conn.get_table(scan.table)
    nrows = int(handle.row_count or 0)
    nsplits = max(1, -(-nrows // ctx.config.batch_rows))
    columns = list(scan.assignments.values())
    symbols = list(scan.assignments.keys())
    if not columns:
        # COUNT(*)-style scan with no referenced columns: fabricate liveness.
        # In a distributed task each task accounts its slice of the rows.
        per = nrows // ctx.n_tasks + (1 if ctx.task_index < nrows % ctx.n_tasks else 0)
        cap = round_up_capacity(min(per, ctx.config.batch_rows) or 1)
        done = 0
        while done < per or (done == 0 and ctx.task_index == 0):
            take = min(cap, per - done)
            live = np.zeros(cap, bool)
            live[:take] = True
            yield Batch([], [], [], jnp.asarray(live), {})
            done += take
            if done >= per:
                return
        return
    cap = round_up_capacity(min(nrows, ctx.config.batch_rows) or 1)
    splits = conn.splits(handle, nsplits)
    read_split = conn.read_split
    assigned = (ctx.split_assignment or {}).get(scan.table)
    if ctx.lifespan is not None and any(
            s.bucket is not None for s in splits):
        # grouped execution: this pass reads one bucket only; bucket→task
        # assignment already happened in the lifespan sweep
        splits = [s for s in splits if s.bucket == ctx.lifespan]
    elif assigned is not None:
        # coordinator soft-affinity placement: ordinals index the
        # UNPRUNED enumeration (both sides enumerate deterministically).
        # A count mismatch means the table changed between planning and
        # scan — silently proceeding would drop (or double-read) splits
        expected = (ctx.split_counts or {}).get(scan.table)
        if expected is not None and expected != len(splits):
            raise RuntimeError(
                f"split enumeration for {scan.table} changed underneath "
                f"the plan (coordinator saw {expected}, scan sees "
                f"{len(splits)}) — retry the query")
        splits = [splits[i] for i in assigned if i < len(splits)]
    elif ctx.n_tasks > 1:
        splits = splits[ctx.task_index::ctx.n_tasks]
    if scan.constraints and hasattr(conn, "prune_splits"):
        storage_bounds = _constraints_to_storage(scan, handle)
        if storage_bounds:
            from presto_tpu.scan import metrics as _scan_metrics

            before = len(splits)
            splits = conn.prune_splits(handle, splits, storage_bounds)
            ctx.stats[f"scan.{scan.table}.splits_pruned"] = before - len(splits)
            _scan_metrics.record("splits_pruned", before - len(splits))
    if scan.constraints and hasattr(conn, "read_split_constrained"):
        # full predicate pushdown: the connector evaluates the range
        # constraints at the source (remote service / SQL WHERE) instead
        # of just pruning splits (TupleDomain → getRows semantics)
        bounds = _constraints_to_storage(scan, handle)
        if bounds:
            def read_split(split, columns, capacity=None,
                           _b=bounds):  # noqa: E306
                return conn.read_split_constrained(
                    split, columns, capacity=capacity, constraints=_b)
    if (scan.constraints and ctx.config.selective_scan
            and hasattr(conn, "read_split_selective")):
        # Aria selective scan: compile the constraints into host value
        # filters (scan/filters.py) and read each split through the
        # predicate-during-decode path — filter columns decode first, the
        # cascade shrinks a selection vector in adaptive order, payload
        # columns decode/upload only for survivors. The exact device
        # filter above the scan still runs (host filters are conservative
        # supersets), so results never depend on this layer.
        from presto_tpu.scan import metrics as _scan_metrics
        from presto_tpu.scan.adaptive import AdaptiveFilterOrder
        from presto_tpu.scan.filters import filters_from_constraints

        filters = filters_from_constraints(scan.constraints, handle)
        if filters:
            adaptive = AdaptiveFilterOrder()
            _prefix = f"scan.{scan.table}"

            def _count(name, delta, _p=_prefix):
                key = f"{_p}.{name}"
                ctx.stats[key] = ctx.stats.get(key, 0) + delta
                _scan_metrics.record(name, delta)

            def read_split(split, columns, capacity=None,  # noqa: E306
                           _f=filters, _a=adaptive):
                return conn.read_split_selective(
                    split, columns, _f, capacity=capacity, adaptive=_a,
                    counters=_count)
    tracer = ctx.tracer
    depth = ctx.config.scan_prefetch
    if depth <= 0 or len(splits) <= 1:
        for split in splits:
            with tracer.phase("scan_read"):
                b = read_split(split, columns, capacity=cap)
            yield b.rename(symbols)
        return
    # pipelined scan: a host thread decodes/stages splits ahead of the
    # device (bounded queue so memory stays O(depth) batches)
    import queue as _queue
    import threading as _threading

    q: "_queue.Queue" = _queue.Queue(maxsize=depth)
    _SENTINEL = object()
    stop = _threading.Event()

    def producer():
        try:
            # the producer thread carries the query's tracer so span sites
            # below the connector (selective cascade) keep recording
            with _obs_trace.use(tracer):
                for split in splits:
                    if stop.is_set():
                        break
                    with tracer.phase("scan_read"):
                        b = read_split(split, columns, capacity=cap)
                    with tracer.phase("scan_queue_full", wait=True):
                        q.put(b)
            q.put(_SENTINEL)
        except BaseException as e:  # surface read errors on the consumer
            q.put(e)

    t = _threading.Thread(target=producer, daemon=True,
                          name="scan-prefetch")
    t.start()
    try:
        while True:
            with tracer.phase("scan_wait", wait=True):
                item = q.get()
            if item is _SENTINEL:
                return
            if isinstance(item, BaseException):
                raise item
            yield item.rename(symbols)
    finally:
        # early termination (LIMIT / error): stop the producer after its
        # current read and unblock any pending put
        stop.set()
        while t.is_alive():
            try:
                item = q.get(timeout=0.1)
                if item is _SENTINEL or isinstance(item, BaseException):
                    break
            except _queue.Empty:
                continue


def _constraints_to_storage(scan: TableScan, handle):
    """Engine-level (lo, hi) bounds → the connector's storage value domain
    (dates become datetime.date for parquet date32 statistics)."""
    import datetime

    col_types = {c.name: c.type for c in handle.columns}
    out = {}
    for col, (lo, hi) in scan.constraints.items():
        t = col_types.get(col)
        if t is None:
            continue
        if t.name == "date":
            conv = lambda d: None if d is None else datetime.date.fromordinal(719163 + int(d))
            out[col] = (conv(lo), conv(hi))
        else:
            out[col] = (lo, hi)
    return out


# -- unnest -----------------------------------------------------------------


def unnest_expand(node: Unnest, b: Batch) -> Batch:
    """Traceable core of UNNEST (shared by the streaming executor and the
    mesh executor). TPU-native redesign of operator/unnest/
    UnnestOperator.java: instead of walking per-position offsets, output
    row (i, j) of the static [cap, W] element plane is live iff
    j < max(sizes_src[i]); everything is broadcast + reshape, no dynamic
    shapes (output capacity = cap * W, W = widest source plane)."""
    cap = b.capacity
    srcs = [b.column(s) for s in node.sources]
    w = max([c.values.shape[1] for c in srcs] + [1])

    counts = None
    for c in srcs:
        sz = c.sizes
        if c.validity is not None:
            sz = jnp.where(c.validity, sz, 0)
        counts = sz if counts is None else jnp.maximum(counts, sz)
    counts = jnp.where(b.live, counts, 0)
    j = jnp.arange(w, dtype=jnp.int32)[None, :]
    out_live = (j < counts[:, None]).reshape(-1)

    def flat_plane(plane, width, fill):
        """[cap, width] → [cap*w] padding columns beyond width."""
        if width == w:
            return plane.reshape(-1)
        if width == 0:
            return jnp.full(cap * w, fill, plane.dtype)
        pad = jnp.full((cap, w - width), fill, plane.dtype)
        return jnp.concatenate([plane, pad], axis=1).reshape(-1)

    names, types, cols = [], [], []
    dicts = {}
    child_types = dict(node.child.output)
    for s in node.replicate:
        c = b.column(s)
        cols.append(Column(
            jnp.repeat(c.values, w, axis=0),
            None if c.validity is None else jnp.repeat(c.validity, w),
            None if c.hi is None else jnp.repeat(c.hi, w),
            None if c.sizes is None else jnp.repeat(c.sizes, w),
            None if c.evalid is None else jnp.repeat(c.evalid, w, axis=0),
            None if c.keys is None else jnp.repeat(c.keys, w, axis=0),
        ))
        names.append(s)
        types.append(child_types[s])
        if s in b.dicts:
            dicts[s] = b.dicts[s]
        if s + "#keys" in b.dicts:
            dicts[s + "#keys"] = b.dicts[s + "#keys"]
    for src, c, syms, etypes in zip(node.sources, srcs, node.out_syms,
                                    node.out_types):
        cw = c.values.shape[1]
        present = (jnp.arange(cw, dtype=jnp.int32)[None, :]
                   < c.sizes[:, None]) if cw else jnp.zeros((cap, 0), bool)
        evalid = present if c.evalid is None else (present & c.evalid)
        ev_flat = flat_plane(evalid, cw, False)
        if len(syms) == 2:  # map → (key, value)
            cols.append(Column(flat_plane(c.keys, cw, 0),
                               flat_plane(present, cw, False)))
            names.append(syms[0])
            types.append(etypes[0])
            if src + "#keys" in b.dicts:
                dicts[syms[0]] = b.dicts[src + "#keys"]
            cols.append(Column(flat_plane(c.values, cw, 0), ev_flat))
            names.append(syms[1])
            types.append(etypes[1])
            if src in b.dicts:
                dicts[syms[1]] = b.dicts[src]
        else:
            cols.append(Column(flat_plane(c.values, cw, 0), ev_flat))
            names.append(syms[0])
            types.append(etypes[0])
            if src in b.dicts:
                dicts[syms[0]] = b.dicts[src]
    if node.ordinality_sym:
        ordv = jnp.broadcast_to(
            (j + 1).astype(jnp.int64), (cap, w)).reshape(-1)
        cols.append(Column(ordv, None))
        names.append(node.ordinality_sym)
        types.append(BIGINT)
    return Batch(names, types, cols, out_live, dicts)


def _execute_unnest(node: Unnest, ctx: ExecContext) -> Iterator[Batch]:
    in_stream, chain = _fused_child(node.child, ctx)

    def expand(b: Batch) -> Batch:
        return unnest_expand(node, chain(b))

    jfn = _node_jit(node, "expand", lambda: expand)
    for b in in_stream:
        yield jfn(b)


# -- aggregation ------------------------------------------------------------

_VARIANCE_FNS = {"var_samp", "var_pop", "stddev_samp", "stddev_pop"}
_COVAR_FNS = {"covar_pop", "covar_samp", "corr"}
_NON_DECOMPOSABLE_FNS = {"approx_percentile", "__approx_percentile_w",
                         "max_by", "min_by", "array_agg", "map_agg",
                         "numeric_histogram", "tdigest_agg", "merge",
                         "approx_set",
                         "count_distinct", "sum_distinct", "avg_distinct"}

_CHECKSUM_NULL = jnp.int64(-7046029254386353131)  # fixed NULL contribution


def _as_double(c: Column, t: Type):
    """Column values as float64, unscaling decimals (limb-combined for
    long decimals)."""
    v = c.combined_f64() if c.hi is not None else c.values.astype(jnp.float64)
    if isinstance(t, DecimalType):
        v = v / (10.0 ** t.scale)
    return v


def _content_hash(c: Column, t: Type, dictionary) -> jnp.ndarray:
    """Order-independent per-row content hash for checksum()
    (reference: ChecksumAggregationFunction — XXHash64 of the block value).
    Strings hash by dictionary VALUE (content), not code."""
    if dictionary is not None:
        from presto_tpu.spiller import _strhash_lut

        v = jnp.asarray(_strhash_lut(dictionary))[c.values.astype(jnp.int32) + 1]
    elif jnp.issubdtype(c.values.dtype, jnp.floating):
        v = jax.lax.bitcast_convert_type(
            c.values.astype(jnp.float64), jnp.int64
        )
    else:
        v = c.values.astype(jnp.int64)
    h = v * jnp.int64(-7070675565921424023)  # golden-ratio mix
    h = h ^ (h >> 31)
    if c.validity is not None:
        h = jnp.where(c.validity, h, _CHECKSUM_NULL)
    return h


def _input_state(b: Batch, name: str, op: str, a: AggSpec, st: Type,
                 in_types: Dict[str, Type]) -> StateCol:
    """Raw input column(s) → one state column for grouped_merge
    (the accumulator `addInput` step of the reference's per-fn states:
    VarianceState tracks count/mean/m2; we track count/sum/sumsq etc.)."""
    suffix = name[len(a.symbol):] if name.startswith(a.symbol) else ""
    if op == "count_add":
        if a.fn == "count_if":
            c = b.column(a.arg)
            vals = c.values.astype(jnp.int64)
            if c.validity is not None:
                vals = jnp.where(c.validity, vals, 0)
            return StateCol(vals, None, "count_add")
        if a.fn in _COVAR_FNS:
            both = b.column(a.arg).valid_mask() & b.column(a.arg2).valid_mask()
            return StateCol(both.astype(jnp.int64), None, "count_add")
        if a.fn == "count_star" or a.arg is None:
            return StateCol(b.live.astype(jnp.int64), None, "count_add")
        c = b.column(a.arg)
        vals = (c.validity.astype(jnp.int64) if c.validity is not None
                else jnp.ones(b.capacity, jnp.int64))
        return StateCol(vals, None, "count_add")
    if suffix in ("$hi", "$sum_hi", "$lo", "$sum_lo"):
        # int128 decimal sum limbs (UnscaledDecimal128Arithmetic analog):
        # value = hi * 2^32 + lo, lo canonical in [0, 2^32). Short-decimal
        # input splits arithmetically; long-decimal input is already limbed.
        c = b.column(a.arg)
        if suffix.endswith("hi"):
            vals = c.hi if c.hi is not None else (c.values >> 32)
        else:
            vals = c.values if c.hi is not None else (c.values & 0xFFFFFFFF)
        return StateCol(vals.astype(jnp.int64), c.validity, "sum")
    if a.fn == "checksum":
        c = b.column(a.arg)
        return StateCol(_content_hash(c, in_types[a.arg], b.dicts.get(a.arg)),
                        None, "sum")
    if a.fn in ("bool_and", "bool_or"):
        c = b.column(a.arg)
        return StateCol(c.values.astype(jnp.int8), c.validity, op)
    if a.fn in _VARIANCE_FNS:
        c = b.column(a.arg)
        x = _as_double(c, in_types[a.arg])
        return StateCol(x * x if suffix == "$sumsq" else x, c.validity, "sum")
    if a.fn in _COVAR_FNS:
        cx, cy = b.column(a.arg), b.column(a.arg2)
        x = _as_double(cx, in_types[a.arg])
        y = _as_double(cy, in_types[a.arg2])
        both = cx.valid_mask() & cy.valid_mask()
        val = {"$sx": x, "$sy": y, "$sxy": x * y,
               "$sxx": x * x, "$syy": y * y}[suffix]
        return StateCol(val, both, "sum")
    if a.fn == "geometric_mean":
        c = b.column(a.arg)
        x = _as_double(c, in_types[a.arg])
        return StateCol(jnp.log(x), c.validity, "sum")
    from presto_tpu.functions import registry as _freg

    udf = _freg().aggregate(a.fn)
    if udf is not None:
        # registered UDAF: per-state elementwise input transform over the
        # float64 argument (the addInput step of its accumulator);
        # count_add states took the generic branch at the top
        c = b.column(a.arg)
        x = _as_double(c, in_types[a.arg])
        transform = next(t for s, o, t in udf.states
                         if a.symbol + s == name)
        return StateCol(transform(x) if transform is not None else x,
                        c.validity, op)
    c = b.column(a.arg)
    if c.hi is not None:
        # long-decimal input to min/max/arbitrary: combined float64 value,
        # scaled to the SQL value (matches the DOUBLE output type and the
        # implicit decimal→double casts in comparisons)
        return StateCol(_as_double(c, in_types[a.arg]), c.validity, op)
    return StateCol(c.values.astype(st.dtype), c.validity, op)


def _renorm_limbs(sout: list, pairs) -> list:
    """Carry-propagate int128 limb states after a merge: keep lo canonical
    in [0, 2^32) so limb sums never overflow int64 regardless of row count."""
    for ih, il in pairs:
        hi_s, lo_s = sout[ih], sout[il]
        carry = lo_s.values >> 32
        sout[il] = StateCol(lo_s.values - (carry << 32), lo_s.validity, lo_s.op)
        sout[ih] = StateCol(hi_s.values + carry, hi_s.validity, hi_s.op)
    return sout


def _minmax_ident(dtype, want_min: bool):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.inf if want_min else -jnp.inf, dtype)
    info = jnp.iinfo(dtype)
    return jnp.array(info.max if want_min else info.min, dtype)


def _sorted_group_agg(b: Batch, key_syms, a: AggSpec, cap: int):
    """Per-group order-dependent aggregate over materialized input:
    approx_percentile (exact per-group quantile), max_by / min_by.
    Sorts by (deadness, group keys, order value) — the group enumeration
    (stable sort on the same key operands) matches grouped_merge's, so the
    returned arrays align with its group table rows."""
    n = b.capacity
    dead = (~b.live).astype(jnp.int32)
    operands = [dead]
    for k in key_syms:
        c = b.column(k)
        if c.validity is not None:
            operands.append((~c.validity).astype(jnp.int32))
            operands.append(jnp.where(c.validity, c.values, jnp.zeros_like(c.values)))
        else:
            operands.append(c.values)
    num_key_ops = len(operands)

    cx = b.column(a.arg)
    if a.fn in ("approx_percentile", "__approx_percentile_w",
                "count_distinct", "sum_distinct", "avg_distinct"):
        ov = cx.valid_mask()
        sortval = jnp.where(ov, cx.values, _minmax_ident(cx.values.dtype, True))
    elif a.fn == "max_by":
        cy = b.column(a.arg2)
        ov = cy.valid_mask()
        # NULL-ordering rows first so the LAST row is the max valid
        sortval = jnp.where(ov, cy.values, _minmax_ident(cy.values.dtype, True))
    else:  # min_by
        cy = b.column(a.arg2)
        ov = cy.valid_mask()
        # NULLs last so the FIRST row is the min valid
        sortval = jnp.where(ov, cy.values, _minmax_ident(cy.values.dtype, False))
    operands.append(sortval)

    sorted_ops, sperm = lex_sort(operands)
    sdead = sorted_ops[0]
    change = jnp.zeros(n, dtype=bool).at[0].set(True)
    for sk in sorted_ops[:num_key_ops]:
        change = change.at[1:].set(change[1:] | (sk[1:] != sk[:-1]))
    seg = jnp.cumsum(change.astype(jnp.int32)) - 1
    seg = jnp.where(sdead == 1, cap, seg)
    idx = jnp.arange(n, dtype=jnp.int32)
    start = jnp.full(cap, n, jnp.int32).at[seg].min(idx, mode="drop")
    cnt = jax.ops.segment_sum(jnp.ones(n, jnp.int32), seg, num_segments=cap + 1)[:cap]
    ov_sorted = ov[sperm]
    cntv = jax.ops.segment_sum(ov_sorted.astype(jnp.int32), seg,
                               num_segments=cap + 1)[:cap]
    valid = cntv > 0

    if a.fn in ("count_distinct", "sum_distinct", "avg_distinct"):
        # DISTINCT accumulators (MarkDistinct analog): after the
        # (keys, value) sort, the first row of each equal-value run inside
        # a segment carries the value; everything else contributes zero
        sv = cx.values[sperm]
        ov_sorted2 = ov[sperm] & (sdead == 0)
        prev_same = jnp.zeros(n, bool).at[1:].set(
            (sv[1:] == sv[:-1]) & ~change[1:])
        first_distinct = ov_sorted2 & ~prev_same
        dcount = jax.ops.segment_sum(
            first_distinct.astype(jnp.int64), seg,
            num_segments=cap + 1)[:cap]
        if a.fn == "count_distinct":
            return dcount, None
        acc_dtype = (sv.dtype if jnp.issubdtype(sv.dtype, jnp.floating)
                     else jnp.int64)
        contrib = jnp.where(first_distinct, sv.astype(acc_dtype),
                            jnp.zeros((), acc_dtype))
        dsum = jax.ops.segment_sum(contrib, seg, num_segments=cap + 1)[:cap]
        if a.fn == "sum_distinct":
            return dsum, dcount > 0
        scale = (b.type_of(a.arg).scale
                 if isinstance(b.type_of(a.arg), DecimalType) else 0)
        avg = (dsum.astype(jnp.float64) / (10.0 ** scale)
               / jnp.maximum(dcount, 1).astype(jnp.float64))
        return avg, dcount > 0
    if a.fn == "__approx_percentile_w":
        # weighted-rank selection over sketch bucket rows: the value is the
        # bucket minimum whose cumulative count first reaches ceil(p·total)
        # (the final qdigest.valueAt step of the approx_percentile
        # lowering — inputs here are ≤ occupied-bucket rows, not raw data)
        from presto_tpu.ops.grouping import _segmented_scan

        p = float(a.param)  # lint: allow(host-sync)
        wcol = b.column(a.arg2)
        wsorted = wcol.values.astype(jnp.int64)[sperm]
        wsorted = jnp.where(ov_sorted & (sdead == 0), wsorted, 0)
        cum = _segmented_scan(wsorted, change, "sum")
        totals = jax.ops.segment_sum(wsorted, seg, num_segments=cap + 1)[:cap]
        thresh = jnp.clip(jnp.ceil(p * totals).astype(jnp.int64), 1, None)
        row_thresh = jnp.concatenate([thresh, jnp.zeros(1, jnp.int64)])[
            jnp.clip(seg, 0, cap)]
        candidate = (cum >= row_thresh) & (wsorted > 0)
        idxs = jnp.arange(n, dtype=jnp.int32)
        pick = jnp.full(cap, n, jnp.int32).at[seg].min(
            jnp.where(candidate, idxs, n), mode="drop")
        rows = sperm[jnp.clip(pick, 0, n - 1)]
        vals = cx.values[rows]
        return vals, totals > 0
    if a.fn == "approx_percentile":
        # exact quantile: index ceil(p*n_valid)-1 of the sorted valid values
        # (NULLs sort first, valid range is [start+cnt-cntv, start+cnt))
        p = float(a.param)  # lint: allow(host-sync)
        k = jnp.clip(jnp.ceil(p * cntv).astype(jnp.int32) - 1, 0, jnp.maximum(cntv - 1, 0))
        pos = start + (cnt - cntv) + k
        pos = jnp.clip(pos, 0, n - 1)
        rows = sperm[pos]
        vals = cx.values[rows]
        if cx.validity is not None:
            valid = valid & cx.validity[rows]
        return vals, valid
    if a.fn == "max_by":
        pos = jnp.clip(start + cnt - 1, 0, n - 1)
    else:
        pos = jnp.clip(start, 0, n - 1)
    rows = sperm[pos]
    vals = cx.values[rows]
    if cx.validity is not None:
        valid = valid & cx.validity[rows]
    return vals, valid


def _execute_materialized_aggregate(node: Aggregate, ctx: ExecContext) -> Iterator[Batch]:
    """Aggregates with order-dependent, non-mergeable state
    (approx_percentile / max_by / min_by): materialize the input and compute
    per-group over one global sort. The fragmenter gathers such aggregations
    to a single task (reference computes these via mergeable digest states;
    exact computation satisfies the same contract)."""
    from presto_tpu.plan.agg_states import (
        agg_state_layout as _asl,
        state_types as _sts,
    )

    in_stream, chain = _fused_child(node.child, ctx)
    in_types = dict(node.child.output)
    key_syms = node.group_keys
    key_types = [in_types[k] for k in key_syms]
    decomp = [a for a in node.aggs if a.fn not in _NON_DECOMPOSABLE_FNS]
    _HOST_AGGS = ("array_agg", "map_agg", "numeric_histogram",
                  "tdigest_agg", "merge", "approx_set")
    ndec = [a for a in node.aggs
            if a.fn in _NON_DECOMPOSABLE_FNS and a.fn not in _HOST_AGGS]
    arr_aggs = [a for a in node.aggs if a.fn in _HOST_AGGS]
    layout = _asl(decomp, in_types)
    state_types = _sts(layout, in_types)
    jchain = _node_jit(node, "mat_chain", lambda: chain)
    full = _collect_concat(jchain(b) for b in in_stream)
    if full is None:
        yield _finalize_aggregate(node, None, layout, key_syms, key_types,
                                  state_types, in_types)
        return

    def compute(full: Batch) -> Batch:
        cap = full.capacity  # groups ≤ live rows; trace-time constant
        keys = [KeyCol(full.column(k).values, full.column(k).validity)
                for k in key_syms]
        states = [
            _input_state(full, name, op, a, st, in_types)
            for (name, op, a), st in zip(layout, state_types)
        ]
        kout, sout, out_live, _ = grouped_merge(keys, states, full.live, cap)
        sout = _renorm_limbs(list(sout), limb_pairs(layout))
        cols = [Column(k.values, k.validity) for k in kout] + [
            Column(s.values, s.validity if s.op != "count_add" else None)
            for s in sout
        ]
        names = list(key_syms) + [nm for nm, _, _ in layout]
        types = key_types + state_types
        dicts = {k: full.dicts[k] for k in key_syms if k in full.dicts}
        for nm, op, a in layout:
            if op in ("min", "max") and a.arg in full.dicts:
                dicts[nm] = full.dicts[a.arg]
        acc = Batch(names, types, cols, out_live, dicts)
        for a in ndec:
            vals, valid = _sorted_group_agg(full, key_syms, a, cap)
            acc = acc.with_column(
                a.symbol, a.type, Column(vals.astype(a.type.dtype), valid),
                dictionary=full.dicts.get(a.arg),
            )
        return acc

    acc = _node_jit(node, "mat_compute", lambda: compute)(full)
    if arr_aggs:
        acc = _attach_array_aggs(acc, full, arr_aggs, key_syms)
    yield _finalize_aggregate(node, acc, layout, key_syms, key_types,
                              state_types, in_types)


def _attach_numeric_histogram(acc: Batch, full: Batch, a, row_gi,
                              live) -> Batch:
    """numeric_histogram(buckets, x) → map<double,double> per group
    (reference: NumericHistogramAggregation over aggregation/NumericHistogram
    — streaming nearest-centroid merging). Materialized form: per group,
    start from the distinct (value, count) pairs and merge the CLOSEST
    adjacent pair (weighted mean, summed count) until ≤ buckets remain —
    the same fixed-size centroid invariant, computed over the gathered
    input."""
    b = int(a.param)
    c = full.column(a.arg)
    vals = np.asarray(c.values)[live].astype(np.float64)
    valid = np.asarray(c.valid_mask())[live]
    cap = acc.capacity
    per_group: Dict[int, list] = {}
    for r in np.nonzero(valid)[0]:
        per_group.setdefault(int(row_gi[r]), []).append(vals[r])

    hists = {}
    w = 1
    for gi, xs in per_group.items():
        u, cnt = np.unique(np.asarray(xs), return_counts=True)
        u = u.astype(np.float64)
        cnt = cnt.astype(np.float64)
        while len(u) > b:
            gaps = np.diff(u)
            i = int(np.argmin(gaps))
            tot = cnt[i] + cnt[i + 1]
            merged = (u[i] * cnt[i] + u[i + 1] * cnt[i + 1]) / tot
            u = np.concatenate([u[:i], [merged], u[i + 2:]])
            cnt = np.concatenate([cnt[:i], [tot], cnt[i + 2:]])
        hists[gi] = (u, cnt)
        w = max(w, len(u))

    keys2d = np.zeros((cap, w), np.float64)
    plane = np.zeros((cap, w), np.float64)
    sizes = np.zeros(cap, np.int32)
    # a group whose inputs were all NULL yields SQL NULL, not an empty
    # map (NumericHistogramAggregation's no-input-accumulated contract)
    validity = np.zeros(cap, bool)
    for gi, (u, cnt) in hists.items():
        keys2d[gi, :len(u)] = u
        plane[gi, :len(u)] = cnt
        sizes[gi] = len(u)
        validity[gi] = True
    return acc.with_column(
        a.symbol, a.type,
        Column(jnp.asarray(plane), jnp.asarray(validity),
               sizes=jnp.asarray(sizes),
               evalid=None,
               keys=jnp.asarray(keys2d)))


def _host_format_value(kind: str, param, t, v) -> str:
    """One distinct value → its text (HostProject formatting kernels).
    varchar_cast mirrors the reference's cast-to-varchar renderings;
    date_format uses the MySQL format vocabulary."""
    import datetime as _d

    if kind == "date_format":
        from presto_tpu.expr.compile import mysql_format_to_strptime

        fmt = mysql_format_to_strptime(str(param))
        if t.name == "date":
            dt = _d.datetime(1970, 1, 1) + _d.timedelta(days=int(v))
        else:
            dt = _d.datetime(1970, 1, 1) + _d.timedelta(microseconds=int(v))
        return dt.strftime(fmt)
    # varchar_cast
    if t.name == "boolean":
        return "true" if v else "false"
    if t.name == "date":
        return str(_d.date(1970, 1, 1) + _d.timedelta(days=int(v)))
    if t.name in ("timestamp", "time"):
        if t.name == "time":
            dt = _d.datetime(1970, 1, 1) + _d.timedelta(microseconds=int(v))
            out = dt.strftime("%H:%M:%S.%f")[:-3]
        else:
            dt = _d.datetime(1970, 1, 1) + _d.timedelta(microseconds=int(v))
            out = dt.strftime("%Y-%m-%d %H:%M:%S.%f")[:-3]
        return out
    if isinstance(t, DecimalType):
        import decimal as _dec

        return str(_dec.Decimal(int(v)).scaleb(-t.scale))
    if t.name == "real":
        # numpy's shortest float32 repr — float(v) would widen to float64
        # and print garbage mantissa digits ('1.100000023841858')
        return str(np.float32(v))
    if t.name == "double":
        return str(float(v))
    return str(int(v))


def _execute_host_project(node, ctx: ExecContext) -> Iterator[Batch]:
    """HostProject: string-producing scalars (cast-to-varchar,
    date_format) evaluated on the host at the root, once per DISTINCT
    input value per batch, re-encoded as a fresh dictionary column
    (plan/nodes.HostProject)."""
    from presto_tpu.dictionary import Dictionary
    from presto_tpu.types import VARCHAR as _VC

    in_types = dict(node.child.output)
    for b in execute_node(node.child, ctx):
        for sym, kind, in_sym, param in node.items:
            t = in_types[in_sym]
            c = b.column(in_sym)
            vals = np.asarray(c.values)
            if c.hi is not None:
                # long decimal: exact int128 from the two limbs
                his = np.asarray(c.hi)
                vals = np.array(
                    [(int(h) << 32) + int(lo) for h, lo in zip(his, vals)],
                    dtype=object)
            live = np.asarray(b.live)
            valid = np.asarray(c.valid_mask()) & live
            # format once per distinct value; dead/null lanes format a 0
            # placeholder that the validity mask hides
            safe = np.where(valid, vals, np.zeros((), dtype=vals.dtype)
                            if vals.dtype != object else 0)
            uniq, inv = np.unique(safe, return_inverse=True)
            strs = np.asarray(
                [_host_format_value(kind, param, t, u) for u in uniq],
                dtype=object)
            d, ucodes = Dictionary.encode(strs)
            row_codes = ucodes[inv].astype(np.int32)
            row_codes = np.where(valid, row_codes, -1)
            b = b.with_column(
                sym, _VC,
                Column(jnp.asarray(row_codes), jnp.asarray(valid)),
                dictionary=d)
        yield b


def _attach_sketch(acc: Batch, full: Batch, a, row_gi, live, valid,
                   group_fn) -> Batch:
    """Shared scaffolding for sketch-valued host aggregates (tdigest,
    HyperLogLog): gather valid row indices per group, compute ONE
    serialized entry per group (`group_fn(rows) -> entry | None`; None =
    SQL NULL), and attach the result as a fresh dictionary column."""
    from presto_tpu.dictionary import Dictionary

    cap = acc.capacity
    per_group: Dict[int, list] = {}
    for r in np.nonzero(valid)[0]:
        per_group.setdefault(int(row_gi[r]), []).append(int(r))
    out_entries = np.full(cap, "", dtype=object)
    validity = np.zeros(cap, bool)
    for gi, rows in per_group.items():
        e = group_fn(rows)
        if e is not None:
            out_entries[gi] = e
            validity[gi] = True
    d, codes = Dictionary.encode(out_entries)
    return acc.with_column(
        a.symbol, a.type,
        Column(jnp.asarray(codes.astype(np.int32)), jnp.asarray(validity)),
        dictionary=d)


def _attach_tdigest(acc: Batch, full: Batch, a, row_gi, live) -> Batch:
    """tdigest_agg(x[, w][, compression]) / merge(tdigest) → one digest
    entry per group (expr/tdigest.py). Runs at the gathered single task
    like the other host aggregates (reference:
    TDigestAggregationFunction / MergeTDigestAggregation)."""
    from presto_tpu.expr import tdigest as _td

    c = full.column(a.arg)
    valid = np.asarray(c.valid_mask())[live]
    if a.fn == "merge":
        entries = full.dicts[a.arg].decode(np.asarray(c.values)[live])

        def group_fn(rows):
            return _td.merge([entries[r] for r in rows
                              if entries[r] is not None])
    else:
        vals = np.asarray(c.values)[live].astype(np.float64)
        if a.arg2 is not None:
            wc = full.column(a.arg2)
            wvals = np.asarray(wc.values)[live].astype(np.float64)
            valid = valid & np.asarray(wc.valid_mask())[live]
        else:
            wvals = None
        compression = float(a.param) if a.param else _td.DEFAULT_COMPRESSION

        def group_fn(rows):
            return _td.build(vals[rows],
                             None if wvals is None else wvals[rows],
                             compression)
    return _attach_sketch(acc, full, a, row_gi, live, valid, group_fn)


def _attach_hll(acc: Batch, full: Batch, a, row_gi, live) -> Batch:
    """approx_set(x) / merge(hyperloglog) → one sketch entry per group
    (expr/hll.py). The hash pipeline matches the approx_distinct device
    lowering exactly (content hash for strings, canonical bit pattern
    for doubles), so cardinality(approx_set(x)) == approx_distinct(x).
    Reference: ApproximateSetAggregation / MergeHyperLogLogAggregation."""
    from presto_tpu.expr import hll as _hll

    c = full.column(a.arg)
    valid = np.asarray(c.valid_mask())[live]
    if a.fn == "merge":
        entries = full.dicts[a.arg].decode(np.asarray(c.values)[live])

        def group_fn(rows):
            return _hll.merge([entries[r] for r in rows
                               if entries[r] is not None])
    else:
        vals = np.asarray(c.values)[live]
        hashes = None
        if a.arg in full.dicts:
            lut = np.asarray(full.dicts[a.arg].content_hash_lut())
            hashes = lut[vals.astype(np.int64) + 1]
        reg, rank = _hll.regs_and_ranks(vals, hashes)

        def group_fn(rows):
            return _hll.build(reg[rows], rank[rows])
    return _attach_sketch(acc, full, a, row_gi, live, valid, group_fn)


def _attach_array_aggs(acc: Batch, full: Batch, aggs, key_syms) -> Batch:
    """array_agg: per-group element lists built host-side over the
    materialized input (reference: ArrayAggregationFunction's grouped
    block builders — inherently variable-width output, so it runs at the
    single gathered task and materializes padded [groups, W] planes).
    Element order is input order; NULL elements are kept."""
    live = np.asarray(full.live)
    kvals = [np.asarray(full.column(k).values)[live] for k in key_syms]
    kvalid = [np.asarray(full.column(k).valid_mask())[live] for k in key_syms]
    acc_live = np.asarray(acc.live)
    gkeys = [np.asarray(acc.column(k).values) for k in key_syms]
    gvalid = [np.asarray(acc.column(k).valid_mask()) for k in key_syms]
    _NAN = object()  # canonical NaN key: NaN != NaN would miss the dict,
    # but grouped_merge puts all NaNs in one group — match that here

    def _ckey(v, ok):
        if not ok:
            return None
        x = v.item()
        return _NAN if isinstance(x, float) and x != x else x

    gmap = {}
    for gi in np.nonzero(acc_live)[0]:
        key = tuple(
            _ckey(gv[gi], gva[gi]) for gv, gva in zip(gkeys, gvalid)
        )
        gmap[key] = int(gi)
    cap = acc.capacity
    nrows = int(live.sum())
    row_gi = np.empty(nrows, np.int64)
    for r in range(nrows):
        key = tuple(
            _ckey(kv[r], kva[r]) for kv, kva in zip(kvals, kvalid)
        )
        row_gi[r] = gmap[key]
    for a in aggs:
        if a.fn == "numeric_histogram":
            acc = _attach_numeric_histogram(acc, full, a, row_gi, live)
            continue
        if a.fn == "approx_set" or (
                a.fn == "merge"
                and full.type_of(a.arg).name == "hyperloglog"):
            acc = _attach_hll(acc, full, a, row_gi, live)
            continue
        if a.fn in ("tdigest_agg", "merge"):
            acc = _attach_tdigest(acc, full, a, row_gi, live)
            continue
        is_map = a.fn == "map_agg"
        c = full.column(a.arg)
        vals = np.asarray(c.values)[live]
        valid = np.asarray(c.valid_mask())[live]
        if is_map:
            # map_agg(k, v): k drives placement (first occurrence of each
            # key per group wins, like MapAggregation's first-write), v is
            # the stored element
            vc = full.column(a.arg2)
            mvals = np.asarray(vc.values)[live]
            mvalid = np.asarray(vc.valid_mask())[live]
        sizes = np.zeros(cap, np.int32)
        np.add.at(sizes, row_gi, 1)
        w = max(int(sizes.max()) if cap else 0, 1)
        plane = np.zeros(
            (cap, w), dtype=(mvals.dtype if is_map else vals.dtype))
        kplane = np.zeros((cap, w), dtype=vals.dtype) if is_map else None
        evalid = np.zeros((cap, w), bool)
        slot = np.zeros(cap, np.int32)
        seen: dict = {}
        for r in range(nrows):
            gi = row_gi[r]
            if is_map:
                if not valid[r]:
                    continue  # NULL keys are dropped
                kk = (gi, vals[r].item())
                if kk in seen:
                    continue
                seen[kk] = True
                j = slot[gi]
                kplane[gi, j] = vals[r]
                plane[gi, j] = mvals[r]
                evalid[gi, j] = mvalid[r]
            else:
                j = slot[gi]
                plane[gi, j] = vals[r]
                evalid[gi, j] = valid[r]
            slot[gi] = j + 1
        if is_map:
            sizes = slot  # deduped per-group entry counts
        acc = acc.with_column(
            a.symbol, a.type,
            Column(jnp.asarray(plane), None,
                   sizes=jnp.asarray(sizes),
                   evalid=jnp.asarray(evalid),
                   keys=None if kplane is None else jnp.asarray(kplane)),
            dictionary=(full.dicts.get(a.arg2) if is_map
                        else full.dicts.get(a.arg)),
        )
        if is_map and a.arg in full.dicts:
            acc.dicts[a.symbol + "#keys"] = full.dicts[a.arg]
    return acc


def _registered_aggregate_fn(fn: str):
    from presto_tpu.functions import registry

    return registry().aggregate(fn)


class _GraceOverflow(Exception):
    """Raised when group-table growth crosses the grace ceiling: the
    aggregation switches to hash-partitioned (grace) mode. Carries the
    optimistic window's unmerged raw input batches."""

    def __init__(self, entries):
        super().__init__("aggregate group table crossed the grace ceiling")
        self.entries = entries


class _EngineFlip(Exception):
    """Raised from an overflow replay when the adaptive layer flips the
    breaker engine instead of replaying the loser wider. Only raised when
    the replay checkpoint is EMPTY (the whole aggregation restarts from
    batch 0), so no accumulator state needs converting between engine
    layouts. Carries the unmerged raw input batches, the wave's observed
    group count, and the engine to restart under."""

    def __init__(self, batches, groups, engine):
        super().__init__("adaptive breaker engine flip")
        self.batches = batches
        self.groups = groups
        self.engine = engine


# Fan-out of one adaptive device-side radix partition growth step: the
# budget-blowing partition re-splits by the next two hash bits
# (ops/radix.radix_child_perm), mirroring the host spiller's
# grow_partition recursion — one level deep, then hybrid spill.
_RADIX_GROW_FANOUT = 4


def _adaptive_site(node: PlanNode, ctx: "ExecContext") -> str:
    """Site fingerprint for adaptive_action events: the HBO structural
    fingerprint when derivable (so in-run actions and cross-run history
    key the same way), else a node-typed fallback."""
    try:
        from presto_tpu.obs import runstats as _runstats

        fp = _runstats.node_fingerprint(node, ctx.catalog)
        if fp:
            return fp
    except Exception:
        pass
    return f"{type(node).__name__}:{id(node)}"


def _adaptive_flip_verdict(node: PlanNode, ctx: "ExecContext", engine: str,
                           ngi: int, rows_seen: int) -> Optional[str]:
    """Between replay waves: re-choose the breaker engine from the wave's
    OBSERVED group count / duplication instead of the estimates the first
    choice trusted. Returns the engine to restart under when the adaptive
    layer should act, else None. Flip-at-most-once-per-site: the first
    overflow wave's verdict pins the site for the rest of the query — no
    oscillation, and the pin also covers observe-mode so one run logs one
    would-flip decision per site."""
    if ctx.adaptive is None or node.__dict__.get("_adaptive_engine_pinned"):
        return None
    node.__dict__["_adaptive_engine_pinned"] = True
    if getattr(ctx.config, "breaker_engine", "auto") != "auto":
        return None  # session override forced the engine — nothing to flip
    from presto_tpu.plan.stats import choose_breaker_engine_observed

    try:
        want, why = choose_breaker_engine_observed(
            node, float(ngi), float(rows_seen) if rows_seen else None)
    except Exception:
        return None
    if want == engine:
        return None
    acted = ctx.adaptive.decide(
        "engine_flip", node=node, site=_adaptive_site(node, ctx),
        before=engine, after=want, detail=f"flip {engine}->{want}",
        groups=int(ngi), rows=int(rows_seen or 0), why=why)
    if not acted:
        return None
    # the CONVERGED verdict is what EXPLAIN shows and HBO records — the
    # initial guess lives on only inside the why-string provenance
    node.__dict__["_breaker_engine"] = want
    node.__dict__["_breaker_engine_why"] = f"{why} (adaptive: flipped)"
    node.__dict__["_adaptive_engine_flipped"] = True
    ctx.stats["breaker.engine_flips"] = (
        ctx.stats.get("breaker.engine_flips", 0) + 1)
    return want


def _adaptive_presize_grow(node: PlanNode, ctx: "ExecContext", ngi: int,
                           cap: int, limit: Optional[int]) -> Optional[int]:
    """Forward-propagating presize: a completed window CONFIRMED ``ngi``
    groups within 1/8 of the table capacity, so the next window is odds-on
    to overflow and replay. Grow the table now — the next merge step
    migrates the accumulator to the bigger capacity with zero replay (the
    pow2 ladder step is the same compile the overflow would have paid,
    minus the re-merged batches). ``limit`` bounds growth at the grace
    ceiling when spill is live; per-capacity damping keeps observe mode
    at one logged decision per proposed size."""
    if ctx.adaptive is None or ngi * 8 < cap * 7:
        return None
    want = cap * 2
    if limit is not None and want > limit:
        return None
    if node.__dict__.get("_adaptive_presize_seen", 0) >= cap:
        return None
    node.__dict__["_adaptive_presize_seen"] = cap
    acted = ctx.adaptive.decide(
        "presize_grow", node=node, site=_adaptive_site(node, ctx),
        before=int(cap), after=int(want), detail=f"presize {cap}->{want}",
        groups=int(ngi))
    return want if acted else None


def _grouped_execution_lifespans(node: Aggregate) -> int:
    """GroupedExecutionTagger (reference PlanFragmenter.java:914): when every
    group key traces — through streaming Filter/Project identity refs — down
    to a colocated bucketed join whose preserved-side join keys the group
    keys cover, every group's rows live inside ONE bucket (bucket =
    content-hash of those keys), so the WHOLE agg-over-join pipeline can run
    lifespan-by-lifespan: build one bucket, probe it, aggregate it, finalize
    and RELEASE it. Returns the bucket count, or 0 when not applicable."""
    from presto_tpu.expr.ir import InputRef

    keys = set(node.group_keys)
    if not keys:
        return 0
    n = node.child
    while True:
        if isinstance(n, Filter):
            n = n.child
        elif isinstance(n, Project):
            m = dict(n.exprs)
            mapped = set()
            for k in keys:
                e = m.get(k)
                if not isinstance(e, InputRef):
                    return 0  # computed key — can't trace to a bucket column
                mapped.add(e.name)
            keys = mapped
            n = n.child
        elif isinstance(n, HashJoin) and n.colocated:
            # NULL-extended rows of an outer join carry NULL keys on the
            # non-preserved side and would scatter one NULL group across
            # buckets — only the preserved side's keys qualify (RIGHT is
            # canonicalized to left-with-swapped-sides at plan time, so
            # kind here is only ever inner/left/full)
            if set(n.left_keys) <= keys and n.kind in ("inner", "left"):
                return n.colocated
            if set(n.right_keys) <= keys and n.kind == "inner":
                return n.colocated
            return 0
        else:
            return 0


def _breaker_engine_choice(node: PlanNode, ctx: "ExecContext",
                           record: bool = True) -> str:
    """Resolve the breaker engine ("sort" | "hash") for a pipeline
    breaker: session override (ExecConfig.breaker_engine) first, else the
    CBO's NDV/row-count/payload-width thresholds
    (plan/stats.choose_breaker_engine). Stamps the decision + rationale
    on the node for EXPLAIN and, when ``record``, bumps the
    engine-labeled dispatch counters (ctx.stats + /v1/metrics)."""
    from presto_tpu.plan.stats import choose_breaker_engine
    from presto_tpu.scan import metrics as _scan_metrics

    override = getattr(ctx.config, "breaker_engine", "auto")
    hbo = getattr(ctx.config, "hbo", "observe")
    try:
        engine, why = choose_breaker_engine(node, ctx.catalog, override,
                                            hbo=hbo)
    except Exception:
        engine, why = "sort", "stats derivation failed"
    node.__dict__["_breaker_engine"] = engine
    node.__dict__["_breaker_engine_why"] = why
    if record:
        key = f"breaker.engine_{engine}"
        ctx.stats[key] = ctx.stats.get(key, 0) + 1
        _scan_metrics.record(f"breaker_dispatches_{engine}", 1)
        if "(hbo: observed)" in why:
            try:
                from presto_tpu.obs import runstats as _runstats
                _runstats.record_correction("breaker_engine")
            except Exception:
                pass
        if ctx.tracer.enabled:
            t = time.time()
            ctx.tracer.record("breaker_engine", "breaker_engine", t, t,
                              node=type(node).__name__, engine=engine,
                              why=why)
    return engine


def _engine_key(key: str, engine: str) -> str:
    """Jit-cache key for an engine-dependent program: the hash engine's
    traces differ structurally from the sort engine's, so they must not
    share a structural program-cache entry."""
    return key if engine == "sort" else f"{key}@h"


def _agg_steps(node: Aggregate, engine: str = "sort") -> SimpleNamespace:
    """Structural merge-step closures for one Aggregate node, memoized on
    the node (per breaker engine) so the executor and the install-time
    breaker warmers hand _node_jit the SAME function objects (one trace,
    one shared program). Everything here derives from the node, its
    collapsed child chain and the engine — no runtime data is captured,
    which is what makes the steps warmable ahead of the stream."""
    memos = node.__dict__.setdefault("_agg_steps", {})
    memo = memos.get(engine)
    if memo is not None:
        return memo
    from presto_tpu.plan.agg_states import state_types as _layout_state_types

    _, chain0 = collapse_chain(node.child)
    chain = chain0 or (lambda b: b)
    in_types = dict(node.child.output)
    layout = agg_state_layout(node.aggs, in_types)
    lpairs = limb_pairs(layout)
    key_syms = node.group_keys
    key_types = [in_types[k] for k in key_syms]
    final_mode = node.step == "final"
    if final_mode:
        # input columns ARE the partial state columns (post-exchange)
        state_types = [in_types[name] for name, _, _ in layout]
    else:
        state_types = _layout_state_types(layout, in_types)

    def _key_domain(b: Batch, k: str, t: Type):
        """Static value-domain bound for the direct (sort-free) group path:
        dictionary codes ∈ [0, |dict|), booleans ∈ {0, 1}."""
        d = b.dicts.get(k)
        if d is not None:
            return len(d)
        if t.name == "boolean":
            return 2
        return None

    def in_to_states(b: Batch):
        keys = [KeyCol(b.column(k).values, b.column(k).validity,
                       _key_domain(b, k, t))
                for k, t in zip(key_syms, key_types)]
        states = []
        for (name, op, a), st in zip(layout, state_types):
            if final_mode:
                c = b.column(name)
                # count_add over count values degenerates to summing them
                states.append(StateCol(c.values.astype(st.dtype), c.validity, op))
            else:
                states.append(_input_state(b, name, op, a, st, in_types))
        return keys, states

    def acc_to_states(acc: Batch):
        keys = [KeyCol(acc.column(k).values, acc.column(k).validity,
                       _key_domain(acc, k, t))
                for k, t in zip(key_syms, key_types)]
        states = []
        for name, op, a in layout:
            c = acc.column(name)
            states.append(StateCol(c.values, c.validity, op))
        return keys, states

    @jax.named_scope("breaker_step")
    def merge_step(acc: Optional[Batch], b: Batch, cap: int,
                   prechained: bool = False):
        if not prechained:
            b = chain(b)
        if acc is not None:
            # group keys from different sources (UNION ALL branches,
            # exchange pages) may be coded against different dictionaries;
            # group equality is string equality, so re-encode first
            acc, b = _unify_batch_dicts([acc, b])
        kin, sin = in_to_states(b)
        live = b.live
        if acc is not None:
            ka, sa = acc_to_states(acc)
            kin = [
                KeyCol(
                    jnp.concatenate([a.values, i.values]),
                    _concat_validity(a.validity, i.validity, acc.capacity, b.capacity),
                    a.domain if a.domain == i.domain else None,
                )
                for a, i in zip(ka, kin)
            ]
            sin = [
                StateCol(
                    jnp.concatenate([a.values, i.values]),
                    _concat_validity(a.validity, i.validity, acc.capacity, b.capacity),
                    a.op,
                )
                for a, i in zip(sa, sin)
            ]
            live = jnp.concatenate([acc.live, live])
        kout, sout, out_live, n_groups = grouped_merge(kin, sin, live, cap,
                                                       engine=engine)
        sout = _renorm_limbs(list(sout), lpairs)
        cols = [Column(k.values, k.validity) for k in kout] + [
            Column(s.values, s.validity if s.op != "count_add" else None) for s in sout
        ]
        names = list(key_syms) + [name for name, _, _ in layout]
        types = key_types + state_types
        dicts = {k: b.dicts[k] for k in key_syms if k in b.dicts}
        # string-valued states (min/max/arbitrary) keep the arg's dictionary
        # (final mode: the state column itself carries it post-exchange)
        for name, op, a in layout:
            if op in ("min", "max"):
                if a.arg in b.dicts:
                    dicts[name] = b.dicts[a.arg]
                elif name in b.dicts:
                    dicts[name] = b.dicts[name]
        out = Batch(names, types, cols, out_live, dicts)
        return out, n_groups

    def acc_merge_step(acc: Optional[Batch], b: Batch, cap: int):
        """Merge a previously-spilled accumulator batch (state columns, not
        raw input) into acc — both sides use accumulator semantics."""
        if acc is not None:
            acc, b = _unify_batch_dicts([acc, b])
        kin, sin = acc_to_states(b)
        live = b.live
        if acc is not None:
            ka, sa = acc_to_states(acc)
            kin = [
                KeyCol(
                    jnp.concatenate([a.values, i.values]),
                    _concat_validity(a.validity, i.validity, acc.capacity, b.capacity),
                    a.domain if a.domain == i.domain else None,
                )
                for a, i in zip(ka, kin)
            ]
            sin = [
                StateCol(
                    jnp.concatenate([a.values, i.values]),
                    _concat_validity(a.validity, i.validity, acc.capacity, b.capacity),
                    a.op,
                )
                for a, i in zip(sa, sin)
            ]
            live = jnp.concatenate([acc.live, live])
        kout, sout, out_live, n_groups = grouped_merge(kin, sin, live, cap,
                                                       engine=engine)
        sout = _renorm_limbs(list(sout), lpairs)
        cols = [Column(k.values, k.validity) for k in kout] + [
            Column(s.values, s.validity if s.op != "count_add" else None) for s in sout
        ]
        names = list(key_syms) + [name for name, _, _ in layout]
        types = key_types + state_types
        dicts = {k: v for k, v in b.dicts.items() if k in names}
        return Batch(names, types, cols, out_live, dicts), n_groups

    memo = SimpleNamespace(
        chain=chain, in_types=in_types, layout=layout, lpairs=lpairs,
        key_syms=key_syms, key_types=key_types, state_types=state_types,
        in_to_states=in_to_states, acc_to_states=acc_to_states,
        merge_step=merge_step, acc_merge_step=acc_merge_step)
    memos[engine] = memo
    return memo


def _presized(groups: float) -> int:
    """Group-table capacity for an estimated group count."""
    return round_up_capacity(int(min(groups * 1.25, float(1 << 23))))


def _agg_presize(node: Aggregate, ctx: "ExecContext"):
    """CBO group-table pre-sizing + grace decision for an Aggregate,
    shared by the executor and the install-time breaker warmers (the
    warmers need the same capacity fingerprint the run will use or the
    warm compiles the wrong shape). Returns (cap, ceiling, can_spill,
    grace_from_start)."""
    key_syms = node.group_keys
    cap = ctx.config.agg_capacity
    can_spill = bool(key_syms) and ctx.config.spill_enabled
    ceiling = max(ctx.config.agg_cap_ceiling, ctx.config.agg_capacity)
    if key_syms:
        # CBO capacity pre-sizing: a group table sized from derived NDV
        # stats skips the overflow→replay growth ladder entirely
        # (DetermineJoinDistributionType's cousin for aggregation; the
        # reference sizes hash tables from expectedGroups hints)
        try:
            from presto_tpu.plan.stats import derive as _derive_stats

            _st = _derive_stats(node, ctx.catalog)
        except Exception:
            _st = None
        rows = _st.rows if (_st is not None and _st.rows) else None
        if rows is None and node.grouping_set:
            # behind the exchanges a grouping set's steps take the estimate
            # the fragmenter derived before them: one capacity, or grace
            # from the start past the ceiling, not a ladder of programs
            rows = node.partial_groups
        # behind its exchange a final step derives nothing: where the
        # fragmenter left it its partial step's estimate, one past the
        # ceiling takes the partial's decision (that went passthrough and
        # merged nothing, this goes grace from the start) instead of
        # climbing a ladder of capacities set by the first pages' groups
        # to the same end. A smaller one sizes nothing, as before.
        partial_rows = (node.partial_groups
                        if rows is None and node.step == "final" else None)
        if getattr(ctx.config, "hbo", "observe") == "correct":
            # HBO: a previous run of this structure measured the real
            # group count — presize from the high-water mark instead of
            # the NDV estimate (replaces it: shrinking a bloated estimate
            # is as valid as growing a blind one)
            try:
                from presto_tpu.obs import runstats as _runstats

                h = _runstats.lookup_node(node, ctx.catalog, "agg_groups")
            except Exception:
                h = None
            if h and h.get("actual"):
                rows = float(h["actual"])
                try:
                    _runstats.record_correction("agg_presize")
                except Exception:
                    pass
        if rows:
            if ctx.lifespans:
                # grouped execution: one bucket holds ~1/lifespans of the
                # groups — size the table for a bucket, not the table
                rows = rows / ctx.lifespans
            cap = max(cap, _presized(rows))
        elif (partial_rows and can_spill and _presized(
                partial_rows / (ctx.lifespans or 1)) > ceiling):
            return cap, ceiling, can_spill, True
    # Past the ceiling a fixed-capacity table stops being the right tool
    # (every merge sorts `capacity + batch` rows, nearly all of them dead):
    # go grace from the start — raw input hash-partitions to spill and each
    # partition merges at small capacity (SpillableHashAggregationBuilder /
    # grouped execution; see ExecConfig.agg_cap_ceiling).
    grace_from_start = can_spill and cap > ceiling
    if can_spill:
        cap = min(cap, ceiling)
    return cap, ceiling, can_spill, grace_from_start


def _fragment_eligibility(node: PlanNode, config: ExecConfig) -> Optional[str]:
    """Why a breaker's ingest loop can NOT run as a fused fragment
    (None = eligible). Static structure only — the executors add the
    per-query gates (grouped-execution sweeps, radix engagement,
    grace-from-start). Conservative by design: anything the fuser can't
    prove inert under lax.scan (unnest, host projections, non-scan bases)
    keeps the per-batch path."""
    if not config.fragment_fusion:
        return "off"
    if config.fragment_window < 2:
        return "window < 2"
    if isinstance(node, Aggregate):
        if any(a.fn in _NON_DECOMPOSABLE_FNS for a in node.aggs):
            return "non-decomposable aggregate"
    elif isinstance(node, Sort):
        if node.limit is None:
            return "full sort materializes"
    else:
        return "not a fused breaker"
    try:
        base, _ = collapse_chain(node.child)
    except Exception:
        return "chain does not collapse"
    if not isinstance(base, TableScan):
        return "chain base is not a table scan"
    return None


def _record_fragment_dispatch(node: PlanNode, ctx: "ExecContext",
                              fused: bool, k: int = 1) -> None:
    """Dispatch accounting for breaker ingest loops: one fused fragment
    dispatch covers k batches; a per-batch step covers one. Feeds the
    per-node EXPLAIN ANALYZE rendering, ctx.stats, and the process-wide
    presto_tpu_{fragment,batch}_dispatches_total counters."""
    from presto_tpu.scan import metrics as _scan_metrics

    fs = node.__dict__.setdefault(
        "_fragment_stats",
        {"fragment_dispatches": 0, "batch_dispatches": 0, "fused_batches": 0})
    if fused:
        fs["fragment_dispatches"] += 1
        fs["fused_batches"] += k
        ctx.stats["fragment.dispatches"] = (
            ctx.stats.get("fragment.dispatches", 0) + 1)
        ctx.stats["fragment.fused_batches"] = (
            ctx.stats.get("fragment.fused_batches", 0) + k)
        _scan_metrics.record("fragment_dispatches", 1)
    else:
        fs["batch_dispatches"] += 1
        ctx.stats["fragment.batch_dispatches"] = (
            ctx.stats.get("fragment.batch_dispatches", 0) + 1)
        _scan_metrics.record("batch_dispatches", 1)
    if ctx.inflight is not None:
        # window-boundary heartbeat: counts the driver already holds —
        # never a device sync (obs/inflight.py off-discipline)
        ctx.inflight.publish(type(node).__name__,
                             windows=1 if fused else 0, batches=k)


def _inflight_window_hook(node: PlanNode, ctx: "ExecContext"):
    """WindowSource on_window callback publishing the staging watermark
    (windows staged ahead of the consumer) into the inflight plane.
    None when the plane is off, so the producer thread pays nothing."""
    inf = ctx.inflight
    if inf is None:
        return None
    op = type(node).__name__
    staged = {"n": 0}

    def hook(k: int, width: int) -> None:
        staged["n"] += 1
        inf.publish(op, stagedWindows=staged["n"])

    return hook


def _inflight_spill_hook(node: PlanNode, ctx: "ExecContext"):
    """PartitioningSpiller on_spill callback publishing the spill
    watermark (cumulative bytes + partition-tree depth) per routed
    batch. None when the plane is off."""
    inf = ctx.inflight
    if inf is None:
        return None
    op = type(node).__name__

    def hook(nbytes: int, depth: int) -> None:
        inf.publish(op, spilledBytes=int(nbytes), spillDepth=int(depth))

    return hook


def _bump_replay_wave(node: PlanNode, ctx: "ExecContext",
                      hbo_obs: Optional[dict] = None,
                      cap_to: Optional[int] = None,
                      spilled_leaf: bool = False) -> None:
    """Account one overflow-replay wave: a stats-sized capacity proved too
    small and a breaker re-merged from a checkpoint at a bigger size.
    Plain telemetry (ctx.stats + process counter + zero-width span), not
    gated on hbo — the wave happened regardless of who is watching.
    `spilled_leaf`: the wave re-merged a spilled aggregation's leaf, whose
    table finalize_leaf sizes from the leaf's rows so that there is none:
    one `agg_replay_wave` occurrence (no time of its own) and the process
    counter `agg_replay_waves` say when that stops being so."""
    from presto_tpu.scan import metrics as _scan_metrics

    ctx.stats["breaker.replay_waves"] = (
        ctx.stats.get("breaker.replay_waves", 0) + 1)
    _scan_metrics.record("breaker_replay_waves", 1)
    if spilled_leaf:
        with ctx.tracer.phase("agg_replay_wave", items=1):
            pass
        _scan_metrics.record("agg_replay_waves", 1)
    if hbo_obs is not None:
        hbo_obs["replays"] += 1
    if ctx.tracer.enabled:
        t = time.time()
        attrs = {"node": type(node).__name__}
        if cap_to is not None:
            attrs["cap_to"] = cap_to
        ctx.tracer.record("overflow_replay", "overflow_replay", t, t,
                          **attrs)
    if ctx.inflight is not None:
        ctx.inflight.publish(type(node).__name__,
                             wave=ctx.stats["breaker.replay_waves"],
                             cap=cap_to)


def _spill_stats_for(node: PlanNode, ctx: "ExecContext") -> dict:
    """Per-node spill accounting stamped for EXPLAIN ANALYZE's
    [spill: P=… depth=… reversed=…] rendering and the HBO spill sites."""
    return node.__dict__.setdefault(
        "_spill_stats",
        {"partitions": 0, "repartitions": 0, "reversed": 0, "depth": 0,
         "revocations": 0, "bytes": 0})


def _note_spill_repartition(node: PlanNode, ctx: "ExecContext",
                            child, parent_p: int) -> None:
    """One next-hash-bits split happened (mid-build growth or replay-time
    recursive repartitioning): counters + span + EXPLAIN stats."""
    from presto_tpu.scan import metrics as _scan_metrics

    st = _spill_stats_for(node, ctx)
    st["repartitions"] += 1
    st["depth"] = max(st["depth"], child.depth)
    ctx.stats["spill.repartitions"] = ctx.stats.get("spill.repartitions", 0) + 1
    _scan_metrics.record("spill_repartitions", 1)
    if ctx.tracer.enabled:
        t = time.time()
        ctx.tracer.record("spill_repartition", "spill_repartition", t, t,
                          node=type(node).__name__, partition=int(parent_p),
                          depth=int(child.depth),
                          fanout=int(child.n_partitions))
    if ctx.inflight is not None:
        ctx.inflight.publish(type(node).__name__,
                             repartitions=st["repartitions"],
                             spillDepth=st["depth"])


def _note_spill_revoke(node: PlanNode, ctx: "ExecContext",
                       freed: int) -> None:
    """A pool-pressure revoke request was honored: spillable operator
    state left the device at a batch boundary."""
    from presto_tpu.scan import metrics as _scan_metrics

    st = _spill_stats_for(node, ctx)
    st["revocations"] += 1
    ctx.stats["spill.revocations"] = ctx.stats.get("spill.revocations", 0) + 1
    _scan_metrics.record("spill_revocations", 1)
    if ctx.tracer.enabled:
        t = time.time()
        ctx.tracer.record("spill_revoke", "spill_revoke", t, t,
                          node=type(node).__name__, freed=int(freed))
    if ctx.inflight is not None:
        ctx.inflight.publish(type(node).__name__,
                             spilledBytes=int(freed))


def _spill_replay_budget(ctx: "ExecContext") -> Optional[int]:
    """Byte budget one replayed spill partition's build side must fit in:
    the explicit per-partition budget when set, else the memory pool's
    revoke target (the replay concat has to fit back under the pool limit
    with headroom). None = unbudgeted (replay whole partitions)."""
    if ctx.config.join_spill_budget_bytes is not None:
        return ctx.config.join_spill_budget_bytes
    pool = ctx.memory_pool
    if pool.limit is not None:
        return max(1, int(pool.limit * pool.revoke_target))
    return None


def _hbo_spill_partitions(node: PlanNode, ctx: "ExecContext", site: str,
                          default_p: int) -> int:
    """hbo=correct: seed the initial spill partition count from the leaf
    count a previous run of this structure converged to, so the repeat run
    skips the repartition waves entirely."""
    if getattr(ctx.config, "hbo", "observe") != "correct":
        return default_p
    try:
        from presto_tpu.obs import runstats as _runstats

        h = _runstats.lookup_node(node, ctx.catalog, site)
    except Exception:
        h = None
    if h and h.get("actual"):
        want = int(h["actual"])
        if want > default_p:
            try:
                _runstats.record_correction("spill_partitions")
            except Exception:
                pass
            return min(want, 1024)
    return default_p


def _hbo_radix_partitions(node: PlanNode, ctx: "ExecContext", site: str,
                          default_p: int) -> int:
    """hbo=correct: seed the device-side radix partition count from the
    row count a previous run of this structure observed (join_build /
    agg_groups), targeting ~HASH_MAX_BUILD_ROWS rows per partition — the
    ROADMAP item-3 residual: the radix plane no longer runs a fixed
    per-plan-node count when history knows the state is bigger (same
    discipline as _hbo_spill_partitions for the spiller). Pow2, bounded;
    a changed count is correctness-safe because _radix_tag verifies the
    producer's partition count and falls back to the splitter on
    mismatch — only exchange alignment is lost, never rows."""
    if getattr(ctx.config, "hbo", "observe") != "correct":
        return default_p
    try:
        from presto_tpu.obs import runstats as _runstats

        h = _runstats.lookup_node(node, ctx.catalog, site)
    except Exception:
        h = None
    if h and h.get("actual"):
        from presto_tpu.plan.stats import HASH_MAX_BUILD_ROWS

        want = round_up_capacity(
            max(1, int(float(h["actual"])) // HASH_MAX_BUILD_ROWS))
        if want > default_p:
            try:
                from presto_tpu.obs import runstats as _runstats

                _runstats.record_correction("radix_partitions")
            except Exception:
                pass
            return min(want, 256)
    return default_p


def _record_spill_done(node: PlanNode, ctx: "ExecContext", site: str,
                       est_p: int, spilled_bytes: int, side: str) -> None:
    """Close out one spilling operator: final leaf count to the counter
    plane, spilled bytes to the histogram plane, and the whole shape
    (partitions / repartitions / reversals / depth / skew-visible bytes)
    into HBO history keyed on the node's structural fingerprint."""
    from presto_tpu.obs import metrics as _obs_metrics
    from presto_tpu.scan import metrics as _scan_metrics

    st = _spill_stats_for(node, ctx)
    st["bytes"] += int(spilled_bytes)
    if st["partitions"]:
        _scan_metrics.record("spill_partitions", st["partitions"])
        ctx.stats["spill.partitions"] = (
            ctx.stats.get("spill.partitions", 0) + st["partitions"])
    if spilled_bytes:
        _obs_metrics.SPILLED_BYTES.observe(
            float(spilled_bytes), plane="worker", side=side)
    if getattr(ctx.config, "hbo", "observe") == "off":
        return
    try:
        from presto_tpu.obs import runstats as _runstats

        fp = _runstats.node_fingerprint(node, ctx.catalog)
        if fp is None:
            return
        _runstats.observe(
            fp, site, type(node).__name__.lower(), float(est_p),
            float(max(st["partitions"], 1)),
            extra={"repartitions": int(st["repartitions"]),
                   "reversals": int(st["reversed"]),
                   "depth": int(st["depth"]),
                   "spilled_bytes": int(spilled_bytes)})
    except Exception:
        pass


def _hbo_record_agg(node: Aggregate, ctx: "ExecContext", obs: dict,
                    skew: Optional[float] = None) -> None:
    """Record the aggregate's observed group count into the runstats
    history (the exact confirmed `ng` the overflow protocol already
    fetched — no extra device sync), stamp the node for EXPLAIN ANALYZE
    drift rendering, and count whether the engine choice would flip on
    the observed value."""
    if getattr(ctx.config, "hbo", "observe") == "off":
        return
    if not node.group_keys or not obs.get("groups"):
        return
    try:
        from presto_tpu.obs import runstats as _runstats
        from presto_tpu.plan.stats import choose_breaker_engine
        from presto_tpu.plan.stats import derive as _derive_stats

        fp = _runstats.node_fingerprint(node, ctx.catalog)
        if fp is None:
            return
        try:
            st = _derive_stats(node, ctx.catalog)
        except Exception:
            st = None
        est = float(st.rows) if (st is not None and st.rows) else None
        actual = float(obs["groups"])
        extra = {"replays": int(obs.get("replays", 0))}
        if skew is not None:
            extra["skew"] = float(skew)
        if obs.get("final_cap"):
            # the CONVERGED capacity, not the initial presize — a
            # hbo=correct structure repeat starts where this run ended
            extra["final_cap"] = int(obs["final_cap"])
        made0 = node.__dict__.get("_breaker_engine")
        if made0:
            # the CONVERGED engine: after an adaptive flip this is the
            # winner, with `(adaptive: flipped)` provenance — history
            # records what the run ended on, not what it guessed
            extra["engine"] = made0
            if node.__dict__.get("_adaptive_engine_flipped"):
                extra["adaptive"] = "flipped"
        if getattr(ctx.config, "devprof", "off") == "on" \
                and ctx.memory_pool is not None \
                and getattr(ctx.memory_pool, "peak", 0):
            # devprof plane: the ledger's high-water so far rides the
            # fingerprint into history — ROADMAP item-3 spill sizing
            # reads it back as peak_bytes on a structure repeat
            extra["peak_bytes"] = float(ctx.memory_pool.peak)
        _runstats.observe(fp, "agg_groups", "aggregate", est, actual,
                          extra=extra)
        node.__dict__["_runstats"] = {
            "site": "agg_groups", "est": est, "actual": actual}
        made = node.__dict__.get("_breaker_engine")
        if made:
            would, _ = choose_breaker_engine(
                node, ctx.catalog,
                getattr(ctx.config, "breaker_engine", "auto"),
                hbo="correct")
            if would != made:
                _runstats.record_flip("breaker_engine")
    except Exception:
        pass


def _hbo_fragment_window(node: PlanNode, ctx: "ExecContext") -> int:
    """Fused-fragment window width: the configured value, shrunk to the
    observed batch count of the fragment's base scan (hbo=correct, warm
    history) — stacking an 8-batch window over a source that emits 2
    batches pushes 6 batches of dead padding through every fused step."""
    win = max(1, ctx.config.fragment_window)
    if getattr(ctx.config, "hbo", "observe") != "correct":
        return win
    try:
        from presto_tpu.obs import runstats as _runstats

        base, _ = collapse_chain(node.child)
        if not isinstance(base, TableScan):
            return win
        fp = _runstats.node_fingerprint(base, ctx.catalog)
        h = _runstats.lookup(fp, "scan_rows") if fp else None
        if not h or not h.get("actual"):
            return win
        batches = -(-int(h["actual"]) // max(1, ctx.config.batch_rows))
        if 0 < batches < win:
            _runstats.record_correction("fragment_window")
            return batches
    except Exception:
        pass
    return win


def _hbo_record_scans(root: PlanNode, ctx: "ExecContext") -> None:
    """Observe per-scan actual rows against the derived estimates
    (collect_stats runs only — the row counts ride the instrumented
    stream's existing host sync; an uninstrumented run records nothing
    rather than adding a sync of its own)."""
    if getattr(ctx.config, "hbo", "observe") == "off":
        return
    if not ctx.config.collect_stats or not ctx.node_stats:
        return
    try:
        from presto_tpu.obs import runstats as _runstats
        from presto_tpu.plan.stats import derive as _derive_stats

        def walk(n):
            if isinstance(n, TableScan):
                rec = ctx.node_stats.get(id(n))
                if rec and rec.get("rows"):
                    fp = _runstats.node_fingerprint(n, ctx.catalog)
                    try:
                        st = _derive_stats(n, ctx.catalog)
                    except Exception:
                        st = None
                    est = (float(st.rows)
                           if (st is not None and st.rows) else None)
                    _runstats.observe(fp, "scan_rows", "tablescan", est,
                                      float(rec["rows"]))
                    n.__dict__["_runstats"] = {
                        "site": "scan_rows", "est": est,
                        "actual": float(rec["rows"])}
            for c in n.children():
                walk(c)

        walk(root)
    except Exception:
        pass


def _grouping_set_lanes(stream: Iterator[Batch], tracer) -> Iterator[Batch]:
    """The input of one GROUPING SETS set's aggregate, passed on as it comes;
    once drained, one `grouping_set` occurrence, no time of its own, `items`
    = the lanes of the batches it held (their capacities: host numbers)."""
    lanes = 0
    for b in stream:
        lanes += b.capacity
        yield b
    with tracer.phase("grouping_set", items=lanes):
        pass


def _execute_aggregate(node: Aggregate, ctx: ExecContext) -> Iterator[Batch]:
    if ctx.lifespan is None:
        ls = _grouped_execution_lifespans(node)
        if ls:
            # grouped execution covers the aggregation too: sweep the
            # task's buckets with the sweep rooted HERE so each bucket's
            # accumulator is finalized and freed before the next builds
            try:
                ctx.lifespans = ls
                for b in range(ctx.task_index, ls, ctx.n_tasks):
                    ctx.lifespan = b
                    yield from _execute_aggregate(node, ctx)
            finally:
                ctx.lifespan = None
                ctx.lifespans = None
            return

    if any(a.fn in _NON_DECOMPOSABLE_FNS for a in node.aggs):
        if node.step != "single":
            raise RuntimeError(
                "non-decomposable aggregates must run single-step "
                "(fragmenter gathers them)"
            )
        yield from _execute_materialized_aggregate(node, ctx)
        return

    in_stream, _ = _fused_child(node.child, ctx)
    if node.grouping_set and node.step != "partial" and ctx.tracer.enabled:
        in_stream = _grouping_set_lanes(in_stream, ctx.tracer)
    engine = _breaker_engine_choice(node, ctx)
    steps = _agg_steps(node, engine)
    chain = steps.chain
    in_types = steps.in_types
    layout = steps.layout
    key_syms = steps.key_syms
    key_types = steps.key_types
    state_types = steps.state_types
    in_to_states = steps.in_to_states
    merge_step = steps.merge_step
    acc_merge_step = steps.acc_merge_step

    # global (ungrouped) aggregation threads the accumulator linearly and
    # never replays (no_overflow below): the input acc is dead the moment
    # the step returns, so its device buffers can be donated and updated
    # in place. Keyed aggregation CANNOT donate — the optimistic dispatch
    # window keeps acc_before alive as the overflow-replay checkpoint.
    _step_jit_kw = {}
    if ctx.config.donate_stepping and not key_syms:
        _step_jit_kw["donate_argnums"] = (0,)
    jit_chain = _node_jit(node, "chain_only", lambda: chain)

    from presto_tpu.memory import LocalMemoryContext, batch_device_bytes

    import threading as _threading

    cap, ceiling, can_spill, grace_from_start = _agg_presize(node, ctx)
    # HBO observation scratchpad: confirmed group-count high-water mark +
    # overflow-replay waves, recorded once the stream is fully absorbed
    hbo_obs = {"groups": 0, "replays": 0}
    # whole-fragment fusion gate: static eligibility plus the per-query
    # modes whose ingest must stay per-batch (memory-tight lifespan
    # sweeps pin ~window× the state the mode exists to avoid)
    frag_why = _fragment_eligibility(node, ctx.config)
    if frag_why is None and ctx.lifespans is not None:
        frag_why = "grouped-execution sweep"
    if frag_why is None and grace_from_start:
        frag_why = "grace-from-start spill"
    node.__dict__["_fragment_fusion"] = (
        "fused" if frag_why is None else frag_why)

    # Every engine-keyed closure lives behind one binder so an adaptive
    # mid-query flip (_EngineFlip) can re-point all of them at the other
    # engine's steps under fresh @h-forked program-cache keys. Each call
    # captures that engine's merge closures by VALUE (`ms`/`ams` are
    # locals of the call, one cell per invocation): a later rebind must
    # never leak the new engine's function into a not-yet-traced builder
    # registered under the old engine's cache key.
    _ek = None
    jit_step = jit_step0 = jit_accstep = None
    jit_step_raw = jit_step0_raw = None
    jit_frag_step = jit_frag_step0 = None

    def _bind_engine(new_engine):
        nonlocal engine, steps, merge_step, acc_merge_step, _ek
        nonlocal jit_step, jit_step0, jit_accstep
        nonlocal jit_step_raw, jit_step0_raw
        nonlocal jit_frag_step, jit_frag_step0
        engine = new_engine
        steps = _agg_steps(node, engine)
        ms = merge_step = steps.merge_step
        ams = acc_merge_step = steps.acc_merge_step
        _ek = lambda k: _engine_key(k, new_engine)  # noqa: E731
        jit_step = _node_jit(
            node, _ek("step"),
            lambda: (lambda acc, b, cap: ms(acc, b, cap)),
            static_argnums=(2,), **_step_jit_kw)
        jit_step0 = _node_jit(
            node, _ek("step0"), lambda: (lambda b, cap: ms(None, b, cap)),
            static_argnums=(1,))
        jit_accstep = _node_jit(node, _ek("accstep"), lambda: ams,
                                static_argnums=(2,))
        # grace (hash-partitioned) aggregation: partition replay feeds
        # batches that went through `chain` before spilling — merge must
        # not re-chain
        jit_step_raw = _node_jit(
            node, _ek("step_raw"),
            lambda: (lambda acc, b, cap: ms(acc, b, cap, prechained=True)),
            static_argnums=(2,))
        jit_step0_raw = _node_jit(
            node, _ek("step0_raw"),
            lambda: (lambda b, cap: ms(None, b, cap, prechained=True)),
            static_argnums=(1,))
        if frag_why is None:
            jit_frag_step = _node_jit(
                node, _ek("fragment_step"),
                lambda: _fragment_jit.scan_stepper(ms, False),
                static_argnums=(3,), **_step_jit_kw)
            jit_frag_step0 = _node_jit(
                node, _ek("fragment_step0"),
                lambda: _fragment_jit.scan_stepper(ms, True),
                static_argnums=(2,))

    _bind_engine(engine)

    if node.step == "partial" and grace_from_start:
        node.__dict__["_fragment_fusion"] = "partial passthrough"
        # Adaptive partial-aggregation bypass (reference: partial agg
        # adaptivity — when NDV ≈ row count the partial merge does no
        # reduction): emit per-row state contributions unmerged; the final
        # step after the exchange does the one real merge, partitioned.
        def row_states(b: Batch):
            b = chain(b)
            kin, sin = in_to_states(b)
            cols = [Column(k.values, k.validity) for k in kin] + [
                Column(s.values, s.validity if s.op != "count_add" else None)
                for s in sin]
            names = list(key_syms) + [name for name, _, _ in layout]
            types = key_types + state_types
            dicts = {k: b.dicts[k] for k in key_syms if k in b.dicts}
            for name, op, a in layout:
                if op in ("min", "max") and a.arg in b.dicts:
                    dicts[name] = b.dicts[a.arg]
            return Batch(names, types, cols, b.live, dicts)

        jit_rows = _node_jit(node, "partial_passthrough", lambda: row_states)
        for b in in_stream:
            yield jit_rows(b)
        return

    # Radix only pays when the group table is genuinely large: when the
    # CBO presize fits the base capacity the accumulator already has one
    # small bounded shape, and splitting every input batch by group key
    # would be pure overhead. A spill budget engages it regardless —
    # bounding device residency is the point then, not shapes.
    if (key_syms and ctx.config.radix_partitions > 1
            and (ctx.config.join_spill_budget_bytes is not None
                 or cap > ctx.config.agg_capacity)):
        # Radix-partitioned group-by (ops/radix.py): chained input splits
        # by the top hash bits, each partition merges into its OWN small
        # accumulator with the prechained step closures — P bounded group
        # tables instead of one query-size-dependent one. Per input batch,
        # every partition's merge dispatches before any confirms, so the
        # growth-check sync overlaps the other partitions' device work
        # (the full optimistic window would pin P×depth checkpoints of
        # device state for little extra gain). Partitions whose accumulator
        # exceeds join_spill_budget_bytes hybrid-spill: the confirmed
        # state pages plus all later raw sub-batches go to host files and
        # replay one-at-a-time at the end.
        from presto_tpu.memory import batch_device_bytes as _bdb
        from presto_tpu.obs import metrics as _obs_metrics
        from presto_tpu.scan import metrics as _scan_metrics
        from presto_tpu.spiller import SpillFile

        node.__dict__["_fragment_fusion"] = "radix-partitioned"
        P = _hbo_radix_partitions(node, ctx, "agg_groups",
                                  ctx.config.radix_partitions)
        budget = ctx.config.join_spill_budget_bytes
        split = _radix_splitter(node, ctx, key_syms, P, "agg_")
        jit_accstep0 = _node_jit(
            node, _ek("accstep0"),
            lambda: (lambda b, c: acc_merge_step(None, b, c)),
            static_argnums=(1,))
        # CBO pre-sizing applies per partition: each holds ~1/P of the
        # estimated groups, and the pow2 ladder steps are shared across
        # partitions so one compile serves all P
        start_cap = max(ctx.config.agg_capacity,
                        round_up_capacity(max(cap // P, 1)))
        caps = [start_cap] * P
        accs: List[Optional[Batch]] = [None] * P
        rrows = [0] * P
        part_ng = [0] * P  # confirmed per-partition group counts (host ints)
        afiles: Dict[int, SpillFile] = {}  # spilled accumulator state pages
        rfiles: Dict[int, SpillFile] = {}  # spilled raw (chained) input

        def _stat(key, delta):
            ctx.stats[key] = ctx.stats.get(key, 0) + delta

        _stat("radix.agg_engaged", 1)

        def merge_into(p, sub, step_fn, step0_fn, first=None):
            for attempt in range(ctx.config.max_growth_retries):
                if first is not None and attempt == 0:
                    out, ng = first
                elif accs[p] is None:
                    out, ng = step0_fn(sub, caps[p])
                else:
                    out, ng = step_fn(accs[p], sub, caps[p])
                n2 = int(ng)
                if n2 <= caps[p]:
                    accs[p] = out
                    part_ng[p] = max(part_ng[p], n2)
                    return
                # acc unchanged on overflow: retry same inputs bigger
                caps[p] = round_up_capacity(n2)
                _bump_replay_wave(node, ctx, hbo_obs, cap_to=caps[p])
            raise RuntimeError("aggregate capacity growth exceeded retries")

        def _emit(acc):
            if node.step == "partial":
                return acc
            return _finalize_aggregate(node, acc, layout, key_syms,
                                       key_types, state_types, in_types)

        def spill_partition(p):
            """Hybrid-spill partition p: the confirmed state pages plus all
            later raw sub-batches go to host files and replay at the end."""
            af = ctx.spill_manager.spill_file(f"radix-agg-acc-p{p}")
            ctx.track_spill(af)
            if accs[p] is not None:
                af.append(accs[p])
            afiles[p] = af
            rfiles[p] = ctx.spill_manager.spill_file(f"radix-agg-raw-p{p}")
            ctx.track_spill(rfiles[p])
            accs[p] = None
            caps[p] = start_cap
            _stat("radix.partitions_spilled", 1)
            _scan_metrics.record("radix_partitions_spilled", 1)

        rev = {"flag": False, "targets": []}

        def _revoke(_need):
            # pool-pressure REQUEST honored at the next batch boundary
            # (spilling synchronously inside reserve() would re-enter the
            # accounting — same protocol as the non-radix agg revoker)
            rev["flag"] = True
            return 0

        # adaptive device-side radix growth (ops/radix.radix_child_perm):
        # parent partition id -> {"caps","accs","ng"} child state. A
        # grown partition re-splits its input by the NEXT hash bits down,
        # so a budget-blowing partition stays on device as F small
        # children instead of round-tripping through host spill files.
        grown: Dict[int, dict] = {}
        _child = {"perm": None, "win": None}

        def _child_split(sub):
            if _child["perm"] is None:
                from presto_tpu.ops import radix as _radix

                keys = tuple(key_syms)
                _child["perm"] = _node_jit(
                    node, "agg_child_perm",
                    lambda: (lambda b: _radix.radix_child_perm(
                        b, keys, P, _RADIX_GROW_FANOUT)))
                # same gather program the parent splitter compiles — the
                # shared cache key reuses it instead of re-tracing
                _child["win"] = _node_jit(
                    node, "agg_radix_window",
                    lambda: _radix.radix_window_perm,
                    static_argnames=("bucket",))
            sperm, counts = _child["perm"](sub)
            cnts = np.asarray(counts)
            starts = np.concatenate([[0], np.cumsum(cnts)])
            for c in range(_RADIX_GROW_FANOUT):
                n = int(cnts[c])
                if n:
                    yield c, _child["win"](
                        sub, sperm, np.int32(starts[c]), np.int32(n),
                        bucket=round_up_capacity(n)), n

        def child_merge(p, c, sub, step_fn, step0_fn):
            ch = grown[p]
            for _ in range(ctx.config.max_growth_retries):
                if ch["accs"][c] is None:
                    out, ng = step0_fn(sub, ch["caps"][c])
                else:
                    out, ng = step_fn(ch["accs"][c], sub, ch["caps"][c])
                n2 = int(ng)
                if n2 <= ch["caps"][c]:
                    ch["accs"][c] = out
                    ch["ng"][c] = max(ch["ng"][c], n2)
                    return
                ch["caps"][c] = round_up_capacity(n2)
                _bump_replay_wave(node, ctx, hbo_obs, cap_to=ch["caps"][c])
            raise RuntimeError("aggregate capacity growth exceeded retries")

        def grow_partition_device(p):
            """Adaptive device-side grow_partition: split resident
            partition p by the next hash bits. The confirmed accumulator
            is itself a valid state-page batch, so each child slice
            re-merges through the acc-merge step at a small capacity —
            hot-but-distinct keys separate under fresh entropy while the
            parent decomposition (and any partition-aligned exchange
            tags at the parent P) stays valid."""
            acc0 = accs[p]
            grown[p] = {"caps": [start_cap] * _RADIX_GROW_FANOUT,
                        "accs": [None] * _RADIX_GROW_FANOUT,
                        "ng": [0] * _RADIX_GROW_FANOUT}
            accs[p] = None
            caps[p] = start_cap
            _stat("radix.partitions_grown", 1)
            _scan_metrics.record("radix_partitions_grown", 1)
            if acc0 is not None:
                for c, ss, _n in _child_split(acc0):
                    child_merge(p, c, ss, jit_accstep, jit_accstep0)

        def spill_grown(p):
            """A grown partition's child blew the budget too: fall back
            to hybrid spill for the WHOLE parent partition (children
            rejoin as state pages — child ids refine parent ids, so the
            end-of-stream replay is untouched by the growth detour)."""
            ch = grown.pop(p)
            af = ctx.spill_manager.spill_file(f"radix-agg-acc-p{p}")
            ctx.track_spill(af)
            for a in ch["accs"]:
                if a is not None:
                    af.append(a)
            afiles[p] = af
            rfiles[p] = ctx.spill_manager.spill_file(f"radix-agg-raw-p{p}")
            ctx.track_spill(rfiles[p])
            caps[p] = start_cap
            _stat("radix.partitions_spilled", 1)
            _scan_metrics.record("radix_partitions_spilled", 1)

        def over_budget(p):
            """Budget enforcement with the adaptive rung in front: the
            first breach grows the partition on device (radix_grow); a
            child breach — or adaptive off/observe — hybrid-spills."""
            if p in grown:
                if any(a is not None and _bdb(a) > budget
                       for a in grown[p]["accs"]):
                    spill_grown(p)
                return
            nbytes = _bdb(accs[p])
            if nbytes <= budget:
                return
            if ctx.adaptive is not None:
                acted = ctx.adaptive.decide(
                    "radix_grow", node=node,
                    site=_adaptive_site(node, ctx),
                    before=f"p{p}", after=f"p{p}/{_RADIX_GROW_FANOUT}",
                    detail=(f"grow p{p} into {_RADIX_GROW_FANOUT} "
                            "device children"),
                    bytes=int(nbytes), budget=int(budget))
                if acted:
                    grow_partition_device(p)
                    return
            spill_partition(p)

        # resident-state accounting (LocalMemoryContext protocol, same as
        # the grace path's mctx): without it the pool never sees radix
        # residency and partition-granular revocation has no pressure
        # source to react to. Gated to adaptive=on — off/observe must
        # keep the seed's exact reserve/replay sequence, and only the
        # partial-revocation protocol consumes this pressure anyway.
        from presto_tpu.memory import LocalMemoryContext as _LMC
        _account_on = ctx.adaptive is not None and ctx.adaptive.mode == "on"
        mctx_r = _LMC(ctx.memory_pool, "radix-aggregate")

        def _account_resident():
            if not _account_on:
                return
            total = sum(_bdb(a) for a in accs if a is not None)
            for ch in grown.values():
                total += sum(_bdb(a) for a in ch["accs"] if a is not None)
            mctx_r.set_bytes(int(total))

        _partial_fn = None
        if ctx.config.spill_enabled:
            if ctx.adaptive is not None and ctx.adaptive.mode == "on":
                # partition-granular revocation: pool pressure marks the
                # LARGEST partitions (cross-owner largest-first ranking
                # lives in MemoryPool.request_partial_revoke) instead of
                # flag-spilling blind — cold partitions leave, hot ones
                # stay resident
                def _psizes():
                    return [(pp, int(_bdb(accs[pp]))) for pp in range(P)
                            if accs[pp] is not None and pp not in rfiles
                            and pp not in grown]

                def _prevoke(pp):
                    est = int(_bdb(accs[pp])) if accs[pp] is not None else 0
                    rev["targets"].append(pp)
                    return est

                _partial_fn = ctx.memory_pool.add_partial_revoker(
                    SimpleNamespace(partition_sizes=_psizes,
                                    revoke_partition=_prevoke))
            else:
                ctx.memory_pool.add_revoker(_revoke)
        try:
            for raw_b in in_stream:
                rid = _radix_tag(raw_b, P, key_syms)
                if rid is not None:
                    ub = jit_chain(_untag_batch(raw_b))
                    # num_live stays a device scalar — summed lazily so the
                    # aligned fast path adds no sync of its own
                    subs = [(rid, ub, ub.num_live())]
                    _stat("radix.aligned_batches", 1)
                    _scan_metrics.record("radix_aligned_batches", 1)
                else:
                    subs = split(jit_chain(_untag_batch(raw_b)))
                pend = []
                for p, sub, n in subs:
                    rrows[p] = rrows[p] + n
                    if p in rfiles:
                        rfiles[p].append(sub)
                        continue
                    if p in grown:
                        # grown partitions merge synchronously per child
                        # (the sub re-splits by the next hash bits first)
                        for c, ss, _cn in _child_split(sub):
                            child_merge(p, c, ss, jit_step_raw,
                                        jit_step0_raw)
                        if budget is not None:
                            over_budget(p)
                        continue
                    # dispatch wave: split() yields each partition at most
                    # once per batch, so all merges are independent
                    if accs[p] is None:
                        first = jit_step0_raw(sub, caps[p])
                    else:
                        first = jit_step_raw(accs[p], sub, caps[p])
                    pend.append((p, sub, first))
                for p, sub, first in pend:
                    merge_into(p, sub, jit_step_raw, jit_step0_raw, first)
                    if budget is not None:
                        over_budget(p)
                if rev["flag"] or rev["targets"]:
                    # partition-granular marks first (adaptive partial
                    # revocation, honored here at the batch boundary)
                    targets = []
                    while rev["targets"]:
                        pp = rev["targets"].pop(0)
                        if (accs[pp] is not None and pp not in rfiles
                                and pp not in grown and pp not in targets):
                            targets.append(pp)
                    for pp in targets:
                        nbytes = _bdb(accs[pp])
                        ctx.adaptive.decide(
                            "partial_revoke", node=node,
                            site=_adaptive_site(node, ctx),
                            before=f"p{pp}", after="host",
                            detail=f"revoke p{pp} to host",
                            bytes=int(nbytes))
                        spill_partition(pp)
                        _note_spill_revoke(node, ctx, nbytes)
                    if rev["flag"]:
                        # whole-operator rung (adaptive off/observe):
                        # spill the LARGEST resident partition to host
                        rev["flag"] = False
                        resident = [(pp, _bdb(accs[pp])) for pp in range(P)
                                    if accs[pp] is not None
                                    and pp not in rfiles
                                    and pp not in grown]
                        if resident:
                            pp, nbytes = max(resident, key=lambda t: t[1])
                            if (ctx.adaptive is not None
                                    and ctx.adaptive.mode == "observe"):
                                ctx.adaptive.decide(
                                    "partial_revoke", node=node,
                                    site=_adaptive_site(node, ctx),
                                    before=f"p{pp}", after="host",
                                    detail=f"revoke p{pp} to host",
                                    bytes=int(nbytes))
                            spill_partition(pp)
                            _note_spill_revoke(node, ctx, nbytes)
                # post-boundary accounting: a reserve() here that crosses
                # the pool threshold marks partitions (or sets the flag)
                # for the NEXT boundary — never frees inline
                _account_resident()
            rrows = [int(r) for r in rrows]
            for p in range(P):
                if rrows[p]:
                    _obs_metrics.RADIX_PARTITION_ROWS.observe(
                        rrows[p], plane="worker", side="group")
                if p in grown:
                    ch = grown[p]
                    part_ng[p] = sum(ch["ng"])
                    for c in range(_RADIX_GROW_FANOUT):
                        if ch["accs"][c] is not None:
                            yield _emit(ch["accs"][c])
                            ch["accs"][c] = None
                    continue
                if p in rfiles or accs[p] is None:
                    continue
                yield _emit(accs[p])
                accs[p] = None
            # hybrid-spilled partitions, one resident at a time
            for p in sorted(rfiles):
                t0 = time.time()
                accs[p] = None
                caps[p] = start_cap
                for sub in rfiles[p].read():
                    merge_into(p, sub, jit_step_raw, jit_step0_raw)
                for sub in afiles[p].read():
                    merge_into(p, sub, jit_accstep, jit_accstep0)
                if ctx.tracer.enabled:
                    ctx.tracer.record("radix_spill_replay",
                                      "radix_spill_replay", t0, time.time(),
                                      partition=p, rows=rrows[p])
                if accs[p] is not None:
                    yield _emit(accs[p])
                    accs[p] = None
            if ctx.lifespans is None:
                hbo_obs["groups"] = sum(part_ng)
                _hbo_record_agg(node, ctx, hbo_obs,
                                skew=partition_skew(rrows))
        finally:
            mctx_r.close()
            if ctx.config.spill_enabled:
                ctx.memory_pool.remove_revoker(
                    _partial_fn if _partial_fn is not None else _revoke)
            spilled = (sum(f.bytes for f in afiles.values())
                       + sum(f.bytes for f in rfiles.values()))
            if spilled:
                _stat("radix.spill_bytes", spilled)
                _scan_metrics.record("radix_spill_bytes", spilled)
                ctx.spill_manager.record(spilled)
                _obs_metrics.SPILLED_BYTES.observe(
                    float(spilled), plane="worker", side="group")
            for f in afiles.values():
                f.close()
            for f in rfiles.values():
                f.close()
        return

    # An aligned exchange may still stamp pages with radix tags (the sink
    # can't see the CBO gate above) — strip them before anything jits.
    if ctx.config.radix_partitions > 1:
        in_stream = (_untag_batch(b) for b in in_stream)

    # rows_seen: host-known input watermark (batch capacities — no device
    # sync) feeding the adaptive flip's observed-duplication estimate
    state = {"acc": None, "spiller": None, "raw_spiller": None,
             "revoke_requested": False, "rows_seen": 0}
    mctx = LocalMemoryContext(ctx.memory_pool, "aggregate")
    owner_thread = _threading.get_ident()
    # dynamic hybrid hash: the initial partition count is an ESTIMATE —
    # hbo=correct seeds it from the leaf count a previous run of this
    # structure converged to, so the repeat skips the repartition waves
    grace_P = (_hbo_spill_partitions(node, ctx, "spill_agg",
                                     ctx.config.spill_partitions)
               if can_spill else ctx.config.spill_partitions)

    def mk_raw_spiller():
        if state["raw_spiller"] is None:
            state["raw_spiller"] = ctx.spill_manager.partitioning_spiller(
                key_syms, grace_P, "agg-raw",
                on_grow=lambda child, pp: _note_spill_repartition(
                    node, ctx, child, pp),
                on_spill=_inflight_spill_hook(node, ctx), phases="agg")
            ctx.track_spill(state["raw_spiller"])
        return state["raw_spiller"]

    def do_spill() -> int:
        """Partition-spill the accumulator (SpillableHashAggregationBuilder:
        state pages leave memory partitioned by hash(keys) so each partition
        finalizes independently later)."""
        acc0 = state["acc"]
        if acc0 is None:
            return 0
        if state["spiller"] is None:
            state["spiller"] = ctx.spill_manager.partitioning_spiller(
                key_syms, grace_P, "agg",
                on_grow=lambda child, pp: _note_spill_repartition(
                    node, ctx, child, pp),
                on_spill=_inflight_spill_hook(node, ctx), phases="agg")
            ctx.track_spill(state["spiller"])
        state["spiller"].spill(acc0)
        freed = mctx.bytes
        state["acc"] = None
        mctx.set_bytes(0)
        ctx.spill_manager.record(freed)
        return freed

    def revoke(_need: int) -> int:
        """Pool-pressure callback. Like the reference's revocable-memory
        protocol this is always a REQUEST honored at the next batch
        boundary: spilling synchronously here would re-enter set_bytes
        (a reserve() mid-flight can trigger our own revoker) and corrupt
        the accounting on a worker-shared pool."""
        state["revoke_requested"] = True
        return 0

    def _ceiling_overflow(mode, entries):
        if mode == "fail":
            from presto_tpu.spiller import SpillLimitExceeded

            raise SpillLimitExceeded(
                "aggregate spill partition exceeds the grace ceiling at "
                f"max recursion depth {max(0, ctx.config.spill_max_depth)} "
                "(group keys share too many hash bits to split further)")
        raise _GraceOverflow(entries)

    if can_spill:
        ctx.memory_pool.add_revoker(revoke)
    try:
        def absorb(stream, step_fn, step0_fn, allow_spill=True,
                   on_ceiling=None):
            """Merge the stream into the accumulator with OPTIMISTIC
            dispatch: the per-step group count `ng` (the only data-dependent
            control input) is fetched asynchronously and confirmed up to
            `agg_pipeline_depth` steps later, so the device pipeline never
            stalls on a host round trip (the dominant cost of the old
            sync-per-batch loop).
            A window of (checkpoint-acc, input-batch) pairs is held; on the
            rare capacity overflow the window replays synchronously from
            the last confirmed checkpoint at a bigger capacity.

            `on_ceiling` names what growth past the grace ceiling does:
            "grace" raises _GraceOverflow (hand the input to the
            hash-partitioned spill path — the mid-stream default and the
            replay-time recursive-repartition trigger), "grow" keeps
            growing the table (spill unavailable), "fail" raises
            SpillLimitExceeded (recursive repartitioning hit its depth
            bound without converging)."""
            nonlocal cap
            mode = on_ceiling or ("grace" if allow_spill else "grow")
            if not can_spill:
                mode = "grow"
            depth = max(1, ctx.config.agg_pipeline_depth)
            no_overflow = not key_syms  # global agg: ng ≤ 1, never grows
            # (acc_before, batch, ng_device_scalar, dispatch_cap): the
            # capacity each entry was MERGED at rides the window — after
            # an adaptive presize the overflow check must compare against
            # the entry's own capacity, not the grown one (an acc built
            # at the small cap truncated its overflow groups)
            window = []

            def dispatch(b):
                acc_before = state["acc"]
                if acc_before is None:
                    out, ng = step0_fn(b, cap)
                else:
                    out, ng = step_fn(acc_before, b, cap)
                state["acc"] = out
                state["rows_seen"] += b.capacity
                _record_fragment_dispatch(node, ctx, fused=False)
                if no_overflow:
                    return
                try:
                    ng.copy_to_host_async()
                except Exception:
                    pass
                window.append((acc_before, b, ng, cap))

            def replay(entries, ngi):
                """Re-merge `entries` from the first entry's checkpoint at a
                capacity that fits `ngi` groups (synchronous — rare path).
                Growth past the grace ceiling instead hands the unmerged
                batches to the hash-partitioned path (_GraceOverflow) —
                an ever-bigger table would make every later merge sort
                millions of dead slots."""
                nonlocal cap
                state["acc"] = entries[0][0]
                if entries[0][0] is None and allow_spill:
                    # adaptive flip window: the checkpoint is EMPTY, so
                    # the whole aggregation can restart under the engine
                    # the OBSERVED group count picks — instead of
                    # replaying the loser wider
                    flipped = _adaptive_flip_verdict(
                        node, ctx, engine, ngi, state["rows_seen"])
                    if flipped is not None:
                        raise _EngineFlip([e[1] for e in entries],
                                          ngi, flipped)
                want2 = round_up_capacity(ngi)
                if mode != "grow" and want2 > ceiling:
                    _ceiling_overflow(mode, entries)
                cap = want2
                _bump_replay_wave(node, ctx, hbo_obs, cap_to=cap,
                                  spilled_leaf=not allow_spill)
                for i, e in enumerate(entries):
                    b = e[1]
                    for _ in range(ctx.config.max_growth_retries):
                        acc_before = state["acc"]
                        if acc_before is None:
                            out, ng2 = step0_fn(b, cap)
                        else:
                            out, ng2 = step_fn(acc_before, b, cap)
                        n2 = int(ng2)
                        if n2 <= cap:
                            state["acc"] = out
                            hbo_obs["groups"] = max(hbo_obs["groups"], n2)
                            break
                        # power-of-two bucketing already gives ≤2× headroom;
                        # doubling on top would 4× the memory footprint
                        want2 = round_up_capacity(n2)
                        if mode != "grow" and want2 > ceiling:
                            # acc still holds the pre-entry checkpoint:
                            # entries[i:] have not been merged into it
                            _ceiling_overflow(mode, entries[i:])
                        cap = want2
                    else:
                        raise RuntimeError(
                            "aggregate capacity growth exceeded retries")

            def confirm(block, site="agg_confirm"):
                nonlocal cap
                while window and (block or len(window) > depth):
                    with ctx.tracer.phase("host_sync:" + site):
                        ngi = int(window[0][2])  # usually already on host
                    dcap = window[0][3]  # capacity the entry merged at
                    if ngi <= dcap:
                        hbo_obs["groups"] = max(hbo_obs["groups"], ngi)
                        window.pop(0)
                        if ctx.adaptive is not None and allow_spill:
                            # forward presize: grow BEFORE the overflow
                            # the near-full table is about to pay (the
                            # next merge migrates the acc, zero replay)
                            want = _adaptive_presize_grow(
                                node, ctx, ngi, cap,
                                ceiling if mode != "grow" else None)
                            if want is not None:
                                cap = want
                        continue
                    entries = list(window)
                    window.clear()
                    replay(entries, ngi)

            for b in stream:
                dispatch(b)
                # while replaying spilled partitions (allow_spill=False) or
                # sweeping lifespans run synchronously: the optimistic
                # window pins ~3× the accumulator footprint, which is
                # exactly what the memory-bounded modes cannot afford
                confirm(block=not allow_spill or ctx.lifespans is not None)
                # account EVERYTHING the optimistic window pins on device:
                # the live accumulator plus each unconfirmed checkpoint and
                # its input batch — otherwise spill/revoke fires ~depth×
                # too late
                out_bytes = batch_device_bytes(state["acc"])
                for acc_before, wb, _, _dc in window:
                    out_bytes += batch_device_bytes(wb)
                    if acc_before is not None:
                        out_bytes += batch_device_bytes(acc_before)
                if allow_spill and can_spill and (
                    state["revoke_requested"]
                    or ctx.should_spill(out_bytes - mctx.bytes)
                ):
                    confirm(block=True)  # spill only a confirmed accumulator
                    was_revoke = state["revoke_requested"]
                    state["revoke_requested"] = False
                    freed = do_spill()
                    if was_revoke:
                        _note_spill_revoke(node, ctx, freed)
                else:
                    mctx.set_bytes(out_bytes)
            confirm(block=True, site="breaker_finish")

        def grace_ingest(stream, unmerged=()):
            """Hash-partition chained input batches straight to spill (the
            grace-hash build phase; host-side, so dynamic row counts are
            free). No device merge happens until the per-partition phase.
            `unmerged`: what a table that outgrew the ceiling mid-stream
            had pulled and not merged; its confirmed state goes first, as
            state pages. One `agg_partition` occurrence an aggregate, from
            the first pull to the last page written, `items` its batches."""
            with ctx.tracer.phase("agg_partition") as ph:
                do_spill()
                raw = mk_raw_spiller()
                for b in itertools.chain(unmerged, stream):
                    raw.spill(jit_chain(b))
                    ph.items += 1
                ctx.spill_manager.record(raw.spilled_bytes)

        def absorb_fused(stream):
            """Whole-fragment ingest: consecutive same-structure batches
            arrive as a WINDOW of references (WindowSource stages one
            ahead), and one fused program stacks the window and folds
            chain+merge over it on-device via lax.scan — O(batches /
            window) dispatches instead of O(batches). The overflow
            protocol matches absorb(): an optimistic window of (checkpoint,
            item, max-ng) confirms up to `depth` items late and replays
            from the checkpoint on the rare capacity overflow, with whole
            windows as the replay unit. Growth past the grace ceiling hands
            the unmerged windows' real batches to the hash-partitioned
            spill path."""
            nonlocal cap
            depth = max(1, ctx.config.agg_pipeline_depth)
            no_overflow = not key_syms
            # (acc_before, WindowItem, ng, dispatch_cap) — see absorb():
            # each entry confirms against the capacity it merged at
            window = []

            def apply(acc_before, item, c):
                if isinstance(item, _fragment_jit.Window):
                    if acc_before is None:
                        return jit_frag_step0(*item.operands, c)
                    return jit_frag_step(acc_before, *item.operands, c)
                if acc_before is None:
                    return jit_step0(item, c)
                return jit_step(acc_before, item, c)

            def expand(entries):
                """Unmerged optimistic-window entries → raw-batch triples
                the _GraceOverflow handler understands."""
                out = []
                for e in entries:
                    item = e[1]
                    if isinstance(item, _fragment_jit.Window):
                        out.extend((None, rb, None)
                                   for rb in item.batches[:item.k])
                    else:
                        out.append((None, item, None))
                return out

            def dispatch(item):
                acc_before = state["acc"]
                out, ng = apply(acc_before, item, cap)
                state["acc"] = out
                fused = isinstance(item, _fragment_jit.Window)
                state["rows_seen"] += (item.k * item.width if fused
                                       else item.capacity)
                _record_fragment_dispatch(node, ctx, fused,
                                          item.k if fused else 1)
                if no_overflow:
                    return
                try:
                    ng.copy_to_host_async()
                except Exception:
                    pass
                window.append((acc_before, item, ng, cap))

            def replay(entries, ngi):
                nonlocal cap
                state["acc"] = entries[0][0]
                if entries[0][0] is None:
                    # adaptive flip window — see absorb().replay
                    flipped = _adaptive_flip_verdict(
                        node, ctx, engine, ngi, state["rows_seen"])
                    if flipped is not None:
                        raise _EngineFlip(
                            [rb for _, rb, _ in expand(entries)],
                            ngi, flipped)
                want2 = round_up_capacity(ngi)
                if can_spill and want2 > ceiling:
                    raise _GraceOverflow(expand(entries))
                cap = want2
                _bump_replay_wave(node, ctx, hbo_obs, cap_to=cap)
                for i, e in enumerate(entries):
                    item = e[1]
                    for _ in range(ctx.config.max_growth_retries):
                        acc_before = state["acc"]
                        out, ng2 = apply(acc_before, item, cap)
                        n2 = int(ng2)
                        if n2 <= cap:
                            state["acc"] = out
                            hbo_obs["groups"] = max(hbo_obs["groups"], n2)
                            break
                        want2 = round_up_capacity(n2)
                        if can_spill and want2 > ceiling:
                            # acc holds the pre-entry checkpoint:
                            # entries[i:] have not been merged into it
                            raise _GraceOverflow(expand(entries[i:]))
                        cap = want2
                    else:
                        raise RuntimeError(
                            "aggregate capacity growth exceeded retries")

            def confirm(block, site="agg_confirm"):
                nonlocal cap
                while window and (block or len(window) > depth):
                    with ctx.tracer.phase("host_sync:" + site):
                        ngi = int(window[0][2])
                    dcap = window[0][3]
                    if ngi <= dcap:
                        hbo_obs["groups"] = max(hbo_obs["groups"], ngi)
                        window.pop(0)
                        if ctx.adaptive is not None:
                            want = _adaptive_presize_grow(
                                node, ctx, ngi, cap,
                                ceiling if can_spill else None)
                            if want is not None:
                                cap = want
                        continue
                    entries = list(window)
                    window.clear()
                    replay(entries, ngi)

            def pinned_bytes(item):
                if isinstance(item, _fragment_jit.Window):
                    return _fragment_jit.window_device_bytes(item)
                return batch_device_bytes(item)

            src = _fragment_jit.WindowSource(
                stream, _hbo_fragment_window(node, ctx),
                bucket=ctx.config.shape_bucketing != "off",
                on_window=_inflight_window_hook(node, ctx))
            try:
                for item in src:
                    dispatch(item)
                    confirm(block=False)
                    out_bytes = batch_device_bytes(state["acc"])
                    for acc_before, wi, _, _dc in window:
                        out_bytes += pinned_bytes(wi)
                        if acc_before is not None:
                            out_bytes += batch_device_bytes(acc_before)
                    if can_spill and (
                        state["revoke_requested"]
                        or ctx.should_spill(out_bytes - mctx.bytes)
                    ):
                        confirm(block=True)
                        was_revoke = state["revoke_requested"]
                        state["revoke_requested"] = False
                        freed = do_spill()
                        if was_revoke:
                            _note_spill_revoke(node, ctx, freed)
                    else:
                        mctx.set_bytes(out_bytes)
                confirm(block=True, site="breaker_finish")
            except _GraceOverflow as ov:
                # recover everything the producer pulled but never delivered
                # so the grace handler spills the COMPLETE remaining input
                rest = src.drain()
                raise _GraceOverflow(list(ov.entries)
                                     + [(None, rb, None) for rb in rest])
            except _EngineFlip as fl:
                # same recovery for a flip: the restart must re-absorb the
                # COMPLETE remaining input under the new engine
                rest = src.drain()
                raise _EngineFlip(fl.batches + list(rest), fl.groups,
                                  fl.engine)
            finally:
                src.close()

        if grace_from_start:
            grace_ingest(in_stream)
        else:
            try:
                try:
                    if frag_why is None:
                        absorb_fused(in_stream)
                    else:
                        absorb(in_stream, jit_step, jit_step0)
                except _EngineFlip as fl:
                    # the wave's OBSERVED group count re-ran the engine
                    # choice and the other engine won: re-absorb the
                    # unmerged input through the flipped engine's programs
                    # (fresh @h-forked cache keys) at a capacity sized to
                    # the observed count — instead of replaying the loser
                    # wider and paying the same overflow again next wave
                    _bind_engine(fl.engine)
                    want = round_up_capacity(int(fl.groups))
                    cap = min(want, ceiling) if can_spill else want
                    # rebind in_stream so a later _GraceOverflow's
                    # grace_ingest still sees the un-pulled remainder
                    in_stream = itertools.chain(fl.batches, in_stream)
                    if frag_why is None:
                        absorb_fused(in_stream)
                    else:
                        absorb(in_stream, jit_step, jit_step0)
            except _GraceOverflow as ov:
                # the table outgrew the ceiling mid-stream: spill the
                # confirmed accumulator as state pages, the unmerged window
                # + the rest of the input as raw partitions. Entries are
                # raw-batch triples from expand() or 4-tuple window entries
                # (batch at [1] either way)
                grace_ingest(in_stream, [e[1] for e in ov.entries])

        if state["spiller"] is None and state["raw_spiller"] is None:
            if ctx.lifespans is None:
                # spilled/sweeping runs hold only per-bucket group counts,
                # which would poison the history as a whole-table total
                hbo_obs["final_cap"] = cap
                _hbo_record_agg(node, ctx, hbo_obs)
            acc = state["acc"]
            if node.step == "partial":
                # emit raw state columns for the exchange; no finalization
                if acc is not None:
                    yield acc
                return
            yield _finalize_aggregate(node, acc, layout, key_syms, key_types,
                                      state_types, in_types)
            return

        # spilled: finalize bucket-by-bucket (grouped-execution style).
        # Spilling to NEW files stays off during the per-partition merge,
        # but a partition whose replay outgrows the grace ceiling no longer
        # fails the query: it re-partitions by the NEXT hash bits
        # ((hash // divisor) % fanout — fresh entropy, so skewed-but-
        # distinct keys do split) and recurses, bounded by spill_max_depth.
        # Only keys that share every hash bit (one-hot identical groups
        # never overflow a 1-group table, so in practice adversarial
        # collisions) reach the bound and fail with SPILL_LIMIT_EXCEEDED.
        do_spill()
        ctx.memory_pool.remove_revoker(revoke)
        spiller = state["spiller"]
        raw_spiller = state["raw_spiller"]
        jit_accstep0 = _node_jit(
            node, "accstep0", lambda: (lambda b, cap: acc_merge_step(None, b, cap)),
            static_argnums=(1,),
        )
        max_sdepth = max(0, ctx.config.spill_max_depth)

        def finalize_leaf(rsp, asp, p, sdepth):
            """One `agg_replay` occurrence a leaf begun (`items` = batches
            merged), one `agg_repartition` a leaf split."""
            nonlocal cap
            ph = ctx.tracer.phase("agg_replay")
            with ph:
                state["acc"] = None
                # The spiller counted the leaf's rows on the host. Groups
                # never outnumber rows, so a table sized from them once
                # merges the leaf with no overflow wave, and its pages come
                # back packed into whole batches of one capacity: the scan's,
                # or the power of two above a smaller leaf's rows. Neither
                # shape follows the data. A leaf with more rows than the
                # ceiling is tried at the ceiling and splits if its groups
                # do not fit.
                rows = sum(sp.partition_rows(p) for sp in (rsp, asp)
                           if sp is not None)
                fit = max(ctx.config.agg_capacity, round_up_capacity(rows))
                cap = min(ceiling, fit)
                batch_cap = min(round_up_capacity(ctx.config.batch_rows), fit)

                def counted(batches):
                    for b in batches:
                        ph.items += 1
                        yield b

                mode = "grace" if sdepth < max_sdepth else "fail"
                try:
                    for sp, step_fn, step0_fn in (
                            (rsp, jit_step_raw, jit_step0_raw),
                            (asp, jit_accstep, jit_accstep0)):
                        if sp is not None:
                            absorb(counted(sp.read_batches(p, batch_cap)),
                                   step_fn, step0_fn, allow_spill=False,
                                   on_ceiling=mode)
                    split = False
                except _GraceOverflow:
                    # the leaf's groups outnumber the ceiling: its files are
                    # still intact on disk, so drop the partial merge
                    split = True
                    state["acc"] = None
                    mctx.set_bytes(0)
            if split:
                # split by the next hash bits and finalize the children (raw
                # and state-page trees split in lockstep → co-partitioned)
                with ctx.tracer.phase("agg_repartition"):
                    sub_r = rsp.grow_partition(p) if rsp is not None else None
                    sub_a = (asp.grow_partition(
                        p, fanout=(sub_r.n_partitions if sub_r is not None
                                   else None))
                        if asp is not None else None)
                fanout = (sub_r or sub_a).n_partitions
                for q in range(fanout):
                    yield from finalize_leaf(sub_r, sub_a, q, sdepth + 1)
                return
            acc = state["acc"]
            if acc is None:
                return
            _spill_stats_for(node, ctx)["partitions"] += 1
            if node.step != "partial":
                with ph:
                    acc = _finalize_aggregate(node, acc, layout, key_syms,
                                              key_types, state_types,
                                              in_types)
            yield acc
            mctx.set_bytes(0)

        for p in range((raw_spiller or spiller).n_partitions):
            yield from finalize_leaf(raw_spiller, spiller, p, 0)
        spilled_total = ((raw_spiller.spilled_bytes if raw_spiller else 0)
                         + (spiller.spilled_bytes if spiller else 0))
        _record_spill_done(node, ctx, "spill_agg", grace_P, spilled_total,
                           side="group")
        if spiller is not None:
            spiller.close()
        if raw_spiller is not None:
            raw_spiller.close()
    finally:
        if can_spill:
            ctx.memory_pool.remove_revoker(revoke)
        mctx.set_bytes(0)
        if state["spiller"] is not None:
            state["spiller"].close()
        if state["raw_spiller"] is not None:
            state["raw_spiller"].close()


def _concat_validity(a, b, cap_a, cap_b):
    if a is None and b is None:
        return None
    av = a if a is not None else jnp.ones(cap_a, dtype=bool)
    bv = b if b is not None else jnp.ones(cap_b, dtype=bool)
    return jnp.concatenate([av, bv])


def _finalize_aggregate(node, acc, layout, key_syms, key_types, state_types, in_types):
    out_syms = [s for s, _ in node.output]
    out_types = [t for _, t in node.output]
    if acc is None:
        # empty input: global aggregation still yields one row
        if not key_syms:
            data = {}
            cols = []
            live = np.zeros(128, bool)
            live[0] = True
            for a in node.aggs:
                from presto_tpu.types import ArrayType as _AT, MapType as _MT

                if isinstance(a.type, (_AT, _MT)):
                    cols.append(Column(
                        jnp.zeros((128, 1), a.type.dtype),
                        jnp.zeros(128, bool),
                        sizes=jnp.zeros(128, jnp.int32),
                    ))
                    continue
                vals = np.zeros(128, dtype=a.type.dtype)
                if a.fn in ("count", "count_star", "count_if"):
                    cols.append(Column(jnp.asarray(vals), None))
                else:
                    cols.append(Column(jnp.asarray(vals), jnp.zeros(128, bool)))
            return Batch(
                [a.symbol for a in node.aggs],
                [a.type for a in node.aggs],
                cols,
                jnp.asarray(live),
                {},
            )
        return Batch(
            out_syms,
            out_types,
            [Column(jnp.zeros(128, t.dtype), None) for t in out_types],
            jnp.zeros(128, dtype=bool),
            {},
        )

    return _node_jit(
        node, "finalize",
        lambda: build_agg_finalizer(node, key_syms, key_types, in_types),
    )(acc)


def build_agg_finalizer(node, key_syms, key_types, in_types):
    """Traceable accumulator→final-values function (avg division, variance
    assembly, int128 limb recombination). Shared by the streaming executor
    and the mesh executor (parallel/mesh_exec.py), which traces it inside
    one shard_map program."""

    def finalize(acc: Batch):
        names, types, cols = [], [], []
        for k, t in zip(key_syms, key_types):
            c = acc.column(k)
            names.append(k)
            types.append(t)
            cols.append(c)
        for a in node.aggs:
            if a.fn == "avg":
                c = acc.column(a.symbol + "$cnt")
                cnt = c.values
                ok = cnt > 0
                denom = jnp.where(ok, cnt, 1).astype(jnp.float64)
                if (a.symbol + "$sum_hi") in acc.names:
                    # int128 decimal sum limbs; scale rides the lo state type
                    hi = acc.column(a.symbol + "$sum_hi").values
                    lo = acc.column(a.symbol + "$sum_lo").values
                    lo_t = acc.type_of(a.symbol + "$sum_lo")
                    num = (hi.astype(jnp.float64) * float(1 << 32)
                           + lo.astype(jnp.float64)) / (10.0 ** lo_t.scale)
                else:
                    s = acc.column(a.symbol + "$sum")
                    if node.step == "final":
                        src_t = in_types[a.symbol + "$sum"]
                    else:
                        src_t = sum_state_type(a, in_types)
                    if isinstance(src_t, DecimalType):
                        num = s.values.astype(jnp.float64) / (10.0 ** src_t.scale)
                    else:
                        num = s.values.astype(jnp.float64)
                vals = num / denom
                cols.append(Column(vals, ok))
            elif a.fn == "sum" and (a.symbol + "$hi") in acc.names:
                # exact int128 decimal total as a two-limb long-decimal column
                hi = acc.column(a.symbol + "$hi")
                lo = acc.column(a.symbol + "$lo")
                cols.append(Column(lo.values, lo.validity, hi.values))
            elif a.fn in _VARIANCE_FNS:
                n = acc.column(a.symbol + "$cnt").values.astype(jnp.float64)
                s = acc.column(a.symbol + "$sum").values
                ss = acc.column(a.symbol + "$sumsq").values
                pop = a.fn.endswith("_pop")
                ok = n > (0 if pop else 1)
                nn = jnp.where(n > 0, n, 1.0)
                denom = jnp.where(ok, n if pop else n - 1, 1.0)
                var = jnp.maximum((ss - s * s / nn) / denom, 0.0)
                vals = jnp.sqrt(var) if a.fn.startswith("stddev") else var
                cols.append(Column(vals, ok))
            elif a.fn in ("covar_pop", "covar_samp"):
                n = acc.column(a.symbol + "$cnt").values.astype(jnp.float64)
                sx = acc.column(a.symbol + "$sx").values
                sy = acc.column(a.symbol + "$sy").values
                sxy = acc.column(a.symbol + "$sxy").values
                pop = a.fn.endswith("_pop")
                ok = n > (0 if pop else 1)
                nn = jnp.where(n > 0, n, 1.0)
                denom = jnp.where(ok, n if pop else n - 1, 1.0)
                cols.append(Column((sxy - sx * sy / nn) / denom, ok))
            elif a.fn == "corr":
                n = acc.column(a.symbol + "$cnt").values.astype(jnp.float64)
                sx = acc.column(a.symbol + "$sx").values
                sy = acc.column(a.symbol + "$sy").values
                sxy = acc.column(a.symbol + "$sxy").values
                sxx = acc.column(a.symbol + "$sxx").values
                syy = acc.column(a.symbol + "$syy").values
                vx = n * sxx - sx * sx
                vy = n * syy - sy * sy
                ok = (n > 1) & (vx > 0) & (vy > 0)
                denom = jnp.sqrt(jnp.where(ok, vx * vy, 1.0))
                cols.append(Column((n * sxy - sx * sy) / denom, ok))
            elif a.fn == "geometric_mean":
                n = acc.column(a.symbol + "$cnt").values.astype(jnp.float64)
                ls = acc.column(a.symbol + "$lsum").values
                ok = n > 0
                cols.append(Column(jnp.exp(ls / jnp.where(ok, n, 1.0)), ok))
            elif a.fn in ("bool_and", "bool_or"):
                c = acc.column(a.symbol)
                cols.append(Column(c.values.astype(bool), c.validity))
            elif a.fn == "checksum":
                c = acc.column(a.symbol)
                cols.append(Column(c.values, None))
            elif _registered_aggregate_fn(a.fn) is not None:
                udf = _registered_aggregate_fn(a.fn)
                states = {s: acc.column(a.symbol + s).values
                          for s, _, _ in udf.states}
                vals = udf.finalize(states)
                cnt = next((s for s, op, _ in udf.states
                            if op == "count_add"), None)
                if cnt is not None:
                    ok = acc.column(a.symbol + cnt).values > 0
                else:
                    first = udf.states[0][0]
                    ok = acc.column(a.symbol + first).validity
                cols.append(Column(vals.astype(a.type.dtype), ok))
            else:
                # count/sum/min/max/arbitrary/count_if + materialized
                # (approx_percentile/max_by/min_by) pass through
                c = acc.column(a.symbol)
                cols.append(c)
            names.append(a.symbol)
            types.append(a.type)
        live = acc.live
        if not key_syms:
            # SQL: global aggregation yields exactly one row even when every
            # input row was filtered out (count=0, sums NULL)
            live = live.at[0].set(True)
        return Batch(names, types, cols, live, acc.dicts)

    return finalize


# -- joins ------------------------------------------------------------------


def _cat_batches(bs: List[Batch]) -> Batch:
    names = bs[0].names
    types = bs[0].types
    caps = [b.capacity for b in bs]
    cols = [
        concat_columns([b.columns[i] for b in bs], caps)
        for i in range(len(names))
    ]
    live = jnp.concatenate([b.live for b in bs])
    dicts = {}
    for b in bs:
        dicts.update(b.dicts)
    return Batch(names, types, cols, live, dicts)


# module-level jit wrappers: trace caches persist across queries
_JIT_CAT = jax.jit(_cat_batches)
_JIT_COMPACT = jax.jit(compact, static_argnames=("out_cap",))
_JIT_LIMIT = jax.jit(limit_batch)


def _unify_batch_dicts(batches: List[Batch]) -> List[Batch]:
    """Before concatenating, re-encode any string column whose batches
    carry DIFFERENT Dictionary objects against their merged dictionary
    (code equality must mean string equality across the result — the
    DictionaryBlock id-canonicalization of the reference). Batches from
    one table share dictionary objects, so this is a no-op on hot paths."""
    from presto_tpu.dictionary import Dictionary

    todo = {}
    for name in batches[0].names:
        ds = [b.dicts.get(name) for b in batches]
        present = [d for d in ds if d is not None]
        if not present or all(d is present[0] for d in present):
            continue
        m = present[0]
        for d in present[1:]:
            if d is not m:
                m = Dictionary.merge(m, d)
        todo[name] = m
    if not todo:
        return batches
    out = []
    for b in batches:
        cols = list(b.columns)
        dicts = dict(b.dicts)
        for name, m in todo.items():
            d = b.dicts.get(name)
            dicts[name] = m
            if d is None or d is m:
                continue
            i = b.names.index(name)
            remap = jnp.asarray(d.map_to(m))
            c = cols[i]
            cols[i] = Column(remap[c.values.astype(jnp.int32) + 1], c.validity)
        out.append(Batch(b.names, b.types, cols, b.live, dicts))
    return out


def _collect_concat(stream: Iterator[Batch]) -> Optional[Batch]:
    batches = list(stream)
    if not batches:
        return None
    if len(batches) == 1:
        return batches[0]
    return _JIT_CAT(_unify_batch_dicts(batches))


# ---------------------------------------------------------------------------
# radix-partitioned breakers (ops/radix.py drivers)


def _radix_tag(b: Batch, num_partitions: int, key_names) -> Optional[int]:
    """Radix id if `b` arrived partition-aligned from an OUT_HASH sink with
    a compatible decomposition (same partition count, same key symbols),
    else None — the consumer then re-partitions on device as usual."""
    tag = getattr(b, "radix", None)
    if tag is None:
        return None
    r, total, keys = tag
    if int(total) == num_partitions and tuple(keys) == tuple(key_names):
        return int(r)
    return None


def _untag_batch(b: Batch) -> Batch:
    """Plain Batch from a (possibly) tagged one. Tagged batches are a
    serde-level subclass that is NOT pytree-registered — they must never
    reach a jitted function."""
    if type(b) is Batch:
        return b
    return Batch(b.names, b.types, b.columns, b.live, b.dicts)


def _radix_splitter(node: PlanNode, ctx: ExecContext, key_names, P: int,
                    jkey: str):
    """Per-node split driver: batch → iterator of (partition, sub-batch,
    live rows). One jitted stable sort by radix id per input capacity, a
    P-element count transfer to the host, then one jitted window gather
    per occupied partition — shapes keyed only by (capacity, pow2 bucket).
    """
    from presto_tpu.ops.radix import radix_perm, radix_window_perm

    keys = tuple(key_names)
    jsort = _node_jit(node, jkey + "radix_perm",
                      lambda: (lambda b: radix_perm(b, keys, P)))
    jwin = _node_jit(node, jkey + "radix_window", lambda: radix_window_perm,
                     static_argnames=("bucket",))
    tr = ctx.tracer

    def split(b: Batch):
        t0 = time.time()
        sperm, counts = jsort(b)
        cnts = np.asarray(counts)  # the host-side slicing boundary
        starts = np.concatenate([[0], np.cumsum(cnts)])
        if tr.enabled:
            tr.record("radix_split", "radix_split", t0, time.time(),
                      partitions=int((cnts > 0).sum()), rows=int(cnts.sum()))
        for p in range(P):
            n = int(cnts[p])
            if n == 0:
                continue
            bucket = round_up_capacity(n)
            yield p, jwin(b, sperm, np.int32(starts[p]), np.int32(n),
                          bucket=bucket), n

    return split


def _host_concat(batches: List[Batch]) -> Optional[Batch]:
    """Live rows of many fixed-capacity batches packed into ONE batch of
    pow2 capacity, assembled on the host. The radix join uses this to turn
    a partition's sub-batch list into its build input: a device-side
    concat would compile one program per (cap_1..cap_k) combination —
    exactly the shape storm radix exists to avoid — while the host pays
    one round trip on the (smaller) build side."""
    batches = [b for b in batches if b is not None]
    if not batches:
        return None
    batches = _unify_batch_dicts(batches)
    first = batches[0]
    sel = [np.flatnonzero(np.asarray(b.live)) for b in batches]
    total = int(sum(len(s) for s in sel))
    cap = round_up_capacity(total)

    def stack(planes, fill, width=None):
        """Concatenate the live rows of one plane across batches; `fill`
        synthesizes it for batches where it is None (same defaults as
        concat_columns); 2D planes align on `width`."""
        if all(p is None for p in planes):
            return None
        parts = []
        for p, s in zip(planes, sel):
            a = fill(len(s)) if p is None else np.asarray(p)[s]
            if width is not None and a.ndim == 2 and a.shape[1] < width:
                a = np.concatenate(
                    [a, np.zeros((a.shape[0], width - a.shape[1]), a.dtype)],
                    axis=1)
            parts.append(a)
        out = np.concatenate(parts, axis=0)
        pad = np.zeros((cap - total,) + out.shape[1:], out.dtype)
        return jnp.asarray(np.concatenate([out, pad], axis=0))

    cols = []
    for i in range(len(first.names)):
        cs = [b.columns[i] for b in batches]
        twod = any(c.values.ndim == 2 for c in cs)
        w = max(c.values.shape[1] for c in cs) if twod else None
        vals = stack([c.values for c in cs], None, w)
        valid = stack([c.validity for c in cs],
                      lambda n: np.ones(n, bool))
        hi = stack([c.hi for c in cs], lambda n: np.zeros(n, np.int64))
        sizes = stack([c.sizes for c in cs], lambda n: np.zeros(n, np.int32))
        evalid = stack([c.evalid for c in cs],
                       lambda n: np.ones((n, w), bool), w)
        kd = next((np.asarray(c.keys).dtype for c in cs
                   if c.keys is not None), None)
        keys = stack([c.keys for c in cs],
                     lambda n: np.zeros((n, w), kd), w)
        cols.append(Column(vals, valid, hi, sizes, evalid, keys))
    live = np.zeros(cap, bool)
    live[:total] = True
    dicts = {}
    for b in batches:
        dicts.update(b.dicts)
    return Batch(first.names, first.types, cols, jnp.asarray(live), dicts)


def _radix_join(node: HashJoin, ctx: ExecContext,
                probe_stream: Iterator[Batch],
                build_stream: Iterator[Batch], chain) -> Iterator[Batch]:
    """Radix-partitioned hash join: both sides split by the top bits of
    the content hash (ops/radix.py), each partition built + probed at a
    small bounded capacity by its own _JoinProber. Partitions whose build
    side exceeds `join_spill_budget_bytes` hybrid-spill: their batches go
    to host spill files (serde page format) and are joined one-at-a-time
    after the in-memory partitions, so an oversized build degrades to disk
    instead of recompiling at ever-larger capacities."""
    from presto_tpu.memory import batch_device_bytes
    from presto_tpu.obs import metrics as _obs_metrics
    from presto_tpu.scan import metrics as _scan_metrics
    from presto_tpu.spiller import SpillFile

    cfg = ctx.config
    P = _hbo_radix_partitions(node, ctx, "join_build",
                              cfg.radix_partitions)
    budget = cfg.join_spill_budget_bytes
    tr = ctx.tracer
    split_b = _radix_splitter(node, ctx, node.right_keys, P, "radixb_")
    split_p = _radix_splitter(node, ctx, node.left_keys, P, "radixp_")

    def _stat(key, delta):
        ctx.stats[key] = ctx.stats.get(key, 0) + delta

    parts: List[List[Batch]] = [[] for _ in range(P)]
    pbytes = [0] * P
    prows = [0] * P
    bfiles: Dict[int, "SpillFile"] = {}
    pfiles: Dict[int, "SpillFile"] = {}

    def spill_build_partition(p):
        """Move partition p's resident build batches to a host spill file;
        later build rows for p append straight to it."""
        f = ctx.spill_manager.spill_file(f"radix-join-build-p{p}")
        ctx.track_spill(f)
        for bb in parts[p]:
            f.append(bb)
        parts[p] = []
        pbytes[p] = 0
        bfiles[p] = f
        _stat("radix.partitions_spilled", 1)
        _scan_metrics.record("radix_partitions_spilled", 1)

    rev = {"flag": False}

    def _revoke(_need):
        # pool-pressure REQUEST honored at the next batch boundary
        rev["flag"] = True
        return 0

    if ctx.config.spill_enabled:
        ctx.memory_pool.add_revoker(_revoke)
    try:
        # `join_build`: the drain and split of the build stream and the P
        # partitions' tables (a hybrid-spilled partition's comes later)
        with ctx.tracer.phase("join_build") as build_ph:
            for b in build_stream:
                build_ph.items += 1
                rid = _radix_tag(b, P, node.right_keys)
                if rid is not None:
                    ub = _untag_batch(b)
                    # num_live stays a device scalar — summed lazily so the
                    # aligned fast path adds no per-page sync
                    subs = [(rid, ub, ub.num_live())]
                    _stat("radix.aligned_batches", 1)
                    _scan_metrics.record("radix_aligned_batches", 1)
                else:
                    subs = split_b(_untag_batch(b))
                for p, sub, n in subs:
                    prows[p] = prows[p] + n
                    if p in bfiles:
                        bfiles[p].append(sub)
                        continue
                    parts[p].append(sub)
                    pbytes[p] += batch_device_bytes(sub)
                    if budget is not None and pbytes[p] > budget:
                        spill_build_partition(p)
                if rev["flag"]:
                    # revoke ladder asked for memory back: spill the LARGEST
                    # resident build partition down to host
                    rev["flag"] = False
                    resident = [(pp, pbytes[pp]) for pp in range(P)
                                if parts[pp] and pp not in bfiles]
                    if resident:
                        pp, nbytes = max(resident, key=lambda t: t[1])
                        spill_build_partition(pp)
                        _note_spill_revoke(node, ctx, nbytes)
            with ctx.tracer.phase("host_sync:join_build_rows"):
                prows = [int(r) for r in prows]
            for p in range(P):
                if prows[p]:
                    _obs_metrics.RADIX_PARTITION_ROWS.observe(
                        prows[p], plane="worker", side="build")

            # the chain is applied before the split
            ident = lambda bb: bb  # noqa: E731
            probers: Dict[int, _JoinProber] = {}
            for p in range(P):
                if p in bfiles:
                    continue
                build_in = _host_concat(parts[p])
                parts[p] = []
                probers[p] = _JoinProber(node, ctx, build_in, ident,
                                         jkey="radix_", fanout_scan=16)

        jchain = _node_jit(node, "radix_pchain", lambda: chain)
        for raw in probe_stream:
            rid = _radix_tag(raw, P, node.left_keys)
            if rid is not None:
                _stat("radix.aligned_batches", 1)
                _scan_metrics.record("radix_aligned_batches", 1)
                subs = [(rid, jchain(_untag_batch(raw)), 0)]
            else:
                subs = split_p(jchain(_untag_batch(raw)))
            # dispatch wave: start every partition of this batch before
            # syncing any, so the P per-partition count round trips to the
            # host overlap instead of serializing
            pend = []
            for p, sub, _n in subs:
                if p in bfiles:
                    f = pfiles.get(p)
                    if f is None:
                        f = pfiles[p] = ctx.spill_manager.spill_file(
                            f"radix-join-probe-p{p}")
                        ctx.track_spill(f)
                    f.append(sub)
                else:
                    pend.append((p, probers[p].probe_start(sub)))
            for p, st in pend:
                yield from probers[p].probe_finish(st)
        for p in sorted(probers):
            yield from probers[p].tail()

        # hybrid-spilled partitions, one resident at a time
        for p in sorted(bfiles):
            t0 = time.time()
            build_in = _host_concat(list(bfiles[p].read()))
            prober = _JoinProber(node, ctx, build_in, ident,
                                 jkey="radix_", fanout_scan=16)
            pf = pfiles.get(p)
            if pf is not None:
                for sub in pf.read():
                    yield from prober.probe_batch(sub)
            yield from prober.tail()
            if tr.enabled:
                tr.record("radix_spill_replay", "radix_spill_replay", t0,
                          time.time(), partition=p, rows=prows[p])
    finally:
        if ctx.config.spill_enabled:
            ctx.memory_pool.remove_revoker(_revoke)
        spilled = (sum(f.bytes for f in bfiles.values())
                   + sum(f.bytes for f in pfiles.values()))
        if spilled:
            _stat("radix.spill_bytes", spilled)
            _scan_metrics.record("radix_spill_bytes", spilled)
            ctx.spill_manager.record(spilled)
            _obs_metrics.SPILLED_BYTES.observe(
                float(spilled), plane="worker", side="build")
        for f in bfiles.values():
            f.close()
        for f in pfiles.values():
            f.close()


def _execute_join(node: HashJoin, ctx: ExecContext) -> Iterator[Batch]:
    from presto_tpu.memory import LocalMemoryContext, batch_device_bytes

    if node.colocated and ctx.lifespan is None:
        # grouped (lifespan) execution over a colocated bucketed join
        # (FixedSourcePartitionedScheduler driving lifespans): this task
        # sweeps its buckets sequentially — each pass builds from ONE
        # bucket of the build table and probes the SAME bucket of the
        # probe table, so peak memory is one bucket's build side, and no
        # exchange ever moves a row. Nested colocated joins execute
        # within the sweep (ctx.lifespan already set).
        try:
            for b in range(ctx.task_index, node.colocated, ctx.n_tasks):
                ctx.lifespan = b
                yield from _execute_join(node, ctx)
        finally:
            ctx.lifespan = None
        return

    probe_stream, chain = _fused_child(node.left, ctx)
    build_stream = execute_node(node.right, ctx)

    if ctx.config.radix_partitions > 1:
        yield from _radix_join(node, ctx, probe_stream, build_stream, chain)
        return

    yield from _join_with_spill(node, ctx, probe_stream, build_stream, chain)


def _join_with_spill(node: HashJoin, ctx: ExecContext,
                     probe_stream: Iterator[Batch],
                     build_stream: Iterator[Batch], chain,
                     jkey: str = "") -> Iterator[Batch]:
    """One binary hash join over already-opened child streams. Collect the
    build side with memory accounting; crossing the revoke threshold (or a
    pool-pressure revoke request) switches to the partitioned-spill path
    (HashBuilderOperator's SPILLING_INPUT state +
    GenericPartitioningSpiller): both sides are hash-partitioned to disk
    on the join keys and each bucket is joined independently — with the
    dynamic hybrid-hash escape hatches (mid-build growth, recursive
    repartitioning, per-partition role reversal) when the partition-count
    estimate proves wrong. Also the per-leg engine of the multiway
    executor's binary-cascade fallback (jkey='mwb{i}_'), where the child
    streams are cascade intermediates rather than plan children."""
    from presto_tpu.memory import LocalMemoryContext, batch_device_bytes

    mctx = LocalMemoryContext(ctx.memory_pool, "join-build")
    build_batches: List[Batch] = []
    bspiller = None
    pspiller = None
    est_p = ctx.config.spill_partitions
    can_spill = ctx.config.spill_enabled
    rev = {"flag": False}

    def _revoke(_need: int) -> int:
        # flag only — the spill happens at the next build-batch boundary
        # (spilling synchronously inside pool.reserve would re-enter the
        # ledger mid-update)
        rev["flag"] = True
        return 0

    if can_spill:
        ctx.memory_pool.add_revoker(_revoke)
    try:
        # the host's share of the build: the drain of the build stream, the
        # concat and the table's sort (or the hand-over to the spiller);
        # the program calls and the waits on the exchange are its children
        with ctx.tracer.phase("join_build") as build_ph:
            for b in build_stream:
                build_ph.items += 1
                nb = batch_device_bytes(b)
                if can_spill and (rev["flag"] or ctx.should_spill(nb)):
                    est_p = _hbo_spill_partitions(node, ctx, "spill_join",
                                                  ctx.config.spill_partitions)
                    bspiller = ctx.spill_manager.partitioning_spiller(
                        node.right_keys, est_p, "join-build",
                        partition_budget_bytes=_spill_replay_budget(ctx),
                        max_depth=max(0, ctx.config.spill_max_depth),
                        on_grow=lambda child, pp: _note_spill_repartition(
                            node, ctx, child, pp),
                        on_spill=_inflight_spill_hook(node, ctx))
                    ctx.track_spill(bspiller)
                    for bb in build_batches:
                        bspiller.spill(bb)
                    if rev["flag"]:
                        _note_spill_revoke(node, ctx, mctx.bytes)
                        rev["flag"] = False
                    build_batches = []
                    mctx.set_bytes(0)
                    bspiller.spill(b)
                    for bb in build_stream:
                        build_ph.items += 1
                        bspiller.spill(bb)
                    break
                build_batches.append(b)
                mctx.set_bytes(mctx.bytes + nb)
            if bspiller is None:
                prober = _JoinProber(
                    node, ctx, _collect_concat(iter(build_batches)), chain,
                    jkey=jkey)

        if bspiller is None:
            yield from prober.probe_all(probe_stream)
            return

        # spill the (chained) probe side partitioned by the probe keys —
        # co-partitioned with the build because both sides hash the key
        # CONTENT (string keys by dictionary-independent value hash) with
        # the same divisor/fanout schedule
        pspiller = ctx.spill_manager.partitioning_spiller(
            node.left_keys, bspiller.n_partitions, "join-probe")
        ctx.track_spill(pspiller)
        jchain = _node_jit(node, jkey + "spill_chain", lambda: chain)
        for pb in probe_stream:
            pspiller.spill(jchain(pb))
        # mid-build growth may have split build partitions: mirror the
        # split tree onto the probe side so replay pairs leaf-for-leaf
        pspiller.align_to(bspiller)
        yield from _replay_spilled_join(node, ctx, bspiller, pspiller, mctx)
    finally:
        if can_spill:
            ctx.memory_pool.remove_revoker(_revoke)
        if bspiller is not None:
            spilled = bspiller.spilled_bytes + (
                pspiller.spilled_bytes if pspiller is not None else 0)
            ctx.spill_manager.record(spilled)
            _record_spill_done(node, ctx, "spill_join", est_p, spilled,
                               side="build")
            bspiller.close()
        if pspiller is not None:
            pspiller.close()
        mctx.set_bytes(0)


def _reversed_join_shim(node: HashJoin) -> HashJoin:
    """The same inner join with build/probe roles swapped. Sound only for
    kind == 'inner' with no residual (match semantics are symmetric there;
    outer joins and residual filters are side-dependent). Cached on the
    node so _node_jit reuses one shim's program entries across partitions;
    build_unique is dropped — uniqueness of the original build side says
    nothing about the reversed one."""
    shim = node.__dict__.get("_reversed_shim")
    if shim is None:
        shim = HashJoin(kind="inner", left=node.right, right=node.left,
                        left_keys=list(node.right_keys),
                        right_keys=list(node.left_keys),
                        residual=None, build_unique=False)
        node.__dict__["_reversed_shim"] = shim
    return shim


def _reorder_output(b: Batch, names: List[str]) -> Batch:
    """Columns of b in `names` order — a reversed-role join emits
    right-then-left columns while the consumer contracted for the node's
    left-then-right."""
    return Batch(list(names), [b.type_of(n) for n in names],
                 [b.column(n) for n in names], b.live, b.dicts)


def _replay_spilled_join(node: HashJoin, ctx: ExecContext,
                         bspiller, pspiller, mctx) -> Iterator[Batch]:
    """Replay a co-partitioned spilled join leaf-by-leaf with the dynamic
    hybrid-hash degradation ladder: a leaf whose nominal build side misses
    the replay budget first tries ROLE REVERSAL (build from the smaller
    probe side — inner joins without residuals only), then RECURSIVE
    REPARTITIONING by the next hash bits (both sides split in lockstep so
    leaves stay co-partitioned), and only at the depth bound fails with a
    structured SPILL_LIMIT_EXCEEDED."""
    from presto_tpu.memory import batch_device_bytes
    from presto_tpu.scan import metrics as _scan_metrics
    from presto_tpu.spiller import SpillLimitExceeded

    budget = _spill_replay_budget(ctx)
    max_depth = max(0, ctx.config.spill_max_depth)
    st = _spill_stats_for(node, ctx)
    out_names = [s for s, _ in node.output]
    ident = lambda b: b  # noqa: E731 — chain already applied pre-spill

    def replay_leaf(bsp, psp, p: int) -> Iterator[Batch]:
        bc, pc = bsp.children.get(p), psp.children.get(p)
        if bc is not None or pc is not None:
            # one side split here (mid-build growth or an earlier replay
            # pass): mirror so both sides expose the identical leaf set
            if bc is None:
                bc = bsp.grow_partition(p, fanout=pc.n_partitions)
            if pc is None:
                pc = psp.grow_partition(p, fanout=bc.n_partitions)
            bc.align_to(pc)
            pc.align_to(bc)
            for q in range(bc.n_partitions):
                yield from replay_leaf(bc, pc, q)
            return

        bb = bsp.partition_est_bytes(p)
        pb = psp.partition_est_bytes(p)
        reversed_ = (budget is not None and bb > budget and pb < bb
                     and node.kind == "inner" and node.residual is None)
        build_bytes = pb if reversed_ else bb
        if budget is not None and build_bytes > budget:
            # even the smaller side misses the budget: split this leaf by
            # the NEXT hash bits and recurse — bounded by the depth cap
            if bsp.depth >= max_depth:
                raise SpillLimitExceeded(
                    f"join spill partition is {build_bytes} bytes against a "
                    f"{budget}-byte replay budget at max recursion depth "
                    f"{max_depth} (join keys too skewed to split further)")
            sub_b = bsp.grow_partition(p)
            sub_p = psp.grow_partition(p, fanout=sub_b.n_partitions)
            for q in range(sub_b.n_partitions):
                yield from replay_leaf(sub_b, sub_p, q)
            return

        if reversed_:
            st["reversed"] += 1
            ctx.stats["spill.role_reversals"] = (
                ctx.stats.get("spill.role_reversals", 0) + 1)
            _scan_metrics.record("spill_role_reversals", 1)
            if ctx.tracer.enabled:
                t = time.time()
                ctx.tracer.record(
                    "spill_role_reversal", "spill_role_reversal", t, t,
                    node=type(node).__name__, partition=int(p),
                    build_bytes=int(pb), probe_bytes=int(bb))
            build_sp, probe_sp = psp, bsp
            jnode, jkey = _reversed_join_shim(node), "spill_rev_"
        else:
            build_sp, probe_sp = bsp, psp
            jnode, jkey = node, "spill_"

        st["partitions"] += 1
        st["depth"] = max(st["depth"], bsp.depth)
        build_in = _collect_concat(build_sp.read_partition(p))
        if build_in is None and node.kind == "inner":
            return
        # account the materialized bucket — a skewed partition that
        # exceeds the pool limit must fail cleanly, not OOM silently
        if build_in is not None:
            mctx.set_bytes(batch_device_bytes(build_in))
        out = _join_probe(jnode, ctx, build_in,
                          probe_sp.read_partition(p), ident, jkey=jkey)
        if reversed_:
            for ob in out:
                yield _reorder_output(ob, out_names)
        else:
            yield from out
        mctx.set_bytes(0)

    for p in range(bspiller.n_partitions):
        yield from replay_leaf(bspiller, pspiller, p)


def _execute_index_join(node, ctx: ExecContext) -> Iterator[Batch]:
    """Index join (reference: operator/index/IndexLoader.java driving a
    connector ConnectorIndex): each probe batch's live key values are fed
    to the connector's keyed lookup; only the matching build rows come
    back, and the regular sorted-hash probe joins them batch-wise. No
    full-table scan, no full build — the host sync to extract keys is the
    price (the reference pays the same in IndexLoader's key snapshots)."""
    conn = ctx.catalog.connectors[node.catalog]
    handle = conn.get_table(node.table)
    idx = conn.get_index(handle, node.index_key_cols)
    if idx is None:
        raise RuntimeError(
            f"connector {node.catalog!r} no longer provides an index over "
            f"{node.index_key_cols} on {node.table!r}")

    # shim HashJoin so _join_probe's machinery (and its per-node jit
    # caches) applies unchanged: the 'right' child is a never-executed
    # scan carrying the index-side symbols
    shim = node.__dict__.get("_probe_shim")
    if shim is None:
        inv = {c: s for s, c in node.assignments.items()}
        shim = HashJoin(
            kind=node.kind, left=node.left,
            right=TableScan(catalog=node.catalog, table=node.table,
                            assignments=dict(node.assignments),
                            output=list(node.index_output)),
            left_keys=list(node.left_keys),
            right_keys=[inv[c] for c in node.index_key_cols],
            build_unique=node.build_unique,
        )
        node.__dict__["_probe_shim"] = shim

    probe_stream, chain = _fused_child(node.left, ctx)
    jit_chain = _node_jit(node, "index_chain", lambda: chain)
    ident = lambda b: b  # noqa: E731 — chain applied before key extraction
    src_cols = [node.assignments[s] for s, _ in node.index_output]
    syms = [s for s, _ in node.index_output]

    for b in probe_stream:
        b = jit_chain(b)
        live = np.asarray(b.live)
        valid = live.copy()
        key_vals = {}
        for sym, col_name in zip(node.left_keys, node.index_key_cols):
            c = b.column(sym)
            if c.validity is not None:
                valid &= np.asarray(c.validity)
            vals = np.asarray(c.values)
            d = b.dicts.get(sym)
            if d is not None:
                codes = vals.astype(np.int64)
                safe = np.clip(codes, 0, max(len(d) - 1, 0))
                vals = np.asarray(d.values, dtype=object)[safe]
            key_vals[col_name] = vals
        key_vals = {c: v[valid] for c, v in key_vals.items()}
        looked = idx.lookup(key_vals, src_cols)
        build = Batch(syms, [t for _, t in node.index_output],
                      [looked.column(c) for c in src_cols], looked.live,
                      {s: looked.dicts[c] for s, c in zip(syms, src_cols)
                       if c in looked.dicts})
        yield from _join_probe(shim, ctx, build, iter([b]), ident,
                               jkey="index_")


def _join_plan_cdt(node) -> tuple:
    """Per-key-position pairwise-promoted compare dtypes of an equi-join,
    derived from PLAN output types alone (ops/join.join_compare_dtypes is
    the batch-side twin). Purely structural, so probe closures computing
    it stay shareable across the structural program cache."""
    ltypes = dict(node.left.output)
    rtypes = dict(node.right.output)
    return tuple(
        jnp.result_type(jnp.dtype(rtypes[rk].dtype),
                        jnp.dtype(ltypes[lk].dtype))
        for lk, rk in zip(node.left_keys, node.right_keys))


def _observe_build_table(ctx: "ExecContext", table) -> float:
    """A built join table's live row count, read once from the device; the
    sorted engine's bucket-search steps and unique-probe verify width ride
    in the same transfer. A sorted build is one occurrence each of
    `join_build_table` (`items` = its live rows), `join_search` and
    `join_verify` (`items` = its steps, its width)."""
    with ctx.tracer.phase("host_sync:join_build_rows"):
        rows, steps, width = table_stats(table)
    if steps is not None:
        from presto_tpu.scan import metrics as _scan_metrics

        for phase, counter, items in (
                ("join_build_table", "join_build_rows", int(rows)),
                ("join_search", "join_search_steps", steps),
                ("join_verify", "join_verify_width", width)):
            with ctx.tracer.phase(phase, items=items):
                pass
            _scan_metrics.record(counter, items)
    return float(rows)


class _PendingJoinOutput:
    """What a unique probe knows of one batch before the join's output is
    gathered: the chained probe batch, each row's build index and match
    bit, and `count`, the rows the join hands on, as a device scalar from
    the probe's own program. `emit(n)` gathers the output once the count
    is on the host (`_merging_output`); `_gathered` does where nobody
    reads it. One occurrence of `join_emit` either way."""

    __slots__ = ("prober", "pb", "idx", "matched", "count")

    def __init__(self, prober, pb, idx, matched, count):
        self.prober, self.pb, self.idx = prober, pb, idx
        self.matched, self.count = matched, count

    def emit(self, n: Optional[int]):
        """(batch, compacted) once the live count is `n`, or is not known
        (None). Nothing is gathered for no row; an inner join's sparse
        output (`2 n < capacity`, `_merging_output`'s rule) is gathered
        compacted at `round_up_capacity(n)` lanes; everything else —
        dense, LEFT / FULL, a count nobody read — at the probe's capacity
        with the probe's columns handed on."""
        if n == 0:
            return None, False
        prober, cap = self.prober, self.pb.capacity
        sparse = (n is not None and prober.node.kind == "inner"
                  and 2 * n < cap)
        out_cap = min(round_up_capacity(n), cap) if sparse else None
        with _emit_phase(prober.ctx.tracer, out_cap or cap):
            out = prober.jemit(prober.table, self.pb, self.idx,
                               self.matched, out_cap=out_cap)
        if prober.node.kind != "inner":
            _outer_phase(prober.ctx.tracer, cap)
        return out, sparse


def _gathered(b) -> Batch:
    """`b` as a `Batch`: a pending join output gathered at the probe's
    capacity, its count unread."""
    return b.emit(None)[0] if isinstance(b, _PendingJoinOutput) else b


class _JoinProber:
    """One build table, probed incrementally.

    The body of the classic `_join_probe` split into (construct,
    probe_batch, tail) so the radix driver can hold P probers at once and
    feed each its per-partition probe sub-batches as they arrive — a
    probe stream can only be consumed once, so probing cannot restart per
    partition. `probe_batch` yields the matches for one probe batch
    (LEFT/FULL null-extension included); `tail` yields the FULL OUTER
    build remainder. With a unique build what a probe batch yields is one
    `_PendingJoinOutput`: the probe's program stops at each row's build
    index and the count of rows handed on, and its `emit` gathers the
    output once that count is read, at the size of what matched.
    """

    def __init__(self, node: HashJoin, ctx: ExecContext,
                 build_in: Optional[Batch], chain, jkey: str = "",
                 fanout_scan: int = 8):
        # jkey prefixes the per-node jit-cache keys: the spilled/radix paths
        # probe with an identity chain and must not reuse closures compiled
        # with the real one
        self.node, self.ctx = node, ctx
        lsyms = self.lsyms = [n for n, _ in node.left.output]
        rsyms = self.rsyms = [n for n, _ in node.right.output]
        self.overflow_rows = 0
        # probe-selectivity accumulators (device scalars, summed lazily;
        # one host sync at tail): output rows / probe rows feeds the
        # join_probe_sel HBO site for choose_join_mode
        self._n_probe = jnp.zeros((), jnp.int64)
        self._n_out = jnp.zeros((), jnp.int64)
        self.empty = build_in is None and node.kind == "inner"
        if self.empty:
            return  # empty build side: no output
        if build_in is None:
            build_in = Batch(
                rsyms,
                [t for _, t in node.right.output],
                [Column(jnp.zeros(128, t.dtype), None) for _, t in node.right.output],
                jnp.zeros(128, bool),
                {},
            )

        engine = _breaker_engine_choice(node, ctx)
        # pairwise-promoted compare dtypes come from the PLAN's output
        # types on both sides, so the probe closures (shared across the
        # radix path's P probers, never seeing a build batch) agree with
        # hash_build_side's encode. An executed batch that deviates from
        # its plan-declared dtype would silently mis-encode — fall back.
        ltypes = dict(node.left.output)
        probe_dtypes = tuple(
            jnp.dtype(ltypes[lk].dtype) for lk in node.left_keys)
        if engine == "hash" and join_compare_dtypes(
                build_in, tuple(node.right_keys),
                probe_dtypes) != _join_plan_cdt(node):
            engine = "sort"
            node.__dict__["_breaker_engine"] = "sort"
            node.__dict__["_breaker_engine_why"] = (
                "build batch dtypes deviate from plan types")
        self.engine = engine
        self.fanout_scan = fanout_scan
        _ek = lambda k: _engine_key(k, engine)  # noqa: E731
        self._ek, self._jkey, self._chain = _ek, jkey, chain

        if engine == "hash":
            table = _node_jit(
                node, _ek("build"), lambda: hash_build_side,
                static_argnames=("key_names", "probe_dtypes"))(
                build_in, tuple(node.right_keys), probe_dtypes)
        else:
            table = _node_jit(node, "build", lambda: build_side, static_argnames=("key_names",))(
                build_in, tuple(node.right_keys)
            )
        self.table = table
        self._hbo_observe_build()

        self.want_full = node.kind == "full"
        build_cap = int(table.hashes.shape[0])
        self.bm = jnp.zeros(build_cap, bool) if self.want_full else None

        def build_remainder_fn(t: BuildTable, bm):
            """FULL OUTER tail: build rows no probe row matched, with NULL
            probe columns (reference: LookupJoinOperators.fullOuterJoin's
            lookup-outer positions pass)."""
            ltypes = dict(node.left.output)
            names, types, cols = [], [], []
            cap = t.hashes.shape[0]
            for c in lsyms:
                names.append(c)
                types.append(ltypes[c])
                cols.append(Column(jnp.zeros(cap, ltypes[c].dtype),
                                   jnp.zeros(cap, bool)))
            for c in rsyms:
                names.append(c)
                types.append(t.batch.type_of(c))
                cols.append(t.batch.column(c))
            # orig_live, not batch.live: NULL-key build rows were live-killed
            # for matching but a FULL JOIN must still emit them unmatched
            live = t.orig_live & ~bm
            return Batch(names, types, cols, live,
                         {c: t.batch.dicts[c] for c in rsyms if c in t.batch.dicts})

        self.jremainder = _node_jit(node, jkey + "full_tail",
                                    lambda: build_remainder_fn)

        if node.build_unique:

            def probe_fn(table, pb: Batch, bm):
                """Which build row each probe row matches, and how many
                rows the join will hand on: nothing of the output is
                gathered before that count is read (`emit_fn`)."""
                pb = chain(pb)
                pba = align_probe_strings(pb, tuple(node.left_keys), table, tuple(node.right_keys))
                if engine == "hash":
                    idx, matched = hash_probe_unique(
                        table, pba, tuple(node.left_keys),
                        _join_plan_cdt(node))
                else:
                    idx, matched = probe_unique(table, pba, tuple(node.left_keys), tuple(node.right_keys))
                if bm is not None:
                    bm = bm.at[idx].max(matched & pb.live, mode="drop")
                # left/full outer keep every probe row
                keep = pb.live & matched if node.kind == "inner" else pb.live
                return (pb, idx, matched, bm,
                        jnp.sum(pb.live).astype(jnp.int64), jnp.sum(keep))

            def emit_fn(table, pb: Batch, idx, matched, out_cap):
                """The join's output for one probed batch. `out_cap` None:
                at the probe's capacity, the probe's columns handed on as
                they are and the build's gathered through `idx`. An
                `out_cap` (inner joins): compacted to that many lanes,
                each plane gathered once through the head of the
                compaction order."""
                live = pb.live & matched if node.kind == "inner" else pb.live
                rows = None
                if out_cap is not None:
                    rows = compact_permutation(live, out_cap)
                    idx, live = idx[rows], live[rows]
                out = gather_join_output(pb, table, rows, idx, live,
                                         lsyms, rsyms)
                if node.kind == "inner":
                    return out
                # left/full outer: null out build columns where unmatched
                cols = list(out.columns)
                for i, nme in enumerate(out.names):
                    if nme in rsyms:
                        c = cols[i]
                        cols[i] = Column(
                            c.values,
                            matched if c.validity is None else c.validity & matched,
                            c.hi, c.sizes, c.evalid, c.keys)
                return Batch(out.names, out.types, cols, out.live, out.dicts)

            self.jfn = _node_jit(node, _ek(jkey + "probe"), lambda: probe_fn)
            self.jemit = _node_jit(node, _ek(jkey + "emit"), lambda: emit_fn,
                                   static_argnames=("out_cap",))
            return

        # general fanout join (inner / left): counts pass + chunked
        # expansion. LEFT semantics: track verified per-probe existence
        # across chunks and emit the NULL-extended non-matching probe rows
        # at the end (the role of LookupJoinOperators.probeOuterJoin in the
        # reference).
        # `t` is an argument, not a closure capture: the jit cache entry is
        # shared across probers with the same jkey (the radix path keeps P
        # of them), so a captured table would bake the first prober's build
        # side into the compiled program as a constant
        def chain_align(t, pb):
            pb = chain(pb)
            pba = align_probe_strings(pb, tuple(node.left_keys), t, tuple(node.right_keys))
            return pb, pba

        self.chain_j = _node_jit(node, jkey + "chain_align", lambda: chain_align)
        # the fanout window is part of the compiled closure: a non-default
        # scan width (the radix path probes with a wider one, the hash
        # engine's overflow ladder doubles it) keys its own cache entry
        self.counts_fn = self._counts_program(fanout_scan)

        def expand_fn(t, pb, pba, lo, counts, offsets, base, out_cap, bm):
            # hash engine: `lo` is the match matrix mm[n, F] (exact build
            # row indices); sort engine: the range starts, re-verified
            if engine == "hash":
                pr, bi, ol = hash_probe_expand(
                    t, lo, counts, offsets, base, out_cap)
            else:
                pr, bi, ol = probe_expand(
                    t, pba, tuple(node.left_keys), tuple(node.right_keys),
                    lo, counts, offsets, base, out_cap,
                )
            out = gather_join_output(pb, t, pr, bi, ol, lsyms, rsyms)
            exists = (
                jnp.zeros(pb.capacity, dtype=jnp.int32)
                .at[pr]
                .max(ol.astype(jnp.int32), mode="drop")
                .astype(bool)
            )
            if bm is not None:
                bm = bm.at[bi].max(ol, mode="drop")
            return out, exists, bm

        def null_extend_fn(t, pb, exists):
            # unmatched probe rows with NULL build columns
            zero_idx = jnp.zeros(pb.capacity, dtype=jnp.int32)
            out = gather_join_output(
                pb, t, jnp.arange(pb.capacity, dtype=jnp.int32), zero_idx,
                pb.live & ~exists, lsyms, rsyms,
            )
            cols = list(out.columns)
            for i, nme in enumerate(out.names):
                if nme in rsyms:
                    cols[i] = Column(cols[i].values, jnp.zeros(out.capacity, bool),
                                     cols[i].hi)
            return Batch(out.names, out.types, cols, out.live, out.dicts)

        self.jexpand = _node_jit(node, _ek("expand"), lambda: expand_fn,
                                 static_argnames=("out_cap",))
        self.jnull = _node_jit(node, "null_extend", lambda: null_extend_fn)
        # the `total` the host read for this prober's previous general
        # batch: it sizes the next batch's chunk 0 (`_chunk0_lanes`)
        self._prev_total: Optional[int] = None

    def _chunk0_lanes(self, out_cap: int) -> int:
        """Lanes of a general batch's first expand chunk, dispatched before
        its `total` reaches the host: `out_cap` for the prober's first
        batch, else the power-of-two bucket of twice the previous batch's
        total, at most `out_cap`. The full batches of one probe stream
        expand to totals a few percent apart (TPC-H Q9 and TPC-DS Q72 at
        SF1), so the doubling covers the next batch, and it keeps the
        chunk at most half full, as `out_cap` kept it: `_merging_output`
        compacts and merges it, where a dense chunk would travel
        downstream alone. A batch that outgrows its chunk 0 takes further
        chunks once its total is read; no row is lost."""
        prev = self._prev_total
        if prev is None:
            return out_cap
        return min(out_cap, round_up_capacity(2 * prev))

    def _hbo_observe_build(self) -> None:
        """Observe the build side's actual live row count (one host sync of
        an already-materialized device scalar) against the CBO's estimate.
        Whole-build probers only — the radix/spilled drivers hold P probers
        over per-partition sub-builds whose counts are not table totals."""
        ctx = self.ctx
        if getattr(ctx.config, "hbo", "observe") == "off" or self._jkey:
            return
        try:
            from presto_tpu.obs import runstats as _runstats
            from presto_tpu.plan.stats import choose_breaker_engine
            from presto_tpu.plan.stats import derive as _derive_stats

            node = self.node
            fp = _runstats.node_fingerprint(node, ctx.catalog)
            if fp is None:
                return
            actual = _observe_build_table(ctx, self.table)
            if actual <= 0:
                return
            try:
                bst = _derive_stats(node.right, ctx.catalog)
            except Exception:
                bst = None
            est = float(bst.rows) if (bst is not None and bst.rows) else None
            _runstats.observe(fp, "join_build", type(node).__name__.lower(),
                              est, actual)
            node.__dict__["_runstats"] = {
                "site": "join_build", "est": est, "actual": actual}
            made = node.__dict__.get("_breaker_engine")
            if made:
                would, _ = choose_breaker_engine(
                    node, ctx.catalog,
                    getattr(ctx.config, "breaker_engine", "auto"),
                    hbo="correct")
                if would != made:
                    _runstats.record_flip("breaker_engine")
        except Exception:
            pass

    def _counts_program(self, fanout: int):
        """Counting-pass program for one fanout width (jit-cached per
        width: the hash engine's overflow ladder re-probes at doubled
        widths, each its own compiled shape)."""
        node = self.node
        if self.engine == "hash":
            return _node_jit(
                self.node, f"counts@h{fanout}",
                lambda: lambda t, pba: hash_probe_counts(
                    t, pba, tuple(node.left_keys), _join_plan_cdt(node),
                    max_fanout_scan=fanout,
                ),
            )
        ckey = "counts" if fanout == 8 else f"counts{fanout}"
        return _node_jit(
            self.node, ckey,
            lambda: lambda t, pba: probe_counts(
                t, pba, tuple(node.left_keys), tuple(node.right_keys),
                max_fanout_scan=fanout,
            ),
        )

    def probe_start(self, pb_raw: Batch):
        """Dispatch phase of one probe batch: everything up to (not
        including) the host sync on `total`. Chunk 0 is dispatched
        unconditionally, at `_chunk0_lanes`, while `total` travels to the
        host (it is usually the only chunk). The radix driver starts ALL
        partitions of a batch before finishing any, so the P count round
        trips overlap instead of serializing. The state carries the batch's `join_probe` phase:
        `probe_finish` enters it again."""
        if self.empty:
            return None
        node, table = self.node, self.table
        ph = self.ctx.tracer.phase("join_probe")
        with ph:
            if node.build_unique:
                pb, idx, matched, self.bm, n_probe, count = self.jfn(
                    table, pb_raw, self.bm)
                self._n_probe = self._n_probe + n_probe
                self._n_out = self._n_out + count
                ph.items = 1
                return ("u", _PendingJoinOutput(self, pb, idx, matched,
                                                count))
            pb, pba = self.chain_j(table, pb_raw)
            self._n_probe = self._n_probe + jnp.sum(pb.live)
            lo, counts, offsets, total, _, ovf = self.counts_fn(table, pba)
            try:
                total.copy_to_host_async()
                ovf.copy_to_host_async()
            except Exception:
                pass
            out_cap = self.ctx.config.join_out_capacity or pb.capacity
            chunk0 = self._chunk0_lanes(out_cap)
            out, exists_acc, self.bm = self.jexpand(
                table, pb, pba, lo, counts, offsets, 0, chunk0, self.bm)
            return ("g", pb, pba, lo, counts, offsets, total, ovf, out_cap,
                    chunk0, out, exists_acc, ph)

    def probe_finish(self, st) -> Iterator[Batch]:
        """The chunks of one started probe batch. The batch's `join_probe`
        phase is left before each chunk is handed on (the consumer's time
        is not the probe's) and entered again after; `items` counts the
        chunks."""
        if st is None:
            return
        if st[0] == "u":
            yield st[1]
            return
        node, table, phase = self.node, self.table, self.ctx.tracer.phase
        (_, pb, pba, lo, counts, offsets, total, ovf, out_cap, chunk0,
         out, exists_acc, ph) = st
        with ph:
            # the sort engine's overflow is informational (counts already
            # widened) and syncs after the chunk loop; the hash engine's
            # must be confirmed BEFORE chunk 0 is yielded
            ovn = 0
            if self.engine == "hash":
                with phase("host_sync:join_overflow"):
                    ovn = int(ovf)
            if ovn:
                # hash-engine fanout overflow: counts/total are EXACT but
                # the match matrix truncated past its width — the
                # optimistically dispatched chunk 0 would duplicate the
                # last held match, so discard it, re-probe at doubled
                # widths until every row fits, and redo chunk 0 from the
                # full matrix. (The discarded chunk's bm/exists updates
                # only marked GENUINE matches, so they stand.) Counts
                # don't change, so no re-cumsum drift.
                ov_rows = ovn
                fanout = self.fanout_scan
                while ovn:
                    fanout *= 2
                    if fanout > int(self.table.slot_row.shape[0]):
                        raise RuntimeError(
                            "join fanout exceeded build table capacity")
                    _bump_replay_wave(node, self.ctx, cap_to=fanout)
                    lo, counts, offsets, total, _, ovf = \
                        self._counts_program(fanout)(table, pba)
                    with phase("host_sync:join_overflow"):
                        ovn = int(ovf)
                out, exists, self.bm = self.jexpand(
                    table, pb, pba, lo, counts, offsets, 0, chunk0, self.bm)
                exists_acc = exists_acc | exists
                ovn = ov_rows  # recorded after the chunk loop
            self._n_out = self._n_out + jnp.sum(out.live)
            ph.items = 1
        yield out
        with ph:
            with phase("host_sync:join_total"):
                tot = int(total)
        self._prev_total = tot
        # the chunks after the first cover [chunk0, tot), each at the bucket
        # of what remains, at most `out_cap`; `base` ends at the lanes
        # gathered
        base = chunk0
        while base < tot:
            lanes = min(out_cap, round_up_capacity(tot - base))
            with ph:
                out, exists, self.bm = self.jexpand(
                    table, pb, pba, lo, counts, offsets, base, lanes,
                    self.bm)
                exists_acc = exists_acc | exists
                self._n_out = self._n_out + jnp.sum(out.live)
                ph.items = 1
            yield out
            base += lanes
        nb = None
        with ph:
            if self.engine != "hash":
                with phase("host_sync:join_overflow"):
                    ovn = int(ovf)
            if ovn:
                from presto_tpu.scan import metrics as _scan_metrics

                self.overflow_rows += ovn
                key = "join.fanout_overflow_rows"
                self.ctx.stats[key] = self.ctx.stats.get(key, 0) + ovn
                _scan_metrics.record("join_fanout_overflow_rows", ovn)
                if getattr(self.ctx.config, "hbo", "observe") != "off":
                    try:
                        from presto_tpu.obs import runstats as _runstats

                        _runstats.note(
                            _runstats.node_fingerprint(node,
                                                       self.ctx.catalog),
                            "join_build", fanout_overflow_rows=ovn)
                    except Exception:
                        pass
            if node.kind in ("left", "full"):
                nb = self.jnull(table, pb, exists_acc)
                self._n_out = self._n_out + jnp.sum(nb.live)
                ph.items = 1
        _expand_phases(self.ctx.tracer, tot, base, ovn,
                       sized=chunk0 if chunk0 < out_cap else 0)
        if nb is not None:
            _outer_phase(self.ctx.tracer, pb.capacity)
            yield nb

    def probe_batch(self, pb_raw: Batch) -> Iterator[Batch]:
        yield from self.probe_finish(self.probe_start(pb_raw))

    def probe_all(self, probe_stream: Iterator[Batch]) -> Iterator[Batch]:
        for pb in probe_stream:
            yield from self.probe_batch(pb)
        yield from self.tail()

    def tail(self) -> Iterator[Batch]:
        if not self.empty and self.want_full:
            b = self.jremainder(self.table, self.bm)
            self._n_out = self._n_out + jnp.sum(b.live)
            yield b
        self._observe_selectivity()

    def _observe_selectivity(self) -> None:
        """Record the join's observed probe selectivity (output rows /
        probe rows) under its structural fingerprint — the site
        choose_join_mode consults, so the multiway-vs-binary verdict is
        history-corrected on fingerprint repeat. Whole-build probers only
        (the radix/spilled drivers see partition slices); one host sync
        of two already-materialized device scalars."""
        ctx = self.ctx
        if (self.empty or self._jkey
                or getattr(ctx.config, "hbo", "observe") == "off"):
            return
        try:
            from presto_tpu.obs import runstats as _runstats
            from presto_tpu.plan.stats import derive as _derive

            # the stream's end: the first read that waits for every probe
            # the device still has queued
            with ctx.tracer.phase("host_sync:join_selectivity"):
                n_probe = float(self._n_probe)
            if n_probe <= 0:
                return
            fp = _runstats.node_fingerprint(self.node, ctx.catalog)
            if fp is None:
                return
            est = None
            try:
                pst = _derive(self.node.left, ctx.catalog)
                ost = _derive(self.node, ctx.catalog)
                if pst is not None and ost is not None and pst.rows:
                    est = ost.rows / pst.rows
            except Exception:
                pass
            _runstats.observe(fp, "join_probe_sel",
                              type(self.node).__name__.lower(), est,
                              float(self._n_out) / n_probe,
                              extra={"probe_rows": n_probe})
        except Exception:
            pass


def _join_probe(node: HashJoin, ctx: ExecContext, build_in: Optional[Batch],
                probe_stream: Iterator[Batch], chain,
                jkey: str = "") -> Iterator[Batch]:
    yield from _JoinProber(node, ctx, build_in, chain,
                           jkey=jkey).probe_all(probe_stream)


# ---------------------------------------------------------------------------
# multiway (N-ary) join executor — plan/multiway.py's MultiwayJoin node:
# N resident build tables, one probe pass through all N probes per batch
# inside one fragment (ops/join.multiway_*). Budget-exceeded builds fall
# back to the binary cascade so each leg keeps the partitioned spiller.


def _mw_stub_build(node: MultiwayJoin, i: int) -> Batch:
    """Zero-row stand-in for an empty LEFT-leg build stream (inner legs
    with an empty build short-circuit the whole node instead)."""
    schema = node.builds[i].output
    return Batch([s for s, _ in schema], [t for _, t in schema],
                 [Column(jnp.zeros(128, t.dtype), None) for _, t in schema],
                 jnp.zeros(128, bool), {})


def _mw_cascade_shims(node: MultiwayJoin) -> List[HashJoin]:
    """Per-leg binary HashJoin shims: leg i's join with a never-executed
    scan stub standing in for the cascade intermediate (probe output +
    payloads of legs < i) on the left. They carry the leg's key/kind/
    uniqueness contract for _JoinProber / choose_breaker_engine and give
    _node_jit a stable per-leg home for the fallback path's programs
    (same trick as _execute_index_join's _probe_shim)."""
    shims = node.__dict__.get("_mw_shims")
    if shims is None:
        shims = []
        schema = list(node.probe.output)
        for i in range(len(node.builds)):
            stub = TableScan(catalog="", table=f"__mw_cascade_{i}__",
                             assignments={}, output=list(schema))
            shims.append(HashJoin(
                kind=node.kinds[i], left=stub, right=node.builds[i],
                left_keys=list(node.probe_keys[i]),
                right_keys=list(node.build_keys[i]),
                build_unique=bool(node.build_unique[i])))
            schema = schema + list(node.builds[i].output)
        node.__dict__["_mw_shims"] = shims
    return shims


def _mw_plan_specs(node: MultiwayJoin):
    """Plan-only per-leg key plumbing, memoized on the node: key sources
    (-1 = probe batch, j >= 0 = unique build j's payload), the planned
    probe-side encode dtypes, and the pairwise-promoted compare dtypes
    (the multiway twin of _join_plan_cdt)."""
    memo = node.__dict__.get("_mw_plan")
    if memo is not None:
        return memo
    pout = dict(node.probe.output)
    bouts = [dict(b.output) for b in node.builds]
    legs = []
    for i in range(len(node.builds)):
        sources, pdts = [], []
        for sym in node.probe_keys[i]:
            if sym in pout:
                sources.append(-1)
                pdts.append(jnp.dtype(pout[sym].dtype))
            else:
                for j in range(i):
                    if node.build_unique[j] and sym in bouts[j]:
                        sources.append(j)
                        pdts.append(jnp.dtype(bouts[j][sym].dtype))
                        break
                else:
                    raise KeyError(
                        f"multiway probe key {sym!r} resolves against no "
                        f"probe column or earlier unique build payload")
        cdts = tuple(
            jnp.result_type(jnp.dtype(bouts[i][bk].dtype), pd)
            for bk, pd in zip(node.build_keys[i], pdts))
        legs.append((tuple(sources), tuple(pdts), cdts))
    node.__dict__["_mw_plan"] = legs
    return legs


def _mw_stat(ctx: ExecContext, key: str, delta: int = 1) -> None:
    ctx.stats[key] = ctx.stats.get(key, 0) + delta


class _MultiwayProber:
    """N resident build tables, probed in one pass per batch.

    Per leg: unique builds probe through the sorted engine's single-match
    kernel; fanout builds through the Pallas hash kernel (exact counts —
    required for LEFT null-extension) or, for inner kinds, the sorted
    range engine (expand re-verifies keys). All-unique chains — the
    dominant star shape — run ONE compiled program per probe batch with
    the fused child chain inlined; general chains run a counts pass (per-
    leg fanout ladder on hash overflow) plus chunked mixed-radix
    expansion. ``cascade`` set at construction means a leg cannot run
    fused (left fanout leg without exact counts) and the caller must fall
    back to the binary cascade."""

    def __init__(self, node: MultiwayJoin, ctx: ExecContext,
                 builds_in: List[Optional[Batch]], chain):
        self.node, self.ctx = node, ctx
        self.cascade = None  # reason string when fused execution is off
        self.empty = any(
            b is None and k == "inner"
            for b, k in zip(builds_in, node.kinds))
        if self.empty:
            return
        N = len(node.builds)
        self.psyms = [s for s, _ in node.probe.output]
        self.bsyms = tuple(
            tuple(s for s, _ in b.output) for b in node.builds)
        legs = _mw_plan_specs(node)
        shims = _mw_cascade_shims(node)
        override = getattr(ctx.config, "breaker_engine", "auto")
        hbo = getattr(ctx.config, "hbo", "observe")

        specs, tables = [], []
        for i in range(N):
            build_in = builds_in[i]
            if build_in is None:
                build_in = _mw_stub_build(node, i)
            sources, pdts, cdts = legs[i]
            unique = bool(node.build_unique[i])
            hash_engine = False
            if not unique:
                from presto_tpu.plan.stats import choose_breaker_engine
                try:
                    eng, _ = choose_breaker_engine(
                        shims[i], ctx.catalog, override, hbo=hbo)
                except Exception:
                    eng = "sort"
                hash_engine = eng == "hash"
                if hash_engine and join_compare_dtypes(
                        build_in, tuple(node.build_keys[i]), pdts) != cdts:
                    # executed batch deviates from plan dtypes: the hash
                    # encode would be wrong — same gate as _JoinProber
                    hash_engine = False
                if not hash_engine and node.kinds[i] == "left":
                    # sorted fanout counts can widen, which breaks the
                    # left leg's digit-0 null-extension — whole-node
                    # binary decomposition instead of a wrong answer
                    self.cascade = (
                        f"left fanout leg {i} lacks exact counts")
                    return
            specs.append(MwSpec(
                probe_keys=tuple(node.probe_keys[i]),
                build_keys=tuple(node.build_keys[i]),
                sources=sources, kind=node.kinds[i], unique=unique,
                hash_engine=hash_engine,
                compare_dtypes=cdts if hash_engine else ()))
            if hash_engine:
                table = _node_jit(
                    node, f"mw_build{i}@h", lambda: hash_build_side,
                    static_argnames=("key_names", "probe_dtypes"))(
                    build_in, tuple(node.build_keys[i]), pdts)
            else:
                table = _node_jit(
                    node, f"mw_build{i}", lambda: build_side,
                    static_argnames=("key_names",))(
                    build_in, tuple(node.build_keys[i]))
            tables.append(table)
        self.specs = tuple(specs)
        self.tables = tuple(tables)
        # per-leg engine vector: hbo/override-chosen engines are volatile
        # config, so the shared probe-program keys must fork on them the
        # same way _JoinProber's `@h` suffix forks the binary path
        self._evec = "".join(
            "h" if s.hash_engine else "u" if s.unique else "s"
            for s in self.specs)
        self.fanouts = tuple(
            0 if s.unique else 16 for s in self.specs)
        self.all_unique = all(s.unique for s in self.specs)
        self._hbo_observe_builds()

        # selectivity accumulators (device scalars; one host sync in
        # tail): probe rows in, leg-0 binary-equivalent rows, final rows
        self._n_probe = jnp.zeros((), jnp.int64)
        self._n_leg0 = jnp.zeros((), jnp.int64)
        self._n_out = jnp.zeros((), jnp.int64)

        psyms, bsyms = self.psyms, self.bsyms
        specs_t = self.specs

        if self.all_unique:
            def unique_fn(ts, pb_raw):
                pb = chain(pb_raw)
                out, n_probe, n_leg0 = multiway_probe_unique(
                    ts, pb, specs_t, psyms, bsyms)
                return out, n_probe, n_leg0
            self.junique = _node_jit(
                node, f"mw_unique@e{self._evec}", lambda: unique_fn)
            return

        def expand_fn(ts, pb, state, chats, offsets, T, base, out_cap):
            return multiway_expand(ts, pb, specs_t, state, chats, offsets,
                                   T, base, out_cap, psyms, bsyms)
        self.jexpand = _node_jit(
            node, f"mw_expand@e{self._evec}", lambda: expand_fn,
            static_argnames=("out_cap",))
        self._chain = chain
        self._counts_cache = {}

    def _counts_program(self, fanouts):
        """Counting-pass program for one per-leg fanout vector (jit-cached
        per vector: a hash leg's overflow ladder doubles only that leg's
        width, each combination its own compiled shape). The fused child
        chain is inlined, so the chained probe batch comes back as an
        output alongside the per-leg state."""
        fn = self._counts_cache.get(fanouts)
        if fn is None:
            chain, specs = self._chain, self.specs

            def counts_fn(ts, pb_raw):
                pb = chain(pb_raw)
                return (pb,) + multiway_counts(ts, pb, specs, fanouts)
            fn = self._counts_cache[fanouts] = _node_jit(
                self.node,
                f"mw_counts@f{','.join(map(str, fanouts))}"
                f"@e{self._evec}",
                lambda: counts_fn)
        return fn

    def _hbo_observe_builds(self) -> None:
        """Per-leg build row counts into HBO under the ORIGINAL binary
        joins' fingerprints (stashed by the collapse pass), so
        choose_join_mode's per-join build sizing is history-corrected on
        fingerprint repeat even when the chain ran multiway."""
        ctx = self.ctx
        if getattr(ctx.config, "hbo", "observe") == "off":
            return
        leg_fps = self.node.__dict__.get("_leg_fps") or []
        if not leg_fps:
            return
        try:
            from presto_tpu.obs import runstats as _runstats

            for i, fp in enumerate(leg_fps):
                if fp is None or i >= len(self.tables):
                    continue
                actual = _observe_build_table(ctx, self.tables[i])
                if actual <= 0:
                    continue
                try:
                    from presto_tpu.plan.stats import derive as _derive
                    bst = _derive(self.node.builds[i], ctx.catalog)
                except Exception:
                    bst = None
                est = float(bst.rows) if (bst is not None
                                          and bst.rows) else None
                _runstats.observe(fp, "join_build", "multiwayjoin",
                                  est, actual)
        except Exception:
            pass

    def probe_batch(self, pb_raw: Batch) -> Iterator[Batch]:
        if self.empty:
            return
        node, ctx, tables = self.node, self.ctx, self.tables
        # one `join_probe` occurrence a batch, left before each chunk is
        # handed on and entered again after (as _JoinProber.probe_finish)
        phase = ctx.tracer.phase
        ph = phase("join_probe")
        if self.all_unique:
            with ph:
                out, n_probe, n_leg0 = self.junique(tables, pb_raw)
                self._n_probe = self._n_probe + n_probe
                self._n_leg0 = self._n_leg0 + n_leg0
                self._n_out = self._n_out + jnp.sum(out.live)
                ph.items = 1
            yield out
            return
        with ph:
            fanouts = self.fanouts
            (pb, state, chats, offsets, T, total,
             ovfs) = self._counts_program(fanouts)(tables, pb_raw)
            try:
                total.copy_to_host_async()
                ovfs.copy_to_host_async()
            except Exception:
                pass
            out_cap = ctx.config.join_out_capacity or pb.capacity
            # optimistic chunk-0 dispatch while total/ovfs travel to the
            # host
            out = self.jexpand(tables, pb, state, chats, offsets, T, 0,
                               out_cap)
            with phase("host_sync:join_overflow"):
                ovn = np.asarray(ovfs)
            ov_rows = 0
            if int(ovn.sum()):
                # hash-leg fanout overflow: counts are EXACT but that leg's
                # match matrix truncated — the dispatched chunk 0 would
                # duplicate its last held match, so discard it, double the
                # overflowing legs' widths until every row fits, and redo
                # chunk 0 (the widening-replay ladder, per table)
                ov_rows = int(ovn.sum())
                while int(ovn.sum()):
                    fanouts = tuple(
                        f * 2 if int(ovn[i]) else f
                        for i, f in enumerate(fanouts))
                    for i, f in enumerate(fanouts):
                        if (self.specs[i].hash_engine
                                and f > int(tables[i].slot_row.shape[0])):
                            raise RuntimeError(
                                "multiway join fanout exceeded build table "
                                f"capacity on leg {i}")
                    _bump_replay_wave(node, ctx, cap_to=max(fanouts))
                    (pb, state, chats, offsets, T, total,
                     ovfs) = self._counts_program(fanouts)(tables, pb_raw)
                    with phase("host_sync:join_overflow"):
                        ovn = np.asarray(ovfs)
                out = self.jexpand(tables, pb, state, chats, offsets, T, 0,
                                   out_cap)
                self._note_overflow(ov_rows, ovn)
            self._n_probe = self._n_probe + jnp.sum(pb.live)
            self._n_leg0 = self._n_leg0 + jnp.sum(
                jnp.where(pb.live, chats[0], 0))
            self._n_out = self._n_out + jnp.sum(out.live)
            ph.items = 1
        yield out
        with ph:
            with phase("host_sync:join_total"):
                tot = int(total)
        base = out_cap
        while base < tot:
            with ph:
                out = self.jexpand(tables, pb, state, chats, offsets, T,
                                   base, out_cap)
                self._n_out = self._n_out + jnp.sum(out.live)
                ph.items = 1
            yield out
            base += out_cap
        _expand_phases(ctx.tracer, tot, base, ov_rows)

    def _note_overflow(self, ov_rows: int, _ovn) -> None:
        """Per-table overflow accounting into the same counters the binary
        widening-replay ladder feeds."""
        from presto_tpu.scan import metrics as _scan_metrics

        _mw_stat(self.ctx, "join.fanout_overflow_rows", ov_rows)
        _mw_stat(self.ctx, "multiway.fanout_overflow_rows", ov_rows)
        _scan_metrics.record("join_fanout_overflow_rows", ov_rows)
        if getattr(self.ctx.config, "hbo", "observe") != "off":
            try:
                from presto_tpu.obs import runstats as _runstats

                fp = _runstats.node_fingerprint(self.node,
                                                self.ctx.catalog)
                if fp is not None:
                    _runstats.note(fp, "join_build",
                                   fanout_overflow_rows=ov_rows)
            except Exception:
                pass

    def tail(self) -> None:
        """Stream end: one host sync of the selectivity accumulators, then
        the HBO probe-selectivity observations (satellite: history-
        corrected multiway-vs-binary verdicts). Leg-0's binary-equivalent
        selectivity lands on the ORIGINAL bottom join's fingerprint (the
        one choose_join_mode consults); the overall chain selectivity on
        the node's own fingerprint and the collapsed top join's."""
        ctx = self.ctx
        if self.empty or getattr(ctx.config, "hbo", "observe") == "off":
            return
        try:
            from presto_tpu.obs import runstats as _runstats

            with ctx.tracer.phase("host_sync:join_selectivity"):
                n_probe = float(self._n_probe)
            if n_probe <= 0:
                return
            leg0_sel = float(self._n_leg0) / n_probe
            out_sel = float(self._n_out) / n_probe
            leg_fps = self.node.__dict__.get("_leg_fps") or []
            if leg_fps and leg_fps[0] is not None:
                _runstats.observe(leg_fps[0], "join_probe_sel",
                                  "multiwayjoin", None, leg0_sel,
                                  extra={"probe_rows": n_probe})
            for fp in (
                    _runstats.node_fingerprint(self.node, ctx.catalog),
                    self.node.__dict__.get("_origin_fp")):
                if fp is not None:
                    _runstats.observe(fp, "join_probe_sel", "multiwayjoin",
                                      None, out_sel,
                                      extra={"probe_rows": n_probe})
        except Exception:
            pass


def _mw_binary_cascade(node: MultiwayJoin, ctx: ExecContext,
                       probe_stream: Iterator[Batch], chain,
                       collected: List[List[Batch]],
                       pressure_at: Optional[int],
                       partial: List[Batch], bstream,
                       reason: str) -> Iterator[Batch]:
    """Binary decomposition of the chain over the already-opened streams:
    leg i joins the cascade intermediate against build i through the
    regular binary machinery, so a budget-exceeded build degrades through
    the PR 15 partitioned spiller (per leaf) instead of failing. Builds
    collected before the pressure point replay from memory; the
    pressure-point build resumes its partially-consumed stream; later
    builds execute normally."""
    import itertools

    from presto_tpu.scan import metrics as _scan_metrics

    _mw_stat(ctx, "multiway.cascade_fallbacks")
    _scan_metrics.record("multiway_cascade_fallbacks", 1)
    if ctx.tracer.enabled:
        t = time.time()
        ctx.tracer.record("multiway_cascade", "multiway_cascade", t, t,
                          node=type(node).__name__, reason=reason)
    shims = _mw_cascade_shims(node)
    ident = lambda b: b  # noqa: E731 — chain applied by leg 0 only
    stream = probe_stream
    for i, shim in enumerate(shims):
        leg_chain = chain if i == 0 else ident
        jkey = f"mwb{i}_"
        # a leg probes batches: the leg before may hand on pending outputs
        stream = map(_gathered, stream) if i else stream
        if pressure_at is None or i < pressure_at:
            build_in = (_collect_concat(iter(collected[i]))
                        if i < len(collected) else
                        _collect_concat(execute_node(node.builds[i], ctx)))
            stream = _join_probe(shim, ctx, build_in, stream, leg_chain,
                                 jkey=jkey)
        else:
            if i == pressure_at:
                bs = itertools.chain(
                    iter(partial),
                    bstream if bstream is not None else iter(()))
            else:
                bs = execute_node(node.builds[i], ctx)
            stream = _join_with_spill(shim, ctx, stream, bs, leg_chain,
                                      jkey=jkey)
    yield from stream


def _execute_multiway_join(node: MultiwayJoin,
                           ctx: ExecContext) -> Iterator[Batch]:
    """MultiwayJoin executor: collect all N build sides (memory-accounted),
    then run the fused N-ary probe — ONE probe pass per batch, no
    intermediate materialization between legs. Pool pressure during build
    collection, or a leg the fused path cannot run exactly, degrades to
    the binary cascade (each leg keeping the partitioned spiller)."""
    from presto_tpu.memory import LocalMemoryContext, batch_device_bytes

    probe_stream, chain = _fused_child(node.probe, ctx)
    N = len(node.builds)
    _mw_stat(ctx, "multiway.joins", 1)
    _mw_stat(ctx, "multiway.legs", N)

    mctx = LocalMemoryContext(ctx.memory_pool, "mw-join-build")
    rev = {"flag": False}

    def _revoke(_need: int) -> int:
        rev["flag"] = True
        return 0

    can_spill = ctx.config.spill_enabled
    if can_spill:
        ctx.memory_pool.add_revoker(_revoke)
    try:
        collected: List[List[Batch]] = []
        total_bytes = 0
        pressure_at = None
        partial: List[Batch] = []
        bstream = None
        # one `join_build` for the node's N build sides (as
        # _join_with_spill has it for one)
        with ctx.tracer.phase("join_build") as build_ph:
            for i in range(N):
                bstream = execute_node(node.builds[i], ctx)
                partial = []
                for b in bstream:
                    nb = batch_device_bytes(b)
                    if can_spill and (rev["flag"] or ctx.should_spill(nb)):
                        rev["flag"] = False
                        pressure_at = i
                        partial.append(b)
                        break
                    partial.append(b)
                    total_bytes += nb
                    mctx.set_bytes(total_bytes)
                if pressure_at is not None:
                    break
                collected.append(partial)
                partial, bstream = [], None
            if pressure_at is None:
                prober = _MultiwayProber(
                    node, ctx,
                    [_collect_concat(iter(bb)) for bb in collected], chain)
            build_ph.items = sum(map(len, collected)) + len(partial)

        if pressure_at is not None:
            yield from _mw_binary_cascade(
                node, ctx, probe_stream, chain, collected, pressure_at,
                partial, bstream, "build memory pressure")
            return

        if prober.cascade is not None:
            yield from _mw_binary_cascade(
                node, ctx, probe_stream, chain, collected, None, [], None,
                prober.cascade)
            return
        _mw_stat(ctx, "multiway.fused_dispatches")
        for pb in probe_stream:
            yield from prober.probe_batch(pb)
        prober.tail()
    finally:
        if can_spill:
            ctx.memory_pool.remove_revoker(_revoke)
        mctx.set_bytes(0)


def _column_chunk(c: Column, off, size: int) -> Column:
    """Rows [off, off+size) of every plane (traced offset, static size)."""
    def dsl(a):
        return jax.lax.dynamic_slice_in_dim(a, off, size, axis=0)

    return Column(
        dsl(c.values),
        None if c.validity is None else dsl(c.validity),
        None if c.hi is None else dsl(c.hi),
        None if c.sizes is None else dsl(c.sizes),
        None if c.evalid is None else dsl(c.evalid),
        None if c.keys is None else dsl(c.keys),
    )


def _column_repeat(c: Column, k: int) -> Column:
    """Each row k times (out row i*k+j = in row i)."""
    def rep(a):
        return jnp.repeat(a, k, axis=0)

    return Column(
        rep(c.values),
        None if c.validity is None else rep(c.validity),
        None if c.hi is None else rep(c.hi),
        None if c.sizes is None else rep(c.sizes),
        None if c.evalid is None else rep(c.evalid),
        None if c.keys is None else rep(c.keys),
    )


def _column_tile(c: Column, k: int) -> Column:
    """The whole column k times (out row i*n+j = in row j)."""
    def tile(a):
        reps = (k,) + (1,) * (a.ndim - 1)
        return jnp.tile(a, reps)

    return Column(
        tile(c.values),
        None if c.validity is None else tile(c.validity),
        None if c.hi is None else tile(c.hi),
        None if c.sizes is None else tile(c.sizes),
        None if c.evalid is None else tile(c.evalid),
        None if c.keys is None else tile(c.keys),
    )


def _execute_nljoin(node: NestedLoopJoin, ctx: ExecContext) -> Iterator[Batch]:
    """Nested-loop inner join (cross product / non-equi ON). Reference:
    NestedLoopJoinOperator.java — there per-position page crossing; here
    each output batch is one probe batch × one fixed-size build chunk,
    expanded by repeat/tile with the residual predicate fused into the
    same program (static shapes: chunk size is a trace-time constant)."""
    from presto_tpu.expr.compile import compile_predicate

    probe_stream, chain = _fused_child(node.left, ctx)
    build = _collect_concat(execute_node(node.right, ctx))
    if build is None:
        return
    build = _JIT_COMPACT(build)  # live rows to the front
    nb = build.num_live()
    if nb == 0:
        return
    pred = (compile_predicate(node.residual)
            if node.residual is not None else None)
    lnames = [s for s, _ in node.left.output]
    rnames = [s for s, _ in node.right.output]
    out_names = lnames + rnames
    out_types = [t for _, t in node.left.output] + [
        t for _, t in node.right.output]

    def chunk_size(np_cap: int) -> int:
        # ≤512 build rows per output batch, bounded to ~2^21 output rows;
        # powers of two dividing the (pow2) build capacity, so fixed-size
        # dynamic slices never clamp (a clamped tail slice would re-read
        # earlier rows and duplicate join output)
        return min(512, max(1, (1 << 21) // max(np_cap, 1)), build.capacity)

    def expand(pb: Batch, bb: Batch, off):
        pb = chain(pb)
        np_cap = pb.capacity
        c = chunk_size(np_cap)
        chunk_cols = [_column_chunk(col, off, c) for col in bb.columns]
        chunk_live = jax.lax.dynamic_slice_in_dim(bb.live, off, c)
        cols = [_column_repeat(col, c) for col in pb.columns] + [
            _column_tile(col, np_cap) for col in chunk_cols
        ]
        live = (jnp.repeat(pb.live, c) & jnp.tile(chunk_live, np_cap))
        dicts = dict(bb.dicts)
        dicts.update(pb.dicts)
        out = Batch(out_names, out_types, cols, live, dicts)
        if pred is not None:
            out = out.with_live(out.live & pred(out))
        return out

    # chunk size must match expand()'s: recompute identically per capacity.
    # _shared=False: chunk_size bakes THIS build table's capacity into the
    # trace, so a structurally-identical node with a different build side
    # must not reuse the program.
    jexpand = _node_jit(node, "expand", lambda: expand, _shared=False)
    for raw in probe_stream:
        c = chunk_size(raw.capacity)
        for off in range(0, nb, c):
            # traced offset: one compiled program per (capacity) shape,
            # not per chunk position
            yield jexpand(raw, build, jnp.int32(off))


def _execute_semijoin(node: SemiJoin, ctx: ExecContext) -> Iterator[Batch]:
    right_in = _collect_concat(execute_node(node.right, ctx))
    probe_stream, chain = _fused_child(node.left, ctx)
    lkeys, rkeys = tuple(node.left_keys), tuple(node.right_keys)
    if right_in is None:
        jfn = _node_jit(node, "chain", lambda: chain)
        for pb in probe_stream:
            b = jfn(pb)
            if node.negated:
                yield b
            else:
                yield b.with_live(jnp.zeros(b.capacity, bool))
        return

    if node.residual is None:
        engine = _breaker_engine_choice(node, ctx)
        ltypes = dict(node.left.output)
        probe_dtypes = tuple(jnp.dtype(ltypes[lk].dtype) for lk in lkeys)
        if engine == "hash" and join_compare_dtypes(
                right_in, rkeys, probe_dtypes) != _join_plan_cdt(node):
            engine = "sort"
            node.__dict__["_breaker_engine"] = "sort"
            node.__dict__["_breaker_engine_why"] = (
                "build batch dtypes deviate from plan types")
        _ek = lambda k: _engine_key(k, engine)  # noqa: E731

        if engine == "hash":
            # the linear-probing table tolerates duplicate build keys (the
            # probe walks the whole chain; EXISTS only needs count > 0),
            # so the sort engine's dedup pass has no hash twin
            def dedup_build(b: Batch):
                return hash_build_side(b, rkeys, probe_dtypes)
        else:
            def dedup_build(b: Batch):
                cols = [b.column(r) for r in rkeys]
                keys, _, out_live, _ = grouped_merge(
                    [KeyCol(c.values, c.validity) for c in cols], [], b.live, b.capacity
                )
                db = Batch(
                    list(rkeys), [b.type_of(r) for r in rkeys],
                    [Column(k.values, k.validity) for k in keys], out_live, b.dicts,
                )
                return build_side(db, rkeys)

        table = _node_jit(node, _ek("dedup_build"), lambda: dedup_build)(right_in)

        def probe_fn(t, pb: Batch):
            b = chain(pb)
            ba = align_probe_strings(b, lkeys, t, rkeys)
            if engine == "hash":
                _, matched = hash_probe_unique(
                    t, ba, lkeys, _join_plan_cdt(node))
            else:
                _, matched = probe_unique(t, ba, lkeys, rkeys)
            if node.negated:
                if node.null_aware:
                    # SQL: NULL NOT IN (non-empty set) is NULL → row filtered.
                    # (Deviation: NULLs *inside* the subquery should poison
                    # every row; that case is documented as unsupported.)
                    key_valid = jnp.ones(b.capacity, bool)
                    for lk in lkeys:
                        kv = b.column(lk).validity
                        if kv is not None:
                            key_valid = key_valid & kv
                    keep = ~matched & (key_valid | (t.n_rows == 0))
                else:
                    # NOT EXISTS is a pure anti-join: a NULL correlation key
                    # simply never matches, keeping the row
                    keep = ~matched
                return b.with_live(b.live & keep)
            return b.with_live(b.live & matched)

        jfn = _node_jit(node, _ek("probe"), lambda: probe_fn)
        for pb in probe_stream:
            yield jfn(table, pb)
        return

    # residual path (correlated EXISTS with non-equi conjuncts, e.g. Q21):
    # full build table, chunked pair expansion, residual predicate on pairs,
    # per-probe-row ANY-reduction across chunks.
    lsyms = [n for n, _ in node.left.output]
    rsyms = [n for n, _ in node.right.output]
    pred = compile_predicate(node.residual)
    node.__dict__["_breaker_engine"] = "sort"
    node.__dict__["_breaker_engine_why"] = "residual semijoin"
    table = _node_jit(node, "build", lambda: build_side, static_argnames=("key_names",))(
        right_in, rkeys
    )

    def chain_align(pb):
        pb = chain(pb)
        pba = align_probe_strings(pb, lkeys, table, rkeys)
        return pb, pba

    # _shared=False: chain_align closes over THIS query's build table (its
    # string dictionaries become trace constants via align_probe_strings)
    chain_j = _node_jit(node, "chain_align", lambda: chain_align,
                        _shared=False)
    counts_fn = _node_jit(
        node, "counts", lambda: lambda t, pba: probe_counts(t, pba, lkeys, rkeys)
    )

    def exists_fn(t, pb, pba, lo, counts, offsets, base, out_cap):
        pr, bi, ol = probe_expand(
            t, pba, lkeys, rkeys, lo, counts, offsets, base, out_cap
        )
        pair = gather_join_output(pb, t, pr, bi, ol, lsyms, rsyms)
        ok = pred(pair) & pair.live
        return (
            jnp.zeros(pb.capacity, dtype=jnp.int32)
            .at[pr]
            .max(ok.astype(jnp.int32), mode="drop")
            .astype(bool)
        )

    jexists = _node_jit(node, "exists", lambda: exists_fn, static_argnames=("out_cap",))
    for pb_raw in probe_stream:
        pb, pba = chain_j(pb_raw)
        lo, counts, offsets, total, _, _ovf = counts_fn(table, pba)
        # chunk 0 dispatches while `total` travels to the host (see
        # _join_probe — same round-trip overlap)
        try:
            total.copy_to_host_async()
        except Exception:
            pass
        out_cap = ctx.config.join_out_capacity or pb.capacity
        exists_acc = jexists(table, pb, pba, lo, counts, offsets, 0, out_cap)
        tot = int(total)
        base = out_cap
        while base < tot:
            exists_acc = exists_acc | jexists(
                table, pb, pba, lo, counts, offsets, base, out_cap
            )
            base += out_cap
        keep = ~exists_acc if node.negated else exists_acc
        yield pb.with_live(pb.live & keep)


# -- set operations ---------------------------------------------------------


def _align_setop_dicts(node: SetOp, batches: List[Batch]) -> List[Batch]:
    """Re-encode string columns of all batches against shared merged
    dictionaries so code equality == string equality (the DictionaryBlock
    id-canonicalization the reference does inside set-operation hashing).
    Thin wrapper over _unify_batch_dicts, which stamps a dict-less side
    with the merged dictionary too."""
    out = _unify_batch_dicts(batches)
    # a side whose string column carries no dictionary (all-NULL) still
    # needs the shared one for decode
    for i, t in enumerate(node.types):
        if not t.is_string:
            continue
        name = node.symbols[i]
        ds = [b.dicts.get(name) for b in out if b.dicts.get(name) is not None]
        if ds:
            out = [b if name in b.dicts else
                   Batch(b.names, b.types, b.columns, b.live,
                         {**b.dicts, name: ds[0]})
                   for b in out]
    return out


def _null_safe_encode(b: Batch) -> Tuple[Batch, List[str]]:
    """Rows as join keys with NULLs-equal semantics (SQL DISTINCT / set-op
    equality treats NULL = NULL): every column contributes a zero-filled
    value key plus a validity-bit key, so build_side/probe never null-kill
    and NULL cells compare equal. Long decimals contribute their hi limb."""
    names, types, cols = [], [], []
    for i, c in enumerate(b.columns):
        base = f"k{i}"
        v = (c.values if c.validity is None
             else jnp.where(c.validity, c.values, jnp.zeros_like(c.values)))
        names.append(base)
        types.append(b.types[i])
        cols.append(Column(v, None))
        names.append(base + "$v")
        types.append(BIGINT)
        vb = (jnp.ones(b.capacity, jnp.int8) if c.validity is None
              else c.validity.astype(jnp.int8))
        cols.append(Column(vb.astype(jnp.int64), None))
        if c.hi is not None:
            hv = (c.hi if c.validity is None
                  else jnp.where(c.validity, c.hi, jnp.zeros_like(c.hi)))
            names.append(base + "$hi")
            types.append(BIGINT)
            cols.append(Column(hv, None))
    return Batch(names, types, cols, b.live, {}), names


def _distinct_rows(b: Batch) -> Batch:
    """Keep one row per distinct tuple (NULLs equal): sort by all null-safe
    key encodings, keep the first row of each run. Preserves full rows
    (validity + hi limbs) — unlike grouped_merge, which rebuilds columns."""
    enc, _ = _null_safe_encode(b)
    n = b.capacity
    operands = [(~b.live).astype(jnp.int32)] + [c.values for c in enc.columns]
    sorted_ops, sperm = lex_sort(operands)
    sdead = sorted_ops[0]
    first = jnp.zeros(n, dtype=bool).at[0].set(True)
    for sk in sorted_ops:
        first = first.at[1:].set(first[1:] | (sk[1:] != sk[:-1]))
    from presto_tpu.ops.sort import permute_batch

    out = permute_batch(b, sperm)
    return out.with_live((sdead == 0) & first)


def _execute_setop(node: SetOp, ctx: ExecContext) -> Iterator[Batch]:
    """UNION [ALL] / INTERSECT / EXCEPT executor (reference: UnionNode is
    pass-through concat; INTERSECT/EXCEPT lower to mark-joins over hashed
    rows — here a null-safe membership probe over the whole row)."""
    syms = node.symbols

    def renamed(child):
        for b in execute_node(child, ctx):
            yield b.rename(syms)

    if node.all and node.kind == "union":  # UNION ALL: streaming concat
        yield from renamed(node.left)
        yield from renamed(node.right)
        return

    lb = _collect_concat(renamed(node.left))
    rb = _collect_concat(renamed(node.right))
    if node.kind == "union":
        sides = [b for b in (lb, rb) if b is not None]
        if not sides:
            return
        sides = _align_setop_dicts(node, sides)
        merged = sides[0] if len(sides) == 1 else _concat2(sides[0], sides[1])
        yield _node_jit(node, "distinct", lambda: _distinct_rows)(merged)
        return

    # INTERSECT / EXCEPT
    if lb is None:
        return
    if rb is None:
        if node.kind == "except":
            out = (lb if node.all
                   else _node_jit(node, "distinct", lambda: _distinct_rows)(lb))
            yield out
        return
    lb, rb = _align_setop_dicts(node, [lb, rb])

    if node.all:
        # multiset semantics (INTERSECT ALL / EXCEPT ALL): per distinct
        # row, emit min(cl, cr) / max(cl - cr, 0) copies. Row counting on
        # the host over the null-safe encodings, then ONE device gather of
        # the replicated row indices (set ops are gathered single-task;
        # the reference's row-number-marked joins serve the same shape)
        yield _multiset_setop(node, lb, rb)
        return

    def membership(lb: Batch, rb: Batch):
        ld = _distinct_rows(lb)
        lenc, keys = _null_safe_encode(ld)
        renc, _ = _null_safe_encode(rb)
        table = build_side(renc, tuple(keys))
        _, matched = probe_unique(table, lenc, tuple(keys), tuple(keys))
        keep = matched if node.kind == "intersect" else ~matched
        return ld.with_live(ld.live & keep)

    yield _node_jit(node, "membership", lambda: membership)(lb, rb)


# -- window -----------------------------------------------------------------


def _multiset_setop(node: SetOp, lb: Batch, rb: Batch) -> Batch:
    live_l = np.asarray(lb.live)
    orig_idx = np.nonzero(live_l)[0]
    lenc, _ = _null_safe_encode(lb)
    renc, _ = _null_safe_encode(rb)

    def rows_of(enc: Batch, live):
        cols = [np.asarray(c.values)[live] for c in enc.columns]
        return np.stack(cols, axis=1) if cols else np.zeros((int(live.sum()), 0))

    lrows = rows_of(lenc, live_l)
    rrows = rows_of(renc, np.asarray(rb.live))
    uniq, first_pos, lcnt = np.unique(lrows, axis=0, return_index=True,
                                      return_counts=True)
    rcounts: dict = {}
    for row in map(tuple, rrows):
        rcounts[row] = rcounts.get(row, 0) + 1
    reps = np.empty(len(uniq), np.int64)
    for i, row in enumerate(map(tuple, uniq)):
        cr = rcounts.get(row, 0)
        reps[i] = (min(int(lcnt[i]), cr) if node.kind == "intersect"
                   else max(int(lcnt[i]) - cr, 0))
    out_idx = np.repeat(orig_idx[first_pos], reps)
    n = len(out_idx)
    cap = round_up_capacity(max(n, 1))
    idx = np.zeros(cap, np.int32)
    idx[:n] = out_idx
    jidx = jnp.asarray(idx)
    cols = [c.gather(jidx) for c in lb.columns]
    live = np.zeros(cap, bool)
    live[:n] = True
    return Batch(lb.names, lb.types, cols, jnp.asarray(live), lb.dicts)


def _execute_window(node: Window, ctx: ExecContext) -> Iterator[Batch]:
    """Pipeline breaker: materialize the input, sort once by
    (partition keys, order keys), compute every function in the node's spec
    as closed-form vector ops (ops/window.py), emit one batch with the
    window columns appended (reference: WindowOperator.java:47 over a
    PagesIndex — here one lax.sort + O(n) vector passes).

    `window_collect` runs from the first pull of the input to the
    concatenated batch (`items` = the batches concatenated), the wait on the
    fragment upstream inside it; `window_lanes`, no time of its own, counts
    the lanes of the batch the sort runs over. The input is padded with dead
    lanes to its power-of-two bucket: the sum of its batches' capacities
    follows the data, and each new sum would compile the sort again."""
    with ctx.tracer.phase("window_collect") as ph:
        batches = list(execute_node(node.child, ctx))
        ph.items = len(batches)
        acc = _collect_concat(iter(batches))
    if acc is None:
        return
    acc = _pad_batch(acc, round_up_capacity(acc.capacity))
    with ctx.tracer.phase("window_lanes", items=acc.capacity):
        pass
    compute = build_window_compute(node)
    yield _node_jit(node, "window", lambda: compute)(acc)


def build_window_compute(node: Window):
    """Traceable batch → batch window computation (shared by the streaming
    executor and the mesh executor, which traces it inside shard_map)."""
    from presto_tpu.ops import window as W
    from presto_tpu.types import DecimalType as _Dec

    child_types = dict(node.child.output)

    def compute(b: Batch) -> Batch:
        keys = []
        for pk in node.partition_keys:
            c = b.column(pk)
            keys.append(SortKey(c.values, c.validity))
        for oi in node.order_items:
            c = b.column(oi.symbol)
            nf = oi.nulls_first
            if nf is None:
                nf = not oi.ascending  # SQL default: NULLS LAST for ASC
            keys.append(SortKey(c.values, c.validity, not oi.ascending, nf))
        perm = sort_permutation(keys, b.live)
        sb = permute_batch(b, perm)

        part_cols = [
            (sb.column(pk).values, sb.column(pk).validity)
            for pk in node.partition_keys
        ]
        order_cols = [
            (sb.column(oi.symbol).values, sb.column(oi.symbol).validity)
            for oi in node.order_items
        ]
        wk = W.window_keys(part_cols, order_cols, sb.live)

        rng_kw = {"order_vals": None}
        if (any(f.frame and f.frame.startswith("range:") for f in node.funcs)
                and node.order_items):
            # RANGE value offsets: the single order key, ascending-ized
            # (negated for DESC), kept in its NATIVE domain — int64 for
            # integral/decimal/date keys so boundary comparisons are
            # exact past 2^53; decimals compare unscaled with the OFFSET
            # scaled by 10^scale instead (see range_frame_bounds)
            oi = node.order_items[0]
            oc = sb.column(oi.symbol)
            ot = child_types.get(oi.symbol)
            ov = oc.values
            if jnp.issubdtype(ov.dtype, jnp.floating):
                ov = ov.astype(jnp.float64)
            else:
                ov = ov.astype(jnp.int64)
            if not oi.ascending:
                ov = -ov  # NaN survives negation; the kernel masks it
            nf = oi.nulls_first
            if nf is None:
                nf = not oi.ascending
            rng_kw = {
                "order_vals": ov,
                "order_valid": oc.validity,
                "nulls_first": nf,
                "offset_scale": 10 ** ot.scale if isinstance(ot, _Dec) else 1,
            }

        out = sb
        for f in node.funcs:
            if f.fn == "row_number":
                v, valid = W.row_number(wk)
            elif f.fn == "rank":
                v, valid = W.rank(wk)
            elif f.fn == "dense_rank":
                v, valid = W.dense_rank(wk)
            elif f.fn == "percent_rank":
                v, valid = W.percent_rank(wk)
            elif f.fn == "cume_dist":
                v, valid = W.cume_dist(wk)
            elif f.fn == "ntile":
                v, valid = W.ntile(wk, f.param)
            elif f.fn in ("lag", "lead", "first_value", "last_value", "nth_value"):
                c = sb.column(f.arg)
                bounded = f.frame is not None and f.frame.startswith(
                    ("rows:", "range:"))
                if f.fn == "lag":
                    v, valid = W.lag(wk, c.values, c.validity,
                                     f.param if f.param is not None else 1,
                                     f.default)
                elif f.fn == "lead":
                    v, valid = W.lead(wk, c.values, c.validity,
                                      f.param if f.param is not None else 1,
                                      f.default)
                elif bounded:
                    v, valid = W.value_over_frame(
                        wk, f.fn, c.values, c.validity, f.frame,
                        f.param if f.param is not None else 1, **rng_kw)
                elif f.fn == "first_value":
                    v, valid = W.first_value(wk, c.values, c.validity)
                elif f.fn == "last_value":
                    v, valid = W.last_value(wk, c.values, c.validity)
                else:
                    v, valid = W.nth_value(wk, c.values, c.validity, f.param)
            elif f.fn in ("sum", "avg", "min", "max", "count"):
                bounded = f.frame is not None and f.frame.startswith(
                    ("rows:", "range:"))
                if not node.order_items:
                    frame = "whole"
                elif f.frame == "rows_unbounded_current":
                    frame = "rows"
                else:
                    frame = "range"
                if bounded and f.arg is None:
                    v, valid = W.agg_window_bounded(
                        wk, "count", jnp.zeros(sb.capacity, jnp.int64), None,
                        f.frame, False, **rng_kw)
                elif f.arg is None:
                    v, valid = W.agg_window(
                        wk, "count", jnp.zeros(sb.capacity, jnp.int64), None,
                        frame, False,
                    )
                elif bounded:
                    c = sb.column(f.arg)
                    vals = c.values
                    arg_t = child_types.get(f.arg)
                    is_float = jnp.issubdtype(vals.dtype, jnp.floating)
                    if f.fn == "avg" and not is_float:
                        scale = arg_t.scale if isinstance(arg_t, _Dec) else 0
                        vals = vals.astype(jnp.float64) / (10.0 ** scale)
                        is_float = True
                    v, valid = W.agg_window_bounded(
                        wk, f.fn, vals, c.validity, f.frame, is_float,
                        **rng_kw)
                else:
                    c = sb.column(f.arg)
                    vals = c.values
                    arg_t = child_types.get(f.arg)
                    is_float = jnp.issubdtype(vals.dtype, jnp.floating)
                    if f.fn == "avg" and not is_float:
                        # avg computes in double (builder types avg → DOUBLE);
                        # decimals are unscaled ints — rescale on conversion
                        scale = arg_t.scale if isinstance(arg_t, _Dec) else 0
                        vals = vals.astype(jnp.float64) / (10.0 ** scale)
                        is_float = True
                    v, valid = W.agg_window(
                        wk, f.fn, vals, c.validity, frame, is_float
                    )
            else:
                raise NotImplementedError(f"window function {f.fn}")
            dict_ = None
            if f.arg is not None and f.type.is_string:
                dict_ = sb.dict_of(f.arg)
            out = out.with_column(
                f.symbol, f.type,
                Column(v.astype(f.type.dtype), valid), dictionary=dict_,
            )
        return out

    return compute


# -- sort / limit -----------------------------------------------------------


def _sort_keys(node: Sort, b: Batch) -> List[SortKey]:
    keys = []
    for k in node.keys:
        c = b.column(k.symbol)
        nulls_first = k.nulls_first
        if nulls_first is None:
            nulls_first = not k.ascending  # SQL default: NULLS LAST for ASC
        if c.hi is not None:
            # long decimal sorts lexicographically by (hi, lo): lo is the
            # canonical nonnegative low limb, so per-limb monotone encoding
            # composes into the int128 order
            keys.append(SortKey(c.hi, c.validity, not k.ascending, nulls_first))
        keys.append(SortKey(c.values, c.validity, not k.ascending, nulls_first))
    return keys


def _topn_step(node: Sort) -> Callable:
    """Traceable TopN stepping closure (chain → merge → sort → truncate),
    memoized on the node so the executor and the install-time breaker
    warmers hand _node_jit the SAME function object (one trace, one shared
    program). Derives everything from the node and its collapsed child
    chain — no runtime data captured."""
    memo = node.__dict__.get("_topn_step")
    if memo is not None:
        return memo
    _, chain0 = collapse_chain(node.child)
    chain = chain0 or (lambda b: b)
    cap = round_up_capacity(node.limit)

    @jax.named_scope("topn")
    def topn_step(acc: Optional[Batch], b: Batch):
        b = chain(b)
        if acc is not None:
            acc, b = _unify_batch_dicts([acc, b])
            merged = _concat2(acc, b)
        else:
            merged = b
        out = sort_batch(merged, _sort_keys(node, merged), limit=node.limit)
        return _truncate(out, cap)

    node.__dict__["_topn_step"] = topn_step
    return topn_step


def _execute_sort(node: Sort, ctx: ExecContext) -> Iterator[Batch]:
    in_stream, chain = _fused_child(node.child, ctx)
    if node.limit is not None:
        acc: Optional[Batch] = None
        topn_step = _topn_step(node)

        # acc is threaded linearly (the previous acc is dead once the step
        # returns, and only the final one is yielded), so its buffers are
        # donated for in-place update instead of double-buffering the heap
        _topn_kw = ({"donate_argnums": (0,)}
                    if ctx.config.donate_stepping else {})
        jstep = _node_jit(node, "topn", lambda: topn_step, **_topn_kw)
        frag_why = _fragment_eligibility(node, ctx.config)
        node.__dict__["_fragment_fusion"] = (
            "fused" if frag_why is None else frag_why)
        if frag_why is None:
            # fused fragment: stack each window and fold the TopN step over
            # it on-device — the heap never overflows (capacity is the
            # LIMIT) so there is no confirm/replay protocol to thread through
            jfstep = _node_jit(
                node, "fragment_topn",
                lambda: _fragment_jit.topn_stepper(topn_step, False),
                **_topn_kw)
            jfstep0 = _node_jit(
                node, "fragment_topn0",
                lambda: _fragment_jit.topn_stepper(topn_step, True))
            src = _fragment_jit.WindowSource(
                in_stream, ctx.config.fragment_window,
                bucket=ctx.config.shape_bucketing != "off",
                on_window=_inflight_window_hook(node, ctx))
            try:
                for item in src:
                    if isinstance(item, _fragment_jit.Window):
                        acc = (jfstep0(*item.operands) if acc is None
                               else jfstep(acc, *item.operands))
                        _record_fragment_dispatch(node, ctx, True, item.k)
                    else:
                        acc = jstep(acc, item)
                        _record_fragment_dispatch(node, ctx, False)
            finally:
                src.close()
        else:
            for raw in in_stream:
                acc = jstep(acc, raw)
                _record_fragment_dispatch(node, ctx, False)
        if acc is not None:
            yield acc
        return

    jchain = _node_jit(node, "chain", lambda: chain)
    full = _collect_concat(jchain(b) for b in in_stream)
    if full is None:
        return
    yield _node_jit(node, "sort", lambda: (lambda b: sort_batch(b, _sort_keys(node, b))))(full)


def _concat2(a: Batch, b: Batch) -> Batch:
    caps = [a.capacity, b.capacity]
    cols = [
        concat_columns([a.columns[i], b.columns[i]], caps)
        for i in range(len(a.names))
    ]
    dicts = dict(a.dicts)
    dicts.update(b.dicts)
    return Batch(a.names, a.types, cols, jnp.concatenate([a.live, b.live]), dicts)


def _truncate(b: Batch, cap: int) -> Batch:
    cols = [slice_column(c, cap) for c in b.columns]
    return Batch(b.names, b.types, cols, b.live[:cap], b.dicts)


# ---------------------------------------------------------------------------
# plan entry


def bind_scalar_subqueries(qp: QueryPlan, ctx: ExecContext) -> None:
    """Execute the plan's uncorrelated scalar subqueries (each gathers to
    one value via the local streaming engine) and bind them as Constants —
    shared by run_plan, the coordinator and the mesh executor so the
    0-row/multi-row semantics can never diverge between engines."""
    if not qp.scalar_subqueries:
        return
    bindings = {}
    for sym, sub in qp.scalar_subqueries.items():
        sub_out = run_plan(sub, ctx)
        vals = sub_out.to_pydict(decode_strings=False)[sub_out.names[0]]
        if len(vals) != 1:
            raise RuntimeError(f"scalar subquery returned {len(vals)} rows")
        bindings[sym] = Constant(sub_out.types[0], vals[0], raw=True)
    _bind_plan_params(qp.root, bindings)


# breaker children pulled through _fused_child (their chain fuses into the
# breaker's own stepping programs — no separate "down" program exists for
# them, so precompiling one would be wasted work)
_FUSED_CHILD_SIDES = {
    Aggregate: (0,), Sort: (0,), Unnest: (0,),
    HashJoin: (0,), SemiJoin: (0,), NestedLoopJoin: (0,), IndexJoin: (0,),
}


def _scan_warm_cap(scan: TableScan, ctx: ExecContext) -> Optional[int]:
    """Eligibility + capacity for fabricating this scan's runtime batch
    structure ahead of the stream. VARCHAR columns ARE warmable when the
    table handle carries their (identity-stable) dictionary — the batch
    codes against that same object at run time, so the fabricated treedef
    matches. Decimals past 18 digits (hi-limb plane) and types without a
    static dtype stay unwarmable: their plane layout depends on decoded
    data."""
    from presto_tpu.types import DecimalType as _Dec

    if not scan.assignments:
        return None
    types = dict(scan.output)
    try:
        handle = ctx.catalog.connectors[scan.catalog].get_table(scan.table)
        nrows = int(handle.row_count or 0)
    except Exception:
        return None
    for sym, colname in scan.assignments.items():
        t = types[sym]
        if isinstance(t, _Dec) and t.precision > 18:
            return None
        try:
            t.dtype
        except Exception:
            return None
        if getattr(t, "is_string", False):
            try:
                if handle.column(colname).dictionary is None:
                    return None
            except Exception:
                return None
    return round_up_capacity(min(nrows, ctx.config.batch_rows) or 1)


def _fabricate_scan_batch(scan: TableScan, cap: int,
                          ctx: ExecContext) -> Optional[Batch]:
    """Zero-filled batch with the same pytree STRUCTURE runtime scan
    batches will have: per-column dtype, validity-plane presence (stats
    null_fraction hint), and the handle's own Dictionary objects (treedef
    identity — Dictionary equality is `is`)."""
    types = dict(scan.output)
    try:
        handle = ctx.catalog.connectors[scan.catalog].get_table(scan.table)
    except Exception:
        return None
    names, btypes, cols, dicts = [], [], [], {}
    for sym, colname in scan.assignments.items():
        t = types[sym]
        try:
            info = handle.column(colname)
        except Exception:
            info = None
        st = info.stats if info is not None else None
        validity = (jnp.ones(cap, dtype=bool)
                    if st is not None and (st.null_fraction or 0.0) > 0.0
                    else None)
        d = info.dictionary if info is not None else None
        if getattr(t, "is_string", False) and d is None:
            return None
        if d is not None:
            dicts[sym] = d
        names.append(sym)
        btypes.append(t)
        cols.append(Column(jnp.zeros(cap, t.dtype), validity))
    return Batch(names, btypes, cols, jnp.zeros(cap, dtype=bool), dicts)


def _chain_warmers(root: PlanNode, ctx: ExecContext) -> List[Callable]:
    """Warm tasks for ahead-of-stream precompilation: the scan-side fused
    chain programs execute_node will jit under key "down", plus the
    breaker step / fused fragment-step programs of Aggregate and TopN
    nodes whose collapsed child base is a warmable TableScan (their chain
    fuses INTO the stepping programs, so the breaker warm is the only way
    those chains precompile). Best-effort by contract: a missed warm only
    means the compile happens on batch 0, as it did before the compile
    plane existed; a structurally-wrong fabrication compiles one unused
    specialization."""
    tasks: List[Callable] = []

    def breaker_scan(n: PlanNode) -> Optional[Tuple[TableScan, int]]:
        try:
            base, _ = collapse_chain(n.child)
        except Exception:
            return None
        if not isinstance(base, TableScan):
            return None
        cap = _scan_warm_cap(base, ctx)
        return None if cap is None else (base, cap)

    def visit(n: PlanNode, top: bool):
        if isinstance(n, (Filter, Project)):
            base, down = collapse_chain(n)
            if top and down is not None and isinstance(base, TableScan):
                cap = _scan_warm_cap(base, ctx)
                if cap is not None:
                    tasks.append(partial(_warm_down_chain, n, down, base, cap))
            visit(base, False)
            return
        if (isinstance(n, Aggregate)
                and not any(a.fn in _NON_DECOMPOSABLE_FNS for a in n.aggs)):
            hit = breaker_scan(n)
            if hit is not None:
                tasks.append(partial(_warm_agg_breaker, n, *hit, ctx))
        elif isinstance(n, Sort) and n.limit is not None:
            hit = breaker_scan(n)
            if hit is not None:
                tasks.append(partial(_warm_topn_breaker, n, *hit, ctx))
        fused = _FUSED_CHILD_SIDES.get(type(n), ())
        for i, c in enumerate(n.children()):
            visit(c, i not in fused)

    visit(root, True)
    return tasks


def _warm_down_chain(node: PlanNode, down, scan: TableScan, cap: int,
                     ctx: Optional[ExecContext] = None) -> None:
    if ctx is not None:
        zb = _fabricate_scan_batch(scan, cap, ctx)
    else:
        types = dict(scan.output)
        syms = list(scan.assignments.keys())
        zb = Batch(syms, [types[s] for s in syms],
                   [Column(jnp.zeros(cap, types[s].dtype), None)
                    for s in syms],
                   jnp.zeros(cap, bool), {})
    if zb is None:
        return
    out = _node_jit(node, "down", lambda: down)(zb)
    jax.block_until_ready(out.live)


def _warm_agg_breaker(node: Aggregate, scan: TableScan, scan_cap: int,
                      ctx: ExecContext) -> None:
    """Warm the Aggregate breaker's step/step0 (and, when the fragment
    fuses, fragment_step/fragment_step0) programs at the runtime presize
    fingerprint. The builders come from the SAME memoized _agg_steps
    closures and _node_jit keys the executor will use, so the warm and
    the run share one trace and one compiled program. Modes whose ingest
    never uses these programs (grace-from-start, radix engagement,
    grouped-execution sweeps) are skipped rather than guessed at."""
    if _grouped_execution_lifespans(node):
        return
    cap, ceiling, can_spill, grace_from_start = _agg_presize(node, ctx)
    if grace_from_start:
        return
    # same engine chooser as the run (no counter bump: warming is not a
    # dispatch) so the warm compiles the programs the run will use
    engine = _breaker_engine_choice(node, ctx, record=False)
    _ek = lambda k: _engine_key(k, engine)  # noqa: E731
    steps = _agg_steps(node, engine)
    merge_step = steps.merge_step
    key_syms = steps.key_syms
    if (key_syms and ctx.config.radix_partitions > 1
            and (ctx.config.join_spill_budget_bytes is not None
                 or cap > ctx.config.agg_capacity)):
        return  # radix ingest uses the prechained step family instead
    zb = _fabricate_scan_batch(scan, scan_cap, ctx)
    if zb is None:
        return
    _step_jit_kw = {}
    if ctx.config.donate_stepping and not key_syms:
        _step_jit_kw["donate_argnums"] = (0,)
    jit_step = _node_jit(node, _ek("step"), lambda: (lambda acc, b, cap: merge_step(acc, b, cap)), static_argnums=(2,), **_step_jit_kw)
    jit_step0 = _node_jit(node, _ek("step0"), lambda: (lambda b, cap: merge_step(None, b, cap)), static_argnums=(1,))
    acc, _ = jit_step0(zb, cap)
    acc, _ = jit_step(acc, zb, cap)
    if _fragment_eligibility(node, ctx.config) is None:
        width = max(2, ctx.config.fragment_window)
        win = _fragment_jit.Window((zb,) * width, width)
        jit_frag_step = _node_jit(
            node, _ek("fragment_step"),
            lambda: _fragment_jit.scan_stepper(merge_step, False),
            static_argnums=(3,), **_step_jit_kw)
        jit_frag_step0 = _node_jit(
            node, _ek("fragment_step0"),
            lambda: _fragment_jit.scan_stepper(merge_step, True),
            static_argnums=(2,))
        facc, _ = jit_frag_step0(*win.operands, cap)
        facc, _ = jit_frag_step(facc, *win.operands, cap)
        jax.block_until_ready(facc.live)
    jax.block_until_ready(acc.live)


def _warm_topn_breaker(node: Sort, scan: TableScan, scan_cap: int,
                       ctx: ExecContext) -> None:
    """Warm the TopN breaker's stepping programs (per-batch and, when the
    fragment fuses, the window variants) from a fabricated scan
    batch — same memoized _topn_step closure and _node_jit keys as the
    executor."""
    zb = _fabricate_scan_batch(scan, scan_cap, ctx)
    if zb is None:
        return
    topn_step = _topn_step(node)
    _topn_kw = ({"donate_argnums": (0,)}
                if ctx.config.donate_stepping else {})
    jstep = _node_jit(node, "topn", lambda: topn_step, **_topn_kw)
    acc = jstep(None, zb)
    acc = jstep(acc, zb)
    if _fragment_eligibility(node, ctx.config) is None:
        width = max(2, ctx.config.fragment_window)
        win = _fragment_jit.Window((zb,) * width, width)
        jfstep = _node_jit(
            node, "fragment_topn",
            lambda: _fragment_jit.topn_stepper(topn_step, False),
            **_topn_kw)
        jfstep0 = _node_jit(
            node, "fragment_topn0",
            lambda: _fragment_jit.topn_stepper(topn_step, True))
        facc = jfstep0(*win.operands)
        facc = jfstep(facc, *win.operands)
        jax.block_until_ready(facc.live)
    jax.block_until_ready(acc.live)


def install_plan_programs(root: PlanNode, ctx: ExecContext) -> None:
    """Compile-plane entry point for a bound, fully-rewritten plan: stamp
    every node's structural program namespace (so _node_jit shares
    programs process-wide) and, when configured, kick off ahead-of-stream
    precompilation so scan-chain compiles overlap host decode. Call after
    every structure-mutating pass (subquery binding, colocation tagging,
    fragment decode)."""
    from presto_tpu.plan.stats import require_hash_engine

    # loud, at install — never a quiet sort later
    require_hash_engine(getattr(ctx.config, "breaker_engine", "auto"))
    _programs.install_plan(root, ctx.config)
    if getattr(ctx.config, "devprof", "off") == "on":
        from presto_tpu.obs import devprof as _devprof

        _devprof.activate()
    try:
        _mark_fragment_fusion(root, ctx.config)
    except Exception:
        pass  # cosmetic EXPLAIN marker; the executor re-stamps on run
    try:
        _mark_breaker_engines(root, ctx)
    except Exception:
        pass  # cosmetic EXPLAIN marker; the executor re-stamps on run
    if _farm.enabled(ctx.config):
        try:
            _farm.record_plan(root, ctx)
        except Exception:
            pass  # corpus write is advisory; never fail an install on it
    if ctx.config.precompile_workers > 0:
        warmers = _chain_warmers(root, ctx)
        if _farm.enabled(ctx.config):
            warmers = _farm.wrap_claims(warmers)
        _programs.submit_warmers(warmers, ctx.config.precompile_workers)


def _mark_breaker_engines(root: PlanNode, ctx: "ExecContext") -> None:
    """Stamp the CBO's breaker-engine verdict (sort | hash + rationale)
    on every engine-dimensioned breaker so EXPLAIN (without ANALYZE)
    already shows it; the executors re-stamp on run (adding per-query
    gates like a build-batch dtype deviation) and bump the dispatch
    counters there."""

    def visit(n: PlanNode):
        if isinstance(n, (Aggregate, HashJoin, SemiJoin)):
            _breaker_engine_choice(n, ctx, record=False)
        for c in n.children():
            visit(c)

    visit(root)


def _mark_fragment_fusion(root: PlanNode, config: ExecConfig) -> None:
    """Stamp the static fragment-fusion eligibility verdict on every
    breaker so EXPLAIN (without ANALYZE) already shows which fragments
    will fuse; executors overwrite with the runtime decision (which adds
    per-query gates like grace-from-start)."""

    def visit(n: PlanNode):
        if isinstance(n, (Aggregate, Sort)):
            why = _fragment_eligibility(n, config)
            n.__dict__["_fragment_fusion"] = (
                "fused" if why is None else why)
        for c in n.children():
            visit(c)

    visit(root)


def run_plan(qp: QueryPlan, ctx: ExecContext) -> Batch:
    """Execute a QueryPlan to a single host-collectable Batch."""
    try:
        with _obs_trace.use(ctx.tracer), ctx.tracer.span("query", "query"):
            if getattr(ctx.config, "devprof", "off") != "on":
                return _run_plan_inner(qp, ctx)
            # devprof plane: HBM watermarks at the query span boundaries
            # plus a ledger-vs-device reconciliation once the query's pool
            # peak is final (obs/devprof.py; activate happens at plan
            # install)
            from presto_tpu.obs import devprof as _devprof

            _devprof.activate()
            _devprof.sample_hbm(tag="query_start")
            try:
                return _run_plan_inner(qp, ctx)
            finally:
                _devprof.sample_hbm(tag="query_end")
                try:
                    _devprof.reconcile(ctx.memory_pool, plane="worker",
                                       site="local_query")
                except Exception:
                    pass
    finally:
        # spill-file leak guard: whatever the operator generators left
        # open (mid-spill failure, abandoned iterator) is closed+unlinked
        ctx.cleanup_spill()


def _run_plan_inner(qp: QueryPlan, ctx: ExecContext) -> Batch:
    bind_scalar_subqueries(qp, ctx)

    # local grouped execution: mark bucket-colocated joins so the executor
    # sweeps them lifespan-by-lifespan (the fragmenter does this for the
    # distributed path); tagged once — cached plans skip the re-walk
    if not qp.__dict__.get("_colocated_tagged"):
        from presto_tpu.plan.fragmenter import tag_colocated_joins

        tag_colocated_joins(qp.root, ctx.catalog)
        qp.__dict__["_colocated_tagged"] = True

    # stamp structural program namespaces once the plan is fully bound
    # (subqueries bound, colocation tagged); re-stamped only when the
    # config's program-relevant fields change
    cfg_fp = _programs.config_fingerprint(ctx.config)
    if qp.__dict__.get("_programs_installed") != cfg_fp:
        install_plan_programs(qp.root, ctx)
        qp.__dict__["_programs_installed"] = cfg_fp

    out_node = qp.root
    batches = list(execute_node(out_node.child, ctx))
    _hbo_record_scans(qp.root, ctx)
    merged = _collect_concat(iter(batches))
    if merged is None:
        types = dict(out_node.child.output)
        merged = Batch(
            out_node.symbols,
            [types[s] for s in out_node.symbols],
            [Column(jnp.zeros(128, types[s].dtype), None) for s in out_node.symbols],
            jnp.zeros(128, bool),
            {},
        )
    merged = merged.select(out_node.symbols).rename(out_node.names)
    out = _JIT_COMPACT(merged)
    cfg = ctx.config
    if (cfg.max_compiled_shapes or cfg.max_compiled_shapes_scan
            or cfg.max_compiled_shapes_breaker):
        from presto_tpu.analysis.recompile import enforce

        enforce(qp.root, cfg.max_compiled_shapes,
                scan_budget=cfg.max_compiled_shapes_scan,
                breaker_budget=cfg.max_compiled_shapes_breaker)
    return out


def _bind_plan_params(node: PlanNode, bindings):
    if isinstance(node, Filter):
        node.predicate = substitute_params(node.predicate, bindings)
    elif isinstance(node, Project):
        node.exprs = [(s, substitute_params(e, bindings)) for s, e in node.exprs]
    elif isinstance(node, HashJoin) and node.residual is not None:
        node.residual = substitute_params(node.residual, bindings)
    for c in node.children():
        _bind_plan_params(c, bindings)
