"""Mesh SQL executor — a fragmented DistributedPlan as ONE shard_map program.

Reference mapping (SURVEY §2e "TPU-native equivalent"): the reference moves
pages between fragments through PartitionedOutputOperator.partitionPage:377
→ OutputBuffer → HTTP → ExchangeClient.java:69. Within a TPU slice the
same dataflow is a synchronous collective: every OUT_HASH exchange lowers
to a hash-partition kernel + `jax.lax.all_to_all`, OUT_BROADCAST /
OUT_GATHER lower to `all_gather`, and the fragments themselves — scan
chains, partial/final aggregation, co-located hash joins — trace into one
XLA program executed SPMD over the mesh. The HTTP cluster
(server/coordinator.py) remains the cross-host path; this executor is the
intra-slice path where the shuffle rides ICI and the host never touches
row data.

Supported fragment shapes (the TPC-H star-join/aggregate core and beyond):
scans with filter/project chains, partial→final aggregate splits,
broadcast and hash-partitioned joins (unique and bounded-fanout; INNER /
LEFT / FULL OUTER — RIGHT normalizes to LEFT at analysis), semi joins,
window functions (one-sort closed-form kernels), UNION [ALL] /
INTERSECT / EXCEPT, UNNEST, gathered sort/topn/limit/output.

The exchange plane is production-shaped along four axes:

1. **Stats-sized lanes** — an OUT_HASH exchange's per-lane capacity comes
   from the producing fragment's CBO estimate (Fragment.est_rows /
   est_key_ndv via plan/stats.exchange_lane_rows) with a skew headroom
   factor, clamped by the pessimistic padding bound, so ICI bytes track
   estimated rows instead of `capacity // n_dev * 2` padding.
2. **Fused single-buffer collectives** — every exchanged plane (values /
   validity / hi / live) is packed into dtype-bucketed dense buffers
   (parallel/lanes.py) and the exchange issues ONE all_to_all per dtype
   bucket instead of one per array; the partition scatter and the packing
   fuse into a single scatter per bucket (ops/partition.partition_layout).
3. **Surgical overflow replay** — every data-dependent capacity (exchange
   lane, group table, join fanout width, join output) claims a SITE in
   lowering order; its overflow diagnostic is psum-reduced into a per-site
   vector checked on the host. A retry re-traces with ONLY the overflowing
   sites' capacities doubled — not the old global `_cap_boost *= 2` that
   re-padded every capacity and stayed sticky across queries.
4. **Hash-engine breakers on-mesh** — `choose_breaker_engine` (the PR 7
   CBO) routes small-NDV/high-duplication aggregates and small-build
   joins/semijoins to the Pallas linear-probing kernels inside the
   shard_map program (`interpret=True` off-TPU keeps CPU sweeps exact);
   the engine choice is part of the traced structure, so it keys the
   mesh program cache.

Structurally identical queries reuse the compiled shard_map program via a
per-executor cache keyed on (fragment canonical JSON, per-site boosts,
config fingerprint) — the mesh analog of exec/programs.py.
"""

from __future__ import annotations

import hashlib
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from presto_tpu.batch import Batch, Column, round_up_capacity
from presto_tpu.connector import Catalog
from presto_tpu.exec.runtime import (
    ExecConfig,
    _input_state,
    _join_plan_cdt,
    _renorm_limbs,
    build_agg_finalizer,
    collapse_chain,
)
from presto_tpu.ops.grouping import KeyCol, StateCol, grouped_merge
from presto_tpu.ops.join import (
    align_probe_strings,
    build_side,
    gather_join_output,
    hash_build_side,
    hash_probe_counts,
    hash_probe_expand,
    hash_probe_unique,
    join_compare_dtypes,
    probe_counts,
    probe_expand,
    probe_unique,
)
from presto_tpu.ops.partition import partition_for_exchange, partition_layout
from presto_tpu.ops.sort import limit_batch, sort_batch
from presto_tpu.parallel import lanes
from presto_tpu.parallel.mesh import WORKERS, shard_map
from presto_tpu.plan.agg_states import (
    agg_state_layout,
    limb_pairs,
    state_types as layout_state_types,
)
from presto_tpu.plan.fragmenter import (
    OUT_BROADCAST,
    OUT_GATHER,
    OUT_HASH,
    DistributedPlan,
    fragment_plan,
)
from presto_tpu.plan.nodes import (
    Aggregate,
    Filter,
    HashJoin,
    Limit,
    Output,
    PlanNode,
    Project,
    RemoteSource,
    SemiJoin,
    Sort,
    TableScan,
    Window,
)
from presto_tpu.exec.runtime import _sort_keys
from presto_tpu.obs import trace as _obs_trace
from presto_tpu.scan import metrics as _scan_metrics


class MeshOverflow(RuntimeError):
    """A capacity site overflowed. `sites` maps site id → globally dropped
    rows; `site_caps` maps site id → the capacity that overflowed (for
    diagnostics); `labels` names each site."""

    def __init__(self, msg: str, sites=None, site_caps=None, labels=None):
        super().__init__(msg)
        self.sites: Dict[int, int] = dict(sites or {})
        self.site_caps: Dict[int, int] = dict(site_caps or {})
        self.labels = list(labels or [])


class _SiteTracker:
    """Per-trace registry of data-dependent capacity sites.

    A site id is the claim ORDER during lowering — deterministic because
    lowering walks the fragment DAG identically on every trace of the
    same plan — so a host-side {site: boost} map survives re-tracing and
    a retry can double exactly the site that overflowed. Each claimed
    site must `record` exactly one overflow diagnostic."""

    def __init__(self, boosts: Dict[int, int],
                 lane_overrides: Optional[Dict[int, int]] = None):
        self._boosts = boosts
        # adaptive lane resize: fid -> observed lane_max from a failed
        # attempt THIS run — the retry sizes that exchange exactly instead
        # of walking the ×2 boost ladder
        self.lane_overrides = lane_overrides or {}
        self.labels: List[tuple] = []
        self.caps: List[Optional[int]] = []
        self.diags: List[Optional[jnp.ndarray]] = []
        # OUT_HASH exchange accounting, in exchange order:
        self.exchanges: List[dict] = []       # static per-exchange meta
        self.lane_used: List[jnp.ndarray] = []  # traced occupied-slot counts
        # traced UNCAPPED per-lane row maxima (pmax-reduced): the true lane
        # capacity this exchange needed — obs/runstats records it against
        # est_lane_rows so a repeat run sizes lanes from observation
        self.lane_max: List[jnp.ndarray] = []

    def claim(self, label: tuple) -> Tuple[int, int]:
        i = len(self.labels)
        self.labels.append(label)
        self.caps.append(None)
        self.diags.append(None)
        return i, self._boosts.get(i, 1)

    def record(self, site: int, diag, cap: Optional[int] = None) -> None:
        self.diags[site] = diag
        if cap is not None:
            self.caps[site] = cap


class _CachedProgram:
    __slots__ = ("fn", "meta")

    def __init__(self):
        self.fn = None
        # filled at trace time: n_sites, labels, caps, exchanges, traces
        self.meta: dict = {"traces": 0}


def _all_to_all_batch(b: Batch, n_dev: int, per_cap: int) -> Batch:
    """Per-plane exchange — the fallback when the lane packer declines the
    batch (structural columns); one all_to_all per array."""

    def a2a(x):
        if x is None:
            return None
        y = jax.lax.all_to_all(x.reshape(n_dev, per_cap), WORKERS,
                               split_axis=0, concat_axis=0, tiled=False)
        return y.reshape(-1)

    cols = [Column(a2a(c.values), a2a(c.validity), a2a(c.hi))
            for c in b.columns]
    return Batch(b.names, b.types, cols, a2a(b.live), b.dicts)


def _fused_all_to_all(bufs, n_dev: int, per_cap: int):
    """Exchange packed lane buffers: one collective per dtype bucket. Each
    buffer is [L, n_dev*per_cap]; splitting the folded partition axis and
    concatenating received chunks on the same axis preserves the (device,
    partition, slot) addressing the per-plane path uses."""
    out = []
    for buf in bufs:
        nl = buf.shape[0]
        y = jax.lax.all_to_all(buf.reshape(nl, n_dev, per_cap), WORKERS,
                               split_axis=1, concat_axis=1, tiled=False)
        out.append(y.reshape(nl, n_dev * per_cap))
    return out


def _gather_batch(b: Batch) -> Batch:
    """Replicate all rows on every device (OUT_GATHER / OUT_BROADCAST)."""

    def ag(x):
        if x is None:
            return None
        return jax.lax.all_gather(x, WORKERS, tiled=True)

    cols = [Column(ag(c.values), ag(c.validity), ag(c.hi)) for c in b.columns]
    return Batch(b.names, b.types, cols, ag(b.live), b.dicts)


class MeshExecutor:
    """Executes SQL over an n-device mesh with collective exchanges."""

    def __init__(self, catalog: Catalog, mesh, config: Optional[ExecConfig] = None,
                 fanout_budget: int = 4, max_retries: int = 6):
        self.catalog = catalog
        self.mesh = mesh
        self.n_dev = mesh.shape[WORKERS]
        self.config = config or ExecConfig()
        self.fanout_budget = fanout_budget
        self.max_retries = max_retries
        # structural program cache: (plan digest, boosts) → compiled
        # shard_map program + its trace-time site/exchange metadata
        self._progs: Dict[tuple, _CachedProgram] = {}
        # observability snapshot of the most recent run_dplan: retries,
        # per-site boosts (always fresh per run — overflow inflation must
        # not leak into later queries), and per-attempt site/exchange meta
        self.last_run: Optional[dict] = None

    # -- host-side staging -------------------------------------------------

    def _stage_scan(self, scan: TableScan, sharded: bool) -> Batch:
        """Read splits per device; build a row-sharded (SOURCE/HASH
        fragments: splits d::N per device) or replicated (SINGLE fragments:
        every device reads all splits) global Batch."""
        conn = self.catalog.connectors[scan.catalog]
        handle = conn.get_table(scan.table)
        nrows = int(handle.row_count or 0)
        nsplits = max(self.n_dev, -(-nrows // self.config.batch_rows))
        columns = list(scan.assignments.values())
        symbols = list(scan.assignments.keys())
        out_types = dict(scan.output)
        if not columns and handle.columns:
            # COUNT(*)-style scan: stage one carrier column purely for row
            # multiplicity (the streaming engine fabricates liveness; the
            # mesh stager derives liveness from column data)
            columns = [handle.columns[0].name]
            symbols = ["__rowcount__"]
            out_types = {"__rowcount__": handle.columns[0].type}
        splits = conn.splits(handle, nsplits)
        if sharded:
            if any(s.bucket is not None for s in splits):
                # bucketed table: place by bucket id so colocated joins
                # stay aligned across tables (bucket b of every table
                # lands on device b % N)
                per_splits = [
                    [s for s in splits if s.bucket % self.n_dev == d]
                    for d in range(self.n_dev)
                ]
            else:
                per_splits = [splits[d::self.n_dev]
                              for d in range(self.n_dev)]
            per_dev: List[List[Batch]] = [
                [conn.read_split(s, columns) for s in ss]
                for ss in per_splits
            ]
        else:
            all_b = [conn.read_split(s, columns) for s in splits]
            per_dev = [all_b]  # one logical copy; replicated by sharding
        cap = max((sum(int(np.asarray(b.live).sum()) for b in bs) or 1)
                  for bs in per_dev)
        cap = round_up_capacity(cap)
        names, types = symbols, [out_types[s] for s in symbols]
        groups = len(per_dev)
        data = {}
        live = np.zeros((groups, cap), bool)
        dicts = {}
        for ci, cname in enumerate(columns):
            arrs = np.zeros((groups, cap), dtype=types[ci].dtype)
            valid = None
            for d, bs in enumerate(per_dev):
                pos = 0
                for b in bs:
                    lv = np.asarray(b.live)
                    v = np.asarray(b.column(cname).values)[lv]
                    arrs[d, pos:pos + len(v)] = v
                    bv = b.column(cname).validity
                    if bv is not None:
                        if valid is None:
                            valid = np.ones((groups, cap), bool)
                        valid[d, pos:pos + len(v)] = np.asarray(bv)[lv]
                    if ci == 0:
                        live[d, pos:pos + len(v)] = True
                    pos += len(v)
                    if cname in b.dicts:
                        dicts[symbols[ci]] = b.dicts[cname]
            data[symbols[ci]] = (arrs, valid)
        spec = P(WORKERS) if sharded else P()
        sharding = NamedSharding(self.mesh, spec)
        cols = [
            Column(jax.device_put(data[s][0].reshape(-1), sharding),
                   None if data[s][1] is None
                   else jax.device_put(data[s][1].reshape(-1), sharding))
            for s in symbols
        ]
        return Batch(names, types, cols,
                     jax.device_put(live.reshape(-1), sharding), dicts)

    # -- engine choice (CBO) -----------------------------------------------

    def _engine_for(self, node: PlanNode) -> str:
        """Breaker engine for an on-mesh Aggregate/join: the session
        override, else the CBO thresholds. Stamped on the node (EXPLAIN)
        and counted on the shared engine-dispatch families. Runs at trace
        time, so a cached mesh program keeps its engine choice."""
        from presto_tpu.plan.stats import choose_breaker_engine

        override = getattr(self.config, "breaker_engine", "auto")
        hbo = getattr(self.config, "hbo", "observe")
        try:
            engine, why = choose_breaker_engine(node, self.catalog, override,
                                                hbo=hbo)
        except Exception:
            engine, why = "sort", "stats derivation failed"
        node.__dict__["_breaker_engine"] = engine
        node.__dict__["_breaker_engine_why"] = why
        _scan_metrics.record(f"breaker_dispatches_{engine}", 1)
        if "(hbo: observed)" in why:
            try:
                from presto_tpu.obs import runstats
                runstats.record_correction("breaker_engine")
            except Exception:
                pass
        tracer = _obs_trace.current()
        if tracer.enabled:
            t = time.time()
            tracer.record("breaker_engine", "breaker_engine", t, t,
                          node=type(node).__name__, engine=engine, why=why)
        return engine

    def _join_engine(self, node, build: Batch):
        """(engine, probe_dtypes, compare_dtypes) for a HashJoin/SemiJoin.
        Mirrors the streaming engine's guard (_JoinProber): a build batch
        whose key dtypes deviate from the plan's output types would
        mis-encode the hash planes — fall back to the sort engine."""
        engine = self._engine_for(node)
        ltypes = dict(node.left.output)
        probe_dtypes = tuple(
            jnp.dtype(ltypes[lk].dtype) for lk in node.left_keys)
        cdt = _join_plan_cdt(node)
        if engine == "hash" and join_compare_dtypes(
                build, tuple(node.right_keys), probe_dtypes) != cdt:
            engine = "sort"
            node.__dict__["_breaker_engine"] = "sort"
            node.__dict__["_breaker_engine_why"] = (
                "build batch dtypes deviate from plan types")
        return engine, probe_dtypes, cdt

    def _build_table(self, node, build: Batch, engine: str,
                     probe_dtypes):
        if engine == "hash":
            return hash_build_side(build, tuple(node.right_keys),
                                   probe_dtypes)
        return build_side(build, tuple(node.right_keys))

    # -- trace-time node lowering -----------------------------------------

    def _lower_agg(self, node: Aggregate, child: Batch, cap: int,
                   sites: _SiteTracker, site: int) -> Batch:
        in_types = dict(node.child.output)
        layout = agg_state_layout(node.aggs, in_types)
        lpairs = limb_pairs(layout)
        key_syms = node.group_keys
        key_types = [in_types[k] for k in key_syms]
        final_mode = node.step == "final"
        if final_mode:
            st_types = [in_types[name] for name, _, _ in layout]
        else:
            st_types = layout_state_types(layout, in_types)
        b = child
        keys = [KeyCol(b.column(k).values, b.column(k).validity,
                       len(b.dicts[k]) if k in b.dicts else None)
                for k in key_syms]
        states = []
        for (name, op, a), st in zip(layout, st_types):
            if final_mode:
                c = b.column(name)
                states.append(StateCol(c.values.astype(st.dtype), c.validity, op))
            else:
                states.append(_input_state(b, name, op, a, st, in_types))
        engine = self._engine_for(node)
        kout, sout, out_live, ng = grouped_merge(keys, states, b.live, cap,
                                                 engine=engine)
        sout = _renorm_limbs(list(sout), lpairs)
        sites.record(site, jnp.maximum(ng - cap, 0), cap)
        cols = [Column(k.values, k.validity) for k in kout] + [
            Column(s.values, s.validity if s.op != "count_add" else None)
            for s in sout
        ]
        names = list(key_syms) + [name for name, _, _ in layout]
        types = key_types + st_types
        dicts = {k: b.dicts[k] for k in key_syms if k in b.dicts}
        for name, op, a in layout:
            if op in ("min", "max"):
                if a.arg in b.dicts:
                    dicts[name] = b.dicts[a.arg]
                elif name in b.dicts:  # final mode: state col carries it
                    dicts[name] = b.dicts[name]
        acc = Batch(names, types, cols, out_live, dicts)
        if node.step == "partial":
            return acc
        fin = build_agg_finalizer(node, key_syms, key_types, in_types)
        return fin(acc)

    def _build_remainder(self, node: HashJoin, table, bm) -> Batch:
        """FULL OUTER tail: build rows no probe row matched, NULL probe
        columns (LookupJoinOperators.fullOuterJoin's lookup-outer pass).
        Correct on-mesh because the fragmenter never broadcasts a FULL
        join's build side (plan/fragmenter.py:157) — each device owns a
        disjoint hash partition of the build rows. Engine-agnostic: both
        BuildTable and HashJoinTable keep the hashes/orig_live/batch
        shape contract."""
        lsyms = [n for n, _ in node.left.output]
        rsyms = [n for n, _ in node.right.output]
        ltypes = dict(node.left.output)
        cap = table.hashes.shape[0]
        names, types, cols = [], [], []
        for c in lsyms:
            names.append(c)
            types.append(ltypes[c])
            cols.append(Column(jnp.zeros(cap, ltypes[c].dtype),
                               jnp.zeros(cap, bool)))
        for c in rsyms:
            names.append(c)
            types.append(table.batch.type_of(c))
            cols.append(table.batch.column(c))
        live = table.orig_live & ~bm
        return Batch(names, types, cols, live,
                     {c: table.batch.dicts[c] for c in rsyms
                      if c in table.batch.dicts})

    def _expand_pairs(self, probe: Batch, table, pba, lkeys, rkeys,
                      sites: _SiteTracker, engine: str = "sort", cdt=None):
        """Bounded-fanout pair expansion with overflow accounting — shared
        by joins and residual semijoins so the capacity formula and the
        per-site overflow protocol can't diverge. The hash engine claims a
        SECOND site for the match-matrix width: its surgical replay IS the
        streaming engine's fanout-widening ladder."""
        site, boost = sites.claim(("join_out",))
        out_cap = probe.capacity * self.fanout_budget * boost
        if engine == "hash":
            wsite, wboost = sites.claim(("join_fanout",))
            fanout = 8 * wboost  # pow2 — the probe kernel requires it
            mm, counts, offsets, total, _, wovf = hash_probe_counts(
                table, pba, lkeys, cdt, max_fanout_scan=fanout)
            sites.record(wsite, wovf, fanout)
            pr, bi, ol = hash_probe_expand(table, mm, counts, offsets,
                                           0, out_cap)
        else:
            lo, counts, offsets, total, _, _ovf = probe_counts(
                table, pba, lkeys, rkeys)
            pr, bi, ol = probe_expand(table, pba, lkeys, rkeys,
                                      lo, counts, offsets, 0, out_cap)
        sites.record(site, jnp.maximum(total - out_cap, 0), out_cap)
        return pr, bi, ol

    def _lower_join(self, node: HashJoin, probe: Batch, build: Batch,
                    sites: _SiteTracker) -> Batch:
        lsyms = [n for n, _ in node.left.output]
        rsyms = [n for n, _ in node.right.output]
        lkeys, rkeys = tuple(node.left_keys), tuple(node.right_keys)
        engine, probe_dtypes, cdt = self._join_engine(node, build)
        table = self._build_table(node, build, engine, probe_dtypes)
        pba = align_probe_strings(probe, lkeys, table, rkeys)
        build_cap = table.hashes.shape[0]
        if node.build_unique:
            if engine == "hash":
                idx, matched = hash_probe_unique(table, pba, lkeys, cdt)
            else:
                idx, matched = probe_unique(table, pba, lkeys, rkeys)
            out = gather_join_output(
                probe, table, jnp.arange(probe.capacity, dtype=jnp.int32),
                idx, probe.live, lsyms, rsyms)
            if node.kind == "inner":
                return out.with_live(out.live & matched)
            cols = list(out.columns)
            for i, nme in enumerate(out.names):
                if nme in rsyms:
                    c = cols[i]
                    valid = (c.validity if c.validity is not None
                             else jnp.ones(out.capacity, bool))
                    cols[i] = Column(c.values, valid & matched, c.hi)
            out = Batch(out.names, out.types, cols, out.live, out.dicts)
            if node.kind == "full":
                bm = (jnp.zeros(build_cap, bool)
                      .at[idx].max(matched & probe.live, mode="drop"))
                out = _trace_concat(out, self._build_remainder(node, table,
                                                               bm))
            return out
        # bounded fanout: one expansion chunk of probe_cap × fanout_budget
        pr, bi, ol = self._expand_pairs(probe, table, pba, lkeys, rkeys,
                                        sites, engine, cdt)
        out = gather_join_output(probe, table, pr, bi, ol, lsyms, rsyms)
        if node.kind in ("left", "full"):
            exists = (jnp.zeros(probe.capacity, dtype=jnp.int32)
                      .at[pr].max(ol.astype(jnp.int32), mode="drop")
                      .astype(bool))
            tail = gather_join_output(
                probe, table, jnp.arange(probe.capacity, dtype=jnp.int32),
                jnp.zeros(probe.capacity, dtype=jnp.int32),
                probe.live & ~exists, lsyms, rsyms)
            tcols = [
                Column(c.values, (jnp.zeros(tail.capacity, bool)
                                  if nme in rsyms else c.validity), c.hi)
                for nme, c in zip(tail.names, tail.columns)
            ]
            tail = Batch(tail.names, tail.types, tcols, tail.live, tail.dicts)
            out = _trace_concat(out, tail)
        if node.kind == "full":
            bm = (jnp.zeros(build_cap, bool)
                  .at[bi].max(ol, mode="drop"))
            out = _trace_concat(out, self._build_remainder(node, table, bm))
        return out

    def _lower(self, node: PlanNode, fragments, staged, memo,
               sites: _SiteTracker) -> Batch:
        """Per-device local lowering of a fragment subtree."""
        base, chain = collapse_chain(node)
        if chain is not None:
            return chain(self._lower(base, fragments, staged, memo, sites))
        if isinstance(node, TableScan):
            return staged[id(node)]
        if isinstance(node, RemoteSource):
            return self._lower_exchange(node.fragment_id, fragments, staged,
                                        memo, sites)
        if isinstance(node, Aggregate):
            child = self._lower(node.child, fragments, staged, memo, sites)
            site, boost = sites.claim(("agg", node.step or "single"))
            cap = self._agg_cap(node) * boost
            return self._lower_agg(node, child, cap, sites, site)
        if isinstance(node, HashJoin):
            probe = self._lower(node.left, fragments, staged, memo, sites)
            build = self._lower(node.right, fragments, staged, memo, sites)
            return self._lower_join(node, probe, build, sites)
        if isinstance(node, SemiJoin):
            probe = self._lower(node.left, fragments, staged, memo, sites)
            build = self._lower(node.right, fragments, staged, memo, sites)
            lkeys, rkeys = tuple(node.left_keys), tuple(node.right_keys)
            engine, probe_dtypes, cdt = self._join_engine(node, build)
            table = self._build_table(node, build, engine, probe_dtypes)
            pba = align_probe_strings(probe, lkeys, table, rkeys)
            if node.residual is None:
                if engine == "hash":
                    _, matched = hash_probe_unique(table, pba, lkeys, cdt)
                else:
                    _, matched = probe_unique(table, pba, lkeys, rkeys)
            else:
                # correlated EXISTS with non-equi conjuncts (Q21 shape):
                # bounded pair expansion + residual + per-probe-row ANY —
                # the mesh form of _execute_semijoin's residual path
                from presto_tpu.expr.compile import compile_predicate

                lsyms = [n for n, _ in node.left.output]
                rsyms = [n for n, _ in node.right.output]
                pred = compile_predicate(node.residual)
                pr, bi, ol = self._expand_pairs(probe, table, pba,
                                                lkeys, rkeys, sites,
                                                engine, cdt)
                pair = gather_join_output(probe, table, pr, bi, ol,
                                          lsyms, rsyms)
                ok = pred(pair) & pair.live
                matched = (jnp.zeros(probe.capacity, dtype=jnp.int32)
                           .at[pr].max(ok.astype(jnp.int32), mode="drop")
                           .astype(bool))
            if node.negated:
                keep = ~matched
                if node.null_aware and node.residual is None:
                    # NOT IN three-valued logic (same as the local
                    # engine): a NULL probe key against a non-empty set
                    # is NULL → row filtered
                    key_valid = jnp.ones(probe.capacity, bool)
                    for lk in lkeys:
                        kv = probe.column(lk).validity
                        if kv is not None:
                            key_valid = key_valid & kv
                    keep = keep & (key_valid | (table.n_rows == 0))
            else:
                keep = matched
            return probe.with_live(probe.live & keep)
        if isinstance(node, Sort):
            child = self._lower(node.child, fragments, staged, memo, sites)
            return sort_batch(child, _sort_keys(node, child), limit=node.limit)
        if isinstance(node, Limit):
            child = self._lower(node.child, fragments, staged, memo, sites)
            return limit_batch(child, node.count)
        if isinstance(node, Output):
            child = self._lower(node.child, fragments, staged, memo, sites)
            return child.select(node.symbols).rename(node.names)
        from presto_tpu.plan.nodes import SetOp, Unnest

        if isinstance(node, Unnest):
            from presto_tpu.exec.runtime import unnest_expand

            child = self._lower(node.child, fragments, staged, memo, sites)
            return unnest_expand(node, child)
        if isinstance(node, SetOp) and node.kind == "union":
            from presto_tpu.exec.runtime import (
                _distinct_rows,
                _unify_batch_dicts,
            )

            left = self._lower(node.left, fragments, staged, memo, sites)
            right = self._lower(node.right, fragments, staged, memo, sites)
            left = left.rename(node.symbols)
            right = right.rename(node.symbols)
            left, right = _unify_batch_dicts([left, right])
            merged = _trace_concat(left, right)
            if node.all:
                return merged
            return _distinct_rows(merged)
        if isinstance(node, SetOp) and node.kind in ("intersect", "except"):
            # membership on ALL columns, then distinct — the runtime's
            # _execute_setop shape, traced per device (inputs arrive
            # co-partitioned: the fragmenter hash-exchanges both branches
            # on the full column list)
            from presto_tpu.exec.runtime import (
                _distinct_rows,
                _unify_batch_dicts,
            )

            left = self._lower(node.left, fragments, staged, memo, sites)
            right = self._lower(node.right, fragments, staged, memo, sites)
            left = left.rename(node.symbols)
            right = right.rename(node.symbols)
            left, right = _unify_batch_dicts([left, right])
            keys = tuple(node.symbols)
            table = build_side(right, keys)
            pba = align_probe_strings(left, keys, table, keys)
            _, matched = probe_unique(table, pba, keys, keys)
            keep = matched if node.kind == "intersect" else ~matched
            return _distinct_rows(left.with_live(left.live & keep))
        if isinstance(node, Window):
            from presto_tpu.exec.runtime import build_window_compute

            child = self._lower(node.child, fragments, staged, memo, sites)
            return build_window_compute(node)(child)
        raise NotImplementedError(
            f"mesh executor: {type(node).__name__}")

    def _exchange_fp(self, f) -> str:
        """obs/runstats history key for an exchange: the producing
        fragment's root structure + catalog snapshot."""
        from presto_tpu.obs import runstats

        return runstats.node_fingerprint(f.root, self.catalog)

    def _observed_lane_rows(self, f) -> Optional[float]:
        """Observed per-lane row maximum from a prior run of the same
        structure, when hbo=correct and history exists."""
        if getattr(self.config, "hbo", "observe") != "correct":
            return None
        try:
            from presto_tpu.obs import runstats

            h = runstats.lookup(self._exchange_fp(f), "exchange_lane")
            if h and h.get("actual"):
                return float(h["actual"])
        except Exception:
            pass
        return None

    def _exchange_cap(self, f, out: Batch, boost: int,
                      observed_lane_rows: Optional[float] = None) -> int:
        """Per-lane row capacity of an OUT_HASH exchange. Observation-sized
        when hbo=correct and a prior run of the same structure recorded the
        true lane maximum; else stats-sized when the fragmenter stamped an
        estimate (exchange_lane_rows: uniform rows/n_dev² vs low-NDV
        concentration, × skew headroom), else the pessimistic
        capacity//n_dev×2 padding. The site boost doubles it on surgical
        replay; a lane never needs to exceed the producing batch's own
        capacity (it can hold every local row), which bounds the replay
        ladder."""
        fallback = max(out.capacity // self.n_dev, 128) * 2
        cap = fallback
        rows = getattr(f, "est_rows", None)
        if rows or observed_lane_rows is not None:
            from presto_tpu.plan.stats import exchange_lane_rows

            est = exchange_lane_rows(rows or 0.0,
                                     getattr(f, "est_key_ndv", None),
                                     self.n_dev,
                                     observed_lane_rows=observed_lane_rows)
            cap = int(min(max(est, 64.0), float(max(out.capacity, 64))))
        cap = min(cap * boost, round_up_capacity(out.capacity, minimum=64))
        return round_up_capacity(cap, minimum=64)

    def _lower_exchange(self, fid: int, fragments, staged, memo,
                        sites: _SiteTracker) -> Batch:
        if fid in memo:
            return memo[fid]
        f = fragments[fid]
        out = self._lower(f.root, fragments, staged, memo, sites)
        if f.output_partitioning == OUT_HASH:
            site, boost = sites.claim(("exchange", fid))
            obs_rows = self._observed_lane_rows(f)
            ovr = sites.lane_overrides.get(fid)
            if ovr is not None:
                # adaptive lane resize: the failed attempt MEASURED this
                # exchange's true per-lane requirement — size to it
                # exactly (clamped like _exchange_cap) instead of
                # replaying through the ×2 boost ladder
                per_cap = min(round_up_capacity(max(int(ovr), 64),
                                                minimum=64),
                              round_up_capacity(out.capacity, minimum=64))
            else:
                per_cap = self._exchange_cap(f, out, boost, obs_rows)
            if obs_rows is not None:
                try:
                    from presto_tpu.obs import runstats
                    runstats.record_correction("exchange_lane")
                except Exception:
                    pass
            keys = list(f.output_keys)
            out_n = self.n_dev * per_cap
            plan = lanes.plan_lanes(out)
            if plan is not None:
                sperm, dest, counts, routed, ovf = partition_layout(
                    out, keys, self.n_dev, per_cap)
                bufs = lanes.pack_partitioned(out, plan, sperm, dest,
                                              routed, out_n)
                bufs = _fused_all_to_all(bufs, self.n_dev, per_cap)
                exch = lanes.unpack_batch(out, plan, bufs)
                nbytes = plan.nbytes(out_n) * self.n_dev
                n_coll = plan.n_collectives
            else:
                parts, counts, ovf = partition_for_exchange(
                    out, keys, self.n_dev, per_cap)
                exch = _all_to_all_batch(parts, self.n_dev, per_cap)
                planes = [p for c in parts.columns
                          for p in (c.values, c.validity, c.hi)
                          if p is not None] + [parts.live]
                nbytes = sum(int(p.size) * p.dtype.itemsize
                             for p in planes) * self.n_dev
                n_coll = len(planes)
            sites.record(site, ovf, per_cap)
            sites.lane_used.append(
                jnp.sum(jnp.minimum(counts, per_cap)).astype(jnp.int64))
            sites.lane_max.append(jnp.max(counts).astype(jnp.int64))
            try:
                fp = self._exchange_fp(f)
            except Exception:
                fp = ""
            sites.exchanges.append({
                "fid": fid, "site": site, "per_cap": per_cap,
                "lanes_total": self.n_dev * self.n_dev * per_cap,
                "bytes": int(nbytes), "a2a": n_coll,
                "fused": plan is not None,
                # what the pre-stats sizing rule would have allocated —
                # bench/tests measure the utilization win against it
                "naive_per_cap": round_up_capacity(
                    max(out.capacity // self.n_dev, 128) * 2),
                # runstats plane: history key, the pure static estimate
                # (no boost, no HBO) the drift is measured against, and
                # whether observation sized this run's lanes
                "fp": fp,
                "est_lane_rows": self._exchange_cap(f, out, 1),
                "hbo_sized": obs_rows is not None,
                "lane_plan": plan.describe() if plan is not None else None,
            })
            out = exch
        elif f.output_partitioning in (OUT_GATHER, OUT_BROADCAST):
            out = _gather_batch(out)
        elif f.output_partitioning == "rr":
            # round-robin redistribution exists to balance load; on-mesh
            # every device already holds its share — rows stay put
            pass
        memo[fid] = out
        return out

    def _agg_cap(self, node: Aggregate) -> int:
        cap = self.config.agg_capacity
        try:
            from presto_tpu.plan.stats import derive

            st = derive(node, self.catalog)
        except Exception:
            st = None
        rows = st.rows if (st is not None and st.rows) else None
        if getattr(self.config, "hbo", "observe") == "correct":
            # observed group count from a prior run of this structure
            # (streaming or mesh — the fingerprint space is shared)
            try:
                from presto_tpu.obs import runstats

                h = runstats.lookup_node(node, self.catalog, "agg_groups")
                if h and h.get("actual"):
                    rows = float(h["actual"])
                    runstats.record_correction("agg_presize")
            except Exception:
                pass
        if rows:
            cap = max(cap, round_up_capacity(
                int(min(rows * 1.25, float(1 << 22)))))
        return cap

    # -- entry -------------------------------------------------------------

    def run_batch(self, sql: str) -> Batch:
        from presto_tpu.plan.builder import plan_query
        from presto_tpu.plan.optimizer import optimize

        qp = optimize(plan_query(sql, self.catalog), self.catalog)
        if qp.scalar_subqueries:
            # bind uncorrelated scalar subqueries before fragmenting (they
            # gather to one value; the local streaming engine computes
            # them host-side — shared helper with run_plan/coordinator)
            from presto_tpu.exec.runtime import (
                ExecContext,
                bind_scalar_subqueries,
            )

            bind_scalar_subqueries(qp, ExecContext(self.catalog, self.config))
        dplan = fragment_plan(qp, self.catalog,
                              hbo=getattr(self.config, "hbo", "observe"))
        return self.run_dplan(dplan)

    def run_dplan(self, dplan: DistributedPlan) -> Batch:
        """Execute with surgical per-site overflow replay: a retry doubles
        ONLY the sites that overflowed. Boosts are local to this call —
        an overflow on one query must not permanently inflate every later
        query's capacities (the old executor-level _cap_boost did)."""
        from presto_tpu.plan.stats import require_hash_engine

        require_hash_engine(getattr(self.config, "breaker_engine", "auto"))
        boosts: Dict[int, int] = {}
        lane_overrides: Dict[int, int] = {}
        adaptive_state = None
        if getattr(self.config, "adaptive", "off") != "off":
            try:
                from presto_tpu.exec.adaptive import AdaptiveState

                adaptive_state = AdaptiveState(
                    self.config.adaptive,
                    query_id=getattr(_obs_trace.current(), "trace_id",
                                     "") or "")
            except Exception:
                adaptive_state = None
        attempts: List[dict] = []
        last = None
        for _ in range(self.max_retries + 1):
            try:
                out = self._run_dplan_once(dplan, boosts, attempts,
                                           lane_overrides)
                self.last_run = {
                    "retries": len(attempts) - 1,
                    "boosts": dict(boosts),
                    "lane_overrides": dict(lane_overrides),
                    "attempts": attempts,
                }
                return out
            except MeshOverflow as e:
                last = e
                # adaptive lane resize: the failed attempt already pmax'd
                # each exchange's TRUE per-lane requirement — feed it back
                # as an exact override so the retry fits in one replay
                # instead of walking the ×2 boost ladder site by site
                handled = set()
                if adaptive_state is not None and attempts:
                    for ex in attempts[-1].get("exchanges", ()):
                        s = ex.get("site")
                        if s not in e.sites or ex.get("lane_max", 0) <= 0:
                            continue
                        new_cap = round_up_capacity(
                            max(int(ex["lane_max"]), 64), minimum=64)
                        if new_cap <= ex["per_cap"]:
                            continue
                        acted = adaptive_state.decide(
                            "lane_resize",
                            site=f"exchange_f{ex['fid']}",
                            before=int(ex["per_cap"]), after=int(new_cap),
                            detail=(f"lane f{ex['fid']} "
                                    f"{ex['per_cap']}->{new_cap}"),
                            lane_max=int(ex["lane_max"]))
                        if acted:
                            lane_overrides[ex["fid"]] = int(ex["lane_max"])
                            handled.add(s)
                for s in e.sites:
                    if s not in handled:
                        boosts[s] = boosts.get(s, 1) * 2
                _scan_metrics.record("mesh_exchange_overflow_retries", 1)
                _scan_metrics.record("breaker_replay_waves", 1)
                tracer = _obs_trace.current()
                if tracer.enabled:
                    t = time.time()
                    tracer.record(
                        "overflow_replay", "overflow_replay", t, t,
                        sites=",".join(str(s) for s in sorted(e.sites)),
                        cap_to=",".join(
                            str(e.site_caps.get(s, 0) * 2)
                            for s in sorted(e.sites)))
        self.last_run = {"retries": len(attempts) - 1,
                         "boosts": dict(boosts),
                         "lane_overrides": dict(lane_overrides),
                         "attempts": attempts}
        raise last

    def _dplan_key(self, dplan: DistributedPlan):
        """Structural digest for the mesh program cache. None (no caching)
        when a fragment has no canonical codec form."""
        from presto_tpu.exec.programs import config_fingerprint
        from presto_tpu.plan.codec import canonical_node_json

        h = hashlib.sha256()
        h.update(config_fingerprint(self.config).encode())
        h.update(f"|n={self.n_dev}|fb={self.fanout_budget}".encode())
        hbo = getattr(self.config, "hbo", "observe")
        if hbo == "correct":
            # corrected capacities are baked into the trace; mixing the
            # history generation in forces a re-trace once new
            # observations land ("hbo" itself is a volatile config field,
            # so config_fingerprint alone would collide with observe-mode)
            try:
                from presto_tpu.obs import runstats
                h.update(f"|hbo=c{runstats.generation()}".encode())
            except Exception:
                h.update(b"|hbo=c?")
        try:
            for fid in sorted(dplan.fragments):
                f = dplan.fragments[fid]
                h.update((f"|{fid}|{f.partitioning}|{f.output_partitioning}"
                          f"|{','.join(f.output_keys)}|").encode())
                h.update(canonical_node_json(f.root).encode())
        except Exception:
            return None
        h.update(f"|root={dplan.root_fid}".encode())
        return h.hexdigest()

    def _build_program(self, dplan, scan_nodes, scan_sharded,
                       boosts: Dict[int, int],
                       lane_overrides: Optional[Dict[int, int]] = None,
                       ) -> _CachedProgram:
        fragments = dplan.fragments
        root = fragments[dplan.root_fid]
        boosts = dict(boosts)
        lane_overrides = dict(lane_overrides or {})
        entry = _CachedProgram()
        meta = entry.meta

        def program(*scan_batches):
            # the body runs at TRACE time only — meta capture is free on
            # cached executions
            meta["traces"] = meta.get("traces", 0) + 1
            st = {nid: b for nid, b in zip([id(s) for s in scan_nodes],
                                           scan_batches)}
            sites = _SiteTracker(boosts, lane_overrides)
            memo: Dict[int, Batch] = {}
            out = self._lower(root.root, fragments, st, memo, sites)
            meta["n_sites"] = len(sites.labels)
            meta["labels"] = list(sites.labels)
            meta["caps"] = list(sites.caps)
            meta["exchanges"] = [dict(e) for e in sites.exchanges]
            diags = [jnp.int64(0) if d is None else d.astype(jnp.int64)
                     for d in sites.diags]
            # one psum over the stacked site vector (trailing sentinel 0
            # keeps the stack non-empty for site-free plans)
            ovf = jax.lax.psum(jnp.stack(diags + [jnp.int64(0)]), WORKERS)
            used = jax.lax.psum(
                jnp.stack(sites.lane_used + [jnp.int64(0)]), WORKERS)
            # pmax, not psum: the lane maximum is a high-water mark — the
            # worst (src device, dst partition) lane anywhere on the mesh
            # (in int32 — a lane holds far fewer than 2^31 rows, and the
            # TPU compiler lowers a 64-bit all-reduce only for sums)
            lmax = jax.lax.pmax(
                jnp.stack(sites.lane_max + [jnp.int64(0)]).astype(jnp.int32),
                WORKERS)
            return out, ovf, used, lmax

        in_specs = tuple(P(WORKERS) if sh else P()
                         for sh in scan_sharded)
        # the root fragment is always SINGLE (fragment_plan gathers before
        # it), so with multiple fragments every device computes an identical
        # replica; a one-fragment plan is row-sharded and the global view
        # IS the concatenated result
        entry.fn = jax.jit(shard_map(
            program, mesh=self.mesh,
            in_specs=in_specs,
            out_specs=(P(WORKERS), P(), P(), P()),
            check_vma=False,
        ))
        return entry

    def _run_dplan_once(self, dplan: DistributedPlan,
                        boosts: Dict[int, int],
                        attempts: List[dict],
                        lane_overrides: Optional[Dict[int, int]] = None,
                        ) -> Batch:
        fragments = dplan.fragments
        staged: Dict[int, Batch] = {}
        scan_nodes: List[TableScan] = []
        scan_sharded: List[bool] = []

        def find_scans(n: PlanNode, sharded: bool):
            if isinstance(n, TableScan):
                scan_nodes.append(n)
                scan_sharded.append(sharded)
            for c in n.children():
                find_scans(c, sharded)

        from presto_tpu.plan.fragmenter import SINGLE

        for f in fragments.values():
            find_scans(f.root, f.partitioning != SINGLE)
        for s, sh in zip(scan_nodes, scan_sharded):
            staged[id(s)] = self._stage_scan(s, sh)

        pkey = self._dplan_key(dplan)
        # lane overrides fork the program key exactly like boosts: an
        # adaptively resized exchange compiles different lane shapes
        key = (None if pkey is None
               else (pkey, tuple(sorted(boosts.items())),
                     tuple(sorted((lane_overrides or {}).items()))))
        entry = None if key is None else self._progs.get(key)
        if entry is None:
            entry = self._build_program(dplan, scan_nodes, scan_sharded,
                                        boosts, lane_overrides)
            if key is not None:
                self._progs[key] = entry
            from presto_tpu.obs import devprof as _devprof

            if _devprof.active():
                # devprof plane: analyze the whole-mesh program once on
                # build (the lowering is cheap; the compile the analysis
                # forces is the same one the first call pays anyway)
                try:
                    lowered = entry.fn.lower(
                        *[staged[id(s)] for s in scan_nodes])
                    rec = _devprof.analyze_lowered(lowered)
                    _devprof.record_program(
                        f"mesh|{pkey or 'uncached'}", rec,
                        kind="mesh_program", key=len(scan_nodes))
                except Exception:
                    pass

        t0 = time.time()
        out, ovf_vec, used_vec, lmax_vec = entry.fn(
            *[staged[id(s)] for s in scan_nodes])
        meta = entry.meta
        n_sites = meta.get("n_sites", 0)
        ovf = np.asarray(ovf_vec)[:n_sites]
        exchanges = [dict(e) for e in meta.get("exchanges", ())]
        used = np.asarray(used_vec)[:len(exchanges)]
        lmax = np.asarray(lmax_vec)[:len(exchanges)]
        t1 = time.time()

        total_bytes = total_slots = total_used = 0
        for e, u, lm in zip(exchanges, used, lmax):
            e["lanes_used"] = int(u)
            e["lane_max"] = int(lm)
            e["util"] = (float(u) / e["lanes_total"]
                         if e["lanes_total"] else 0.0)
            total_bytes += e["bytes"]
            total_slots += e["lanes_total"]
            total_used += int(u)
        _scan_metrics.record("mesh_exchange_bytes", total_bytes)
        _scan_metrics.record("mesh_exchange_lanes_used", total_used)
        _scan_metrics.record("mesh_exchange_lanes_total", total_slots)

        # mid-flight telemetry: per-site overflow watermarks + per-exchange
        # lane utilization into the inflight plane (no-op unless the query
        # registered with inflight=on; the vectors above are already host)
        if getattr(self.config, "inflight", "off") == "on":
            try:
                from presto_tpu.obs import inflight as _obs_inflight

                qid = getattr(_obs_trace.current(), "trace_id", None)
                if qid is not None and _obs_inflight.get(qid) is not None:
                    labels = meta.get("labels", [])
                    for i, v in enumerate(ovf):
                        _obs_inflight.publish(
                            qid, f"site{i}:{labels[i]}" if i < len(labels)
                            else f"site{i}", windows=1,
                            overflow=int(v), site=i)
                    for e in exchanges:
                        _obs_inflight.publish(
                            qid, f"exchange_f{e['fid']}",
                            task_id=f"mesh.f{e['fid']}",
                            fragment=int(e["fid"]), windows=1,
                            laneUtil=round(e["util"], 4),
                            lanesUsed=e["lanes_used"],
                            lanesTotal=e["lanes_total"])
            except Exception:
                pass
        attempts.append({
            "labels": list(meta.get("labels", ())),
            "site_caps": list(meta.get("caps", ())),
            "exchanges": exchanges,
            "overflow": [int(v) for v in ovf],
        })

        # runstats observation — BEFORE the overflow raise, so even a run
        # that overflows teaches the next one its true lane maxima
        if getattr(self.config, "hbo", "observe") != "off":
            try:
                from presto_tpu.obs import runstats

                for e in exchanges:
                    if e.get("fp") and e.get("lane_max", 0) > 0:
                        runstats.observe(
                            e["fp"], "exchange_lane", "exchange",
                            float(e.get("est_lane_rows") or 0.0),
                            float(e["lane_max"]),
                            extra={"util": round(e["util"], 4)})
            except Exception:
                pass

        # host-side trace spans: the fused program bypasses the tracer
        # (everything inside shard_map is traced code), so the dispatch
        # wall is covered by one mesh_program span with per-exchange
        # exchange_wait markers and lane_pack layout markers under it
        tracer = _obs_trace.current()
        if tracer.enabled:
            sp = tracer.record(
                "mesh_program", "mesh_program", t0, t1,
                n_sites=n_sites, exchanges=len(exchanges),
                traces=meta.get("traces", 0))
            for e in exchanges:
                tracer.record(
                    f"exchange f{e['fid']}", "exchange_wait", t1, t1,
                    parent_id=sp.span_id, fid=e["fid"], bytes=e["bytes"],
                    a2a=e["a2a"], per_cap=e["per_cap"],
                    lanes_used=e["lanes_used"],
                    lanes_total=e["lanes_total"],
                    util=round(e["util"], 4))
                if e.get("lane_plan"):
                    tracer.record(
                        f"lane_pack f{e['fid']}", "lane_pack", t1, t1,
                        parent_id=sp.span_id, fid=e["fid"],
                        **e["lane_plan"])

        bad = {i: int(v) for i, v in enumerate(ovf) if int(v) > 0}
        if bad:
            labels = meta.get("labels", [])
            caps = meta.get("caps", [])
            desc = ", ".join(
                f"site {i} {labels[i]} cap={caps[i]} dropped={n}"
                for i, n in bad.items())
            raise MeshOverflow(
                f"static capacity overflow: {desc}",
                sites=bad,
                site_caps={i: caps[i] for i in bad if caps[i] is not None},
                labels=labels)

        # stamp the exchange telemetry onto the plan for EXPLAIN-style
        # rendering (DistributedPlan.to_string shows [mesh: …] markers)
        for e in exchanges:
            frag = fragments.get(e["fid"])
            if frag is not None:
                frag.__dict__["_mesh_a2a"] = {
                    "a2a": e["a2a"], "bytes": e["bytes"], "util": e["util"],
                    "per_cap": e["per_cap"], "fused": e["fused"],
                }

        if len(fragments) > 1:
            # keep the first replica's rows
            from presto_tpu.exec.runtime import _truncate

            return _truncate(out, out.capacity // self.n_dev)
        return out

    def run(self, sql: str):
        return self.run_batch(sql).to_pandas()


def _trace_concat(a: Batch, b: Batch) -> Batch:
    from presto_tpu.exec.runtime import _concat2

    return _concat2(a, b)
