"""Logical plan optimization passes.

Analog of sql/planner/PlanOptimizers.java (76 passes) reduced to the ones
that matter for this execution model:

- PredicatePushdown (optimizations/PredicatePushDown.java): split conjuncts,
  push each to the deepest node whose output covers its inputs — through
  Projects (with substitution), past Joins into the covering side, below
  Aggregates when the conjunct only references group keys.
- PruneUnreferencedOutputs / PushdownSubfields-style column pruning: trim
  Project expressions and TableScan assignments to what the query needs.
  On this engine column pruning is the *scan pushdown* — the parquet reader
  only materializes referenced columns (the moral of the Aria selective
  reader's column skipping).
- Cleanup: merge adjacent Filters, drop identity Projects.

Join ordering happens at plan-build time (builder._assemble_joins) with
connector row counts — the stand-in for the cost-based ReorderJoins.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Set

from presto_tpu.expr.ir import (
    Call,
    InputRef,
    RowExpression,
    expr_inputs,
    substitute_refs,
)
from presto_tpu.plan.nodes import (
    Aggregate,
    Filter,
    HashJoin,
    Limit,
    Output,
    PlanNode,
    Project,
    QueryPlan,
    SemiJoin,
    SetOp,
    Sort,
    TableScan,
    Unnest,
    Window,
)
from presto_tpu.types import BOOLEAN


def _conjuncts(e: RowExpression) -> List[RowExpression]:
    if isinstance(e, Call) and e.fn == "and":
        out = []
        for a in e.args:
            out.extend(_conjuncts(a))
        return out
    return [e]


def _combine(es: List[RowExpression]) -> Optional[RowExpression]:
    if not es:
        return None
    out = es[0]
    for e in es[1:]:
        out = Call(BOOLEAN, "and", (out, e))
    return out


def push_filters(node: PlanNode) -> PlanNode:
    """Recursively push filter conjuncts toward the leaves."""
    if isinstance(node, Filter):
        child = push_filters(node.child)
        conjs = _conjuncts(node.predicate)
        return _push_into(child, conjs)
    for attr in ("child", "left", "right"):
        if hasattr(node, attr):
            setattr(node, attr, push_filters(getattr(node, attr)))
    return node


def _push_into(node: PlanNode, conjs: List[RowExpression]) -> PlanNode:
    if not conjs:
        return node
    if isinstance(node, Filter):
        return _push_into(node.child, conjs + _conjuncts(node.predicate))
    if isinstance(node, Project):
        mapping = {s: e for s, e in node.exprs}
        pushable, kept = [], []
        for c in conjs:
            # only substitute through cheap expressions (refs / arithmetic);
            # always safe since Project is stateless and deterministic
            pushable.append(substitute_refs(c, mapping))
        node.child = _push_into(node.child, pushable)
        return node
    if isinstance(node, HashJoin):
        lsyms = {n for n, _ in node.left.output}
        rsyms = {n for n, _ in node.right.output}
        lpush, rpush, kept = [], [], []
        for c in conjs:
            ins = expr_inputs(c)
            if ins <= lsyms and node.kind != "full":
                # probe-side push is fine for INNER and LEFT (probe rows
                # keep their own values); NOT for FULL — the build
                # remainder's NULL probe columns must be filtered
                # post-join, and pre-join evaluation can't see them
                lpush.append(c)
            elif ins <= rsyms and node.kind == "inner":
                rpush.append(c)
            else:
                # NOTE: a WHERE conjunct on build-side columns above a LEFT
                # join must NOT be pushed below it — it filters the
                # NULL-extended post-join rows (pushing it would resurrect
                # non-matching probe rows). ON-clause residuals are pushed at
                # plan-build time instead (builder.plan_join).
                kept.append(c)
        if lpush:
            node.left = _push_into(node.left, lpush)
        if rpush:
            node.right = _push_into(node.right, rpush)
        node.left = push_filters(node.left)
        node.right = push_filters(node.right)
        if kept:
            if node.kind == "inner":
                return Filter(node, _combine(kept))
            return Filter(node, _combine(kept))
        return node
    if isinstance(node, SemiJoin):
        lsyms = {n for n, _ in node.left.output}
        lpush, kept = [], []
        for c in conjs:
            (lpush if expr_inputs(c) <= lsyms else kept).append(c)
        if lpush:
            node.left = _push_into(node.left, lpush)
        node.left = push_filters(node.left)
        node.right = push_filters(node.right)
        return Filter(node, _combine(kept)) if kept else node
    from presto_tpu.plan.nodes import NestedLoopJoin as _NLJ

    if isinstance(node, _NLJ):
        # inner semantics: single-side conjuncts push through freely
        lsyms = {n for n, _ in node.left.output}
        rsyms = {n for n, _ in node.right.output}
        lpush, rpush, kept = [], [], []
        for c in conjs:
            ins = expr_inputs(c)
            if ins <= lsyms:
                lpush.append(c)
            elif ins <= rsyms:
                rpush.append(c)
            else:
                kept.append(c)
        if lpush:
            node.left = _push_into(node.left, lpush)
        if rpush:
            node.right = _push_into(node.right, rpush)
        node.left = push_filters(node.left)
        node.right = push_filters(node.right)
        return Filter(node, _combine(kept)) if kept else node
    if isinstance(node, Aggregate):
        keys = set(node.group_keys)
        below, above = [], []
        for c in conjs:
            (below if expr_inputs(c) <= keys else above).append(c)
        if below:
            node.child = _push_into(node.child, below)
        node.child = push_filters(node.child)
        return Filter(node, _combine(above)) if above else node
    if isinstance(node, (Sort, Limit)):
        # filters commute with sort/limit only if limit absent
        if isinstance(node, Sort) and node.limit is None:
            node.child = _push_into(node.child, conjs)
            return node
        node.child = push_filters(node.child)
        return Filter(node, _combine(conjs))
    # TableScan and everything else: stop here
    if isinstance(node, TableScan):
        _derive_scan_constraints(node, conjs)
    node2 = push_filters(node) if node.children() else node
    return Filter(node2, _combine(conjs))


def _derive_scan_constraints(scan: TableScan, conjs: List[RowExpression]):
    """Extract per-column (lo, hi) bounds from simple comparison conjuncts
    for connector split pruning (coarse TupleDomain pushdown — the IO-level
    slice of the reference's selective-reader filter pushdown). The exact
    filter still runs on-device; this only skips row groups."""
    from presto_tpu.expr.ir import Constant

    sym_to_col = {s: c for s, c in scan.assignments.items()}
    for c in conjs:
        if not (isinstance(c, Call) and c.fn in ("lt", "le", "gt", "ge", "eq")):
            continue
        a, b = c.args
        if isinstance(a, InputRef) and isinstance(b, Constant) and b.value is not None:
            ref, const, op = a, b, c.fn
        elif isinstance(b, InputRef) and isinstance(a, Constant) and a.value is not None:
            flip = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le", "eq": "eq"}
            ref, const, op = b, a, flip[c.fn]
        else:
            continue
        if ref.name not in sym_to_col:
            continue
        if const.type.is_string and not isinstance(const.value, str):
            # string bounds feed dictionary-code filters downstream; only
            # plain python-str constants have a well-defined order there
            continue
        col = sym_to_col[ref.name]
        lo, hi = scan.constraints.get(col, (None, None))
        v = const.value
        t = const.type
        from presto_tpu.types import DecimalType as _Dec

        if isinstance(t, _Dec) and not const.raw:
            v = int(round(float(v) * 10 ** t.scale))
        if op in ("gt", "ge"):
            lo = v if lo is None else max(lo, v)
        elif op in ("lt", "le"):
            hi = v if hi is None else min(hi, v)
        else:  # eq
            lo = v if lo is None else max(lo, v)
            hi = v if hi is None else min(hi, v)
        scan.constraints[col] = (lo, hi)


# ---------------------------------------------------------------------------
# column pruning


def prune_columns(node: PlanNode, required: Set[str]) -> PlanNode:
    if isinstance(node, Output):
        node.child = prune_columns(node.child, set(node.symbols))
        return node
    if isinstance(node, TableScan):
        node.assignments = {s: c for s, c in node.assignments.items() if s in required}
        node.output = [(s, t) for s, t in node.output if s in required]
        return node
    if isinstance(node, Filter):
        need = required | expr_inputs(node.predicate)
        node.child = prune_columns(node.child, need)
        return node
    if isinstance(node, Project):
        node.exprs = [(s, e) for s, e in node.exprs if s in required]
        need = set()
        for _, e in node.exprs:
            need |= expr_inputs(e)
        node.child = prune_columns(node.child, need)
        return node
    if isinstance(node, Aggregate):
        node.aggs = [a for a in node.aggs if a.symbol in required]
        need = set(node.group_keys) | {a.arg for a in node.aggs if a.arg}
        need |= {a.arg2 for a in node.aggs if a.arg2}
        if node.grouping_set:
            # every set reads the shared pre-projection whole: the sets'
            # copies of the input stay alike and compile to one program each
            need |= {n for n, _ in node.child.output}
        node.child = prune_columns(node.child, need)
        return node
    if isinstance(node, HashJoin):
        need = required | set(node.left_keys) | set(node.right_keys)
        if node.residual is not None:
            need |= expr_inputs(node.residual)
        lsyms = {n for n, _ in node.left.output}
        rsyms = {n for n, _ in node.right.output}
        node.left = prune_columns(node.left, need & lsyms)
        node.right = prune_columns(node.right, need & rsyms)
        return node
    if isinstance(node, SemiJoin):
        res_syms = expr_inputs(node.residual) if node.residual is not None else set()
        rsyms = {n for n, _ in node.right.output}
        node.left = prune_columns(
            node.left, required | set(node.left_keys) | (res_syms - rsyms)
        )
        node.right = prune_columns(
            node.right, set(node.right_keys) | (res_syms & rsyms)
        )
        return node
    if isinstance(node, Window):
        need = set(required) - {f.symbol for f in node.funcs}
        need |= set(node.partition_keys)
        need |= {k.symbol for k in node.order_items}
        need |= {f.arg for f in node.funcs if f.arg}
        node.child = prune_columns(node.child, need)
        return node
    if isinstance(node, Sort):
        need = required | {k.symbol for k in node.keys}
        node.child = prune_columns(node.child, need)
        return node
    if isinstance(node, Limit):
        node.child = prune_columns(node.child, required)
        return node
    if isinstance(node, SetOp):
        # positional: both sides keep the positions the node keeps. UNION
        # ALL drops a position nobody above reads (one stays, for the row
        # count); the DISTINCT variants compare whole rows and keep all
        keep = list(range(len(node.symbols)))
        if node.kind == "union" and node.all:
            keep = [i for i in keep if node.symbols[i] in required] or keep[:1]
        node.left = _prune_positions(node.left, keep)
        node.right = _prune_positions(node.right, keep)
        node.symbols = [node.symbols[i] for i in keep]
        node.types = [node.types[i] for i in keep]
        return node
    from presto_tpu.plan.nodes import HostProject as _HP

    if isinstance(node, _HP):
        # host outputs resolve to their device inputs below this node
        need = (required - {s for s, _, _, _ in node.items}) | {
            in_s for _, _, in_s, _ in node.items}
        node.child = prune_columns(node.child, need)
        return node
    if isinstance(node, Unnest):
        node.replicate = [s for s in node.replicate if s in required]
        node.child = prune_columns(
            node.child, set(node.replicate) | set(node.sources))
        return node
    from presto_tpu.plan.nodes import NestedLoopJoin as _NLJ

    if isinstance(node, _NLJ):
        need = set(required)
        if node.residual is not None:
            need |= expr_inputs(node.residual)
        lsyms = {n for n, _ in node.left.output}
        rsyms = {n for n, _ in node.right.output}
        node.left = prune_columns(node.left, need & lsyms)
        node.right = prune_columns(node.right, need & rsyms)
        return node
    for c in node.children():
        prune_columns(c, required)
    return node


def _prune_positions(child: PlanNode, keep: List[int]) -> PlanNode:
    """A set operation's child pruned to the output positions `keep`, in
    that order (a GROUPING SETS branch's padding projection, or a nested
    query's Output)."""
    if isinstance(child, Output):
        child.names = [child.names[i] for i in keep]
        child.symbols = [child.symbols[i] for i in keep]
        return prune_columns(child, set())
    out = child.output
    want = [out[i] for i in keep]
    child = prune_columns(child, {n for n, _ in want})
    if [n for n, _ in child.output] != [n for n, _ in want]:
        child = Project(child, [(n, InputRef(t, n)) for n, t in want])
    return child


def cleanup(node: PlanNode) -> PlanNode:
    """Merge adjacent filters; drop empty/identity projects."""
    for attr in ("child", "left", "right"):
        if hasattr(node, attr):
            setattr(node, attr, cleanup(getattr(node, attr)))
    if isinstance(node, Filter) and isinstance(node.child, Filter):
        inner = node.child
        return cleanup(Filter(inner.child, _combine(_conjuncts(node.predicate) + _conjuncts(inner.predicate))))
    if isinstance(node, Project):
        child_names = [n for n, _ in node.child.output]
        if (
            len(node.exprs) == len(child_names)
            and all(
                isinstance(e, InputRef) and e.name == s and s == cn
                for (s, e), cn in zip(node.exprs, child_names)
            )
        ):
            return node.child
    return node


def make_index_joins(node: PlanNode, catalog) -> PlanNode:
    """Rewrite HashJoins whose build side is a bare scan of a table whose
    connector exposes a ConnectorIndex over exactly the join keys
    (reference: IndexJoinOptimizer.java — the source side collapses into
    an IndexSourceNode driven by probe keys)."""
    from presto_tpu.plan.nodes import IndexJoin

    for attr in ("child", "left", "right"):
        if hasattr(node, attr):
            setattr(node, attr, make_index_joins(getattr(node, attr), catalog))
    if (isinstance(node, HashJoin) and node.kind in ("inner", "left")
            and node.residual is None and not node.colocated):
        from presto_tpu.plan.builder import _derives_unique

        left, lkeys, rkeys = node.left, node.left_keys, node.right_keys
        right, key_cols = _indexed_scan(node.right, rkeys, catalog)
        if key_cols is None and node.kind == "inner":
            # the join order may put the indexed table on the probe side:
            # an inner join turns round, the index looked up by the other
            left, lkeys, rkeys = node.right, rkeys, lkeys
            right, key_cols = _indexed_scan(node.left, rkeys, catalog)
        if key_cols is None:
            return node
        return IndexJoin(
            kind=node.kind, left=left,
            catalog=right.catalog, table=right.table,
            left_keys=list(lkeys), index_key_cols=key_cols,
            assignments=dict(right.assignments),
            index_output=list(right.output),
            build_unique=_derives_unique(right, rkeys),
        )
    return node


def _indexed_scan(node: PlanNode, keys: List[str], catalog):
    """(scan, the columns of `keys`) where `node` is a scan of a table whose
    connector exposes an index over exactly those columns - bare, or under
    the join order's IS NOT NULL filter of the keys, which a lookup by key
    applies anyway - else (node, None)."""
    scan = node
    if isinstance(node, Filter) and all(
            isinstance(c, Call) and c.fn == "is_not_null"
            and expr_inputs(c) <= set(keys)
            for c in _conjuncts(node.predicate)):
        scan = node.child
    if not isinstance(scan, TableScan):
        return node, None
    try:
        conn = catalog.connectors[scan.catalog]
        handle = conn.get_table(scan.table)
    except Exception:
        return node, None
    key_cols = [scan.assignments.get(k) for k in keys]
    if None in key_cols or conn.get_index(handle, key_cols) is None:
        return node, None
    return scan, key_cols


def _debug_checks_enabled() -> bool:
    import os

    return os.environ.get("PRESTO_TPU_PLAN_CHECK", "") not in ("", "0")


def optimize(plan: QueryPlan, catalog=None,
             debug_checks: Optional[bool] = None) -> QueryPlan:
    """Run the pass pipeline (reference: PlanOptimizers.java:146 ordering).

    With `debug_checks` (or env PRESTO_TPU_PLAN_CHECK=1), the plan-IR
    invariant checker (analysis/plan_check.py) re-runs after every pass,
    so a violation is attributed to the rewrite rule that introduced it
    instead of surfacing as a KeyError three layers later — the
    PlanSanityChecker-between-optimizers discipline of the reference."""
    from presto_tpu.plan.stats import invalidate

    from presto_tpu.plan.rules import IterativeOptimizer

    if debug_checks is None:
        debug_checks = _debug_checks_enabled()

    def checked(pass_name: str):
        if not debug_checks:
            return
        from presto_tpu.analysis.plan_check import (
            PlanInvariantError,
            check_plan,
        )

        findings = check_plan(plan.root)
        if findings:
            raise PlanInvariantError(pass_name, findings)

    root = plan.root
    checked("input (builder output)")
    root.child = push_filters(root.child)
    checked("push_filters")
    prune_columns(root, set(root.symbols))
    checked("prune_columns")
    root.child = cleanup(root.child)
    checked("cleanup")
    # iterative pattern rules (merge filters/projects/limits, TopN
    # formation) run after the big passes, to fixpoint
    root.child = IterativeOptimizer().optimize(root.child)
    checked("IterativeOptimizer")
    if catalog is not None:
        root.child = make_index_joins(root.child, catalog)
        checked("make_index_joins")
    # builder-time stats memos are stale once filters/pruning rewrote the
    # tree; later consumers (fragmenter, capacity planner) re-derive
    invalidate(root)
    for sub in plan.scalar_subqueries.values():
        optimize(sub, catalog, debug_checks=debug_checks)
    return plan
