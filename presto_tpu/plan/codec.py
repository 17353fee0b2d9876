"""JSON codec for plan fragments — the wire format of the control plane.

Reference: the coordinator ships TaskUpdateRequest as JSON/Smile DTOs
(server/remotetask/HttpRemoteTask.java + jackson codecs;
InternalCommunicationConfig.java:92 binary option). The round-2 engine
pickled fragments, which makes every secret-bearing client an RCE vector;
this codec encodes the CLOSED plan-node vocabulary explicitly — unknown
node/expression kinds are rejected on decode, and no arbitrary object
construction is reachable from the wire.
"""

from __future__ import annotations

from typing import Any, Dict

from presto_tpu.expr.ir import (
    Call,
    Constant,
    InputRef,
    LambdaExpr,
    Param,
    RowExpression,
)
from presto_tpu.plan.fragmenter import Fragment
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
    RemoteSource,
    SemiJoin,
    SetOp,
    Sort,
    SortItem,
    TableScan,
    Unnest,
    Window,
    WindowFunc,
)
from presto_tpu.types import Type, parse_type


class CodecError(ValueError):
    pass


# -- types ------------------------------------------------------------------


def _t(t: Type) -> str:
    return t.name


def _untype(s: str) -> Type:
    return parse_type(s)


# -- expressions ------------------------------------------------------------


def expr_to_json(e: RowExpression) -> Dict[str, Any]:
    if isinstance(e, InputRef):
        return {"k": "ref", "t": _t(e.type), "name": e.name}
    if isinstance(e, Constant):
        v = e.value
        if v is not None and not isinstance(v, (bool, int, float, str)):
            v = v.item() if hasattr(v, "item") else str(v)
        return {"k": "const", "t": _t(e.type), "v": v, "raw": e.raw}
    if isinstance(e, Call):
        return {"k": "call", "t": _t(e.type), "fn": e.fn,
                "args": [expr_to_json(a) for a in e.args]}
    if isinstance(e, Param):
        return {"k": "param", "t": _t(e.type), "name": e.name}
    if isinstance(e, LambdaExpr):
        return {"k": "lambda", "t": _t(e.type),
                "params": [[s, _t(t)] for s, t in e.params],
                "body": expr_to_json(e.body)}
    raise CodecError(f"unencodable expression {type(e).__name__}")


def expr_from_json(d: Dict[str, Any]) -> RowExpression:
    k = d.get("k")
    t = _untype(d["t"])
    if k == "ref":
        return InputRef(t, d["name"])
    if k == "const":
        return Constant(t, d["v"], raw=bool(d.get("raw", False)))
    if k == "call":
        return Call(t, d["fn"], tuple(expr_from_json(a) for a in d["args"]))
    if k == "param":
        return Param(t, d["name"])
    if k == "lambda":
        try:
            params = tuple((s, _untype(ts)) for s, ts in d["params"])
            body = expr_from_json(d["body"])
        except (KeyError, TypeError, ValueError) as e:
            raise CodecError(f"malformed lambda payload: {e}")
        return LambdaExpr(t, params, body)
    raise CodecError(f"unknown expression kind {k!r}")


def _out(node_output) -> list:
    return [[s, _t(t)] for s, t in node_output]


def _unout(lst) -> list:
    return [(s, _untype(t)) for s, t in lst]


# -- plan nodes -------------------------------------------------------------


def node_to_json(n: PlanNode) -> Dict[str, Any]:
    if isinstance(n, TableScan):
        return {"k": "scan", "catalog": n.catalog, "table": n.table,
                "assignments": dict(n.assignments), "output": _out(n.output),
                "constraints": {c: [lo, hi]
                                for c, (lo, hi) in (n.constraints or {}).items()}}
    if isinstance(n, Filter):
        return {"k": "filter", "child": node_to_json(n.child),
                "pred": expr_to_json(n.predicate)}
    if isinstance(n, Project):
        return {"k": "project", "child": node_to_json(n.child),
                "exprs": [[s, expr_to_json(e)] for s, e in n.exprs]}
    if isinstance(n, Aggregate):
        d = {"k": "agg", "child": node_to_json(n.child),
             "keys": list(n.group_keys), "step": n.step,
             "aggs": [{"symbol": a.symbol, "fn": a.fn, "arg": a.arg,
                       "t": _t(a.type), "distinct": a.distinct,
                       "arg2": a.arg2, "param": a.param}
                      for a in n.aggs]}
        if n.partial_groups is not None:
            d["partial_groups"] = float(n.partial_groups)
        if n.grouping_set:
            d["grouping_set"] = True
        return d
    if isinstance(n, HashJoin):
        return {"k": "join", "kind": n.kind,
                "left": node_to_json(n.left), "right": node_to_json(n.right),
                "lkeys": list(n.left_keys), "rkeys": list(n.right_keys),
                "residual": (expr_to_json(n.residual)
                             if n.residual is not None else None),
                "build_unique": n.build_unique,
                "colocated": n.colocated}
    if isinstance(n, MultiwayJoin):
        return {"k": "mwjoin",
                "probe": node_to_json(n.probe),
                "builds": [node_to_json(b) for b in n.builds],
                "kinds": list(n.kinds),
                "pkeys": [list(ks) for ks in n.probe_keys],
                "bkeys": [list(ks) for ks in n.build_keys],
                "build_unique": [bool(u) for u in n.build_unique]}
    if isinstance(n, NestedLoopJoin):
        return {"k": "nljoin",
                "left": node_to_json(n.left), "right": node_to_json(n.right),
                "residual": (expr_to_json(n.residual)
                             if n.residual is not None else None)}
    if isinstance(n, IndexJoin):
        return {"k": "indexjoin", "kind": n.kind,
                "left": node_to_json(n.left),
                "catalog": n.catalog, "table": n.table,
                "lkeys": list(n.left_keys),
                "index_key_cols": list(n.index_key_cols),
                "assignments": dict(n.assignments),
                "index_output": _out(n.index_output),
                "build_unique": n.build_unique}
    if isinstance(n, SemiJoin):
        return {"k": "semijoin", "negated": n.negated,
                "null_aware": n.null_aware,
                "left": node_to_json(n.left), "right": node_to_json(n.right),
                "lkeys": list(n.left_keys), "rkeys": list(n.right_keys),
                "residual": (expr_to_json(n.residual)
                             if n.residual is not None else None)}
    if isinstance(n, SetOp):
        return {"k": "setop", "kind": n.kind, "all": n.all,
                "left": node_to_json(n.left), "right": node_to_json(n.right),
                "symbols": list(n.symbols), "types": [_t(t) for t in n.types]}
    if isinstance(n, Sort):
        return {"k": "sort", "child": node_to_json(n.child),
                "keys": [[s.symbol, s.ascending, s.nulls_first]
                         for s in n.keys],
                "limit": n.limit}
    if isinstance(n, Window):
        return {"k": "window", "child": node_to_json(n.child),
                "pkeys": list(n.partition_keys),
                "okeys": [[s.symbol, s.ascending, s.nulls_first]
                          for s in n.order_items],
                "funcs": [{"symbol": f.symbol, "fn": f.fn, "t": _t(f.type),
                           "arg": f.arg, "param": f.param, "frame": f.frame,
                           "default": f.default}
                          for f in n.funcs]}
    if isinstance(n, Limit):
        return {"k": "limit", "child": node_to_json(n.child), "count": n.count}
    if isinstance(n, Output):
        return {"k": "output", "child": node_to_json(n.child),
                "names": list(n.names), "symbols": list(n.symbols)}
    if isinstance(n, RemoteSource):
        return {"k": "remote", "fid": n.fragment_id, "output": _out(n.output)}
    if isinstance(n, Unnest):
        return {"k": "unnest", "child": node_to_json(n.child),
                "sources": list(n.sources), "replicate": list(n.replicate),
                "out_syms": [list(s) for s in n.out_syms],
                "out_types": [[_t(t) for t in ts] for ts in n.out_types],
                "ordinality": n.ordinality_sym}
    if isinstance(n, OneRow):
        return {"k": "onerow"}
    from presto_tpu.plan.nodes import HostProject, TableWriter

    if isinstance(n, TableWriter):
        return {"k": "tablewriter", "child": node_to_json(n.child),
                "catalog": n.catalog, "table": n.table,
                "write_id": n.write_id}
    if isinstance(n, HostProject):
        return {"k": "hostproject", "child": node_to_json(n.child),
                "items": [[sym, kind, in_sym, param]
                          for sym, kind, in_sym, param in n.items]}
    raise CodecError(f"unencodable plan node {type(n).__name__}")


def canonical_node_json(n: PlanNode, program: bool = False) -> str:
    """Canonical structural serialization of one node's subtree: the wire
    encoding rendered with sorted keys and no whitespace, so it is
    byte-identical for any two nodes that encode to the same logical plan
    — across a codec round trip, across two decodes of one fragment, and
    across processes. strip_runtime_state keeps wire plans free of
    runtime attrs, so nothing execution-dependent can leak in. This is
    the basis of the compile plane's structural program fingerprints
    (exec/programs.py).

    `program=True` is the form a program is keyed on: a RemoteSource
    without its fragment id, which names the exchange that feeds it and
    not what is computed over its pages, so copies of one subtree behind
    different exchanges (the sets of a GROUPING SETS) share programs."""
    import json

    d = node_to_json(n)
    if program:
        d = _without_fragment_ids(d)
    return json.dumps(d, sort_keys=True, separators=(",", ":"), default=str)


def _without_fragment_ids(d):
    if isinstance(d, dict):
        return {k: _without_fragment_ids(v) for k, v in d.items()
                if not (k == "fid" and d.get("k") == "remote")}
    if isinstance(d, list):
        return [_without_fragment_ids(v) for v in d]
    return d


def node_fingerprint(n: PlanNode) -> str:
    """sha256 hex digest of canonical_node_json — the structural identity
    under which exec/programs.py shares compiled programs."""
    import hashlib

    return hashlib.sha256(canonical_node_json(n).encode()).hexdigest()


def node_from_json(d: Dict[str, Any]) -> PlanNode:
    k = d.get("k")
    if k == "scan":
        return TableScan(
            catalog=d["catalog"], table=d["table"],
            assignments=dict(d["assignments"]), output=_unout(d["output"]),
            constraints={c: (lo, hi)
                         for c, (lo, hi) in (d.get("constraints") or {}).items()},
        )
    if k == "filter":
        return Filter(node_from_json(d["child"]), expr_from_json(d["pred"]))
    if k == "project":
        return Project(node_from_json(d["child"]),
                       [(s, expr_from_json(e)) for s, e in d["exprs"]])
    if k == "agg":
        return Aggregate(
            node_from_json(d["child"]), list(d["keys"]),
            [AggSpec(a["symbol"], a["fn"], a["arg"], _untype(a["t"]),
                     bool(a.get("distinct", False)), a.get("arg2"),
                     a.get("param")) for a in d["aggs"]],
            step=d.get("step", "single"),
            partial_groups=d.get("partial_groups"),
            grouping_set=bool(d.get("grouping_set", False)),
        )
    if k == "join":
        return HashJoin(
            kind=d["kind"], left=node_from_json(d["left"]),
            right=node_from_json(d["right"]),
            left_keys=list(d["lkeys"]), right_keys=list(d["rkeys"]),
            residual=(expr_from_json(d["residual"])
                      if d.get("residual") is not None else None),
            build_unique=bool(d.get("build_unique", False)),
            colocated=int(d.get("colocated", 0)),
        )
    if k == "mwjoin":
        return MultiwayJoin(
            probe=node_from_json(d["probe"]),
            builds=[node_from_json(b) for b in d["builds"]],
            kinds=list(d["kinds"]),
            probe_keys=[list(ks) for ks in d["pkeys"]],
            build_keys=[list(ks) for ks in d["bkeys"]],
            build_unique=[bool(u) for u in d["build_unique"]],
        )
    if k == "nljoin":
        return NestedLoopJoin(
            left=node_from_json(d["left"]), right=node_from_json(d["right"]),
            residual=(expr_from_json(d["residual"])
                      if d.get("residual") is not None else None),
        )
    if k == "indexjoin":
        return IndexJoin(
            kind=d["kind"], left=node_from_json(d["left"]),
            catalog=d["catalog"], table=d["table"],
            left_keys=list(d["lkeys"]),
            index_key_cols=list(d["index_key_cols"]),
            assignments=dict(d["assignments"]),
            index_output=_unout(d["index_output"]),
            build_unique=bool(d.get("build_unique", True)),
        )
    if k == "semijoin":
        return SemiJoin(
            left=node_from_json(d["left"]), right=node_from_json(d["right"]),
            left_keys=list(d["lkeys"]), right_keys=list(d["rkeys"]),
            negated=bool(d.get("negated", False)),
            residual=(expr_from_json(d["residual"])
                      if d.get("residual") is not None else None),
            null_aware=bool(d.get("null_aware", True)),
        )
    if k == "setop":
        return SetOp(d["kind"], bool(d["all"]), node_from_json(d["left"]),
                     node_from_json(d["right"]), list(d["symbols"]),
                     [_untype(t) for t in d["types"]])
    if k == "sort":
        return Sort(node_from_json(d["child"]),
                    [SortItem(s, bool(a), nf) for s, a, nf in d["keys"]],
                    limit=d.get("limit"))
    if k == "window":
        return Window(
            node_from_json(d["child"]), list(d["pkeys"]),
            [SortItem(s, bool(a), nf) for s, a, nf in d["okeys"]],
            [WindowFunc(f["symbol"], f["fn"], _untype(f["t"]), f.get("arg"),
                        f.get("param"), f.get("frame"),
                        default=f.get("default")) for f in d["funcs"]],
        )
    if k == "limit":
        return Limit(node_from_json(d["child"]), int(d["count"]))
    if k == "output":
        return Output(node_from_json(d["child"]), list(d["names"]),
                      list(d["symbols"]))
    if k == "remote":
        return RemoteSource(fragment_id=int(d["fid"]),
                            output=_unout(d["output"]))
    if k == "unnest":
        return Unnest(
            child=node_from_json(d["child"]), sources=list(d["sources"]),
            replicate=list(d["replicate"]),
            out_syms=[list(s) for s in d["out_syms"]],
            out_types=[[_untype(t) for t in ts] for ts in d["out_types"]],
            ordinality_sym=d.get("ordinality"),
        )
    if k == "onerow":
        return OneRow()
    if k == "tablewriter":
        from presto_tpu.plan.nodes import TableWriter

        return TableWriter(node_from_json(d["child"]), d["catalog"],
                           d["table"], d["write_id"])
    if k == "hostproject":
        from presto_tpu.plan.nodes import HostProject

        return HostProject(
            node_from_json(d["child"]),
            [(sym, kind, in_sym, param)
             for sym, kind, in_sym, param in d["items"]])
    raise CodecError(f"unknown plan node kind {k!r}")


# -- fragments + task updates ----------------------------------------------


def fragment_to_json(f: Fragment) -> Dict[str, Any]:
    return {"fid": f.fid, "root": node_to_json(f.root),
            "partitioning": f.partitioning,
            "output_partitioning": f.output_partitioning,
            "output_keys": list(f.output_keys),
            "radix_align": bool(f.radix_align)}


def fragment_from_json(d: Dict[str, Any]) -> Fragment:
    return Fragment(
        fid=int(d["fid"]), root=node_from_json(d["root"]),
        partitioning=d["partitioning"],
        output_partitioning=d["output_partitioning"],
        output_keys=list(d.get("output_keys") or []),
        radix_align=bool(d.get("radix_align") or False),
    )


def task_update_to_json(u) -> Dict[str, Any]:
    out = {"fragment": fragment_to_json(u.fragment),
           "task_index": u.task_index, "n_tasks": u.n_tasks,
           "n_out_partitions": u.n_out_partitions,
           "upstreams": {str(k): list(v) for k, v in u.upstreams.items()},
           "config": dict(u.config), "spool": bool(u.spool)}
    if u.split_assignment is not None:
        out["split_assignment"] = {
            t: list(map(int, idxs)) for t, idxs in u.split_assignment.items()}
    if u.split_counts is not None:
        out["split_counts"] = {t: int(n) for t, n in u.split_counts.items()}
    return out


def task_update_from_json(d: Dict[str, Any]):
    from presto_tpu.server.worker import TaskUpdate

    return TaskUpdate(
        fragment=fragment_from_json(d["fragment"]),
        task_index=int(d["task_index"]), n_tasks=int(d["n_tasks"]),
        n_out_partitions=int(d["n_out_partitions"]),
        upstreams={int(k): list(v) for k, v in d["upstreams"].items()},
        config=dict(d.get("config") or {}),
        spool=bool(d.get("spool", False)),
        split_assignment=(
            {t: [int(i) for i in idxs]
             for t, idxs in d["split_assignment"].items()}
            if d.get("split_assignment") is not None else None),
        split_counts=(
            {t: int(n) for t, n in d["split_counts"].items()}
            if d.get("split_counts") is not None else None),
    )
