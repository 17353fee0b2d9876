"""Knob-flow taint pass: every program/result cache key must cover what
the cached value actually reads.

Every cache tier in this engine — the structural program cache
(``exec/programs.py``), the semantic result cache
(``server/result_cache.py``), the compile farm's cross-process corpus
(``exec/farm.py``) and the HBO history (``obs/runstats.py``) — is sound
only if its fingerprint covers everything that shapes the cached value.
That contract used to live in comments ("knob is cache-volatile") and
the hand-curated ``_VOLATILE_CONFIG_FIELDS`` list; this pass machine-
checks it the way the concurrency pass machine-checks lock discipline.

Sources (taint *labels*):

- ``config.<field>`` — an ExecConfig field read (``ctx.config.f``,
  ``cfg.f``, ``getattr(config, "f", ...)``); the field set is parsed
  from the ExecConfig dataclass, the volatile subset from
  ``_VOLATILE_CONFIG_FIELDS``, both straight out of the shipped source
  so the checker can never drift from the code.
- ``config`` — the wildcard: a whole ExecConfig value (a parameter
  named ``config`` / ``cfg`` inside a ``# fp: uses-key(...)`` function).
- ``env.<NAME>`` — an ``os.environ`` / ``os.getenv`` read. Vars listed
  in ``_FINGERPRINTED_ENVS`` (exec/programs.py) are mixed into
  ``config_fingerprint`` and therefore covered; vars declared
  cache-volatile in ``_CACHE_VOLATILE_ENVS`` below never change a
  computed value (paths, limits, worker counts) and carry no taint;
  anything else is an undeclared knob.
- ``session.<prop>`` — a ``session.get("prop")`` read. Properties that
  lower into ExecConfig (parsed from ``Session.exec_config``) convert
  to their ``config.<field>`` label; properties that shape the plan
  (``_PLANNER_SIDE_PROPERTIES``) are covered by the structural
  fingerprint; admission/limit properties are declared value-neutral in
  ``_VOLATILE_PROPERTIES``.

Sinks are traced-program construction: the closure environment captured
by a ``_node_jit(node, key, builder)`` builder, Pallas kernel bodies,
and any function reachable from one through the interprocedural
may-call graph. Static args are NOT sinks: jax's jit cache keys static
values per call and ``_avals_key`` bakes non-array leaf reprs into the
artifact key, so statics fork programs by construction.

Rules:

- ``volatile-leak`` — a ``_VOLATILE_CONFIG_FIELDS`` field's taint
  reaches a program sink without the program KEY covering it. Volatile
  fields are excluded from the config fingerprint, so a leak means two
  sessions differing only in that knob share one cached program — the
  wrong-program bug class. The blessed idiom is the engine-key suffix
  (``key@h``, ``key@e<vec>``): derive the key from the same tainted
  value the closure captures and the cache forks correctly.
- ``unfingerprinted-knob`` — a session property or env var reaches a
  sink without fingerprint coverage or a declared volatility class.
- ``cache-key-drift`` — a ``# fp: uses-key(<name>)`` function consumes
  config/env/session values its key's declared ``covers(...)`` set does
  not include (and that are not value-neutral). Key contracts are
  declared on the deriving function:
  ``# fp: key(<name>) covers(<input>, ...)``.
- ``unregistered-state`` — an operator-state NamedTuple in a device
  library (``ops/``, ``expr/``) missing from the jax.export pytree
  registration table in ``exec/programs.py``, or a plan-node class
  absent from the codec (both break the PR 16 artifact persist/restore
  chain exactly the way unregistered BuildTable once did).

Suppressions: ``# fp: allow(<rule>[, <rule>...])`` on the offending
line (def lines cover the body). Every suppression needs a
justification comment; the ``--stale-suppressions`` reporter flags
suppressions whose rule no longer fires.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Sequence, Set, Tuple

from presto_tpu.analysis import astutil
from presto_tpu.analysis.astutil import Suppressions, _root_name
from presto_tpu.analysis.findings import Finding

RULES = ("volatile-leak", "unfingerprinted-knob", "cache-key-drift",
         "unregistered-state")

PLANE = "knob-flow"

# env knobs that never change what any cached value computes: artifact
# locations, capacity limits, worker counts, observability sampling.
# Reading one is host-side policy, not program input — they carry no
# taint. A program-affecting env var must instead appear in
# _FINGERPRINTED_ENVS (exec/programs.py) so config_fingerprint forks on
# it; anything in neither set is an undeclared knob and flags at sinks.
_CACHE_VOLATILE_ENVS = {
    "PRESTO_TPU_CACHE_DIR": "artifact/corpus location, not content",
    "PRESTO_TPU_DEVPROF_SAMPLE_S": "device-memory sampling period",
    "PRESTO_TPU_FARM": "arms boot-time pre-compilation",
    "PRESTO_TPU_FARM_LIMIT": "boot arming budget",
    "PRESTO_TPU_FARM_WORKERS": "warm pool width",
    "PRESTO_TPU_HBO_MAX_AGE_S": "history retention bound",
    "PRESTO_TPU_HBO_MAX_ENTRIES": "history size bound",
    "PRESTO_TPU_PLAN_CHECK": "debug plan-invariant checking",
    "PRESTO_TPU_PROGRAM_PERSIST": "arms jax.export artifact persistence",
    "PRESTO_TPU_RESULT_CACHE_BYTES": "result-cache capacity bound",
}

# session properties that never reach ExecConfig because they shape the
# PLAN (join strategy, partition counts, optimizer passes): the codec
# canonical JSON — and therefore every structural fingerprint — covers
# their effect, so they need no config-fingerprint membership.
_PLANNER_SIDE_PROPERTIES = frozenset({
    "join_distribution_type", "hash_partition_count",
    "redistribute_writes", "optimize_plan",
})

# session properties that are pure admission/SLO policy: they decide
# WHETHER/WHEN a query runs, never what any program computes.
_VOLATILE_PROPERTIES = frozenset({
    "query_max_run_time_s", "query_priority", "slo_objectives",
    "latency_regression_factor", "query_max_memory_mb",
})

# cache-key contracts the shipped tree must declare (module basename ->
# key names): deleting a `# fp: key(...)` annotation is itself a drift
# finding, so the contracts cannot silently rot.
_EXPECTED_KEYS = {
    "result_cache.py": ("result-cache",),
    "farm.py": ("farm-corpus",),
    "runstats.py": ("hbo-history",),
    "programs.py": ("program-ns",),
}

_KEY_RE = re.compile(
    r"#\s*fp:\s*key\(([\w\-]+)\)\s*covers\(([\w\-.:, ]*)\)")
_USES_RE = re.compile(r"#\s*fp:\s*uses-key\(([\w\-]+)\)")


# ---------------------------------------------------------------------------
# ground truth parsed from the shipped tree


class GroundTruth:
    """Fingerprint facts parsed from the source of record — the checker
    re-derives them per run so it can never disagree with the code."""

    def __init__(self):
        self.config_fields: Set[str] = set()
        self.volatile_fields: Set[str] = set()
        self.fingerprinted_envs: Set[str] = set()
        self.registered_state: Set[str] = set()
        # session properties: name -> (py_type, default, hidden)
        self.session_props: Dict[str, Tuple[str, object, bool]] = {}
        self.lowering: Dict[str, str] = {}  # property -> ExecConfig field
        self.codec_names: Set[str] = set()
        self.node_classes: List[Tuple[str, int]] = []  # plan/nodes.py

    def env_class(self, name: str) -> str:
        if name in self.fingerprinted_envs:
            return "fingerprinted"
        if name in _CACHE_VOLATILE_ENVS:
            return "cache-volatile"
        return "undeclared"

    def property_class(self, name: str) -> str:
        if name in self.lowering:
            f = self.lowering[name]
            return ("volatile" if f in self.volatile_fields
                    else "fingerprinted")
        if name in _PLANNER_SIDE_PROPERTIES:
            return "planner"
        if name in _VOLATILE_PROPERTIES:
            return "volatile"
        return "undeclared"


def _const_strs(node: ast.AST) -> List[str]:
    return [n.value for n in ast.walk(node)
            if isinstance(n, ast.Constant) and isinstance(n.value, str)]


def _pkg_dir() -> str:
    import presto_tpu

    return os.path.dirname(os.path.abspath(presto_tpu.__file__))


_GT_CACHE: List[Optional[GroundTruth]] = [None]


def load_ground_truth(pkg: Optional[str] = None) -> GroundTruth:
    if pkg is None and _GT_CACHE[0] is not None:
        return _GT_CACHE[0]
    root = pkg or _pkg_dir()
    gt = GroundTruth()
    _parse_programs(os.path.join(root, "exec", "programs.py"), gt)
    _parse_exec_config(os.path.join(root, "exec", "runtime.py"), gt)
    _parse_session(os.path.join(root, "server", "session.py"), gt)
    _parse_codec(os.path.join(root, "plan", "codec.py"),
                 os.path.join(root, "plan", "nodes.py"), gt)
    if pkg is None:
        _GT_CACHE[0] = gt
    return gt


def _parse_programs(path: str, gt: GroundTruth) -> None:
    _, tree = astutil.load_file(path)
    fp_fn = None
    env_names: List[str] = []
    for n in ast.walk(tree):
        if isinstance(n, ast.Assign):
            tgt = n.targets[0]
            if isinstance(tgt, ast.Name):
                if tgt.id == "_VOLATILE_CONFIG_FIELDS":
                    gt.volatile_fields = set(_const_strs(n.value))
                elif tgt.id == "_FINGERPRINTED_ENVS":
                    env_names = _const_strs(n.value)
        elif isinstance(n, ast.FunctionDef):
            if n.name == "config_fingerprint":
                fp_fn = n
            elif n.name == "_register_pytree_serialization":
                _parse_registration(n, gt)
    # an env var counts as fingerprinted only if the declaration list is
    # actually consumed by config_fingerprint — a dangling list is drift
    if fp_fn is not None and any(
            isinstance(x, ast.Name) and x.id == "_FINGERPRINTED_ENVS"
            for x in ast.walk(fp_fn)):
        gt.fingerprinted_envs = set(env_names)


def _parse_registration(fn: ast.FunctionDef, gt: GroundTruth) -> None:
    """The pytree-serialization table: direct ``reg(..., "mod.Name")``
    calls plus the ``for mod, names in ((mod, (n, ...)), ...)`` table."""
    for n in ast.walk(fn):
        if isinstance(n, ast.Call):
            for s in _const_strs(n):
                if s.startswith("presto_tpu.") and s.count(".") >= 2:
                    gt.registered_state.add(s)
        if isinstance(n, ast.For) and isinstance(n.iter, ast.Tuple):
            for elt in n.iter.elts:
                if not (isinstance(elt, ast.Tuple)
                        and len(elt.elts) == 2):
                    continue
                mods = _const_strs(elt.elts[0])
                for name in _const_strs(elt.elts[1]):
                    for m in mods:
                        gt.registered_state.add(f"{m}.{name}")


def _parse_exec_config(path: str, gt: GroundTruth) -> None:
    _, tree = astutil.load_file(path)
    for n in ast.walk(tree):
        if isinstance(n, ast.ClassDef) and n.name == "ExecConfig":
            for stmt in n.body:
                if isinstance(stmt, ast.AnnAssign) \
                        and isinstance(stmt.target, ast.Name):
                    gt.config_fields.add(stmt.target.id)
            return


def _parse_session(path: str, gt: GroundTruth) -> None:
    _, tree = astutil.load_file(path)
    for n in ast.walk(tree):
        if isinstance(n, ast.FunctionDef) and n.name == "_defaults":
            for call in ast.walk(n):
                if not (isinstance(call, ast.Call)
                        and isinstance(call.func, ast.Name)
                        and call.func.id == "PropertyMetadata"
                        and call.args):
                    continue
                name = call.args[0]
                if not (isinstance(name, ast.Constant)
                        and isinstance(name.value, str)):
                    continue
                ptype = "str"
                if len(call.args) >= 3 and isinstance(call.args[2],
                                                      ast.Name):
                    ptype = call.args[2].id
                default: object = None
                if len(call.args) >= 4:
                    try:
                        default = ast.literal_eval(call.args[3])
                    except (ValueError, SyntaxError):
                        default = ast.unparse(call.args[3])
                hidden = any(
                    kw.arg == "hidden"
                    and isinstance(kw.value, ast.Constant)
                    and bool(kw.value.value) for kw in call.keywords)
                gt.session_props[name.value] = (ptype, default, hidden)
        if isinstance(n, ast.FunctionDef) and n.name == "exec_config":
            _parse_lowering(n, gt)


def _parse_lowering(fn: ast.FunctionDef, gt: GroundTruth) -> None:
    """``Session.exec_config``: which property feeds which field — a
    keyword's value walks to ``self.get("prop")`` directly or through a
    local assigned from one (``qmax = self.get(...)``)."""

    def props_in(e: ast.AST, locals_: Dict[str, str]) -> List[str]:
        out = []
        for x in ast.walk(e):
            if isinstance(x, ast.Call) \
                    and isinstance(x.func, ast.Attribute) \
                    and x.func.attr == "get" and x.args \
                    and isinstance(x.args[0], ast.Constant):
                out.append(str(x.args[0].value))
            elif isinstance(x, ast.Name) and x.id in locals_:
                out.append(locals_[x.id])
        return out

    locals_: Dict[str, str] = {}
    for stmt in ast.walk(fn):
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                and isinstance(stmt.targets[0], ast.Name):
            got = props_in(stmt.value, {})
            if got:
                locals_[stmt.targets[0].id] = got[0]
    for call in ast.walk(fn):
        if isinstance(call, ast.Call) and isinstance(call.func, ast.Name) \
                and call.func.id == "ExecConfig":
            for kw in call.keywords:
                if kw.arg is None:
                    continue
                for prop in props_in(kw.value, locals_):
                    gt.lowering.setdefault(prop, kw.arg)


def _parse_codec(codec_path: str, nodes_path: str, gt: GroundTruth) -> None:
    try:
        codec_src, _ = astutil.load_file(codec_path)
        _, nodes_tree = astutil.load_file(nodes_path)
    except OSError:
        return
    gt.codec_names = set(re.findall(r"\b[A-Z]\w+\b", codec_src))
    for n in ast.walk(nodes_tree):
        if isinstance(n, ast.ClassDef) and any(
                isinstance(s, ast.FunctionDef) and s.name == "children"
                for s in n.body):
            gt.node_classes.append((n.name, n.lineno))


# ---------------------------------------------------------------------------
# taint values: {"*": scalar labels, "f:<name>": per-field labels}
# (field sensitivity is what distinguishes `spec.unique` — node
# structure, in the key — from `spec.hash_engine` — hbo-derived, the
# leak — on the same NamedTuple)


def _tv() -> Dict[str, Set[str]]:
    return {}


def _tv_scalar(labels) -> Dict[str, Set[str]]:
    return {"*": set(labels)} if labels else {}


def _tv_union(a: Dict[str, Set[str]],
              b: Dict[str, Set[str]]) -> Dict[str, Set[str]]:
    if not b:
        return a
    if not a:
        return dict(b)
    out = {k: set(v) for k, v in a.items()}
    for k, v in b.items():
        out.setdefault(k, set()).update(v)
    return out


def _tv_all(a: Dict[str, Set[str]]) -> Set[str]:
    out: Set[str] = set()
    for v in a.values():
        out.update(v)
    return out


_CONFIG_ROOTS = {"config", "cfg", "exec_config"}
_CONTAINER_CTORS = {"tuple", "list", "set", "frozenset", "sorted",
                    "reversed", "iter", "next"}


def _env_read(call: ast.Call) -> Optional[str]:
    """`os.environ.get("X")` / `os.getenv("X")` / `environ.get("X")` /
    `os.environ["X"]` handled by the caller's Subscript case."""
    fn = call.func
    if isinstance(fn, ast.Attribute):
        chain_root = _root_name(fn)
        if fn.attr == "get" and isinstance(fn.value, ast.Attribute) \
                and fn.value.attr == "environ":
            pass
        elif fn.attr == "get" and isinstance(fn.value, ast.Name) \
                and fn.value.id == "environ":
            pass
        elif fn.attr == "getenv" and chain_root == "os":
            pass
        else:
            return None
        if call.args and isinstance(call.args[0], ast.Constant):
            return str(call.args[0].value)
    return None


def _config_attr(e: ast.Attribute, gt: GroundTruth) -> Optional[str]:
    """`<anything>.config.<field>` / `config.<field>` / `cfg.<field>`."""
    if e.attr not in gt.config_fields:
        return None
    base = e.value
    if isinstance(base, ast.Attribute) and base.attr == "config":
        return e.attr
    if isinstance(base, ast.Name) and base.id in _CONFIG_ROOTS:
        return e.attr
    return None


def _getattr_config(call: ast.Call, gt: GroundTruth) -> Optional[str]:
    if not (isinstance(call.func, ast.Name)
            and call.func.id == "getattr" and len(call.args) >= 2):
        return None
    obj, name = call.args[0], call.args[1]
    if not (isinstance(name, ast.Constant)
            and str(name.value) in gt.config_fields):
        return None
    if isinstance(obj, ast.Attribute) and obj.attr == "config":
        return str(name.value)
    if isinstance(obj, ast.Name) and obj.id in _CONFIG_ROOTS:
        return str(name.value)
    return None


def _session_get(call: ast.Call) -> Optional[str]:
    fn = call.func
    if isinstance(fn, ast.Attribute) and fn.attr == "get" \
            and isinstance(fn.value, ast.Name) \
            and fn.value.id == "session" and call.args \
            and isinstance(call.args[0], ast.Constant):
        return str(call.args[0].value)
    return None


class _Evaluator:
    """Expression taint in one function scope. `env` maps local names
    (and `self.<attr>` pseudo-names) to taint values; `resolver` answers
    call-summary queries; `namedtuples` maps constructor names to field
    orders for field-sensitive construction."""

    def __init__(self, env: Dict[str, Dict[str, Set[str]]],
                 gt: GroundTruth, resolver, namedtuples: Dict[str, Tuple]):
        self.env = env
        self.gt = gt
        self.resolver = resolver
        self.namedtuples = namedtuples

    def expr(self, e: Optional[ast.expr],
             local: Optional[Dict] = None) -> Dict[str, Set[str]]:
        if e is None:
            return _tv()
        scope = local or {}
        return self._e(e, scope)

    def _lookup(self, name: str, scope: Dict) -> Dict[str, Set[str]]:
        if name in scope:
            return scope[name]
        return self.env.get(name, _tv())

    def _e(self, e: ast.expr, scope: Dict) -> Dict[str, Set[str]]:
        if isinstance(e, ast.Constant):
            return _tv()
        if isinstance(e, ast.Name):
            tv = self._lookup(e.id, scope)
            if tv:
                return tv
            # a bare reference to a function defined elsewhere carries
            # that function's source summary (device helpers that read
            # env at trace time taint the closures referencing them)
            labels = self.resolver.name_summary(e.id)
            return _tv_scalar(labels)
        if isinstance(e, ast.Attribute):
            field = _config_attr(e, self.gt)
            if field is not None:
                return _tv_scalar({f"config.{field}"})
            if isinstance(e.value, ast.Name) and e.value.id == "self":
                return self._lookup(f"self.{e.attr}", scope)
            base = self._e(e.value, scope)
            fkey = f"f:{e.attr}"
            out = _tv_scalar(base.get("*", set()))
            if fkey in base:
                out = _tv_union(out, _tv_scalar(base[fkey]))
            return out
        if isinstance(e, ast.Subscript):
            if isinstance(e.value, ast.Attribute) \
                    and e.value.attr == "environ" \
                    and isinstance(e.slice, ast.Constant):
                return _tv_scalar({f"env.{e.slice.value}"})
            base = self._e(e.value, scope)
            sl = self._e(e.slice, scope)
            # indexing a container of structured values keeps the
            # structure (specs[i].hash_engine stays field-sensitive)
            return _tv_union(base, sl)
        if isinstance(e, ast.Call):
            return self._call(e, scope)
        if isinstance(e, ast.Lambda):
            return _tv_scalar(self._free_labels(e, scope))
        if isinstance(e, ast.IfExp):
            out = self._e(e.test, scope)
            out = _tv_union(out, self._e(e.body, scope))
            return _tv_union(out, self._e(e.orelse, scope))
        if isinstance(e, (ast.GeneratorExp, ast.ListComp, ast.SetComp,
                          ast.DictComp)):
            return self._comp(e, scope)
        if isinstance(e, ast.BoolOp):
            out = _tv()
            for v in e.values:
                out = _tv_union(out, self._e(v, scope))
            return out
        out = _tv()
        for child in ast.iter_child_nodes(e):
            if isinstance(child, ast.expr):
                out = _tv_union(out, self._e(child, scope))
        return out

    def _comp(self, e, scope: Dict) -> Dict[str, Set[str]]:
        inner = dict(scope)
        for gen in e.generators:
            it = self._e(gen.iter, inner)
            for t in ast.walk(gen.target):
                if isinstance(t, ast.Name):
                    inner[t.id] = it
        out = _tv()
        for gen in e.generators:
            for cond in gen.ifs:
                out = _tv_union(out, self._e(cond, inner))
        if isinstance(e, ast.DictComp):
            out = _tv_union(out, self._e(e.key, inner))
            out = _tv_union(out, self._e(e.value, inner))
        else:
            out = _tv_union(out, self._e(e.elt, inner))
        return out

    def _call(self, e: ast.Call, scope: Dict) -> Dict[str, Set[str]]:
        env_name = _env_read(e)
        if env_name is not None:
            return _tv_scalar({f"env.{env_name}"})
        field = _getattr_config(e, self.gt)
        if field is not None:
            return _tv_scalar({f"config.{field}"})
        prop = _session_get(e)
        if prop is not None:
            return _tv_scalar({f"session.{prop}"})
        fn = e.func
        if isinstance(fn, ast.Name) and fn.id in self.namedtuples:
            fields = self.namedtuples[fn.id]
            tv: Dict[str, Set[str]] = {}
            for i, a in enumerate(e.args):
                if i < len(fields):
                    tv[f"f:{fields[i]}"] = _tv_all(self._e(a, scope))
            for kw in e.keywords:
                if kw.arg:
                    tv[f"f:{kw.arg}"] = _tv_all(self._e(kw.value, scope))
                else:
                    tv = _tv_union(tv, self._e(kw.value, scope))
            return tv
        if isinstance(fn, ast.Name) and fn.id in _CONTAINER_CTORS \
                and len(e.args) == 1 and not e.keywords:
            return self._e(e.args[0], scope)
        out = self._e(fn, scope) if not isinstance(fn, ast.Name) \
            else _tv_scalar(self._lookup(fn.id, scope).get("*", set())
                            | _tv_all(self._lookup(fn.id, scope)))
        for a in e.args:
            out = _tv_union(out, self._e(a, scope))
        for kw in e.keywords:
            out = _tv_union(out, self._e(kw.value, scope))
        out = _tv_union(out, _tv_scalar(self.resolver.call_summary(e)))
        return _tv_scalar(_tv_all(out))

    def _free_labels(self, fn, scope: Dict) -> Set[str]:
        """Labels of a nested def/lambda's free variables — the closure
        environment a `_node_jit` builder hands to jax.jit."""
        bound: Set[str] = set()
        args = fn.args
        for a in (list(args.posonlyargs) + list(args.args)
                  + list(args.kwonlyargs)):
            bound.add(a.arg)
        if args.vararg:
            bound.add(args.vararg.arg)
        if args.kwarg:
            bound.add(args.kwarg.arg)
        body = fn.body if isinstance(fn.body, list) else [fn.body]
        labels: Set[str] = set()
        for stmt in body:
            for n in ast.walk(stmt):
                if isinstance(n, (ast.FunctionDef, ast.Lambda)):
                    continue
                if isinstance(n, ast.Name):
                    if isinstance(n.ctx, ast.Store):
                        bound.add(n.id)
                    elif n.id not in bound:
                        labels.update(_tv_all(self._lookup(n.id, scope)))
                        labels.update(self.resolver.name_summary(n.id))
                elif isinstance(n, ast.Attribute):
                    field = _config_attr(n, self.gt)
                    if field is not None:
                        labels.add(f"config.{field}")
                    elif isinstance(n.value, ast.Name) \
                            and n.value.id == "self":
                        labels.update(_tv_all(
                            self._lookup(f"self.{n.attr}", scope)))
                elif isinstance(n, ast.Call):
                    env_name = _env_read(n)
                    if env_name is not None:
                        labels.add(f"env.{env_name}")
                    labels.update(self.resolver.call_summary(n))
        return labels


# ---------------------------------------------------------------------------
# statement-level taint (weak implicit flow: assignments under a
# tainted branch absorb the branch condition's labels — `f = hash_impl
# if cfg-derived else sort_impl` must taint `f` even without a direct
# dataflow edge)


class _FuncTaint:
    def __init__(self, fn: ast.AST, gt: GroundTruth, resolver,
                 namedtuples: Dict[str, Tuple],
                 seed: Optional[Dict[str, Dict[str, Set[str]]]] = None):
        self.fn = fn
        self.env: Dict[str, Dict[str, Set[str]]] = dict(seed or {})
        self.ev = _Evaluator(self.env, gt, resolver, namedtuples)
        for _ in range(6):
            before = {k: {f: set(v) for f, v in tv.items()}
                      for k, tv in self.env.items()}
            body = fn.body if isinstance(fn.body, list) else [fn.body]
            for stmt in body:
                self._stmt(stmt, set())
            if self.env == before:
                break

    def _assign_to(self, target: ast.expr, tv: Dict[str, Set[str]]):
        if isinstance(target, ast.Name):
            self.env[target.id] = _tv_union(
                self.env.get(target.id, _tv()), tv)
        elif isinstance(target, ast.Attribute) \
                and isinstance(target.value, ast.Name) \
                and target.value.id == "self":
            key = f"self.{target.attr}"
            self.env[key] = _tv_union(self.env.get(key, _tv()), tv)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for elt in target.elts:
                self._assign_to(elt, tv)
        elif isinstance(target, ast.Subscript):
            self._assign_to(target.value, tv)
        elif isinstance(target, ast.Starred):
            self._assign_to(target.value, tv)

    def _stmt(self, stmt: ast.stmt, ctx: Set[str]):
        ev = self.ev
        if isinstance(stmt, ast.Assign):
            tv = _tv_union(ev.expr(stmt.value), _tv_scalar(ctx))
            for t in stmt.targets:
                self._assign_to(t, tv)
        elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)) \
                and getattr(stmt, "value", None) is not None:
            tv = _tv_union(ev.expr(stmt.value), _tv_scalar(ctx))
            self._assign_to(stmt.target, tv)
        elif isinstance(stmt, ast.Expr):
            # container mutation: x.append(v) / x.extend(v) / x.add(v)
            e = stmt.value
            if isinstance(e, ast.Call) \
                    and isinstance(e.func, ast.Attribute) \
                    and e.func.attr in ("append", "extend", "add",
                                        "insert", "update"):
                tv = _tv()
                for a in e.args:
                    tv = _tv_union(tv, ev.expr(a))
                tv = _tv_union(tv, _tv_scalar(ctx))
                self._assign_to(e.func.value, tv)
        elif isinstance(stmt, ast.For):
            it = _tv_union(ev.expr(stmt.iter), _tv_scalar(ctx))
            self._assign_to(stmt.target, it)
            for s in stmt.body + stmt.orelse:
                self._stmt(s, ctx)
        elif isinstance(stmt, (ast.If, ast.While)):
            inner = ctx | _tv_all(ev.expr(stmt.test))
            for s in stmt.body:
                self._stmt(s, inner)
            for s in stmt.orelse:
                self._stmt(s, inner)
        elif isinstance(stmt, ast.With):
            for item in stmt.items:
                tv = ev.expr(item.context_expr)
                if item.optional_vars is not None:
                    self._assign_to(item.optional_vars, tv)
            for s in stmt.body:
                self._stmt(s, ctx)
        elif isinstance(stmt, ast.Try):
            for s in (stmt.body + stmt.orelse + stmt.finalbody):
                self._stmt(s, ctx)
            for h in stmt.handlers:
                for s in h.body:
                    self._stmt(s, ctx)
        elif isinstance(stmt, ast.FunctionDef):
            # a nested def's NAME carries its closure labels: the
            # builder `lambda: probe_fn` then reads them off the name
            labels = ev._free_labels(stmt, {}) | ctx
            self.env[stmt.name] = _tv_union(
                self.env.get(stmt.name, _tv()), _tv_scalar(labels))
        elif isinstance(stmt, ast.Return) and stmt.value is not None:
            pass


# ---------------------------------------------------------------------------
# whole-tree inventory: functions, call edges, source summaries


class _ModScan:
    def __init__(self, source: str, path: str, tree: ast.AST):
        self.source = source
        self.path = path
        self.tree = tree
        self.dotted = _dotted(path)
        self.import_aliases: Dict[str, str] = {}
        self.from_funcs: Dict[str, Tuple[str, str]] = {}
        # fkey -> FunctionDef; fkey = (dotted, class_name | None, name)
        self.funcs: Dict[Tuple, ast.AST] = {}
        self.func_class: Dict[int, Optional[str]] = {}
        self.parents: Dict[int, ast.AST] = {}
        self.namedtuples: Dict[str, Tuple] = {}
        for n in ast.walk(tree):
            for c in ast.iter_child_nodes(n):
                self.parents[id(c)] = n
        for n in ast.walk(tree):
            if isinstance(n, ast.Import):
                for a in n.names:
                    self.import_aliases[a.asname or
                                        a.name.split(".")[0]] = a.name
            elif isinstance(n, ast.ImportFrom) and n.module:
                for a in n.names:
                    self.from_funcs[a.asname or a.name] = (n.module,
                                                           a.name)
            elif isinstance(n, ast.ClassDef):
                if _is_namedtuple(n):
                    self.namedtuples[n.name] = _nt_fields(n)
        for n in ast.walk(tree):
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                cls = self._enclosing_class(n)
                self.funcs.setdefault((self.dotted, cls, n.name), n)
                self.func_class[id(n)] = cls

    def _enclosing_class(self, n: ast.AST) -> Optional[str]:
        p = self.parents.get(id(n))
        while p is not None:
            if isinstance(p, ast.ClassDef):
                return p.name
            if isinstance(p, (ast.FunctionDef, ast.AsyncFunctionDef)):
                # nested def: attribute to the outer def's class
                return self.func_class.get(id(p))
            p = self.parents.get(id(p))
        return None

    def enclosing_function(self, n: ast.AST) -> Optional[ast.AST]:
        p = self.parents.get(id(n))
        while p is not None:
            if isinstance(p, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return p
            p = self.parents.get(id(p))
        return None

    def outermost_function(self, n: ast.AST) -> Optional[ast.AST]:
        out = None
        p = self.parents.get(id(n))
        while p is not None:
            if isinstance(p, (ast.FunctionDef, ast.AsyncFunctionDef)):
                out = p
            p = self.parents.get(id(p))
        return out


def _dotted(path: str) -> str:
    norm = path.replace("\\", "/")
    if "presto_tpu/" in norm:
        rel = norm[norm.rindex("presto_tpu/"):]
    else:
        rel = norm
    return rel[:-3].replace("/", ".") if rel.endswith(".py") else rel


def _is_namedtuple(n: ast.ClassDef) -> bool:
    for b in n.bases:
        name = b.attr if isinstance(b, ast.Attribute) else (
            b.id if isinstance(b, ast.Name) else None)
        if name == "NamedTuple":
            return True
    return False


def _nt_fields(n: ast.ClassDef) -> Tuple:
    out = []
    for stmt in n.body:
        if isinstance(stmt, ast.AnnAssign) \
                and isinstance(stmt.target, ast.Name):
            out.append(stmt.target.id)
    return tuple(out)


class _Resolver:
    """Call-target resolution + env/session source summaries over the
    interprocedural may-call graph (the concurrency pass's fixpoint
    shape, re-targeted at taint sources instead of lock acquisition)."""

    def __init__(self, mods: List[_ModScan], gt: GroundTruth):
        self.gt = gt
        self.mods = {m.dotted: m for m in mods}
        self.by_name: Dict[str, List[Tuple]] = {}
        self.direct: Dict[Tuple, Set[str]] = {}
        self.read_sites: Dict[Tuple, List[Tuple[str, int]]] = {}
        self.edges: Dict[Tuple, Set[Tuple]] = {}
        self.summary: Dict[Tuple, Set[str]] = {}
        for m in mods:
            for fkey, fn in m.funcs.items():
                self.by_name.setdefault(fkey[2], []).append(fkey)
                self.direct[fkey] = self._direct_labels(fn, fkey)
                self.edges[fkey] = self._callees(m, fkey, fn)
        self._fixpoint()
        self._mod: Optional[_ModScan] = None

    def bind(self, mod: _ModScan):
        self._mod = mod

    # -- source labels read directly in a function body ---------------------

    def _direct_labels(self, fn: ast.AST, fkey: Tuple) -> Set[str]:
        labels: Set[str] = set()
        sites: List[Tuple[str, int]] = []
        for n in ast.walk(fn):
            lab = None
            if isinstance(n, ast.Call):
                env_name = _env_read(n)
                if env_name is not None:
                    lab = f"env.{env_name}"
                else:
                    prop = _session_get(n)
                    if prop is not None:
                        lab = f"session.{prop}"
            elif isinstance(n, ast.Subscript) \
                    and isinstance(n.value, ast.Attribute) \
                    and n.value.attr == "environ" \
                    and isinstance(n.slice, ast.Constant):
                lab = f"env.{n.slice.value}"
            if lab is None:
                continue
            # value-neutral env knobs carry no taint: a cache-volatile
            # var read deep inside an obs/ helper must not poison every
            # caller's summary
            if lab.startswith("env.") \
                    and self.gt.env_class(lab[4:]) == "cache-volatile":
                continue
            labels.add(lab)
            sites.append((lab, getattr(n, "lineno", 0)))
        self.read_sites[fkey] = sites
        return labels

    # -- call edges ---------------------------------------------------------

    def _callees(self, m: _ModScan, fkey: Tuple,
                 fn: ast.AST) -> Set[Tuple]:
        out: Set[Tuple] = set()
        for n in ast.walk(fn):
            if not isinstance(n, ast.Call):
                continue
            tgt = self.resolve_in(m, fkey[1], n)
            if tgt is not None:
                out.add(tgt)
        return out

    def resolve_in(self, m: _ModScan, cls: Optional[str],
                   call: ast.Call) -> Optional[Tuple]:
        fn = call.func
        if isinstance(fn, ast.Name):
            name = fn.id
            if (m.dotted, cls, name) in m.funcs:
                return (m.dotted, cls, name)
            if (m.dotted, None, name) in m.funcs:
                return (m.dotted, None, name)
            if name in m.from_funcs:
                src_mod, src_name = m.from_funcs[name]
                key = (src_mod, None, src_name)
                if key in self.direct:
                    return key
            return None
        if isinstance(fn, ast.Attribute):
            base = fn.value
            if isinstance(base, ast.Name):
                if base.id == "self" and cls is not None:
                    key = (m.dotted, cls, fn.attr)
                    if key in self.direct:
                        return key
                alias = m.import_aliases.get(base.id)
                if alias is None and base.id in m.from_funcs:
                    src_mod, src_name = m.from_funcs[base.id]
                    alias = f"{src_mod}.{src_name}"
                if alias is not None:
                    key = (alias, None, fn.attr)
                    if key in self.direct:
                        return key
        return None

    def _fixpoint(self):
        self.summary = {k: set(v) for k, v in self.direct.items()}
        changed = True
        while changed:
            changed = False
            for fkey, callees in self.edges.items():
                s = self.summary[fkey]
                n0 = len(s)
                for c in callees:
                    s.update(self.summary.get(c, ()))
                if len(s) != n0:
                    changed = True

    # -- evaluator hooks ----------------------------------------------------

    def call_summary(self, call: ast.Call) -> Set[str]:
        if self._mod is None:
            return set()
        tgt = self.resolve_in(self._mod, None, call)
        if tgt is None and isinstance(call.func, ast.Attribute) \
                and isinstance(call.func.value, ast.Name) \
                and call.func.value.id == "self":
            # method call with unknown class context: any class in the
            # module defining the name (conservative union)
            out: Set[str] = set()
            for key in self.by_name.get(call.func.attr, ()):
                if key[0] == self._mod.dotted:
                    out.update(self.summary.get(key, ()))
            return out
        return set(self.summary.get(tgt, ())) if tgt else set()

    def name_summary(self, name: str) -> Set[str]:
        if self._mod is None:
            return set()
        m = self._mod
        key = (m.dotted, None, name)
        if key in self.summary:
            return set(self.summary[key])
        if name in m.from_funcs:
            src_mod, src_name = m.from_funcs[name]
            return set(self.summary.get((src_mod, None, src_name), ()))
        return set()


# ---------------------------------------------------------------------------
# traced-region reachability (sinks + their transitive callees)


def _traced_seeds(m: _ModScan) -> List[Tuple]:
    norm = m.path.replace("\\", "/")
    if ("/ops/" in norm or norm.startswith("ops/")
            or norm.endswith("exec/fragment_jit.py")):
        # device-library modules: every def is (potential) traced code,
        # matching kernel_lint's region convention
        return list(m.funcs)
    seeds: List[Tuple] = []
    funcs_by_name: Dict[str, List[ast.AST]] = {}
    for (mod, cls, name), fn in m.funcs.items():
        funcs_by_name.setdefault(name, []).append(fn)

    def add(name: str):
        for fn in funcs_by_name.get(name, ()):
            cls = m.func_class.get(id(fn))
            seeds.append((m.dotted, cls, fn.name))

    tree_funcs = astutil.collect_functions(m.tree)
    for root in astutil.jit_roots(m.tree, tree_funcs):
        if isinstance(root, (ast.FunctionDef, ast.AsyncFunctionDef)):
            seeds.append((m.dotted, m.func_class.get(id(root)),
                          root.name))
        elif isinstance(root, ast.Name):
            add(root.id)
    return seeds


def _traced_set(mods: List[_ModScan], resolver: _Resolver) -> Set[Tuple]:
    work: List[Tuple] = []
    for m in mods:
        work.extend(_traced_seeds(m))
    seen: Set[Tuple] = set()
    while work:
        fkey = work.pop()
        if fkey in seen or fkey not in resolver.edges:
            continue
        seen.add(fkey)
        work.extend(resolver.edges[fkey])
    return seen


# ---------------------------------------------------------------------------
# coverage + rule evaluation


def _is_covered(label: str, key_labels: Set[str], gt: GroundTruth,
                context: str) -> Optional[Tuple[str, str]]:
    """None when covered; else (rule, explanation) for a sink reach."""
    if label in key_labels:
        return None
    if label.startswith("session."):
        prop = label[8:]
        cls = gt.property_class(prop)
        if cls == "planner" or cls == "volatile":
            return None
        if cls == "fingerprinted":
            label = f"config.{gt.lowering[prop]}"
            if label in key_labels:
                return None
        else:
            return ("unfingerprinted-knob",
                    f"session property '{prop}' has no fingerprint "
                    f"membership or declared volatility class")
    if label.startswith("config."):
        field = label[7:]
        if field not in gt.volatile_fields:
            return None  # fingerprinted: _program_ns forks on it
        return ("volatile-leak",
                f"volatile ExecConfig field '{field}' {context} but the "
                f"program key does not cover it — two sessions differing "
                f"only in '{field}' would share one cached program; "
                f"derive an engine-key suffix from it (the `key@h` "
                f"idiom) or stop capturing it")
    if label.startswith("env."):
        name = label[4:]
        cls = gt.env_class(name)
        if cls == "fingerprinted" or cls == "cache-volatile":
            return None
        return ("unfingerprinted-knob",
                f"env var '{name}' {context} but is neither in "
                f"_FINGERPRINTED_ENVS (exec/programs.py) nor declared "
                f"cache-volatile in knob_flow._CACHE_VOLATILE_ENVS")
    return None


def _check_node_jit_sites(m: _ModScan, resolver: _Resolver,
                          gt: GroundTruth, supp: Suppressions,
                          namedtuples: Dict[str, Tuple],
                          findings: List[Finding]):
    resolver.bind(m)
    taint_cache: Dict[int, _FuncTaint] = {}
    class_envs: Dict[str, Dict[str, Dict[str, Set[str]]]] = {}

    def class_env(cls: Optional[str]) -> Dict:
        if cls is None:
            return {}
        if cls in class_envs:
            return class_envs[cls]
        env: Dict[str, Dict[str, Set[str]]] = {}
        methods = [fn for (mod, c, name), fn in m.funcs.items()
                   if c == cls]
        # two rounds: self-attr taint set in __init__ is visible from
        # sibling methods (the _counts_program pattern)
        for _ in range(2):
            for fn in methods:
                if not isinstance(fn, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                    continue
                ft = _FuncTaint(fn, gt, resolver, namedtuples, seed=env)
                for k, v in ft.env.items():
                    if k.startswith("self."):
                        env[k] = _tv_union(env.get(k, _tv()), v)
        class_envs[cls] = env
        return env

    def taint_for(fn: ast.AST) -> _FuncTaint:
        ft = taint_cache.get(id(fn))
        if ft is None:
            cls = m.func_class.get(id(fn))
            ft = _FuncTaint(fn, gt, resolver, namedtuples,
                            seed=class_env(cls))
            taint_cache[id(fn)] = ft
        return ft

    for n in ast.walk(m.tree):
        if not isinstance(n, ast.Call):
            continue
        fname = (n.func.id if isinstance(n.func, ast.Name)
                 else n.func.attr if isinstance(n.func, ast.Attribute)
                 else None)
        if fname == "_node_jit" and len(n.args) >= 3:
            host = m.enclosing_function(n)
            if host is None:
                continue
            ft = taint_for(host)
            ev = ft.ev
            key_labels = _tv_all(ev.expr(n.args[1]))
            builder = n.args[2]
            if isinstance(builder, ast.Lambda):
                closure = _tv_all(ev.expr(builder.body))
            else:
                closure = _tv_all(ev.expr(builder))
            line = n.lineno
            for label in sorted(closure):
                hit = _is_covered(label, key_labels, gt,
                                  "is captured by this program's "
                                  "builder closure")
                if hit is None:
                    continue
                rule, msg = hit
                if supp.allowed(rule, line):
                    continue
                findings.append(Finding(rule, f"{m.path}:{line}", msg,
                                        PLANE))
        elif fname == "pallas_call" and n.args:
            tgt = n.args[0]
            if isinstance(tgt, ast.Call) and tgt.args:
                tgt = tgt.args[0]
            if not isinstance(tgt, ast.Name):
                continue
            host = m.enclosing_function(n)
            ft = taint_for(host) if host is not None else None
            ev = ft.ev if ft is not None else _Evaluator(
                {}, gt, resolver, namedtuples)
            closure = _tv_all(ev.expr(tgt))
            line = n.lineno
            for label in sorted(closure):
                hit = _is_covered(label, set(), gt,
                                  "reaches this Pallas kernel")
                if hit is None:
                    continue
                rule, msg = hit
                if supp.allowed(rule, line):
                    continue
                findings.append(Finding(rule, f"{m.path}:{line}", msg,
                                        PLANE))


def _check_traced_reads(m: _ModScan, resolver: _Resolver,
                        traced: Set[Tuple], gt: GroundTruth,
                        supp: Suppressions, findings: List[Finding]):
    """Direct env/session reads inside traced-reachable functions: the
    value bakes into the traced program at trace time with no key
    coverage at all."""
    for fkey, fn in m.funcs.items():
        if fkey not in traced:
            continue
        for label, line in resolver.read_sites.get(fkey, ()):
            hit = _is_covered(label, set(), gt,
                              "is read inside traced-reachable code")
            if hit is None:
                continue
            rule, msg = hit
            if supp.allowed(rule, line):
                continue
            findings.append(Finding(rule, f"{m.path}:{line}", msg,
                                    PLANE))


def _check_unregistered_state(m: _ModScan, gt: GroundTruth,
                              supp: Suppressions,
                              findings: List[Finding]):
    norm = m.path.replace("\\", "/")
    if "/ops/" in norm or "/expr/" in norm or norm.startswith(("ops/",
                                                               "expr/")):
        for name, fields in m.namedtuples.items():
            cls = next(cn for cn in ast.walk(m.tree)
                       if isinstance(cn, ast.ClassDef)
                       and cn.name == name)
            dotted_name = f"{m.dotted}.{name}"
            # injected trees carry synthetic dotted paths; match on the
            # trailing module.Class segments
            tail = ".".join(dotted_name.split(".")[-2:])
            if any(r == dotted_name or r.endswith(f".{tail}")
                   for r in gt.registered_state):
                continue
            if supp.allowed("unregistered-state", cls.lineno):
                continue
            findings.append(Finding(
                "unregistered-state", f"{m.path}:{cls.lineno}",
                f"operator-state NamedTuple '{name}' is not in the "
                f"jax.export pytree registration table "
                f"(exec/programs.py _register_pytree_serialization) — "
                f"persisted artifacts touching it fail to restore "
                f"(the PR-16 BuildTable failure chain)", PLANE))
    if norm.endswith("plan/nodes.py"):
        for name, line in gt.node_classes:
            if name in gt.codec_names:
                continue
            if supp.allowed("unregistered-state", line):
                continue
            findings.append(Finding(
                "unregistered-state", f"{m.path}:{line}",
                f"plan-node class '{name}' has no codec encoding "
                f"(plan/codec.py) — its subtrees cannot be "
                f"fingerprinted, persisted to the farm corpus, or "
                f"shipped to workers", PLANE))


def _parse_key_contracts(mods: List[_ModScan]):
    keys: Dict[str, Tuple[str, int, Set[str]]] = {}
    uses: List[Tuple[_ModScan, int, str]] = []
    for m in mods:
        for i, line in enumerate(m.source.splitlines(), start=1):
            km = _KEY_RE.search(line)
            if km:
                covers = {c.strip() for c in km.group(2).split(",")
                          if c.strip()}
                keys[km.group(1)] = (m.path, i, covers)
            um = _USES_RE.search(line)
            if um:
                uses.append((m, i, um.group(1)))
    return keys, uses


def _check_cache_key_drift(mods: List[_ModScan], resolver: _Resolver,
                           gt: GroundTruth,
                           supps: Dict[str, Suppressions],
                           findings: List[Finding]):
    keys, uses = _parse_key_contracts(mods)
    # expected contracts: deleting a declaration is drift
    for m in mods:
        base = os.path.basename(m.path)
        for want in _EXPECTED_KEYS.get(base, ()):
            if want not in keys:
                findings.append(Finding(
                    "cache-key-drift", f"{m.path}:1",
                    f"expected cache-key contract "
                    f"'# fp: key({want}) covers(...)' is not declared "
                    f"in this module", PLANE))
    for m, line, key_name in uses:
        supp = supps[m.path]
        if key_name not in keys:
            if not supp.allowed("cache-key-drift", line):
                findings.append(Finding(
                    "cache-key-drift", f"{m.path}:{line}",
                    f"uses-key({key_name}) references a key with no "
                    f"'# fp: key({key_name}) covers(...)' declaration",
                    PLANE))
            continue
        _, _, covers = keys[key_name]
        fn = _def_at_line(m, line)
        if fn is None:
            continue
        resolver.bind(m)
        _scan_uses_key(m, fn, key_name, covers, gt, resolver, supp,
                       findings)


def _def_at_line(m: _ModScan, line: int) -> Optional[ast.AST]:
    """The function a `# fp: uses-key(...)` annotation governs: the
    annotation sits on (or immediately above) the def header."""
    for fn in m.funcs.values():
        lo = min(getattr(fn, "lineno", 1 << 30),
                 *[d.lineno for d in getattr(fn, "decorator_list", [])]
                 or [1 << 30])
        hdr_end = fn.body[0].lineno if getattr(fn, "body", None) else lo
        if lo - 1 <= line <= hdr_end:
            return fn
    # else: the innermost function containing the line
    best = None
    for fn in m.funcs.values():
        lo = getattr(fn, "lineno", None)
        hi = getattr(fn, "end_lineno", None)
        if lo is not None and hi is not None and lo <= line <= hi:
            if best is None or lo > best.lineno:
                best = fn
    return best


def _scan_uses_key(m: _ModScan, fn: ast.AST, key_name: str,
                   covers: Set[str], gt: GroundTruth,
                   resolver: _Resolver, supp: Suppressions,
                   findings: List[Finding]):
    """Every config/env/session value a uses-key(...) consumer reads
    must be value-neutral or inside the key's covers() set."""
    wildcard_params: Set[str] = set()
    args = fn.args
    for a in (list(args.posonlyargs) + list(args.args)
              + list(args.kwonlyargs)):
        if a.arg in _CONFIG_ROOTS:
            wildcard_params.add(a.arg)

    def covered(label: str) -> bool:
        if label == "config" or label.startswith("config."):
            if label.startswith("config.") \
                    and label[7:] in gt.volatile_fields:
                return True  # value-neutral by declaration
            return "config" in covers
        if label.startswith("env."):
            name = label[4:]
            if gt.env_class(name) != "undeclared":
                return True
            return f"env:{name}" in covers
        if label.startswith("session."):
            prop = label[8:]
            cls = gt.property_class(prop)
            if cls == "volatile":
                return True
            if cls == "planner":
                return "plan-structure" in covers
            if cls == "fingerprinted":
                return "config" in covers
            return False
        return True

    def report(label: str, line: int):
        if supp.allowed("cache-key-drift", line):
            return
        findings.append(Finding(
            "cache-key-drift", f"{m.path}:{line}",
            f"'{label}' feeds a value keyed by '{key_name}', but the "
            f"key's covers({', '.join(sorted(covers))}) set does not "
            f"include it — the cached value can change while its key "
            f"stays fixed", PLANE))

    seen: Set[str] = set()
    for n in ast.walk(fn):
        labels: Set[str] = set()
        line = getattr(n, "lineno", fn.lineno)
        if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load) \
                and n.id in wildcard_params:
            labels.add("config")
        elif isinstance(n, ast.Attribute):
            f = _config_attr(n, gt)
            if f is not None:
                labels.add(f"config.{f}")
        elif isinstance(n, ast.Call):
            env_name = _env_read(n)
            if env_name is not None:
                labels.add(f"env.{env_name}")
            f = _getattr_config(n, gt)
            if f is not None:
                labels.add(f"config.{f}")
            prop = _session_get(n)
            if prop is not None:
                labels.add(f"session.{prop}")
        for label in labels:
            if label in seen or covered(label):
                continue
            seen.add(label)
            report(label, line)


# ---------------------------------------------------------------------------
# drivers


def analyze_modules(modules: Sequence[Tuple[str, str, ast.AST]],
                    rules: Sequence[str] = RULES,
                    gt: Optional[GroundTruth] = None) -> List[Finding]:
    """Run the knob-flow pass over (source, path, tree) triples."""
    gt = gt or load_ground_truth()
    rules = set(rules)
    mods = [_ModScan(src, path, tree) for src, path, tree in modules]
    namedtuples: Dict[str, Tuple] = {}
    for m in mods:
        namedtuples.update(m.namedtuples)
    resolver = _Resolver(mods, gt)
    traced = _traced_set(mods, resolver)
    supps = {m.path: Suppressions(m.source, marker="fp") for m in mods}
    for m in mods:
        kernels = astutil.kernel_functions(m.tree, m.path)
        supps[m.path].cover_functions(kernels)
        supps[m.path].cover_functions(list(m.funcs.values()))
    findings: List[Finding] = []
    for m in mods:
        supp = supps[m.path]
        _check_node_jit_sites(m, resolver, gt, supp, namedtuples,
                              findings)
        _check_traced_reads(m, resolver, traced, gt, supp, findings)
        _check_unregistered_state(m, gt, supp, findings)
    _check_cache_key_drift(mods, resolver, gt, supps, findings)
    findings = [f for f in findings if f.rule in rules]
    uniq = {}
    for f in findings:
        uniq[(f.rule, f.loc, f.message)] = f
    return sorted(uniq.values(), key=lambda f: (f.loc, f.rule))


def analyze_paths(paths: Sequence[str],
                  rules: Sequence[str] = RULES) -> List[Finding]:
    modules = []
    findings: List[Finding] = []
    for p in astutil.iter_py_files(paths):
        try:
            src, tree = astutil.load_file(p)
        except SyntaxError as e:
            findings.append(Finding("syntax-error",
                                    f"{p}:{e.lineno or 0}",
                                    str(e.msg), PLANE))
            continue
        modules.append((src, p, tree))
    findings.extend(analyze_modules(modules, rules))
    return findings


def analyze_source(source: str, path: str,
                   rules: Sequence[str] = RULES) -> List[Finding]:
    try:
        tree = astutil.parse(source, path)
    except SyntaxError as e:
        return [Finding("syntax-error", f"{path}:{e.lineno or 0}",
                        str(e.msg), PLANE)]
    return analyze_modules([(source, path, tree)], rules)


# ---------------------------------------------------------------------------
# knob inventory (--knobs)


def knob_inventory(pkg: Optional[str] = None) -> List[Dict[str, str]]:
    """Every knob the engine reads — session properties, ExecConfig
    fields, PRESTO_TPU_* env vars — with its volatility class and
    fingerprint membership, derived from the shipped source."""
    gt = load_ground_truth(pkg)
    root = pkg or _pkg_dir()
    rows: List[Dict[str, str]] = []
    lowered_fields = set(gt.lowering.values())
    for prop in sorted(gt.session_props):
        cls = gt.property_class(prop)
        tgt = gt.lowering.get(prop, "—")
        rows.append({
            "knob": prop, "kind": "session",
            "lowers_to": tgt,
            "class": cls,
            "fingerprinted": _fp_mark(cls)})
    for field in sorted(gt.config_fields):
        cls = ("volatile" if field in gt.volatile_fields
               else "fingerprinted")
        rows.append({
            "knob": field, "kind": "config",
            "lowers_to": ("session" if field in lowered_fields
                          else "—"),
            "class": cls,
            "fingerprinted": _fp_mark(cls)})
    for name in sorted(_env_vars_in_tree(root)):
        cls = gt.env_class(name)
        rows.append({
            "knob": name, "kind": "env",
            "lowers_to": "—",
            "class": cls,
            "fingerprinted": _fp_mark(cls)})
    return rows


def _fp_mark(cls: str) -> str:
    return {"fingerprinted": "yes (config fingerprint)",
            "planner": "yes (structural fingerprint)",
            "volatile": "no (value-neutral)",
            "cache-volatile": "no (value-neutral)",
            "undeclared": "NO — undeclared"}.get(cls, cls)


def _env_vars_in_tree(root: str) -> Set[str]:
    out: Set[str] = set()
    pat = re.compile(r"PRESTO_TPU_[A-Z0-9_]+")
    for p in astutil.iter_py_files([root]):
        try:
            src, _ = astutil.load_file(p)
        except (OSError, SyntaxError):
            continue
        out.update(pat.findall(src))
    return out


def render_knob_table(rows: List[Dict[str, str]]) -> str:
    lines = ["| knob | kind | lowers to / from | class | in fingerprint? |",
             "|---|---|---|---|---|"]
    for r in rows:
        lines.append(f"| `{r['knob']}` | {r['kind']} | {r['lowers_to']} "
                     f"| {r['class']} | {r['fingerprinted']} |")
    return "\n".join(lines)
