"""Python-`ast` linter for TPU kernel code.

Scope: the device-kernel modules (`presto_tpu/ops/*.py`) and the jitted
regions of the runtime driver (`presto_tpu/exec/runtime.py`). The rules
encode the discipline the engine's hot path depends on — every violation
class here has produced a real regression shape in engines of this
design (silent host round-trips, f64 emulation on f32 hardware,
per-batch recompiles):

- ``host-sync``: `.item()`, `float(x)` / `int(x)` / `bool(x)` on
  non-static values, and `np.asarray` / `np.array` inside traced code.
  Each forces a device→host transfer per call or breaks tracing
  outright.
- ``float64``: implicit f64 creation — `np.float64(...)` scalars (strong
  typed: they infect f32/weak arrays), array constructors
  (`zeros/ones/full/empty`) without an explicit dtype (this engine runs
  with x64 enabled, so the default is f64), `dtype=float`, and
  `array(...)` literals containing bare floats with no dtype.
- ``traced-branch``: Python `if` / `while` whose test calls into
  `jnp.` / `jax.` or `.any()` / `.all()` — a data-dependent branch on a
  traced array (TracerBoolConversionError at best, a silent host sync
  under concrete re-execution at worst).
- ``pow2-capacity``: integer capacity constants in shape positions that
  are not powers of two. Every distinct capacity is a distinct compiled
  program; the blessed path is `round_up_capacity` / the pow2 bucket
  helpers, never a bare odd constant.
- ``where-free-masking``: multiplying by a boolean mask (a comparison,
  its `.astype`, or a mask-named value like `live` / `validity` /
  `*_mask`) to zero out lanes. Mask-multiply propagates NaN/Inf from the
  dead lanes (NaN·0 = NaN) and silently widens dtypes; the blessed
  pattern is `jnp.where(mask, x, fill)`, which selects instead of
  scaling.
- ``ref-indexing``: dynamic-shape loads/stores on Pallas refs — a
  `*_ref[...]` subscript whose Python-slice bounds are not trace-time
  static, or a `pl.ds(start, size)` whose SIZE is not static. A dynamic
  START is the supported pattern (`pl.ds(traced_start, STATIC_SIZE)`);
  a dynamic extent has no lowering on TPU and fails only at Mosaic
  compile time, far from the offending line.

Kernel-region detection: in `ops/` and `exec/fragment_jit.py` every
function is kernel code (they are device-kernel libraries). Elsewhere a
function is kernel code iff it is reachable from a jit root — decorated
with `jax.jit` / `partial(jax.jit, ...)`, passed to `jax.jit(...)`,
passed to `pl.pallas_call(...)` (directly or through
`functools.partial(kernel, ...)`), or returned by a builder passed to
`_node_jit(...)` — transitively through same-module calls.

Static-expression classification is TAINT-TRACKED: a name assigned from
a session/runtime source (a `.get(...)` property read, an attribute or
subscript rooted at `session` / `ctx` / `cfg` / `config` / `os`, a
`jnp.`/`jax.`/`lax.`/`pl.` call, or a `*_ref[...]` load — transitively
through local assignments) is never classified static, even behind a
`.shape`-style attribute that would otherwise be blessed. A
session-derived capacity flowing into a shape position is a per-session
recompile (or a dynamic Pallas extent), not a constant.

Suppressions: append ``# lint: allow(<rule>[, <rule>...])`` to the
offending line; on a `def` line it covers the whole function.
"""

from __future__ import annotations

import ast
from typing import List, Sequence, Set

from presto_tpu.analysis import astutil
from presto_tpu.analysis.astutil import (
    Suppressions,
    _attr_chain,
    _root_name,
    kernel_functions,
)
from presto_tpu.analysis.findings import Finding

RULES = ("host-sync", "float64", "traced-branch", "pow2-capacity",
         "where-free-masking", "ref-indexing")

_NUMPY_ALIASES = {"np", "numpy"}
_JAX_NUMPY_ALIASES = {"jnp"}
_ARRAY_CTORS = {"zeros", "ones", "full", "empty"}
_SHAPE_CTORS = {"zeros", "ones", "full", "empty", "arange", "iota",
                "broadcasted_iota"}
_CAPACITY_KWARGS = {"capacity", "cap", "bucket", "num_groups_cap",
                    "out_cap", "minimum", "num_segments"}
# attribute tails that are static at trace time (shapes, type params)
_STATIC_ATTRS = {"shape", "ndim", "size", "capacity", "width", "scale",
                 "precision", "dtype", "itemsize", "bits"}
_BLESSED_HELPERS = {"round_up_capacity"}
# jnp/np calls that are dtype metadata queries — static at trace time,
# so branching on them is shape/type dispatch, not a traced branch
_DTYPE_PREDICATES = {"issubdtype", "isdtype", "iinfo", "finfo",
                     "result_type", "promote_types", "dtype",
                     "canonicalize_dtype"}


def _is_pow2(n: int) -> bool:
    return n >= 0 and (n & (n - 1)) == 0


def _is_static_expr(e: ast.expr, tainted: frozenset = frozenset()) -> bool:
    """Conservatively true when an expression is compile-time static:
    literals, len()/shape/type-parameter access, arithmetic over those.

    `tainted` names hold session-/runtime-derived values (see
    `_collect_taint`); any attribute/subscript chain rooted at one is
    non-static even when the attribute tail would normally be blessed —
    `cfg.capacity` is a per-session value, not a trace constant."""
    if isinstance(e, ast.Constant):
        return True
    if isinstance(e, ast.Attribute):
        root = _root_name(e)
        if root is not None and root in tainted:
            return False
        return e.attr in _STATIC_ATTRS or _is_static_expr(e.value, tainted)
    if isinstance(e, ast.Subscript):
        root = _root_name(e.value)
        if root is not None and root in tainted:
            return False
        return _is_static_expr(e.value, tainted)
    if isinstance(e, ast.BinOp):
        return (_is_static_expr(e.left, tainted)
                and _is_static_expr(e.right, tainted))
    if isinstance(e, ast.UnaryOp):
        return _is_static_expr(e.operand, tainted)
    if isinstance(e, ast.Call):
        fn = e.func
        if isinstance(fn, ast.Name) and fn.id == "len":
            # len() of anything (including a traced array) is a host int
            return True
        if isinstance(fn, ast.Name) and fn.id in (
                {"max", "min", "abs"} | _BLESSED_HELPERS):
            return all(_is_static_expr(a, tainted) for a in e.args)
        chain = _attr_chain(fn)
        if chain and chain[1] == "bit_length":
            return True
        if isinstance(fn, ast.Attribute) and fn.attr in ("get",):
            return False
        return False
    if isinstance(e, ast.IfExp):
        return (_is_static_expr(e.test, tainted)
                and _is_static_expr(e.body, tainted)
                and _is_static_expr(e.orelse, tainted))
    return False


# roots whose attribute/subscript reads are runtime values by definition
_RUNTIME_ROOTS = {"session", "ctx", "cfg", "config", "os", "environ",
                  "properties"}


def _expr_taints(e: ast.expr, tainted) -> bool:
    """True when the r.h.s. of an assignment carries runtime/session
    taint: a `.get(...)` read, a chain rooted in _RUNTIME_ROOTS, a
    traced `jnp/jax/lax/pl` call, a `*_ref[...]` load, or an
    already-tainted name."""
    for n in ast.walk(e):
        if isinstance(n, ast.Name) and n.id in tainted:
            return True
        if isinstance(n, ast.Call):
            fn = n.func
            if isinstance(fn, ast.Attribute) and fn.attr == "get":
                return True
            root = _root_name(fn)
            if root in (_JAX_NUMPY_ALIASES | {"jax", "lax", "pl"}):
                return True
        if isinstance(n, ast.Attribute):
            if _root_name(n) in _RUNTIME_ROOTS:
                return True
        if isinstance(n, ast.Subscript):
            root = _root_name(n.value)
            if root in _RUNTIME_ROOTS:
                return True
            if root is not None and root.endswith("_ref"):
                return True
    return False


def _collect_taint(fn: ast.AST) -> frozenset:
    """Fixpoint over a kernel function's assignments: the set of local
    names that (transitively) hold session-/runtime-derived values."""
    tainted: Set[str] = set()
    changed = True
    while changed:
        changed = False
        for n in ast.walk(fn):
            if isinstance(n, ast.Assign):
                targets, value = n.targets, n.value
            elif isinstance(n, (ast.AugAssign, ast.AnnAssign)) \
                    and getattr(n, "value", None) is not None:
                targets, value = [n.target], n.value
            elif isinstance(n, ast.For):
                targets, value = [n.target], n.iter
            else:
                continue
            if not _expr_taints(value, tainted):
                continue
            for t in targets:
                for tn in ast.walk(t):
                    if isinstance(tn, ast.Name) and tn.id not in tainted:
                        tainted.add(tn.id)
                        changed = True
    return frozenset(tainted)


# kernel-region discovery and the `# lint: allow(...)` suppression index
# live in astutil (shared with the concurrency pass — one traversal for
# both analyses); `Suppressions` and `kernel_functions` are re-imported
# above.


# ---------------------------------------------------------------------------
# rules


class _RuleVisitor(ast.NodeVisitor):
    def __init__(self, path: str, supp: Suppressions,
                 rules: Sequence[str], tainted: frozenset = frozenset()):
        self.path = path
        self.supp = supp
        self.rules = set(rules)
        self.tainted = tainted
        self.findings: List[Finding] = []

    def err(self, rule: str, node: ast.AST, msg: str):
        line = getattr(node, "lineno", 0)
        if rule not in self.rules or self.supp.allowed(rule, line):
            return
        self.findings.append(
            Finding(rule, f"{self.path}:{line}", msg, "lint"))

    # do not descend into nested defs here; each kernel function is
    # visited exactly once by the driver (nested defs are themselves in
    # the kernel set when reachable)
    def visit_body(self, fn: ast.AST):
        body = fn.body if isinstance(fn.body, list) else [fn.body]
        for stmt in body:
            self.visit(stmt)

    # -- host-sync ----------------------------------------------------------

    def visit_Call(self, node: ast.Call):
        fn = node.func
        if isinstance(fn, ast.Attribute) and fn.attr == "item":
            self.err("host-sync", node,
                     ".item() forces a device→host sync inside traced "
                     "code")
        if isinstance(fn, ast.Name) and fn.id in ("float", "int", "bool") \
                and node.args:
            if not all(_is_static_expr(a, self.tainted)
                       for a in node.args):
                self.err("host-sync", node,
                         f"{fn.id}() on a non-static value host-syncs (or "
                         f"fails to trace); compute on-device with "
                         f"jnp/astype instead")
        chain = _attr_chain(fn)
        if chain and chain[0] in _NUMPY_ALIASES and chain[1] in (
                "asarray", "array"):
            if not all(_is_static_expr(a, self.tainted)
                       for a in node.args):
                self.err("host-sync", node,
                         f"np.{chain[1]}() on a traced value copies to "
                         f"host; use jnp.{chain[1]} or keep it on-device")
        self._check_float64(node, chain)
        self._check_pow2(node, chain)
        self._check_dslice(node, chain)
        self.generic_visit(node)

    # -- float64 ------------------------------------------------------------

    def visit_Attribute(self, node: ast.Attribute):
        chain = _attr_chain(node)
        if chain and chain[0] in _NUMPY_ALIASES and chain[1] == "float64":
            self.err("float64", node,
                     "np.float64 is strongly typed and promotes f32/weak "
                     "operands to f64; use the column's declared dtype")
        self.generic_visit(node)

    def _has_dtype(self, node: ast.Call, ctor: str) -> bool:
        if any(kw.arg == "dtype" for kw in node.keywords):
            return True
        # positional dtype: zeros(shape, dtype) / full(shape, fill, dtype)
        # / arange(n, dtype) — any arg beyond the shape/fill slots
        slots = {"zeros": 1, "ones": 1, "empty": 1, "full": 2}
        return len(node.args) > slots.get(ctor, 1)

    def _check_float64(self, node: ast.Call, chain):
        if chain is None:
            return
        mod, name = chain
        if mod not in (_NUMPY_ALIASES | _JAX_NUMPY_ALIASES):
            return
        for kw in node.keywords:
            if kw.arg == "dtype" and isinstance(kw.value, ast.Name) \
                    and kw.value.id == "float":
                self.err("float64", node,
                         "dtype=float is float64; name the intended width "
                         "explicitly")
        if name in _ARRAY_CTORS and not self._has_dtype(node, name):
            self.err("float64", node,
                     f"{mod}.{name}() without an explicit dtype creates "
                     f"float64 under x64; pass the intended dtype")
        if name in ("array", "asarray") \
                and not any(kw.arg == "dtype" for kw in node.keywords) \
                and len(node.args) == 1 and _has_bare_float(node.args[0]):
            self.err("float64", node,
                     f"{mod}.{name}() over bare float literals with no "
                     f"dtype creates a strong float64 array")

    # -- pow2-capacity -------------------------------------------------------

    def _check_pow2(self, node: ast.Call, chain):
        fname = None
        if chain is not None:
            mod, name = chain
            if mod in (_NUMPY_ALIASES | _JAX_NUMPY_ALIASES | {"lax"}):
                fname = name
        elif isinstance(node.func, ast.Name):
            fname = node.func.id
        if fname in _SHAPE_CTORS and node.args:
            self._pow2_value(node.args[0], node)
        for kw in node.keywords:
            if kw.arg in _CAPACITY_KWARGS:
                self._pow2_value(kw.value, node)

    def _pow2_value(self, e: ast.expr, node: ast.Call):
        vals = []
        if isinstance(e, ast.Constant) and isinstance(e.value, int) \
                and not isinstance(e.value, bool):
            vals = [e.value]
        elif isinstance(e, ast.Tuple):
            vals = [el.value for el in e.elts
                    if isinstance(el, ast.Constant)
                    and isinstance(el.value, int)
                    and not isinstance(el.value, bool)]
        for v in vals:
            if v > 1 and not _is_pow2(v):
                self.err("pow2-capacity", node,
                         f"capacity constant {v} is not a power of two — "
                         f"each distinct capacity is a distinct compiled "
                         f"program; route sizes through "
                         f"round_up_capacity()")

    # -- ref-indexing --------------------------------------------------------

    def _static_size(self, e: ast.expr) -> bool:
        """A slice bound / dslice size is acceptable when it is a static
        expression OR a bare un-tainted name (kernel closure constants —
        block sizes, capacities — arrive as plain Python ints; traced
        values originate from ref loads or jnp/lax calls and are
        tainted)."""
        if _is_static_expr(e, self.tainted):
            return True
        return isinstance(e, ast.Name) and e.id not in self.tainted

    def _check_dslice(self, node: ast.Call, chain):
        name = None
        if chain is not None and chain[0] == "pl":
            name = chain[1]
        elif isinstance(node.func, ast.Name):
            name = node.func.id
        if name in ("ds", "dslice") and len(node.args) >= 2 \
                and not self._static_size(node.args[1]):
            self.err("ref-indexing", node,
                     "pl.ds with a non-static SIZE is a dynamic-shape "
                     "load — keep the extent a trace-time constant and "
                     "let only the start be traced")

    def visit_Subscript(self, node: ast.Subscript):
        root = _root_name(node.value)
        if root is not None and root.endswith("_ref"):
            sl = node.slice
            elts = sl.elts if isinstance(sl, ast.Tuple) else [sl]
            for e in elts:
                if not isinstance(e, ast.Slice):
                    continue  # scalar / pl.ds indices checked elsewhere
                for bound in (e.lower, e.upper, e.step):
                    if bound is not None and not self._static_size(bound):
                        self.err(
                            "ref-indexing", node,
                            "ref slice with non-static bounds is a "
                            "dynamic-shape load; use pl.ds(start, "
                            "STATIC_SIZE) so the extent stays compiled-in")
                        break
        self.generic_visit(node)

    # -- traced-branch -------------------------------------------------------

    def _test_is_traced(self, test: ast.expr) -> bool:
        for n in ast.walk(test):
            if isinstance(n, ast.Call):
                root = _root_name(n.func)
                if (isinstance(n.func, ast.Attribute)
                        and n.func.attr in _DTYPE_PREDICATES):
                    continue
                if root in (_JAX_NUMPY_ALIASES | {"jax", "lax"}):
                    return True
                if isinstance(n.func, ast.Attribute) and n.func.attr in (
                        "any", "all"):
                    return True
        return False

    # -- where-free-masking --------------------------------------------------

    def visit_BinOp(self, node: ast.BinOp):
        if isinstance(node.op, ast.Mult) and (
                _is_mask_like(node.left) or _is_mask_like(node.right)):
            self.err("where-free-masking", node,
                     "multiplying by a boolean mask propagates NaN/Inf "
                     "from the masked-out lanes (NaN*0 = NaN) and widens "
                     "dtypes silently; select with "
                     "jnp.where(mask, x, fill) instead")
        self.generic_visit(node)

    def visit_If(self, node: ast.If):
        if self._test_is_traced(node.test):
            self.err("traced-branch", node,
                     "Python branch on a traced array value — lower to "
                     "jnp.where / lax.cond, or hoist the decision to the "
                     "host driver")
        self.generic_visit(node)

    def visit_While(self, node: ast.While):
        if self._test_is_traced(node.test):
            self.err("traced-branch", node,
                     "Python loop condition on a traced array value — use "
                     "lax.while_loop or drive the loop from the host")
        self.generic_visit(node)


_MASK_NAMES = {"mask", "live", "valid", "validity", "evalid"}


def _is_mask_like(e: ast.expr) -> bool:
    """True for expressions that read as boolean masks: comparisons,
    their .astype() lifts, and values whose (terminal) name follows the
    engine's mask conventions (live / validity / *_mask / *_valid)."""
    if isinstance(e, ast.Compare):
        return True
    if (isinstance(e, ast.Call) and isinstance(e.func, ast.Attribute)
            and e.func.attr == "astype"):
        return _is_mask_like(e.func.value)
    name = None
    if isinstance(e, ast.Name):
        name = e.id
    elif isinstance(e, ast.Attribute):
        name = e.attr
    if name is not None:
        low = name.lower()
        return (low in _MASK_NAMES or low.endswith("_mask")
                or low.endswith("_valid"))
    return False


def _has_bare_float(e: ast.expr) -> bool:
    for n in ast.walk(e):
        if isinstance(n, ast.Constant) and isinstance(n.value, float):
            return True
    return False


# ---------------------------------------------------------------------------
# driver


def lint_source(source: str, path: str,
                rules: Sequence[str] = RULES,
                tree: ast.AST = None) -> List[Finding]:
    """Lint one module's source text; `path` labels the findings. Pass a
    pre-parsed `tree` to share the AST with other analysis passes."""
    if tree is None:
        try:
            tree = astutil.parse(source, path)
        except SyntaxError as e:
            return [Finding("syntax-error", f"{path}:{e.lineno or 0}",
                            str(e.msg), "lint")]
    supp = Suppressions(source)
    kernels = kernel_functions(tree, path)
    # def-line suppressions cover the function body
    supp.cover_functions(kernels)
    findings: List[Finding] = []
    visited: Set[int] = set()
    nested: Set[int] = set()
    kernel_ids = {id(f) for f in kernels}
    # visit outermost kernel functions only: generic_visit descends into
    # nested defs already, and double-visiting double-reports
    for fn in kernels:
        for sub in ast.walk(fn):
            if sub is not fn and id(sub) in kernel_ids:
                nested.add(id(sub))
    for fn in kernels:
        if id(fn) in visited or id(fn) in nested:
            continue
        visited.add(id(fn))
        v = _RuleVisitor(path, supp, rules, tainted=_collect_taint(fn))
        body = fn.body if isinstance(fn.body, list) else [fn.body]
        for stmt in body:
            v.visit(stmt)
        findings.extend(v.findings)
    # stable order, dedup (a def reachable through two roots reports once)
    uniq = {}
    for f in findings:
        uniq[(f.rule, f.loc, f.message)] = f
    return sorted(uniq.values(), key=lambda f: (f.loc, f.rule))


def lint_paths(paths: Sequence[str],
               rules: Sequence[str] = RULES) -> List[Finding]:
    findings: List[Finding] = []
    for p in astutil.iter_py_files(paths):
        try:
            src, tree = astutil.load_file(p)
        except SyntaxError as e:
            findings.append(Finding("syntax-error", f"{p}:{e.lineno or 0}",
                                    str(e.msg), "lint"))
            continue
        findings.extend(lint_source(src, p, rules, tree=tree))
    return findings
