"""Analyzer + logical planner: AST → typed QueryPlan.

Covers the roles of the reference's sql/analyzer (Analyzer.java:69,
StatementAnalyzer.java:217, ExpressionAnalyzer) and sql/planner
(LogicalPlanner.java:173, QueryPlanner, RelationPlanner, SubqueryPlanner) in
one pass, sized to the executed SQL surface:

- scopes resolve (qualifier, column) → unique plan symbols
- expressions lower to the typed IR with implicit coercions and exact
  decimal scale/precision rules (add/sub align scales via casts; mul adds
  scales; div is exact with Presto's result scale and HALF_UP rounding —
  expr/compile._decimal_div)
- aggregates are extracted and planned as pre-Project → Aggregate →
  post-Project (the reference's QueryPlanner.aggregate path)
- comma-FROM + WHERE equi-conjuncts become a greedy size-heuristic join
  tree (stand-in for ReorderJoins.java:94 + DetermineJoinDistributionType);
  explicit JOIN ... ON trees are kept as written
- IN (subquery) → SemiJoin; uncorrelated scalar subqueries → Param bound
  by pre-executing the subplan
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from presto_tpu.connector import Catalog, TableHandle
from presto_tpu.expr.compile import days_from_civil
from presto_tpu.expr.ir import Call, Constant, InputRef, RowExpression, expr_inputs
from presto_tpu.plan.nodes import (
    Aggregate,
    AggSpec,
    Filter,
    HashJoin,
    Limit,
    NestedLoopJoin,
    OneRow,
    Output,
    PlanNode,
    Project,
    QueryPlan,
    SemiJoin,
    SetOp,
    Sort,
    SortItem,
    TableScan,
    Unnest,
)
from presto_tpu.sql import ast
from presto_tpu.types import (
    BIGINT,
    BOOLEAN,
    DATE,
    DOUBLE,
    ArrayType,
    DecimalType,
    GEOMETRY,
    INTEGER,
    IPADDRESS,
    IPPREFIX,
    IpAddressType,
    IpPrefixType,
    MapType,
    TDIGEST,
    TIME,
    TIMESTAMP,
    Type,
    VARBINARY,
    VARCHAR,
    common_super_type,
    is_floating,
    is_integral,
    is_numeric,
    parse_type,
)


class AnalysisError(Exception):
    pass


def _fold_string_call(e):
    """Constant-fold dictionary-transform string functions whose operand
    and arguments are all plan-time constants (to_hex(<literal bytes>),
    upper('x'), …). Without this, such calls reach the compiler with no
    dictionary to transform (reference: these fold in the interpreter,
    ExpressionInterpreter.java)."""
    if not isinstance(e, Call) or not e.args:
        return e
    if not all(isinstance(a, Constant) for a in e.args):
        return e
    from presto_tpu.expr.compile import (
        _STR_INT_NULLABLE,
        _STR_PRED,
        _STR_TO_INT,
        _STR_TO_STR,
        _str_int_pyfn,
        _str_pred_pyfn,
        _str_xform_pyfn,
        _xform_parts,
    )

    fn = e.fn
    if fn not in _STR_TO_STR and fn not in _STR_TO_INT and fn not in _STR_PRED:
        return e
    try:
        operand, cargs = _xform_parts(e)
    except NotImplementedError:
        # all-constant concat never reaches here (folded at analysis);
        # other shapes _xform_parts can't split stay runtime calls
        return e
    value = operand.value
    if value is None:
        return Constant(e.type, None)
    if isinstance(value, (bytes, bytearray)):
        value = value.decode("latin-1")
    try:
        if fn in _STR_TO_STR:
            out = _str_xform_pyfn(fn, cargs)(str(value))
        elif fn in _STR_TO_INT:
            out = _str_int_pyfn(fn, cargs)(str(value))
            if out is not None and fn not in _STR_INT_NULLABLE:
                out = int(out)
        else:
            out = bool(_str_pred_pyfn(fn, cargs)(str(value)))
    except Exception:
        return e  # leave malformed folds to runtime NULL semantics
    return Constant(e.type, out)


# ---------------------------------------------------------------------------
# symbols & scopes


class SymbolAllocator:
    """Also the per-query shared scratch: nested Planners receive the same
    allocator, so query-scoped state (the fixed start instant for niladic
    datetime functions, the plan-volatility flag) lives here."""

    def __init__(self):
        self.used = set()
        self.query_start_s: Optional[float] = None
        self.volatile_plan = False

    def query_start(self) -> float:
        """One instant per query (Session.getStartTime): first call fixes
        it; every niladic datetime function reads the same value. Using
        it makes the plan non-cacheable."""
        if self.query_start_s is None:
            import time as _time

            self.query_start_s = _time.time()
        self.volatile_plan = True
        return self.query_start_s

    def fresh(self, hint: str) -> str:
        base = hint or "expr"
        if base not in self.used:
            self.used.add(base)
            return base
        i = 1
        while f"{base}#{i}" in self.used:
            i += 1
        name = f"{base}#{i}"
        self.used.add(name)
        return name


@dataclasses.dataclass
class Field:
    qualifier: Optional[str]
    name: str
    symbol: str
    type: Type


class Scope:
    def __init__(self, fields: List[Field]):
        self.fields = fields

    def resolve(self, parts: Tuple[str, ...]) -> Field:
        if len(parts) == 1:
            matches = [f for f in self.fields if f.name == parts[0]]
        else:
            q, n = parts[-2], parts[-1]
            matches = [f for f in self.fields if f.qualifier == q and f.name == n]
        if not matches and len(parts) > 1:
            # ROW field access over flattened struct leaves: `r.f` (and
            # `t.r.f`) resolve against the dotted column name "r.f"
            for k in range(len(parts), 1, -1):
                dotted = ".".join(parts[-k:])
                q = parts[-k - 1] if len(parts) > k else None
                matches = [
                    f for f in self.fields
                    if f.name == dotted and (q is None or f.qualifier == q)
                ]
                if matches:
                    break
        if not matches:
            raise AnalysisError(f"column not found: {'.'.join(parts)}")
        symbols = {m.symbol for m in matches}
        if len(symbols) > 1:
            raise AnalysisError(f"ambiguous column: {'.'.join(parts)}")
        return matches[0]

    def __add__(self, other: "Scope") -> "Scope":
        return Scope(self.fields + other.fields)


class LambdaScope(Scope):
    """Lambda parameters SHADOW same-named outer columns (SQL lambda
    scoping) — unlike Scope concatenation, which treats duplicate names
    as ambiguous."""

    def __init__(self, params: List[Field], outer: Scope):
        super().__init__(params + outer.fields)
        self._params = params
        self._outer = outer

    def resolve(self, parts: Tuple[str, ...]) -> Field:
        if len(parts) == 1:
            for f in self._params:
                if f.name == parts[0]:
                    return f
        return self._outer.resolve(parts)


@dataclasses.dataclass
class RelationPlan:
    node: PlanNode
    scope: Scope
    # estimated rows (connector stats; for join ordering heuristic)
    rows: float = 1e6


def ast_key(node) -> str:
    """Canonical structural key for AST expressions (for GROUP BY matching
    and duplicate-aggregate elimination)."""
    if isinstance(node, ast.Identifier):
        return "id:" + ".".join(node.parts)
    if isinstance(node, ast.Literal):
        return f"lit:{node.kind}:{node.value!r}"
    if isinstance(node, ast.IntervalLiteral):
        return f"interval:{node.value}:{node.unit}"
    if isinstance(node, ast.UnaryOp):
        return f"u{node.op}({ast_key(node.operand)})"
    if isinstance(node, ast.BinaryOp):
        return f"({ast_key(node.left)}){node.op}({ast_key(node.right)})"
    if isinstance(node, ast.Between):
        return f"between{node.negated}({ast_key(node.value)},{ast_key(node.low)},{ast_key(node.high)})"
    if isinstance(node, ast.InList):
        return f"in{node.negated}({ast_key(node.value)};{','.join(ast_key(i) for i in node.items)})"
    if isinstance(node, ast.Like):
        return f"like{node.negated}({ast_key(node.value)},{ast_key(node.pattern)})"
    if isinstance(node, ast.IsNull):
        return f"isnull{node.negated}({ast_key(node.value)})"
    if isinstance(node, ast.FunctionCall):
        star = "*" if node.is_star else ""
        return f"fn:{node.name}{'D' if node.distinct else ''}({star}{','.join(ast_key(a) for a in node.args)})"
    if isinstance(node, ast.Cast):
        return f"cast({ast_key(node.value)} as {node.type_name})"
    if isinstance(node, ast.Case):
        op = ast_key(node.operand) if node.operand else ""
        whens = ";".join(f"{ast_key(c)}->{ast_key(v)}" for c, v in node.whens)
        dflt = ast_key(node.default) if node.default else ""
        return f"case({op};{whens};{dflt})"
    if isinstance(node, ast.Extract):
        return f"extract:{node.field}({ast_key(node.value)})"
    if isinstance(node, ast.WindowFunction):
        args = ",".join(ast_key(a) for a in node.args)
        part = ",".join(ast_key(p) for p in node.partition_by)
        order = ",".join(
            f"{ast_key(o.expr)}:{o.ascending}:{o.nulls_first}" for o in node.order_by
        )
        return f"win:{node.name}({'*' if node.is_star else args};{part};{order};{node.frame})"
    return f"?{id(node)}"


_AGG_FUNCS = {
    "sum", "avg", "count", "min", "max",
    # statistics (reference: operator/aggregation/Variance*, Covariance*,
    # CorrelationAggregation, GeometricMeanAggregations)
    "stddev", "stddev_pop", "stddev_samp", "variance", "var_pop", "var_samp",
    "covar_pop", "covar_samp", "corr", "geometric_mean",
    # boolean / misc (BooleanAndAggregation, ArbitraryAggregationFunction,
    # ChecksumAggregationFunction, CountIfAggregation)
    "bool_and", "bool_or", "every", "arbitrary", "any_value", "checksum",
    "count_if",
    # approx family (ApproximateCountDistinct / ApproximateLongPercentile —
    # here computed exactly, which satisfies the approximation contract)
    "approx_distinct", "approx_percentile", "numeric_histogram",
    # sketches as values (TDigestAggregationFunction,
    # ApproximateSetAggregation, MergeAggregation)
    "tdigest_agg", "merge", "approx_set",
    # argmax family (AbstractMinMaxBy)
    "max_by", "min_by",
    # structural (ArrayAggregationFunction / MapAggregation — materialized
    # single-task here)
    "array_agg", "map_agg",
}

# aliases → canonical names
_AGG_CANON = {"every": "bool_and", "any_value": "arbitrary",
              "stddev": "stddev_samp", "variance": "var_samp"}

_TWO_ARG_AGGS = {"covar_pop", "covar_samp", "corr", "max_by", "min_by",
                 "map_agg"}


def _is_agg_fn(name: str) -> bool:
    """Built-in aggregates plus registry-registered ones
    (FunctionManager.resolveFunction consults registered namespaces)."""
    if name in _AGG_FUNCS:
        return True
    from presto_tpu.functions import registry

    return registry().aggregate(name) is not None


# ---------------------------------------------------------------------------
# expression analysis (AST → typed IR)


class ExprAnalyzer:
    def __init__(self, scope: Scope, planner: "Planner",
                 replacements: Optional[Dict[str, Tuple[str, Type]]] = None):
        self.scope = scope
        self.planner = planner
        self.replacements = replacements or {}

    def analyze(self, node) -> RowExpression:
        k = ast_key(node)
        if k in self.replacements:
            sym, t = self.replacements[k]
            return InputRef(t, sym)
        m = getattr(self, f"_an_{type(node).__name__}", None)
        if m is None:
            raise AnalysisError(f"unsupported expression: {type(node).__name__}")
        return _fold_string_call(m(node))

    # -- leaves -----------------------------------------------------------

    def _an_Identifier(self, node: ast.Identifier) -> RowExpression:
        f = self.scope.resolve(node.parts)
        return InputRef(f.type, f.symbol)

    def _an_Literal(self, node: ast.Literal) -> RowExpression:
        if node.kind == "null":
            return Constant(BIGINT, None)
        if node.kind == "integer":
            return Constant(BIGINT, int(node.value))
        if node.kind == "double":
            return Constant(DOUBLE, float(node.value))
        if node.kind == "decimal":
            txt = node.text
            frac = len(txt.split(".")[1]) if "." in txt else 0
            digits = len(txt.replace(".", "").lstrip("0")) or 1
            return Constant(DecimalType(min(18, max(digits, frac)), frac), float(node.value))
        if node.kind == "string":
            return Constant(VARCHAR, str(node.value))
        if node.kind == "boolean":
            return Constant(BOOLEAN, bool(node.value))
        if node.kind == "date":
            y, m, d = map(int, str(node.value).split("-"))
            return Constant(DATE, days_from_civil(y, m, d))
        if node.kind == "time":
            hms, _, frac = str(node.value).partition(".")
            parts = list(map(int, hms.split(":")))
            while len(parts) < 3:
                parts.append(0)
            hh, mm, ss = parts[:3]
            micros = (hh * 3600 + mm * 60 + ss) * 1_000_000
            if frac:
                micros += int(frac[:6].ljust(6, "0"))
            return Constant(TIME, micros, raw=True)
        if node.kind == "timestamp":
            s = str(node.value)
            datepart, _, timepart = s.partition(" ")
            y, m, d = map(int, datepart.split("-"))
            micros = days_from_civil(y, m, d) * 86_400_000_000
            if timepart:
                hms, _, frac = timepart.partition(".")
                parts = list(map(int, hms.split(":")))
                while len(parts) < 3:
                    parts.append(0)
                hh, mm, ss = parts[:3]
                micros += (hh * 3600 + mm * 60 + ss) * 1_000_000
                if frac:
                    micros += int(frac[:6].ljust(6, "0"))
            return Constant(TIMESTAMP, micros, raw=True)
        raise AnalysisError(f"bad literal {node!r}")

    # -- operators --------------------------------------------------------

    def _an_UnaryOp(self, node: ast.UnaryOp) -> RowExpression:
        v = self.analyze(node.operand)
        if node.op == "not":
            return Call(BOOLEAN, "not", (v,))
        if node.op == "-":
            if isinstance(v, Constant) and v.value is not None:
                return Constant(v.type, -v.value)
            return Call(v.type, "neg", (v,))
        return v

    def _an_BinaryOp(self, node: ast.BinaryOp) -> RowExpression:
        op = node.op
        if op in ("and", "or"):
            l = self.analyze(node.left)
            r = self.analyze(node.right)
            return Call(BOOLEAN, op, (l, r))
        if op in ("eq", "ne", "lt", "le", "gt", "ge"):
            l = self.analyze(node.left)
            r = self.analyze(node.right)
            if isinstance(l.type, (ArrayType, MapType)) or isinstance(
                    r.type, (ArrayType, MapType)):
                raise AnalysisError(
                    "comparisons on ARRAY/MAP values are not supported")
            l, r = self._align_comparable(l, r)
            return Call(BOOLEAN, op, (l, r))
        if op in ("add", "sub", "mul", "div", "mod"):
            return self._arith(op, node.left, node.right)
        if op == "concat":
            l = self.analyze(node.left)
            r = self.analyze(node.right)
            if isinstance(l.type, ArrayType):
                return self._an_structural_fn("concat", (l, r))
            # flatten nested concat so a || b || c becomes one call, and fold
            # all-constant concat to a literal
            args = []
            for a in (l, r):
                if isinstance(a, Call) and a.fn == "concat":
                    args.extend(a.args)
                else:
                    args.append(a)
            if all(isinstance(a, Constant) for a in args):
                if any(a.value is None for a in args):
                    return Constant(VARCHAR, None)  # NULL poisons concat
                return Constant(VARCHAR, "".join(str(a.value) for a in args))
            return Call(VARCHAR, "concat", tuple(args))
        raise AnalysisError(f"unknown operator {op}")

    def _align_comparable(self, l: RowExpression, r: RowExpression):
        ip_types = (IpAddressType, IpPrefixType)
        if (isinstance(l.type, ip_types) or isinstance(r.type, ip_types)) \
                and l.type != r.type:
            # '10.0.0.1' = ip_col: fold the text constant to the canonical
            # entry so it resolves against the ip dictionary. Anything
            # else (ipaddress vs ipprefix, ip vs varchar column) is a
            # type error — byte-comparing 16- against 17-byte entries
            # would be silently always-false
            tgt = l.type if isinstance(l.type, ip_types) else r.type
            if isinstance(l, Constant) and l.type is VARCHAR:
                l = self._ip_cast(l, tgt)
            elif isinstance(r, Constant) and r.type is VARCHAR:
                r = self._ip_cast(r, tgt)
            else:
                raise AnalysisError(
                    f"cannot compare {l.type} with {r.type}")
            return l, r
        if l.type.is_string or r.type.is_string:
            return l, r
        if isinstance(l.type, DecimalType) or isinstance(r.type, DecimalType):
            if is_floating(l.type) or is_floating(r.type):
                return self._to_double(l), self._to_double(r)
            ls = l.type.scale if isinstance(l.type, DecimalType) else 0
            rs = r.type.scale if isinstance(r.type, DecimalType) else 0
            s = max(ls, rs)
            return self._rescale(l, s), self._rescale(r, s)
        return l, r

    def _rescale(self, e: RowExpression, scale: int) -> RowExpression:
        if isinstance(e.type, DecimalType):
            if e.type.scale == scale:
                return e
            t = DecimalType(min(18, e.type.precision + scale - e.type.scale), scale)
            if isinstance(e, Constant) and e.value is not None:
                return Constant(t, e.value)
            return Call(t, "cast", (e,))
        if is_integral(e.type):
            t = DecimalType(18, scale)
            if isinstance(e, Constant) and e.value is not None:
                return Constant(t, e.value)
            return Call(t, "cast", (e,))
        raise AnalysisError(f"cannot rescale {e.type}")

    def _to_double(self, e: RowExpression) -> RowExpression:
        if e.type is DOUBLE:
            return e
        if isinstance(e, Constant) and e.value is not None:
            return Constant(DOUBLE, float(e.value))
        return Call(DOUBLE, "cast", (e,))

    def _arith(self, op: str, last, rast) -> RowExpression:
        # date ± interval
        if isinstance(rast, ast.IntervalLiteral):
            l = self.analyze(last)
            days = rast.value if rast.unit == "day" else None
            if l.type is not DATE:
                raise AnalysisError("interval arithmetic requires a date")
            sign = 1 if op == "add" else -1
            if days is not None:
                if isinstance(l, Constant):
                    return Constant(DATE, l.value + sign * days)
                return Call(DATE, "date_add_days", (l, Constant(INTEGER, sign * days)))
            # month/year intervals: constant-fold only (TPC-H uses literals)
            if isinstance(l, Constant):
                return Constant(DATE, _add_months_days(l.value, sign * rast.value * (12 if rast.unit == "year" else 1)))
            raise AnalysisError("month/year interval on non-constant date")
        l = self.analyze(last)
        r = self.analyze(rast)
        ldec, rdec = isinstance(l.type, DecimalType), isinstance(r.type, DecimalType)
        if l.type is DATE and is_integral(r.type) and op in ("add", "sub"):
            return Call(DATE, "date_add_days", (l, Call(INTEGER, "neg", (r,)) if op == "sub" else r))
        if is_floating(l.type) or is_floating(r.type):
            return Call(DOUBLE, op, (self._to_double(l), self._to_double(r)))
        if ldec or rdec:
            if op in ("add", "sub"):
                s = max(l.type.scale if ldec else 0, r.type.scale if rdec else 0)
                l2, r2 = self._rescale(l, s), self._rescale(r, s)
                return Call(DecimalType(18, s), op, (l2, r2))
            if op == "mul":
                ls = l.type.scale if ldec else 0
                rs = r.type.scale if rdec else 0
                if not ldec:
                    l = self._rescale(l, 0)
                if not rdec:
                    r = self._rescale(r, 0)
                return Call(DecimalType(18, ls + rs), "mul", (l, r))
            if op == "div":
                # Presto DecimalOperators.divideOperator typing: scale =
                # max(s1, s2), precision = p1 - s1 + s2 + scale, ROUND HALF
                # AWAY on the dropped digits. Deviation: result precision
                # caps at 18 (short decimal) — quotients needing 19+ digits
                # fall outside the int64 lane (compile._decimal_div).
                ls = l.type.scale if ldec else 0
                rs = r.type.scale if rdec else 0
                lp = l.type.precision if ldec else 18
                if not ldec:
                    l = self._rescale(l, 0)
                if not rdec:
                    r = self._rescale(r, 0)
                s = max(ls, rs)
                p = max(min(lp - ls + rs + s, 18), 1)
                return Call(DecimalType(p, s), "div", (l, r))
            if op == "mod":
                s = max(l.type.scale if ldec else 0, r.type.scale if rdec else 0)
                return Call(DecimalType(18, s), "mod", (self._rescale(l, s), self._rescale(r, s)))
        t = common_super_type(l.type, r.type)
        return Call(t, op, (l, r))

    # -- predicates -------------------------------------------------------

    def _an_Between(self, node: ast.Between) -> RowExpression:
        v = self.analyze(node.value)
        lo = self.analyze(node.low)
        hi = self.analyze(node.high)
        v1, lo = self._align_comparable(v, lo)
        v2, hi = self._align_comparable(v, hi)
        ge = Call(BOOLEAN, "ge", (v1, lo))
        le = Call(BOOLEAN, "le", (v2, hi))
        e = Call(BOOLEAN, "and", (ge, le))
        return Call(BOOLEAN, "not", (e,)) if node.negated else e

    def _an_InList(self, node: ast.InList) -> RowExpression:
        v = self.analyze(node.value)
        items = []
        for it in node.items:
            c = self.analyze(it)
            if not isinstance(c, Constant):
                raise AnalysisError("IN list items must be literals")
            if not v.type.is_string:
                _, c = self._align_comparable(v, c)
            items.append(c)
        e = Call(BOOLEAN, "in", tuple([v] + items))
        return Call(BOOLEAN, "not", (e,)) if node.negated else e

    def _an_Like(self, node: ast.Like) -> RowExpression:
        v = self.analyze(node.value)
        p = self.analyze(node.pattern)
        if not isinstance(p, Constant):
            raise AnalysisError("LIKE pattern must be a literal")
        args = [v, p]
        if node.escape is not None:
            esc = self.analyze(node.escape)
            if not isinstance(esc, Constant):
                raise AnalysisError("LIKE escape must be a literal")
            args.append(esc)
        e = Call(BOOLEAN, "like", tuple(args))
        return Call(BOOLEAN, "not", (e,)) if node.negated else e

    def _an_IsNull(self, node: ast.IsNull) -> RowExpression:
        v = self.analyze(node.value)
        return Call(BOOLEAN, "is_not_null" if node.negated else "is_null", (v,))

    def _an_Case(self, node: ast.Case) -> RowExpression:
        whens = []
        for cond, val in node.whens:
            if node.operand is not None:
                c = self._an_BinaryOp(ast.BinaryOp("eq", node.operand, cond))
            else:
                c = self.analyze(cond)
            whens.append((c, self.analyze(val)))
        default = self.analyze(node.default) if node.default else None
        # result type: common super type of branches
        branch_types = [v.type for _, v in whens] + ([default.type] if default else [])
        t = branch_types[0]
        for bt in branch_types[1:]:
            t = common_super_type(t, bt)
        # align branch scales for decimals
        def coerce(e):
            if isinstance(t, DecimalType):
                return self._rescale(e, t.scale)
            if t is DOUBLE and e.type is not DOUBLE:
                return self._to_double(e)
            return e
        out = coerce(default) if default else Constant(t, None)
        for c, v in reversed(whens):
            out = Call(t, "if", (c, coerce(v), out))
        return out

    def _an_Cast(self, node: ast.Cast) -> RowExpression:
        t = parse_type(node.type_name)
        if t is GEOMETRY:
            raise AnalysisError(
                "cannot cast to GEOMETRY — use ST_GeometryFromText")
        v = self.analyze(node.value)
        ip_types = (IpAddressType, IpPrefixType)
        if isinstance(t, ip_types) or isinstance(v.type, ip_types):
            return self._ip_cast(v, t)
        if (isinstance(v, Constant) and v.type.is_string
                and not t.is_string and not isinstance(t, (ArrayType,
                                                           MapType))):
            # constant text → value folds at plan time (there is no
            # dictionary to LUT over); unparseable folds to NULL, the
            # engine's documented row-level-cast deviation
            if v.value is None:
                return Constant(t, None)
            from presto_tpu.expr.compile import parse_string_to

            return Constant(t, parse_string_to(t, str(v.value)))
        return Call(t, "cast", (v,))

    def _ip_cast(self, v: RowExpression, t: Type) -> RowExpression:
        """IPADDRESS/IPPREFIX casts are dictionary transforms between
        canonical-byte entries and text/bytes (expr/ip.py; reference
        IpAddressOperators.java / IpPrefixOperators.java). Routed here so
        the generic cast path never passes codes through un-re-encoded."""
        if v.type == t:
            return v
        fn = {
            ("varchar", "ipaddress"): "__to_ipaddress",
            ("varbinary", "ipaddress"): "__vb_to_ipaddress",
            ("ipaddress", "varchar"): "__ip_to_varchar",
            ("ipaddress", "varbinary"): "__ip_to_bytes",
            ("ipaddress", "ipprefix"): "__addr_to_ipprefix",
            ("varchar", "ipprefix"): "__to_ipprefix",
            ("ipprefix", "varchar"): "__ipprefix_to_varchar",
            ("ipprefix", "ipaddress"): "__ipprefix_to_addr",
        }.get((v.type.name, t.name))
        if fn is None:
            raise AnalysisError(f"cannot cast {v.type} to {t}")
        if isinstance(v, Constant):
            if v.value is None:
                return Constant(t, None)
            from presto_tpu.expr.compile import _str_xform_pyfn

            raw = (v.value.decode("latin-1")
                   if isinstance(v.value, (bytes, bytearray))
                   else str(v.value))
            out = _str_xform_pyfn(fn, ())(raw)
            if out is None:
                raise AnalysisError(f"invalid {t.name}: {v.value!r}")
            return Constant(t, out)
        return Call(t, fn, (v,))

    def _an_ip_fn(self, name: str, args) -> RowExpression:
        """IP function family (reference operator/scalar/
        IpPrefixFunctions.java). Operands ride dictionary transforms, so
        every non-operand argument must be a plan-time constant."""
        from presto_tpu.expr import ip as _ip

        def coerce(a, want_prefix=False):
            # bare text constants are a convenience the reference gets via
            # implicit varchar→ipaddress coercion
            if isinstance(a, Constant) and a.type is VARCHAR and a.value is not None:
                t = IPPREFIX if (want_prefix or "/" in str(a.value)) else IPADDRESS
                return self._ip_cast(a, t)
            return a

        if name == "ip_prefix":
            if len(args) != 2:
                raise AnalysisError("ip_prefix(ip, prefix_bits) takes 2 arguments")
            a, bits = args
            if not (isinstance(bits, Constant) and is_integral(bits.type)):
                raise AnalysisError(
                    "ip_prefix: prefix length must be a constant integer")
            if a.type.name not in ("ipaddress", "varchar"):
                raise AnalysisError(f"ip_prefix expects ipaddress, got {a.type}")
            if isinstance(a, Constant):
                if a.value is None or bits.value is None:
                    return Constant(IPPREFIX, None)
                a = coerce(a)
                out = _ip.ip_prefix(str(a.value), int(bits.value))
                if out is None:
                    raise AnalysisError(
                        f"ip_prefix: invalid prefix length {bits.value}")
                return Constant(IPPREFIX, out)
            if a.type is VARCHAR:
                # parse text explicitly — ip_prefix itself takes canonical
                # entries only (a 16-char address TEXT is not 16 bytes)
                a = Call(IPADDRESS, "__to_ipaddress", (a,))
            return Call(IPPREFIX, "ip_prefix", (a, bits))
        if name in ("ip_subnet_min", "ip_subnet_max", "ip_subnet_range"):
            if len(args) != 1:
                raise AnalysisError(f"{name}(prefix) takes 1 argument")
            p = coerce(args[0], want_prefix=True)
            if not isinstance(p.type, IpPrefixType):
                raise AnalysisError(f"{name} expects ipprefix, got {p.type}")
            if name == "ip_subnet_range":
                mn = self._an_ip_fn("ip_subnet_min", (p,))
                mx = self._an_ip_fn("ip_subnet_max", (p,))
                return self._an_structural_fn("array_ctor", (mn, mx))
            if isinstance(p, Constant):
                if p.value is None:
                    return Constant(IPADDRESS, None)
                fn = _ip.subnet_min if name == "ip_subnet_min" else _ip.subnet_max
                return Constant(IPADDRESS, fn(str(p.value)))
            return Call(IPADDRESS, name, (p,))
        # is_subnet_of(prefix, address-or-prefix)
        if len(args) != 2:
            raise AnalysisError("is_subnet_of(prefix, ip) takes 2 arguments")
        p, x = coerce(args[0], want_prefix=True), coerce(args[1])
        if not isinstance(p.type, IpPrefixType):
            raise AnalysisError(f"is_subnet_of expects ipprefix, got {p.type}")
        if not isinstance(x.type, (IpAddressType, IpPrefixType)):
            raise AnalysisError(
                f"is_subnet_of expects ipaddress or ipprefix, got {x.type}")
        if isinstance(p, Constant) and isinstance(x, Constant):
            if p.value is None or x.value is None:
                return Constant(BOOLEAN, None)
            return Constant(BOOLEAN,
                            _ip.is_subnet_of(str(p.value), str(x.value)))
        if isinstance(p, Constant):
            if p.value is None:
                return Constant(BOOLEAN, None)
            return Call(BOOLEAN, "__is_subnet_of_c",
                        (x, Constant(VARCHAR, str(p.value))))
        if isinstance(x, Constant):
            if x.value is None:
                return Constant(BOOLEAN, None)
            return Call(BOOLEAN, "__prefix_contains_c",
                        (p, Constant(VARCHAR, str(x.value))))
        raise AnalysisError(
            "is_subnet_of needs a constant prefix or a constant operand "
            "(two-column containment would need a cross-dictionary product)")

    def _an_tdigest_fn(self, name: str, args) -> RowExpression:
        """TDIGEST scalar family (reference operator/scalar/
        TDigestFunctions.java). Digests are dictionary entries, so these
        evaluate once per distinct digest; the non-digest arguments must
        be plan-time constants."""
        if not args or args[0].type.name != "tdigest(double)":
            got = args[0].type if args else "no arguments"
            raise AnalysisError(f"{name} expects a tdigest, got {got}")
        td = args[0]

        def const_num(a, what):
            if not isinstance(a, Constant) or not is_numeric(a.type):
                raise AnalysisError(f"{name}: {what} must be a numeric constant")
            if a.value is None:
                raise AnalysisError(f"{name}: {what} must not be NULL")
            return float(a.value)

        if name == "value_at_quantile":
            if len(args) != 2:
                raise AnalysisError("value_at_quantile(tdigest, q)")
            q = const_num(args[1], "quantile")
            if not 0.0 <= q <= 1.0:
                raise AnalysisError("quantile must be in [0, 1]")
            return Call(DOUBLE, "value_at_quantile",
                        (td, Constant(DOUBLE, q)))
        if name == "values_at_quantiles":
            if len(args) != 2:
                raise AnalysisError("values_at_quantiles(tdigest, qs)")
            arr = args[1]
            if not (isinstance(arr, Call) and arr.fn == "array_ctor"
                    and all(isinstance(x, Constant)
                            and x.value is not None for x in arr.args)):
                raise AnalysisError(
                    "values_at_quantiles requires a constant array of "
                    "non-null quantiles")
            calls = tuple(
                self._an_tdigest_fn("value_at_quantile",
                                    (td, Constant(DOUBLE, float(x.value))))
                for x in arr.args)
            return self._an_structural_fn("array_ctor", calls)
        if name == "quantile_at_value":
            if len(args) != 2:
                raise AnalysisError("quantile_at_value(tdigest, x)")
            v = const_num(args[1], "value")
            return Call(DOUBLE, "quantile_at_value",
                        (td, Constant(DOUBLE, v)))
        if name == "trimmed_mean":
            if len(args) != 3:
                raise AnalysisError("trimmed_mean(tdigest, lo, hi)")
            lo = const_num(args[1], "low quantile")
            hi = const_num(args[2], "high quantile")
            if not 0.0 <= lo <= hi <= 1.0:
                raise AnalysisError("quantile bounds must satisfy 0<=lo<=hi<=1")
            return Call(DOUBLE, "trimmed_mean",
                        (td, Constant(DOUBLE, lo), Constant(DOUBLE, hi)))
        # scale_tdigest
        if len(args) != 2:
            raise AnalysisError("scale_tdigest(tdigest, factor)")
        f = const_num(args[1], "scale factor")
        if f <= 0:
            raise AnalysisError("scale factor must be positive")
        return Call(TDIGEST, "scale_tdigest", (td, Constant(DOUBLE, f)))

    def _an_Extract(self, node: ast.Extract) -> RowExpression:
        v = self.analyze(node.value)
        if node.field in ("hour", "minute", "second"):
            if v.type not in (TIME, TIMESTAMP):
                raise AnalysisError(
                    f"extract({node.field}) expects time or timestamp, "
                    f"got {v.type}")
            # TIME is micros-of-day; TIMESTAMP micros-since-epoch — the
            # mod-day lowering serves both
            return Call(BIGINT, "__time_" + node.field, (v,))
        if node.field not in ("year", "month", "day"):
            raise AnalysisError(f"extract({node.field}) unsupported")
        return Call(BIGINT, node.field, (v,))

    def _an_FunctionCall(self, node: ast.FunctionCall) -> RowExpression:
        name = node.name.lower()
        if _is_agg_fn(name):
            raise AnalysisError(f"aggregate {name}() not allowed here")
        if name in ("transform", "filter", "reduce", "any_match",
                    "all_match", "none_match", "transform_values",
                    "map_filter", "zip_with"):
            return self._an_higher_order(name, node)
        args = tuple(self.analyze(a) for a in node.args)
        structural = self._an_structural_fn(name, args)
        if structural is not None:
            return structural
        geo = self._an_geo_fn(name, args)
        if geo is not None:
            return geo
        if name == "abs":
            return Call(args[0].type, "abs", args)
        if name in ("sqrt", "exp", "ln", "power", "pow"):
            return Call(DOUBLE, {"pow": "power"}.get(name, name),
                        tuple(self._to_double(a) for a in args))
        if name in ("floor", "ceil", "ceiling"):
            return Call(args[0].type if not is_floating(args[0].type) else DOUBLE,
                        {"ceiling": "ceil"}.get(name, name), args)
        if name == "round":
            return Call(args[0].type, "round", args)
        if name == "try":
            # try(expr): the reference converts row-level errors to NULL;
            # this engine's device computations never raise and its host
            # transforms (string casts etc.) already yield NULL on bad
            # input — try() is the identity, kept for compatibility
            if len(args) != 1:
                raise AnalysisError("try() takes one argument")
            return args[0]
        if name == "coalesce":
            t = args[0].type
            for a in args[1:]:
                t = common_super_type(t, a.type)
            return Call(t, "coalesce", args)
        if name == "nullif":
            return Call(args[0].type, "nullif", args)
        if name in ("year", "month", "day", "quarter", "day_of_week", "dow",
                    "day_of_year", "doy"):
            canon = {"dow": "day_of_week", "doy": "day_of_year"}.get(name, name)
            return Call(BIGINT, canon, args)
        # string functions (dictionary transforms / luts — expr/compile.py)
        if name in ("substr", "substring"):
            return Call(VARCHAR, "substr", args)
        if (name in ("md5", "sha1", "sha256", "sha512", "to_base64")
                and args and args[0].type.name == "varbinary"):
            # VarbinaryFunctions.java: digests of BYTES return varbinary
            # (to_base64 returns varchar); the varchar overloads below
            # hash utf-8 text and return hex — a convenience extension
            out_t = VARCHAR if name == "to_base64" else VARBINARY
            return Call(out_t, "__vb_" + name, args)
        if name in ("to_hex", "from_hex", "to_utf8", "from_utf8"):
            want_vb = name in ("to_hex", "from_utf8")
            got_vb = bool(args) and args[0].type.name == "varbinary"
            if want_vb != got_vb:
                # exact signatures (VarbinaryFunctions.java): to_hex /
                # from_utf8 take varbinary; from_hex / to_utf8 take
                # varchar — silently re-encoding would corrupt bytes
                raise AnalysisError(
                    f"{name}() expects "
                    f"{'varbinary' if want_vb else 'varchar'}")
            out_t = VARCHAR if want_vb else VARBINARY
            return Call(out_t, name, args)
        if name in ("ip_prefix", "ip_subnet_min", "ip_subnet_max",
                    "ip_subnet_range", "is_subnet_of"):
            return self._an_ip_fn(name, args)
        if name in ("value_at_quantile", "values_at_quantiles",
                    "quantile_at_value", "trimmed_mean", "scale_tdigest"):
            return self._an_tdigest_fn(name, args)
        if name == "empty_approx_set":
            if args:
                raise AnalysisError("empty_approx_set() takes no arguments")
            from presto_tpu.expr.hll import empty as _hll_empty
            from presto_tpu.types import HYPERLOGLOG

            return Constant(HYPERLOGLOG, _hll_empty())
        if name in ("upper", "lower", "trim", "ltrim", "rtrim", "reverse",
                    "replace", "lpad", "rpad", "split_part",
                    "url_extract_host", "url_extract_path",
                    "url_extract_query", "url_extract_protocol",
                    "url_extract_fragment", "url_encode", "url_decode",
                    "md5", "sha1", "sha256", "sha512", "to_base64",
                    "from_base64", "normalize"):
            return Call(VARCHAR, name, args)
        if name == "concat":
            if all(isinstance(a, Constant) for a in args):
                if any(a.value is None for a in args):
                    return Constant(VARCHAR, None)  # NULL poisons concat
                return Constant(VARCHAR, "".join(str(a.value) for a in args))
            return Call(VARCHAR, "concat", args)
        if name in ("length", "strpos", "position", "codepoint"):
            return Call(BIGINT, {"position": "strpos"}.get(name, name), args)
        if name == "bit_length":
            if len(args) != 1 or not args[0].type.is_string:
                raise AnalysisError("bit_length expects a string argument")
            vb = args[0].type.name == "varbinary"
            return Call(BIGINT, "__vb_bit_length" if vb else "bit_length",
                        args)
        if name == "date_parse":
            # date_parse(string, format) — MySQL format vocabulary
            # (DateTimeFunctions.java); format must be a constant
            if len(args) != 2:
                raise AnalysisError("date_parse(string, format)")
            if not (isinstance(args[1], Constant)
                    and args[1].type.is_string and args[1].value is not None):
                raise AnalysisError("date_parse format must be a constant string")
            from presto_tpu.expr.compile import mysql_format_to_strptime

            try:
                mysql_format_to_strptime(str(args[1].value))
            except ValueError as ex:
                raise AnalysisError(f"date_parse: {ex}")
            return Call(TIMESTAMP, "date_parse", args)
        if name == "date_format":
            # date_format(ts, fmt) → varchar: a HOST finishing projection
            # (unbounded output domain — no dictionary to transform); the
            # planner accepts it in the top-level SELECT list only
            if len(args) != 2:
                raise AnalysisError("date_format(timestamp, format)")
            if args[0].type.name not in ("timestamp", "date"):
                raise AnalysisError(
                    f"date_format expects timestamp or date, got {args[0].type}")
            if not (isinstance(args[1], Constant)
                    and args[1].type.is_string and args[1].value is not None):
                raise AnalysisError("date_format format must be a constant string")
            from presto_tpu.expr.compile import mysql_format_to_strptime

            try:
                mysql_format_to_strptime(str(args[1].value))
            except ValueError as ex:
                raise AnalysisError(f"date_format: {ex}")
            return Call(VARCHAR, "__host_date_format", args)
        if name in ("from_iso8601_date", "from_iso8601_timestamp"):
            if len(args) != 1 or not args[0].type.is_string:
                raise AnalysisError(f"{name} expects a string argument")
            out_t = DATE if name == "from_iso8601_date" else TIMESTAMP
            return Call(out_t, name, args)
        if name in ("split", "regexp_split"):
            # split(s, delim[, limit]) / regexp_split(s, pattern) →
            # array(varchar): per-dictionary-entry expansion applied as a
            # 2D gather (StringFunctions.split / RegexpFunctions)
            if not 2 <= len(args) <= (3 if name == "split" else 2):
                raise AnalysisError(f"{name}: wrong argument count")
            if not args[0].type.is_string:
                raise AnalysisError(f"{name} expects a string argument")
            if not (isinstance(args[1], Constant) and args[1].value not in
                    (None, "")):
                raise AnalysisError(
                    f"{name}: delimiter must be a non-empty constant")
            if len(args) == 3 and not (isinstance(args[2], Constant)
                                       and is_integral(args[2].type)
                                       and (args[2].value or 0) >= 1):
                raise AnalysisError("split: limit must be a positive constant")
            if isinstance(args[0], Constant):
                # constant operand: fold to an array constructor (there is
                # no dictionary to expand at runtime)
                if args[0].value is None:
                    return Constant(ArrayType(VARCHAR), None)
                s = str(args[0].value)
                if name == "split":
                    lim = (int(args[2].value) - 1 if len(args) == 3 else -1)
                    pieces = s.split(str(args[1].value), lim)
                else:
                    from presto_tpu.expr.compile import regexp_split_pieces

                    pieces = regexp_split_pieces(str(args[1].value))(s)
                return self._an_structural_fn(
                    "array_ctor",
                    tuple(Constant(VARCHAR, p) for p in pieces))
            return Call(ArrayType(VARCHAR), name, args)
        if name in ("regexp_like", "starts_with", "ends_with", "contains"):
            return Call(BOOLEAN, name, args)
        # math
        if name in ("sin", "cos", "tan", "asin", "acos", "atan", "sinh",
                    "cosh", "tanh", "log2", "log10", "cbrt", "degrees",
                    "radians", "atan2"):
            return Call(DOUBLE, name, tuple(self._to_double(a) for a in args))
        if name == "log":
            # log(base, x) = ln(x)/ln(base)
            b, x = (self._to_double(a) for a in args)
            return Call(DOUBLE, "div",
                        (Call(DOUBLE, "ln", (x,)), Call(DOUBLE, "ln", (b,))))
        if name == "sign":
            return Call(args[0].type, "sign", args)
        if name == "truncate":
            return Call(DOUBLE, "truncate", (self._to_double(args[0]),))
        if name == "mod":
            return self._arith("mod", node.args[0], node.args[1])
        if name in ("current_date", "current_timestamp", "now"):
            # plan-time constants, ONE instant per query
            # (Session.getStartTime); marks the plan non-cacheable
            now_s = self.planner.symbols.query_start()
            if name == "current_date":
                return Constant(DATE, int(now_s // 86400), raw=True)
            return Constant(TIMESTAMP, int(now_s * 1e6), raw=True)
        if name == "typeof":
            if len(args) != 1:
                raise AnalysisError("typeof() takes one argument")
            return Constant(VARCHAR, str(args[0].type))
        if name == "version":
            if args:
                raise AnalysisError("version() takes no arguments")
            import presto_tpu

            return Constant(VARCHAR, f"presto-tpu {presto_tpu.__version__}")
        if name == "pi":
            return Constant(DOUBLE, 3.141592653589793, raw=True)
        if name in ("e",):
            return Constant(DOUBLE, 2.718281828459045, raw=True)
        if name in ("greatest", "least"):
            t = args[0].type
            for a in args[1:]:
                t = common_super_type(t, a.type)
            if isinstance(t, DecimalType):
                args = tuple(self._rescale(a, t.scale) for a in args)
            elif t is DOUBLE:
                args = tuple(self._to_double(a) for a in args)
            return Call(t, name, args)
        if name == "if":
            return self._an_Case(
                ast.Case(None, [(node.args[0], node.args[1])],
                         node.args[2] if len(node.args) > 2 else None)
            )
        if name in ("bitwise_and", "bitwise_or", "bitwise_xor",
                    "bitwise_left_shift", "bitwise_right_shift",
                    "bitwise_not"):
            return Call(BIGINT, name, args)
        if name in ("is_nan", "is_finite", "is_infinite"):
            return Call(BOOLEAN, name, args)
        if name == "from_unixtime":
            return Call(TIMESTAMP, name, args)
        if name == "to_unixtime":
            return Call(DOUBLE, name, args)
        if name in ("hour", "minute", "second") and args and args[0].type in (
                TIME, TIMESTAMP):
            return Call(BIGINT, "__time_" + name, args)
        if name == "width_bucket":
            return Call(BIGINT, name, args)
        if name in ("regexp_extract", "regexp_replace", "json_extract_scalar",
                    "json_extract", "json_array_get", "json_format",
                    "json_parse"):
            return Call(VARCHAR, name, args)
        if name in ("json_array_length", "json_size"):
            return Call(BIGINT, name, args)
        if name in ("json_array_contains", "is_json_scalar"):
            return Call(BOOLEAN, name, args)
        if name in ("levenshtein_distance", "hamming_distance"):
            # second operand must be a plan-time constant (dictionary lut)
            return Call(BIGINT, name + "_c", (args[0], args[1]))
        # date
        if name == "date_trunc":
            return Call(DATE, "date_trunc", args)
        if name == "date_diff":
            return Call(BIGINT, "date_diff", args)
        if name == "date_add":
            if len(args) == 2:
                return Call(DATE, "date_add_days", (args[1], args[0]))
            return Call(DATE, "date_add_unit", args)
        # registered (plugin/user) scalars — built-ins above take precedence
        # (FunctionManager: global namespace resolves before plugins)
        from presto_tpu.functions import registry as _freg

        udf = _freg().scalar(name)
        if udf is not None:
            if udf.arity is not None and len(args) != udf.arity:
                raise AnalysisError(
                    f"{name}() takes {udf.arity} arguments, got {len(args)}")
            if udf.coerce_double:
                args = tuple(self._to_double(a) for a in args)
            t = udf.result_type([a.type for a in args])
            return Call(t, "udf:" + udf.name, args)
        raise AnalysisError(f"unknown function {name}")

    def _an_lambda(self, lam, param_types) -> "LambdaExpr":
        """Analyze a lambda body with its params bound in a child scope
        (SqlBase.g4 lambda / ExpressionAnalyzer's lambda scoping)."""
        from presto_tpu.expr.ir import LambdaExpr

        if not isinstance(lam, ast.Lambda):
            raise AnalysisError("expected a lambda argument (x -> ...)")
        if len(lam.params) != len(param_types):
            raise AnalysisError(
                f"lambda takes {len(param_types)} parameters, "
                f"got {len(lam.params)}")
        params = []
        fields = []
        for pname, pt in zip(lam.params, param_types):
            sym = self.planner.symbols.fresh(pname)
            params.append((sym, pt))
            fields.append(Field("", pname, sym, pt))
        sub = ExprAnalyzer(LambdaScope(fields, self.scope), self.planner,
                           self.replacements)
        body = sub.analyze(lam.body)
        return LambdaExpr(body.type, tuple(params), body)

    def _an_higher_order(self, name: str, node: ast.FunctionCall):
        """transform/filter/reduce/…_match over arrays: the lambda body
        vectorizes over the flattened element plane at compile time."""
        if len(node.args) < 2:
            raise AnalysisError(f"{name} expects an array and a lambda")
        arr = self.analyze(node.args[0])
        if name == "zip_with":
            if len(node.args) != 3:
                raise AnalysisError(
                    "zip_with(array, array, (x, y) -> ...) expects 3 "
                    "arguments")
            arr2 = self.analyze(node.args[1])
            if not isinstance(arr.type, ArrayType) or not isinstance(
                    arr2.type, ArrayType):
                raise AnalysisError("zip_with requires two ARRAYs")
            le = self._an_lambda(node.args[2],
                                 [arr.type.element, arr2.type.element])
            return Call(ArrayType(le.type), "zip_with", (arr, arr2, le))
        if name in ("transform_values", "map_filter"):
            if not isinstance(arr.type, MapType):
                raise AnalysisError(f"{name} requires MAP, got {arr.type}")
            le = self._an_lambda(node.args[1],
                                 [arr.type.key, arr.type.value])
            if name == "transform_values":
                return Call(MapType(arr.type.key, le.type),
                            "transform_values", (arr, le))
            if le.type is not BOOLEAN:
                raise AnalysisError("map_filter lambda must return boolean")
            return Call(arr.type, "map_filter", (arr, le))
        if not isinstance(arr.type, ArrayType):
            raise AnalysisError(f"{name} requires ARRAY, got {arr.type}")
        et = arr.type.element
        if name == "reduce":
            if len(node.args) != 3:
                raise AnalysisError(
                    "reduce(array, initial, (state, x) -> ...) expects 3 "
                    "arguments")
            init = self.analyze(node.args[1])
            le = self._an_lambda(node.args[2], [init.type, et])
            return Call(le.type, "reduce", (arr, init, le))
        le = self._an_lambda(node.args[1], [et])
        if name == "transform":
            return Call(ArrayType(le.type), "transform", (arr, le))
        if le.type is not BOOLEAN:
            raise AnalysisError(f"{name} lambda must return boolean")
        if name == "filter":
            return Call(arr.type, "filter", (arr, le))
        return Call(BOOLEAN, name, (arr, le))  # any/all/none_match

    _GEO_ALIASES = {
        "st_geometry_from_text": "st_geometryfromtext",
        "st_geomfromtext": "st_geometryfromtext",
        "st_as_text": "st_astext",
    }

    def _an_geo_fn(self, name: str, args) -> Optional[RowExpression]:
        """Geospatial functions (reference: presto-geospatial
        GeoFunctions.java). GEOMETRY values flow only between geo
        functions — ST_AsText is the way out, ST_GeometryFromText /
        ST_Point the ways in."""
        name = self._GEO_ALIASES.get(name, name)

        def need(n, what):
            if len(args) != n:
                raise AnalysisError(f"{what} takes {n} argument(s)")

        def geom(i):
            if args[i].type is not GEOMETRY:
                raise AnalysisError(
                    f"{name} argument {i + 1} must be a GEOMETRY "
                    f"(got {args[i].type})")

        if name == "st_geometryfromtext":
            need(1, name)
            if not args[0].type.is_string:
                raise AnalysisError(
                    "ST_GeometryFromText takes a varchar WKT argument")
            return Call(GEOMETRY, "st_geometryfromtext", args)
        if name == "st_point":
            need(2, name)
            return Call(GEOMETRY, "st_point",
                        tuple(self._to_double(a) for a in args))
        if name == "st_astext":
            need(1, name)
            geom(0)
            inner = args[0]
            if isinstance(inner, Call) and inner.fn == "st_geometryfromtext":
                return inner.args[0]  # text round-trips unchanged
            raise AnalysisError(
                "ST_AsText is supported only on geometries parsed from "
                "text (derived geometries have no stored representation)")
        if name in ("st_x", "st_y", "st_area", "st_perimeter", "st_length",
                    "st_xmin", "st_xmax", "st_ymin", "st_ymax"):
            need(1, name)
            geom(0)
            return Call(DOUBLE, name, args)
        if name == "st_npoints":
            need(1, name)
            geom(0)
            return Call(BIGINT, name, args)
        if name == "st_centroid":
            need(1, name)
            geom(0)
            return Call(GEOMETRY, name, args)
        if name in ("st_contains", "st_intersects", "st_within"):
            need(2, name)
            geom(0)
            geom(1)
            if name == "st_within":  # within(a, b) == contains(b, a)
                return Call(BOOLEAN, "st_contains", (args[1], args[0]))
            return Call(BOOLEAN, name, args)
        if name == "st_distance":
            need(2, name)
            geom(0)
            geom(1)
            return Call(DOUBLE, name, args)
        if name == "great_circle_distance":
            need(4, name)
            return Call(DOUBLE, name,
                        tuple(self._to_double(a) for a in args))
        return None

    def _an_structural_fn(self, name: str, args) -> Optional[RowExpression]:
        """ARRAY/MAP function typing (spi/type/ArrayType + MapType;
        scalar surface of operator/scalar array/map functions). Returns
        None when `name` is not structural (or is a polymorphic name like
        contains/concat applied to non-structural operands)."""
        t0 = args[0].type if args else None

        if name == "array_ctor":
            et = None
            for a in args:
                if isinstance(a, Constant) and a.value is None:
                    continue
                et = a.type if et is None else common_super_type(et, a.type)
            et = et or BIGINT
            coerced = []
            for a in args:
                if isinstance(a, Constant) and a.value is None:
                    coerced.append(Constant(et, None))
                elif isinstance(et, DecimalType):
                    coerced.append(self._rescale(a, et.scale))
                elif et is DOUBLE and a.type is not DOUBLE:
                    coerced.append(self._to_double(a))
                else:
                    coerced.append(a)
            return Call(ArrayType(et), "array_ctor", tuple(coerced))

        if name == "subscript":
            if isinstance(t0, ArrayType):
                return Call(t0.element, "subscript", args)
            if isinstance(t0, MapType):
                return Call(t0.value, "element_at", args)
            raise AnalysisError(f"[] requires ARRAY or MAP, got {t0}")
        if name == "element_at":
            if isinstance(t0, ArrayType):
                return Call(t0.element, "element_at", args)
            if isinstance(t0, MapType):
                return Call(t0.value, "element_at", args)
            raise AnalysisError(f"element_at requires ARRAY or MAP, got {t0}")
        if name == "cardinality":
            if t0.name == "hyperloglog":
                # HyperLogLogFunctions.cardinality: the sketch estimate,
                # evaluated once per distinct sketch entry
                return Call(BIGINT, "__hll_cardinality", args)
            if not isinstance(t0, (ArrayType, MapType)):
                raise AnalysisError(f"cardinality requires ARRAY or MAP, got {t0}")
            return Call(BIGINT, "cardinality", args)
        if name == "contains" and isinstance(t0, ArrayType):
            return Call(BOOLEAN, "contains", args)
        if name == "array_position":
            return Call(BIGINT, "array_position", args)
        if name == "array_remove":
            if not isinstance(t0, ArrayType):
                raise AnalysisError(f"array_remove requires ARRAY, got {t0}")
            if len(args) != 2:
                raise AnalysisError("array_remove(array, element)")
            et, xt = t0.element, args[1].type
            if not ((is_numeric(et) and is_numeric(xt))
                    or (et.is_string and xt.is_string) or et == xt):
                raise AnalysisError(
                    f"array_remove: cannot match {xt} against array({et})")
            return Call(t0, "array_remove", args)
        if name in ("array_min", "array_max"):
            if not isinstance(t0, ArrayType):
                raise AnalysisError(f"{name} requires ARRAY, got {t0}")
            return Call(t0.element, name, args)
        if name == "array_sum":
            if not isinstance(t0, ArrayType):
                raise AnalysisError(f"array_sum requires ARRAY, got {t0}")
            return Call(
                DOUBLE if is_floating(t0.element) else BIGINT, name, args)
        if name == "array_average":
            return Call(DOUBLE, name, args)
        if name in ("array_distinct", "array_sort"):
            if not isinstance(t0, ArrayType):
                raise AnalysisError(f"{name} requires ARRAY, got {t0}")
            return Call(t0, name, args)
        if name == "slice" and isinstance(t0, ArrayType):
            return Call(t0, "slice", args)
        if name == "sequence":
            for a in args:
                if not isinstance(a, Constant):
                    raise AnalysisError(
                        "sequence bounds must be constants (static array "
                        "width under XLA)")
            return Call(ArrayType(BIGINT), "sequence", args)
        if name == "repeat":
            if not isinstance(args[1], Constant):
                raise AnalysisError("repeat count must be a constant")
            return Call(ArrayType(args[0].type), "repeat", args)
        if name == "map":
            if len(args) != 2 or not all(isinstance(a.type, ArrayType) for a in args):
                raise AnalysisError("map() expects two ARRAY arguments")
            return Call(MapType(args[0].type.element, args[1].type.element),
                        "map", args)
        if name == "map_keys":
            if not isinstance(t0, MapType):
                raise AnalysisError(f"map_keys requires MAP, got {t0}")
            return Call(ArrayType(t0.key), "map_keys", args)
        if name == "map_values":
            if not isinstance(t0, MapType):
                raise AnalysisError(f"map_values requires MAP, got {t0}")
            return Call(ArrayType(t0.value), "map_values", args)
        if name == "concat" and isinstance(t0, ArrayType):
            out = t0
            for a in args[1:]:
                if not isinstance(a.type, ArrayType):
                    raise AnalysisError("concat mixes ARRAY and non-ARRAY")
                out = ArrayType(common_super_type(out.element, a.type.element))
            return Call(out, "concat", args)
        if name in ("array_union", "array_intersect", "array_except"):
            if len(args) != 2 or not all(
                    isinstance(a.type, ArrayType) for a in args):
                raise AnalysisError(f"{name} expects two ARRAY arguments")
            et = common_super_type(args[0].type.element,
                                   args[1].type.element)
            return Call(ArrayType(et), name, args)
        if name == "arrays_overlap":
            if len(args) != 2 or not all(
                    isinstance(a.type, ArrayType) for a in args):
                raise AnalysisError("arrays_overlap expects two ARRAYs")
            return Call(BOOLEAN, name, args)
        if name == "map_concat":
            if len(args) < 2 or not all(
                    isinstance(a.type, MapType) for a in args):
                raise AnalysisError("map_concat expects MAP arguments")
            t = args[0].type
            for a in args[1:]:
                if a.type.key.name != t.key.name:
                    raise AnalysisError("map_concat key types differ")
            if is_floating(t.key):
                raise AnalysisError(
                    "map_concat with floating-point keys is not supported")
            return Call(t, "map_concat", args)
        return None

    def _an_Parameter(self, node: "ast.Parameter") -> RowExpression:
        raise AnalysisError(
            "unbound prepared-statement parameter (use EXECUTE ... USING)")

    def _an_ScalarSubquery(self, node: ast.ScalarSubquery) -> RowExpression:
        return self.planner.plan_scalar_subquery(node.query)

    def _an_IntervalLiteral(self, node):
        raise AnalysisError("interval literal outside date arithmetic")


def _add_months_days(days: int, months: int) -> int:
    """Host-side month arithmetic on days-since-epoch (constant folding)."""
    from presto_tpu.expr.compile import _civil_from_days
    import numpy as np
    import jax.numpy as jnp

    y, m, d = _civil_from_days(jnp.asarray(days, jnp.int32))
    y, m, d = int(y), int(m), int(d)
    m0 = (m - 1) + months
    y += m0 // 12
    m = m0 % 12 + 1
    # clamp day to month length
    mdays = [31, 29 if (y % 4 == 0 and (y % 100 != 0 or y % 400 == 0)) else 28,
             31, 30, 31, 30, 31, 31, 30, 31, 30, 31][m - 1]
    return days_from_civil(y, m, min(d, mdays))


# ---------------------------------------------------------------------------
# conjunct utilities


def _resolve_limit(limit) -> Optional[int]:
    """LIMIT is an int after parsing, or an AST node when it came from a
    bound (or unbound) prepared-statement parameter."""
    if limit is None or isinstance(limit, int):
        return limit
    if isinstance(limit, ast.Literal) and limit.kind == "integer":
        return int(limit.value)
    if isinstance(limit, ast.Parameter):
        raise AnalysisError(
            "unbound prepared-statement parameter in LIMIT "
            "(use EXECUTE ... USING)")
    raise AnalysisError("LIMIT must be an integer")


def split_conjuncts(e) -> List:
    if isinstance(e, ast.BinaryOp) and e.op == "and":
        return split_conjuncts(e.left) + split_conjuncts(e.right)
    return [e]


def combine_conjuncts(es: List[RowExpression]) -> Optional[RowExpression]:
    if not es:
        return None
    out = es[0]
    for e in es[1:]:
        out = Call(BOOLEAN, "and", (out, e))
    return out


# ---------------------------------------------------------------------------
# planner


class Planner:
    def __init__(self, catalog: Catalog, symbols: Optional[SymbolAllocator] = None,
                 ctes: Optional[Dict[str, ast.Query]] = None):
        self.catalog = catalog
        self.symbols = symbols or SymbolAllocator()
        self.ctes = dict(ctes or {})
        self.scalar_subqueries: Dict[str, QueryPlan] = {}

    # -- relations --------------------------------------------------------

    def plan_relation(self, rel) -> RelationPlan:
        if isinstance(rel, ast.Table):
            name = rel.name[-1]
            if len(rel.name) == 1 and name not in self.ctes and (
                    name in self.catalog.views):
                # view expansion: plan the stored query like a subquery
                sub = Planner(self.catalog, self.symbols)
                qp = sub.plan(self.catalog.views[name])
                self.scalar_subqueries.update(sub.scalar_subqueries)
                out = qp.root
                fields = [
                    Field(rel.alias or name, n, s, t)
                    for (n, s), (_, t) in zip(zip(out.names, out.symbols),
                                              out.output)
                ]
                return RelationPlan(out.child, Scope(fields), rows=1e5)
            if len(rel.name) == 1 and name in self.ctes:
                sub = Planner(self.catalog, self.symbols, self.ctes)
                qp = sub.plan(self.ctes[name])
                self.scalar_subqueries.update(sub.scalar_subqueries)
                out = qp.root
                fields = [
                    Field(rel.alias or name, n, s, t)
                    for (n, s), (_, t) in zip(zip(out.names, out.symbols), out.output)
                ]
                return RelationPlan(out.child, Scope(fields), rows=1e6)
            conn, handle = self.catalog.resolve(rel.name)
            qualifier = rel.alias or name
            assignments = {}
            output = []
            fields = []
            for c in handle.columns:
                sym = self.symbols.fresh(c.name)
                assignments[sym] = c.name
                output.append((sym, c.type))
                fields.append(Field(qualifier, c.name, sym, c.type))
            node = TableScan(catalog=conn.name, table=handle.name,
                             assignments=assignments, output=output)
            if handle.primary_key:
                col_to_sym = {c: s for s, c in assignments.items()}
                node.primary_key_symbols = [col_to_sym[c] for c in handle.primary_key]
            rows = handle.row_count or 1e6
            return RelationPlan(node, Scope(fields), rows=rows)
        if isinstance(rel, ast.SubqueryRelation):
            sub = Planner(self.catalog, self.symbols, self.ctes)
            qp = sub.plan(rel.query)
            self.scalar_subqueries.update(sub.scalar_subqueries)
            out = qp.root
            fields = [
                Field(rel.alias, n, s, t)
                for (n, s), (_, t) in zip(zip(out.names, out.symbols), out.output)
            ]
            return RelationPlan(out.child, Scope(fields), rows=1e5)
        if isinstance(rel, ast.ValuesRelation):
            sub = Planner(self.catalog, self.symbols, self.ctes)
            qp = sub.plan(rel.query)
            self.scalar_subqueries.update(sub.scalar_subqueries)
            out = qp.root
            names = list(rel.column_names or out.names)
            if len(names) != len(out.symbols):
                raise AnalysisError(
                    f"VALUES alias declares {len(names)} columns, rows "
                    f"have {len(out.symbols)}")
            fields = [
                Field(rel.alias, n, s, t)
                for (n, s), (_, t) in zip(zip(names, out.symbols), out.output)
            ]
            return RelationPlan(out.child, Scope(fields), rows=4.0)
        if isinstance(rel, ast.Join):
            return self.plan_join(rel)
        if isinstance(rel, ast.UnnestRelation):
            # top-level FROM UNNEST(ARRAY[...]): expand over one synthetic row
            return self.plan_unnest(rel, None)
        raise AnalysisError(f"unsupported relation {type(rel).__name__}")

    def plan_unnest(self, rel: ast.UnnestRelation,
                    left: Optional[RelationPlan]) -> RelationPlan:
        """UNNEST as a (lateral) relation: project the array/map expressions
        onto the input, then expand (reference: RelationPlanner.visitUnnest
        → planner/plan/UnnestNode; lateral column references resolve
        against the left relation like the reference's implicit lateral)."""
        if left is None:
            child: PlanNode = OneRow()
            scope = Scope([])
            rows = 1.0
        else:
            left = self._resolved(left)
            child, scope, rows = left.node, left.scope, left.rows
        analyzer = ExprAnalyzer(scope, self)
        exprs = [analyzer.analyze(a) for a in rel.exprs]
        for e in exprs:
            if not isinstance(e.type, (ArrayType, MapType)):
                raise AnalysisError(
                    f"UNNEST argument must be ARRAY or MAP, got {e.type}")
        # project sources (keeping all existing columns)
        proj_exprs = [(f.symbol, InputRef(f.type, f.symbol))
                      for f in scope.fields]
        sources = []
        for e in exprs:
            s = self.symbols.fresh("unnest_src")
            proj_exprs.append((s, e))
            sources.append(s)
        proj = Project(child, proj_exprs)

        qualifier = rel.alias or "unnest"
        wanted = list(rel.column_names or [])
        out_syms, out_types, new_fields = [], [], []

        def take_name(default):
            return wanted.pop(0) if wanted else default

        for e, s in zip(exprs, sources):
            if isinstance(e.type, MapType):
                kn, vn = take_name("key"), take_name("value")
                ks = self.symbols.fresh(kn)
                vs = self.symbols.fresh(vn)
                out_syms.append([ks, vs])
                out_types.append([e.type.key, e.type.value])
                new_fields.append(Field(qualifier, kn, ks, e.type.key))
                new_fields.append(Field(qualifier, vn, vs, e.type.value))
            else:
                n = take_name("col")
                s2 = self.symbols.fresh(n)
                out_syms.append([s2])
                out_types.append([e.type.element])
                new_fields.append(Field(qualifier, n, s2, e.type.element))
        ord_sym = None
        if rel.ordinality:
            n = take_name("ordinality")
            ord_sym = self.symbols.fresh(n)
            new_fields.append(Field(qualifier, n, ord_sym, BIGINT))
        node = Unnest(
            child=proj,
            sources=sources,
            replicate=[f.symbol for f in scope.fields],
            out_syms=out_syms,
            out_types=out_types,
            ordinality_sym=ord_sym,
        )
        return RelationPlan(node, Scope(list(scope.fields) + new_fields),
                            rows=rows * 4)

    def plan_join(self, rel: ast.Join) -> RelationPlan:
        """A join as written. Inner joins (comma, CROSS JOIN, INNER JOIN ...
        ON) are not planned here: they stay pending, their ON conjuncts
        beside them, and `_assemble_joins` orders their leaves with the
        WHERE conjuncts as one join graph (reference: ReorderJoins over a
        MultiJoinNode). A LEFT (or RIGHT, turned round) join is a leaf of
        that graph, planned once WHERE is known (`_plan_outer`); a FULL
        join is planned here."""
        if isinstance(rel.right, ast.UnnestRelation):
            if rel.kind not in ("cross", "inner") or rel.condition is not None:
                raise AnalysisError(
                    "UNNEST is only supported with CROSS JOIN")
            return self.plan_unnest(rel.right, self.plan_relation(rel.left))
        left = self.plan_relation(rel.left)
        right = self.plan_relation(rel.right)
        scope = left.scope + right.scope
        cond = ExprAnalyzer(scope, self).analyze(rel.condition) if rel.condition else None
        conjs = _split_ir_conjuncts(cond) if cond is not None else []
        if rel.kind in ("cross", "inner"):
            rows = left.rows * right.rows if rel.kind == "cross" else \
                max(left.rows, right.rows)
            return RelationPlan(_PendingCross(left, right, conjs), scope,
                                rows=rows)
        if rel.kind == "right":
            left, right = right, left
        lsyms = {f.symbol for f in left.scope.fields}
        rsyms = {f.symbol for f in right.scope.fields}
        if not _extract_equi_keys(conjs, lsyms, rsyms)[0]:
            raise AnalysisError(
                "outer joins require at least one equi-join condition")
        if rel.kind == "full":
            return self._plan_outer("full", left, right, conjs, [])
        return RelationPlan(_PendingOuter(left, right, conjs), scope,
                            rows=max(left.rows, right.rows))

    def _plan_outer(self, kind: str, left: RelationPlan, right: RelationPlan,
                    conjs: List[RowExpression],
                    where: List[RowExpression]) -> RelationPlan:
        """A LEFT or FULL join over its two sides. `where` is what the
        preserved side of a LEFT join is assembled with: WHERE conjuncts
        over its columns alone. The other side is assembled with its own ON
        conjuncts only, and builds."""
        if where:
            node, _, rest = self._order_joins(left, where)
            if rest:
                node = Filter(node, combine_conjuncts(rest))
            left = RelationPlan(node, left.scope, left.rows)
        else:
            left = self._resolved(left)
        right = self._resolved(right)
        lsyms = {f.symbol for f in left.scope.fields}
        rsyms = {f.symbol for f in right.scope.fields}
        lkeys, rkeys, residual = _extract_equi_keys(conjs, lsyms, rsyms)
        if kind == "left":
            # push build-side-only residuals into the build side (correct for
            # LEFT: non-matching build rows are dropped pre-join)
            for c in residual:
                if not expr_inputs(c) <= rsyms:
                    raise AnalysisError("left join residual on probe side unsupported")
                right = RelationPlan(Filter(right.node, c), right.scope, right.rows)
            residual = []
        if kind == "full" and residual:
            # an ON residual must not drop unmatched rows on either side;
            # no correct place to evaluate it outside the join yet
            raise AnalysisError("FULL JOIN with non-equi residual not supported")
        node = HashJoin(kind=kind, left=left.node, right=right.node,
                        left_keys=lkeys, right_keys=rkeys,
                        build_unique=_derives_unique(right.node, rkeys))
        return RelationPlan(node, left.scope + right.scope,
                            rows=max(left.rows, right.rows))

    def _resolved(self, rp: RelationPlan) -> RelationPlan:
        """`rp` with its pending joins planned, from its ON conjuncts
        alone."""
        if not isinstance(rp.node, _PendingCross):
            return rp
        node, _, rest = self._order_joins(rp, [])
        if rest:
            node = Filter(node, combine_conjuncts(rest))
        return RelationPlan(node, rp.scope, rp.rows)

    # -- set operations ---------------------------------------------------

    def plan_setop(self, q: ast.SetOp) -> QueryPlan:
        """UNION/INTERSECT/EXCEPT: plan both sides independently, align
        arity and types positionally, wrap in a SetOp node; a trailing
        ORDER BY/LIMIT sorts the combined result (reference:
        StatementAnalyzer set-operation analysis + UnionNode planning)."""
        ctes = dict(self.ctes)
        for name, sub in q.ctes:
            ctes[name] = sub

        def plan_side(side):
            sub = Planner(self.catalog, self.symbols, ctes)
            qp = sub.plan(side)
            self.scalar_subqueries.update(sub.scalar_subqueries)
            return qp

        lqp, rqp = plan_side(q.left), plan_side(q.right)
        lout, rout = lqp.root, rqp.root
        self.scalar_subqueries.update(lqp.scalar_subqueries)
        self.scalar_subqueries.update(rqp.scalar_subqueries)
        if len(lout.symbols) != len(rout.symbols):
            raise AnalysisError(
                f"{q.kind.upper()} arity mismatch: {len(lout.symbols)} vs "
                f"{len(rout.symbols)} columns")
        ltypes = [t for _, t in lout.output]
        rtypes = [t for _, t in rout.output]
        for i, (lt, rt) in enumerate(zip(ltypes, rtypes)):
            # exact logical-type compatibility: dtype equality is not
            # enough (decimal scales, dates and bigints all share int64 —
            # mixing them would compare raw representations)
            same = lt.name == rt.name or (
                lt.dtype == rt.dtype
                and not lt.is_string and not rt.is_string
                and not isinstance(lt, DecimalType)
                and not isinstance(rt, DecimalType)
                and lt.name not in ("date", "timestamp", "time")
                and rt.name not in ("date", "timestamp", "time")
            )
            if not same:
                raise AnalysisError(
                    f"{q.kind.upper()} column {i + 1} type mismatch: "
                    f"{lt} vs {rt}")
        symbols = [self.symbols.fresh(n or f"col{i}")
                   for i, n in enumerate(lout.names)]
        node: PlanNode = SetOp(q.kind, q.all, lout, rout, symbols, ltypes)

        # ORDER BY / LIMIT over the combined result (names or ordinals)
        if q.order_by:
            name_to_sym = dict(zip(lout.names, symbols))
            keys = []
            for oi in q.order_by:
                if isinstance(oi.expr, ast.Literal) and oi.expr.kind == "integer":
                    pos = int(oi.expr.value)
                    if not 1 <= pos <= len(symbols):
                        raise AnalysisError(
                            f"ORDER BY position {pos} out of range "
                            f"(1..{len(symbols)})")
                    sym = symbols[pos - 1]
                elif isinstance(oi.expr, ast.Identifier):
                    nm = oi.expr.parts[-1]
                    if nm not in name_to_sym:
                        raise AnalysisError(f"ORDER BY column {nm} not in output")
                    sym = name_to_sym[nm]
                else:
                    raise AnalysisError(
                        "set-operation ORDER BY supports output columns only")
                keys.append(SortItem(sym, oi.ascending, oi.nulls_first))
            node = Sort(node, keys, q.limit)
        elif q.limit is not None:
            node = Limit(node, q.limit)
        root = Output(node, list(lout.names), symbols)
        return QueryPlan(root, self.scalar_subqueries,
                         cacheable=not self.symbols.volatile_plan)

    # -- query ------------------------------------------------------------

    def plan(self, q) -> QueryPlan:
        if isinstance(q, ast.SetOp):
            return self.plan_setop(q)
        q = dataclasses.replace(q, limit=_resolve_limit(q.limit))
        ctes = dict(self.ctes)
        for name, sub in q.ctes:
            ctes[name] = sub
        self.ctes = ctes

        from presto_tpu.plan.decorrelate import decorrelate

        q = decorrelate(q, self.catalog, self.ctes)

        if q.from_ is None:
            # SELECT <exprs> with no FROM: one synthetic row (the
            # reference's ValuesNode single-row plan)
            rp = RelationPlan(OneRow(), Scope([]), rows=1.0)
        else:
            rp = self.plan_relation(q.from_)

        # WHERE: analyze conjuncts; subquery predicates become semi-joins
        where_conjs_ast = split_conjuncts(q.where) if q.where is not None else []
        plain_conjs_ast = []
        semi_asts = []
        for c in where_conjs_ast:
            # NOT EXISTS / NOT IN parse as UnaryOp('not', ...); fold the
            # negation into the subquery predicate node
            if isinstance(c, ast.UnaryOp) and c.op == "not" and isinstance(
                c.operand, (ast.InSubquery, ast.Exists)
            ):
                c = dataclasses.replace(c.operand, negated=not c.operand.negated)
            if isinstance(c, ast.InSubquery):
                semi_asts.append(("in", c))
            elif isinstance(c, ast.Exists):
                semi_asts.append(("exists", c))
            else:
                plain_conjs_ast.append(c)

        node, scope, residuals = self._assemble_joins(rp, plain_conjs_ast)

        for kind, c in semi_asts:
            node = self._plan_semijoin(node, scope, kind, c)

        if residuals:
            node = Filter(node, combine_conjuncts(residuals))

        # aggregation?
        has_group = bool(q.group_by)
        has_aggs = any(_contains_agg(it.expr) for it in q.select) or (
            q.having is not None and _contains_agg(q.having)
        )

        select_items = list(q.select)
        # expand stars
        expanded = []
        for it in select_items:
            if isinstance(it.expr, ast.Star):
                for f in scope.fields:
                    if it.expr.qualifier and f.qualifier != it.expr.qualifier:
                        continue
                    expanded.append(ast.SelectItem(ast.Identifier((f.name,)), None))
            else:
                expanded.append(it)
        select_items = expanded

        # resolve group-by ordinals
        group_by = []
        for g in q.group_by:
            if isinstance(g, ast.Literal) and g.kind == "integer":
                group_by.append(select_items[int(g.value) - 1].expr)
            else:
                group_by.append(g)

        if has_group or has_aggs:
            node, post_scope_repl = self._plan_aggregation(
                node, scope, select_items, group_by, q.having
            )
            analyzer = ExprAnalyzer(scope, self, replacements=post_scope_repl)
            if q.having is not None:
                having_ast = _rewrite_aggs_to_keys(q.having)
                node = Filter(node, analyzer.analyze(having_ast))
        else:
            analyzer = ExprAnalyzer(scope, self)

        # window functions (computed after WHERE/GROUP BY/HAVING, before the
        # select projection — SQL evaluation order)
        windows: List[ast.WindowFunction] = []

        def collect_windows(n):
            if isinstance(n, ast.WindowFunction):
                windows.append(n)
            for ch in _ast_children(n):
                collect_windows(ch)

        for it in select_items:
            collect_windows(it.expr)
        for oi in q.order_by or []:
            collect_windows(oi.expr)
        if windows:
            node = self._plan_windows(node, analyzer, windows)

        if has_group or has_aggs:
            select_exprs = [
                analyzer.analyze(_rewrite_aggs_to_keys(it.expr)) for it in select_items
            ]
        else:
            select_exprs = [analyzer.analyze(it.expr) for it in select_items]

        # select projection
        proj_exprs: List[Tuple[str, RowExpression]] = []
        display_names: List[str] = []
        select_symbols: List[str] = []
        alias_map: Dict[str, Tuple[str, Type]] = {}
        host_items: List[tuple] = []  # HostProject finishing items
        host_syms: set = set()
        # (symbol, type) per SELECT item, aligned with select_items — the
        # ORDER BY resolver must not zip proj_exprs (host items don't
        # always add a projection)
        select_sym_types: List[Tuple[str, Type]] = []
        for it, e in zip(select_items, select_exprs):
            name = it.alias or _derive_name(it.expr)
            if e.type is GEOMETRY:
                raise AnalysisError(
                    "GEOMETRY values cannot be output directly — wrap the "
                    "expression in ST_AsText(...)")
            hs = _host_split(e)
            if hs is not None:
                # string-producing host function (cast-to-varchar /
                # date_format): its DEVICE input rides the projection; the
                # formatting happens in a HostProject above the root
                inner, kind, param = hs
                if isinstance(inner, InputRef):
                    in_sym = inner.name
                else:
                    in_sym = self.symbols.fresh("hostin")
                if not any(s == in_sym for s, _ in proj_exprs):
                    proj_exprs.append((in_sym, inner))
                sym = self.symbols.fresh(it.alias or name)
                host_items.append((sym, kind, in_sym, param))
                host_syms.add(sym)
                display_names.append(name)
                select_symbols.append(sym)
                select_sym_types.append((sym, VARCHAR))
                if it.alias:
                    # ORDER BY <alias> must bind here (and then fail the
                    # host-sym check), not to a same-named table column
                    alias_map[f"id:{it.alias}"] = (sym, VARCHAR)
                continue
            if isinstance(e, InputRef) and it.alias is None:
                sym = e.name
            else:
                sym = self.symbols.fresh(it.alias or name)
            proj_exprs.append((sym, e))
            display_names.append(name)
            select_symbols.append(sym)
            select_sym_types.append((sym, e.type))
            if it.alias:
                alias_map[f"id:{it.alias}"] = (sym, e.type)

        # ORDER BY may reference select aliases, ordinals, or agg exprs
        sort_items: List[SortItem] = []
        extra_order_exprs: List[Tuple[str, RowExpression]] = []
        if q.order_by:
            repl = dict(getattr(analyzer, "replacements", {}))
            repl.update(alias_map)
            # select expressions themselves are available as symbols
            # (aligned per select item — proj_exprs may not be)
            for (sym, ty), it in zip(select_sym_types, select_items):
                repl.setdefault(ast_key(it.expr), (sym, ty))
            order_an = ExprAnalyzer(scope, self, replacements=repl)
            for oi in q.order_by:
                if isinstance(oi.expr, ast.Literal) and oi.expr.kind == "integer":
                    pos = int(oi.expr.value)
                    if not 1 <= pos <= len(select_symbols):
                        raise AnalysisError(
                            f"ORDER BY position {pos} out of range "
                            f"(1..{len(select_symbols)})")
                    sym = select_symbols[pos - 1]
                    if sym in host_syms:
                        raise AnalysisError(
                            "ORDER BY on a host-computed expression "
                            "(cast to varchar / date_format) is not "
                            "supported — order by the underlying value")
                else:
                    e = order_an.analyze(
                        _rewrite_aggs_to_keys(oi.expr) if (has_group or has_aggs) else oi.expr
                    )
                    if isinstance(e, InputRef):
                        sym = e.name
                        if sym in host_syms:
                            raise AnalysisError(
                                "ORDER BY on a host-computed expression "
                                "(cast to varchar / date_format) is not "
                                "supported — order by the underlying value")
                        # ORDER BY a non-selected column: the sort key must
                        # ride through the projection (Output drops it)
                        if not any(s == sym for s, _ in proj_exprs) and not any(
                                s == sym for s, _ in extra_order_exprs):
                            extra_order_exprs.append((sym, e))
                    else:
                        if _host_split(e) is not None:
                            raise AnalysisError(
                                "ORDER BY on a host-computed expression "
                                "(cast to varchar / date_format) is not "
                                "supported — order by the underlying value")
                        sym = self.symbols.fresh("orderkey")
                        extra_order_exprs.append((sym, e))
                sort_items.append(SortItem(sym, oi.ascending, oi.nulls_first))

        node = Project(node, proj_exprs + extra_order_exprs)

        if q.distinct:
            if host_items:
                raise AnalysisError(
                    "SELECT DISTINCT over host-computed expressions "
                    "(cast to varchar / date_format) is not supported")
            node = Aggregate(node, [s for s, _ in proj_exprs], [], step="single")

        if sort_items:
            node = Sort(node, sort_items, limit=q.limit)
        elif q.limit is not None:
            node = Limit(node, q.limit)

        if host_items:
            from presto_tpu.plan.nodes import HostProject

            node = HostProject(node, host_items)

        root = Output(node, display_names, select_symbols)
        return QueryPlan(root, dict(self.scalar_subqueries),
                         cacheable=not self.symbols.volatile_plan)

    # -- join assembly from FROM + WHERE ----------------------------------

    def _assemble_joins(self, rp: RelationPlan, conjs_ast) -> Tuple[PlanNode, Scope, List[RowExpression]]:
        analyzer = ExprAnalyzer(rp.scope, self)
        return self._order_joins(rp, [analyzer.analyze(c) for c in conjs_ast])

    def _order_joins(self, rp: RelationPlan, conjs: List[RowExpression]) -> Tuple[PlanNode, Scope, List[RowExpression]]:
        """The leaves of `rp`'s inner joins, ordered by their ON conjuncts
        and `conjs` (the ON conjuncts first, in the text's order). A LEFT
        join among the leaves is planned first, its preserved side taking
        the conjuncts over its columns alone. Returns (node, scope, the
        conjuncts no join consumed)."""
        scope = rp.scope
        leaves: List[RelationPlan] = []
        on: List[RowExpression] = []
        _collect_cross_leaves(rp, leaves, on)
        conjs = on + list(conjs)
        for i, leaf in enumerate(leaves):
            if isinstance(leaf.node, _PendingOuter):
                outer = leaf.node
                psyms = {f.symbol for f in outer.left.scope.fields}
                mine = [c for c in conjs if expr_inputs(c) <= psyms]
                conjs = [c for c in conjs if not expr_inputs(c) <= psyms]
                leaves[i] = self._plan_outer("left", outer.left, outer.right,
                                             outer.conjs, mine)
        if len(leaves) == 1:
            return leaves[0].node, scope, conjs

        # Stats-driven greedy join ordering (CBO v1 — the role of
        # ReorderJoins.java:94 with JoinStatsRule estimates): each leaf's
        # cardinality is adjusted by the selectivity of its single-leaf
        # WHERE conjuncts; each step joins the connected leaf minimizing the
        # estimated intermediate; the smaller estimated side builds.
        from presto_tpu.plan.stats import NodeStats, derive, filter_selectivity

        def leaf_estimate(leaf: RelationPlan, pending) -> Tuple[float, Optional[NodeStats]]:
            st = derive(leaf.node, self.catalog)
            rows = st.rows if st is not None else leaf.rows
            if st is not None:
                syms = {f.symbol for f in leaf.scope.fields}
                for c in pending:
                    if expr_inputs(c) <= syms:
                        rows *= filter_selectivity(c, st)
            return max(rows, 1.0), st

        def join_out_estimate(a_rows, a_st, a_keys, b_rows, b_st, b_keys) -> float:
            ndvs = []
            for ak, bk in zip(a_keys, b_keys):
                for st, k in ((a_st, ak), (b_st, bk)):
                    cs = st.col(k) if st is not None else None
                    if cs is not None and cs.ndv:
                        ndvs.append(cs.ndv)
            if ndvs:
                return max(1.0, a_rows * b_rows / max(ndvs))
            return max(a_rows, b_rows)

        remaining = list(leaves)
        pending = list(conjs)
        est = {id(l): leaf_estimate(l, pending) for l in remaining}

        # DP plan enumeration (ReorderJoins.java:94 — there a memo over
        # MultiJoinNode partitions, here bushy DP over connected subsets)
        # when the join graph is connected and small enough. Cost model:
        # Σ per join (probe_rows + 2·build_rows + out_rows) — probing is a
        # stream pass, building sorts (≈2×), output rows feed the parent.
        # The greedy below remains the fallback (disconnected graphs, >10
        # relations), deliberately starting from the fact table; DP instead
        # can discover plans like (customer⋈orders)⋈lineitem where the big
        # fact relation flows through ONE join against a pre-reduced build.
        if 2 <= len(leaves) <= 10:
            dp_out = self._dp_join_order(leaves, pending, est,
                                         join_out_estimate)
            if dp_out is not None:
                node, pending = dp_out
                return node, scope, pending

        # start from the largest relation (likely the fact table → probe side)
        remaining.sort(key=lambda r: -est[id(r)][0])
        current = remaining.pop(0)
        cur_rows, cur_st = est[id(current)]
        while remaining:
            cur_syms = {f.symbol for f in current.scope.fields}
            best = None
            for leaf in remaining:
                leaf_syms = {f.symbol for f in leaf.scope.fields}
                lkeys, rkeys, rest = _extract_equi_keys(pending, cur_syms, leaf_syms)
                if not lkeys:
                    continue
                leaf_rows, leaf_st = est[id(leaf)]
                out_rows = join_out_estimate(cur_rows, cur_st, lkeys,
                                             leaf_rows, leaf_st, rkeys)
                if best is None or out_rows < best[0]:
                    best = (out_rows, leaf, lkeys, rkeys, rest, leaf_rows, leaf_st)
            if best is None:
                # disconnected join graph: cross product via nested loop
                # against the smallest remaining leaf (ReorderJoins keeps
                # cross products last for the same reason); conjuncts that
                # span the two sides (non-equi) fuse as the residual
                remaining.sort(key=lambda r: est[id(r)][0])
                leaf = remaining.pop(0)
                leaf_rows, leaf_st = est[id(leaf)]
                cur_syms2 = cur_syms | {f.symbol for f in leaf.scope.fields}
                covered = [c for c in pending if expr_inputs(c) <= cur_syms2]
                pending = [c for c in pending if expr_inputs(c) > cur_syms2]
                node = NestedLoopJoin(current.node, leaf.node,
                                      residual=combine_conjuncts(covered))
                out_rows = max(cur_rows * leaf_rows, 1.0)
                merged_cols = {}
                for st in (cur_st, leaf_st):
                    if st is not None:
                        merged_cols.update(st.columns)
                cur_st = NodeStats(out_rows, merged_cols)
                cur_rows = out_rows
                current = RelationPlan(node, current.scope + leaf.scope,
                                       rows=out_rows)
                continue
            out_rows, leaf, lkeys, rkeys, rest, leaf_rows, leaf_st = best
            remaining.remove(leaf)
            # consumed conjuncts: pending minus rest
            pending = rest
            if leaf_rows <= cur_rows:
                probe, build = current, leaf
                pkeys, bkeys = lkeys, rkeys
            else:
                probe, build = leaf, current
                pkeys, bkeys = rkeys, lkeys
            node = HashJoin(
                kind="inner", left=probe.node, right=build.node,
                left_keys=pkeys, right_keys=bkeys,
                build_unique=_derives_unique(build.node, bkeys),
            )
            merged_cols = {}
            for st in (cur_st, leaf_st):
                if st is not None:
                    merged_cols.update(st.columns)
            cur_st = NodeStats(out_rows, merged_cols)
            cur_rows = out_rows
            current = RelationPlan(node, probe.scope + build.scope,
                                   rows=out_rows)
        # apply any conjunct that is now fully covered; keep the rest as residuals
        return current.node, scope, pending

    def _notnull_side(self, node: PlanNode, keys: List[str]) -> PlanNode:
        """IS NOT NULL inference (reference: the predicate-inference half of
        optimizations/PredicatePushDown — inner-join equi keys can't match
        NULL, so null rows are droppable BEFORE the join). Skipped when
        stats prove the column never null (filter would be a no-op)."""
        from presto_tpu.plan.stats import derive

        try:
            st = derive(node, self.catalog)
        except Exception:
            st = None
        types = dict(node.output)
        conjs = []
        for k in keys:
            cs = st.col(k) if st is not None else None
            if cs is not None and cs.null_fraction == 0.0:
                continue
            conjs.append(Call(BOOLEAN, "is_not_null",
                              (InputRef(types[k], k),)))
        if not conjs:
            return node
        return Filter(node, combine_conjuncts(conjs))

    def _dp_join_order(self, leaves, conjs, est, join_out_estimate):
        """Bushy dynamic-programming join enumeration over connected
        subsets. Returns (root PlanNode, leftover conjuncts) or None when
        the join graph is disconnected (caller falls back to the greedy
        path, which handles cross products)."""
        from presto_tpu.plan.stats import NodeStats

        n = len(leaves)
        syms = [frozenset(f.symbol for f in l.scope.fields) for l in leaves]
        full = (1 << n) - 1

        def mask_syms(mask):
            s = set()
            for i in range(n):
                if mask >> i & 1:
                    s |= syms[i]
            return s

        # connectivity over equi edges (cross-join elimination: DP only
        # combines subsets an equi conjunct connects)
        parent = list(range(n))

        def find(x):
            while parent[x] != x:
                parent[x] = parent[parent[x]]
                x = parent[x]
            return x

        for i in range(n):
            for j in range(i + 1, n):
                lk, _, _ = _extract_equi_keys(conjs, syms[i], syms[j])
                if lk:
                    parent[find(i)] = find(j)
        if len({find(i) for i in range(n)}) != 1:
            return None

        # dp[mask] = (cost, rows, stats, repr) where repr is a leaf index
        # or (maskA, maskB) with A the probe (larger) side
        dp = {}
        for i, leaf in enumerate(leaves):
            rows, st = est[id(leaf)]
            dp[1 << i] = (0.0, rows, st, i)
        msyms = {1 << i: syms[i] for i in range(n)}

        for mask in range(3, full + 1):
            if mask in dp or bin(mask).count("1") < 2:
                continue
            best = None
            sub = (mask - 1) & mask
            while sub:
                other = mask ^ sub
                if sub < other:  # each unordered split once
                    a, b = dp.get(sub), dp.get(other)
                    if a is not None and b is not None:
                        sa = msyms.get(sub)
                        if sa is None:
                            sa = msyms[sub] = frozenset(mask_syms(sub))
                        sb = msyms.get(other)
                        if sb is None:
                            sb = msyms[other] = frozenset(mask_syms(other))
                        lk, rk, _ = _extract_equi_keys(conjs, sa, sb)
                        if lk:
                            out = join_out_estimate(a[1], a[2], lk,
                                                    b[1], b[2], rk)
                            probe, build = max(a[1], b[1]), min(a[1], b[1])
                            cost = (a[0] + b[0] + probe + 2.0 * build + out)
                            if best is None or cost < best[0]:
                                pa, pb = ((sub, other) if a[1] >= b[1]
                                          else (other, sub))
                                merged = {}
                                for st in (a[2], b[2]):
                                    if st is not None:
                                        merged.update(st.columns)
                                best = (cost, out,
                                        NodeStats(out, merged), (pa, pb))
                sub = (sub - 1) & mask
            if best is not None:
                dp[mask] = best
        if full not in dp:
            return None

        pending = list(conjs)

        def build_tree(mask):
            entry = dp[mask]
            if isinstance(entry[3], int):
                leaf = leaves[entry[3]]
                return leaf.node, msyms[mask]
            pa, pb = entry[3]
            lnode, lsyms = build_tree(pa)
            rnode, rsyms = build_tree(pb)
            nonlocal pending
            lk, rk, pending = _extract_equi_keys(pending, lsyms, rsyms)
            node = HashJoin(
                kind="inner",
                left=self._notnull_side(lnode, lk),
                right=self._notnull_side(rnode, rk),
                left_keys=lk, right_keys=rk,
                build_unique=_derives_unique(rnode, rk),
            )
            return node, msyms.setdefault(mask, frozenset(mask_syms(mask)))

        root, _ = build_tree(full)
        return root, pending

    # -- semi joins -------------------------------------------------------

    def _plan_semijoin(self, node: PlanNode, scope: Scope, kind: str, c) -> PlanNode:
        sub = Planner(self.catalog, self.symbols, self.ctes)
        if kind == "in":
            qp = sub.plan(c.query)
            self.scalar_subqueries.update(sub.scalar_subqueries)
            out = qp.root
            if len(out.symbols) != 1:
                raise AnalysisError("IN subquery must produce one column")
            left_e = ExprAnalyzer(scope, self).analyze(c.value)
            if not isinstance(left_e, InputRef):
                raise AnalysisError("IN subquery LHS must be a column")
            return SemiJoin(node, out.child, [left_e.name], [out.symbols[0]], c.negated)
        # correlated [NOT] EXISTS (reference: TransformExistsApplyToLateralNode
        # + PlanNodeDecorrelator → SemiJoinNode). The subquery's WHERE is split
        # into pure-inner conjuncts (stay inside the build plan), equi
        # correlation pairs (become semi-join keys), and residual correlated
        # conjuncts (become the semi-join residual, evaluated over probe∪build
        # pairs — covers Q21's `l2.l_suppkey <> l1.l_suppkey`).
        sq = c.query
        if sq.group_by or sq.having or sq.order_by or sq.limit:
            raise AnalysisError("EXISTS subquery with group/order/limit unsupported")
        for name, cq in sq.ctes:
            sub.ctes[name] = cq
        rel = sub._resolved(sub.plan_relation(sq.from_))
        inner_scope = rel.scope
        inner_syms = {f.symbol for f in inner_scope.fields}
        combined = scope + inner_scope
        combined_an = ExprAnalyzer(combined, self)
        inner_an = ExprAnalyzer(inner_scope, sub)
        pure_inner: List[RowExpression] = []
        correlated: List[RowExpression] = []
        for conj in split_conjuncts(sq.where) if sq.where is not None else []:
            try:
                pure_inner.append(inner_an.analyze(conj))
            except AnalysisError:
                correlated.append(combined_an.analyze(conj))
        # after the conjunct loop: scalar subqueries inside the EXISTS WHERE
        # register params on the sub-planner during analysis above
        self.scalar_subqueries.update(sub.scalar_subqueries)
        outer_syms = {f.symbol for f in scope.fields}
        lkeys, rkeys, residual = _extract_equi_keys(correlated, outer_syms, inner_syms)
        if not lkeys:
            raise AnalysisError("uncorrelated / non-equi-correlated EXISTS unsupported")
        build = rel.node
        if pure_inner:
            build = Filter(build, combine_conjuncts(pure_inner))
        return SemiJoin(node, build, lkeys, rkeys, c.negated,
                        residual=combine_conjuncts(residual), null_aware=False)

    # -- window functions -------------------------------------------------

    def _plan_windows(self, node: PlanNode, analyzer: "ExprAnalyzer",
                      windows: List[ast.WindowFunction]) -> PlanNode:
        """Lower window function instances onto the plan: pre-project any
        computed inputs, group instances by (partition, order) spec, stack a
        Window node per spec, and register replacements so the select/order
        analyzers resolve each OVER() expression to its output symbol
        (reference: sql/planner/QueryPlanner.window + WindowNode)."""
        from presto_tpu.plan.nodes import Window, WindowFunc

        pre_exprs: List[Tuple[str, RowExpression]] = [
            (s, InputRef(t, s)) for s, t in node.output
        ]
        added = False

        def to_symbol(e_ast) -> Tuple[str, Type]:
            nonlocal added
            e = analyzer.analyze(_rewrite_aggs_to_keys(e_ast))
            if isinstance(e, InputRef):
                return e.name, e.type
            sym = self.symbols.fresh("winexpr")
            pre_exprs.append((sym, e))
            added = True
            return sym, e.type

        def const_int(e_ast, what: str) -> int:
            e = analyzer.analyze(e_ast)
            if not isinstance(e, Constant) or e.value is None:
                raise AnalysisError(f"{what} must be an integer literal")
            return int(e.value)

        specs: Dict[tuple, tuple] = {}
        for w in windows:
            key = ast_key(w)
            if key in analyzer.replacements:
                continue
            part_syms = [to_symbol(p)[0] for p in w.partition_by]
            order_pairs = [to_symbol(oi.expr) for oi in w.order_by]
            order_items = [
                SortItem(sym, oi.ascending, oi.nulls_first)
                for (sym, _), oi in zip(order_pairs, w.order_by)
            ]
            if (w.frame and w.frame.startswith("range:")
                    and any(b[0] in "pf" for b in w.frame.split(":")[1:])):
                # value-offset RANGE frame: one numeric/temporal sort key
                # (reference: WindowFrameTypeCheck in sql/analyzer)
                if len(order_pairs) != 1:
                    raise AnalysisError(
                        "RANGE frame with value offsets requires exactly "
                        "one ORDER BY key")
                ot = order_pairs[0][1]
                if ot is TIMESTAMP:
                    # bare integer offsets would silently mean microseconds;
                    # reject until INTERVAL offsets exist (cast to date)
                    raise AnalysisError(
                        "RANGE frame offsets over a timestamp ORDER BY key "
                        "are not supported (cast the key to date — offsets "
                        "are then in days)")
                if not (is_integral(ot) or is_floating(ot)
                        or isinstance(ot, DecimalType) or ot is DATE):
                    raise AnalysisError(
                        "RANGE frame offsets require a numeric or date "
                        f"ORDER BY key (date offsets are in days), got {ot}")
                if isinstance(ot, DecimalType) and ot.precision > 18:
                    # two-limb int128 decimals: only the low limb reaches
                    # the frame binary search, so comparisons would lie
                    raise AnalysisError(
                        "RANGE frame offsets over decimal keys wider than "
                        "18 digits are not supported")
            name = w.name.lower()
            arg_sym: Optional[str] = None
            param: Optional[int] = None
            default: Optional[object] = None
            if name in ("row_number", "rank", "dense_rank"):
                t: Type = BIGINT
            elif name in ("percent_rank", "cume_dist"):
                t = DOUBLE
            elif name == "ntile":
                param = const_int(w.args[0], "ntile buckets")
                t = BIGINT
            elif name in ("lag", "lead"):
                arg_sym, t = to_symbol(w.args[0])
                param = const_int(w.args[1], f"{name} offset") if len(w.args) > 1 else 1
                if len(w.args) > 2:
                    de = analyzer.analyze(w.args[2])
                    if not isinstance(de, Constant):
                        raise AnalysisError(
                            f"{name} default must be a literal")
                    if de.value is None:
                        pass  # NULL default == no default
                    elif t.is_string or de.type.is_string:
                        raise AnalysisError(
                            f"{name} default on string columns is not "
                            "supported")
                    elif t is BOOLEAN:
                        if de.type is not BOOLEAN:
                            raise AnalysisError(
                                f"{name} default must be boolean for a "
                                "boolean column")
                        default = bool(de.value)
                    elif isinstance(t, DecimalType):
                        # store in the column's unscaled representation
                        default = int(round(float(de.value) * 10 ** t.scale))
                    elif is_integral(t):
                        if float(de.value) != int(float(de.value)):
                            raise AnalysisError(
                                f"{name} default {de.value} does not fit "
                                f"the {t} column (would truncate)")
                        default = int(de.value)
                    elif is_floating(t):
                        default = float(de.value)
                    elif t is DATE or t is TIMESTAMP:
                        default = int(de.value)
                    else:
                        raise AnalysisError(
                            f"{name} default unsupported for {t}")
            elif name in ("first_value", "last_value"):
                arg_sym, t = to_symbol(w.args[0])
            elif name == "nth_value":
                arg_sym, t = to_symbol(w.args[0])
                param = const_int(w.args[1], "nth_value n")
            elif name in _AGG_FUNCS:
                if w.is_star or (name == "count" and not w.args):
                    name, t = "count", BIGINT
                else:
                    arg_sym, arg_t = to_symbol(w.args[0])
                    t = _agg_output_type(name, arg_t, False)
            else:
                raise AnalysisError(f"unknown window function {name}")
            if name in ("row_number", "rank", "dense_rank", "percent_rank",
                        "cume_dist", "ntile", "lag", "lead") and not w.order_by:
                raise AnalysisError(f"{name}() requires ORDER BY in its OVER clause")
            wsym = self.symbols.fresh(name)
            skey = (
                tuple(part_syms),
                tuple((o.symbol, o.ascending, o.nulls_first) for o in order_items),
            )
            if skey not in specs:
                specs[skey] = (part_syms, order_items, [])
            specs[skey][2].append(
                WindowFunc(wsym, name, t, arg_sym, param, frame=w.frame,
                           default=default)
            )
            analyzer.replacements[key] = (wsym, t)

        if added:
            node = Project(node, pre_exprs)
        for part_syms, order_items, funcs in specs.values():
            node = Window(node, part_syms, order_items, funcs)
        return node

    # -- scalar subqueries ------------------------------------------------

    def plan_scalar_subquery(self, q: ast.Query) -> RowExpression:
        sub = Planner(self.catalog, self.symbols, self.ctes)
        qp = sub.plan(q)
        self.scalar_subqueries.update(sub.scalar_subqueries)
        out = qp.root
        if len(out.symbols) != 1:
            raise AnalysisError("scalar subquery must produce one column")
        sym = self.symbols.fresh("param")
        t = out.output[0][1]
        self.scalar_subqueries[sym] = qp
        from presto_tpu.expr.ir import Param

        return Param(t, sym)

    # -- aggregation ------------------------------------------------------

    def _plan_aggregation(self, node, scope, select_items, group_by, having):
        analyzer = ExprAnalyzer(scope, self)

        # collect aggregates from select + having
        aggs_by_key: Dict[str, ast.FunctionCall] = {}
        grouping_calls: Dict[str, ast.FunctionCall] = {}

        def collect(n):
            if isinstance(n, ast.FunctionCall) and _is_agg_fn(n.name.lower()):
                aggs_by_key.setdefault("agg:" + ast_key(n), n)
                return
            if isinstance(n, ast.FunctionCall) and n.name.lower() == "grouping":
                grouping_calls.setdefault(ast_key(n), n)
                return
            for child in _ast_children(n):
                collect(child)

        for it in select_items:
            collect(it.expr)
        if having is not None:
            collect(having)

        # GROUPING SETS / ROLLUP / CUBE: the full key list is the ordered
        # union of all sets; each set plans its own aggregate below
        grouping_sets: Optional[List[List[str]]] = None
        set_asts: Optional[list] = None
        if len(group_by) == 1 and isinstance(group_by[0], ast.GroupingSets):
            set_asts = group_by[0].sets
            seen_keys: Dict[str, ast.Node] = {}
            for s in set_asts:
                for g in s:
                    seen_keys.setdefault(ast_key(g), g)
            group_by = list(seen_keys.values())

        # pre-projection: group keys + agg args
        pre_exprs: List[Tuple[str, RowExpression]] = []
        group_syms: List[str] = []
        repl: Dict[str, Tuple[str, Type]] = {}
        for g in group_by:
            e = analyzer.analyze(g)
            if isinstance(e.type, (ArrayType, MapType)):
                raise AnalysisError("GROUP BY on ARRAY/MAP is not supported")
            if isinstance(e, InputRef):
                sym = e.name
            else:
                sym = self.symbols.fresh("groupkey")
            pre_exprs.append((sym, e))
            group_syms.append(sym)
            repl["id:" + sym] = (sym, e.type)
            repl[ast_key(g)] = (sym, e.type)

        agg_specs: List[AggSpec] = []
        for key, fc in aggs_by_key.items():
            fn = _AGG_CANON.get(fc.name.lower(), fc.name.lower())
            distinct = fc.distinct
            arg2_sym = None
            param = None
            if fc.is_star:
                arg_sym = None
                arg_t = BIGINT
            else:
                if fn == "numeric_histogram":
                    # numeric_histogram(buckets, x) — buckets is the
                    # leading CONSTANT (NumericHistogramAggregation)
                    if len(fc.args) != 2:
                        raise AnalysisError(
                            "numeric_histogram(buckets, x) takes two "
                            "arguments")
                    be = analyzer.analyze(fc.args[0])
                    from presto_tpu.expr.ir import Constant as _Const

                    if not isinstance(be, _Const) or be.value is None:
                        raise AnalysisError(
                            "numeric_histogram bucket count must be a "
                            "constant")
                    param = float(int(be.value))
                    if param < 2:
                        raise AnalysisError("bucket count must be >= 2")
                    ae = analyzer._to_double(analyzer.analyze(fc.args[1]))
                elif fn == "tdigest_agg":
                    # tdigest_agg(x[, w][, compression]) — weight is a
                    # column, compression a constant (reference:
                    # TDigestAggregationFunction signatures)
                    if not 1 <= len(fc.args) <= 3:
                        raise AnalysisError(
                            "tdigest_agg(x[, w][, compression]) takes "
                            "1-3 arguments")
                    ae = analyzer._to_double(analyzer.analyze(fc.args[0]))
                    if len(fc.args) == 3:
                        from presto_tpu.expr.ir import Constant as _Const

                        ce = analyzer.analyze(fc.args[2])
                        if not isinstance(ce, _Const) or ce.value is None:
                            raise AnalysisError(
                                "tdigest_agg compression must be a constant")
                        param = float(ce.value)
                        if param < 10:
                            raise AnalysisError("compression must be >= 10")
                elif fn == "merge":
                    if len(fc.args) != 1:
                        raise AnalysisError("merge(sketch) takes one argument")
                    ae = analyzer.analyze(fc.args[0])
                    if ae.type.name not in ("tdigest(double)",
                                            "hyperloglog"):
                        raise AnalysisError(
                            f"merge expects tdigest or hyperloglog, "
                            f"got {ae.type}")
                else:
                    ae = analyzer.analyze(fc.args[0])
                if isinstance(ae, InputRef):
                    arg_sym = ae.name
                else:
                    arg_sym = self.symbols.fresh(f"{fn}_arg")
                if not any(s == arg_sym for s, _ in pre_exprs):
                    pre_exprs.append((arg_sym, ae))
                arg_t = ae.type
                if fn in _TWO_ARG_AGGS:
                    if len(fc.args) < 2:
                        raise AnalysisError(f"{fn} takes two arguments")
                    ae2 = analyzer.analyze(fc.args[1])
                    arg2_t = ae2.type
                    if isinstance(ae2, InputRef):
                        arg2_sym = ae2.name
                    else:
                        arg2_sym = self.symbols.fresh(f"{fn}_arg2")
                    if not any(s == arg2_sym for s, _ in pre_exprs):
                        pre_exprs.append((arg2_sym, ae2))
                elif fn == "tdigest_agg" and len(fc.args) >= 2:
                    ae2 = analyzer._to_double(analyzer.analyze(fc.args[1]))
                    if isinstance(ae2, InputRef):
                        arg2_sym = ae2.name
                    else:
                        arg2_sym = self.symbols.fresh(f"{fn}_arg2")
                    if not any(s == arg2_sym for s, _ in pre_exprs):
                        pre_exprs.append((arg2_sym, ae2))
                elif fn == "approx_percentile":
                    if len(fc.args) < 2:
                        raise AnalysisError("approx_percentile(x, p) takes two arguments")
                    pe = analyzer.analyze(fc.args[1])
                    from presto_tpu.expr.ir import Constant as _Const

                    if not isinstance(pe, _Const) or pe.value is None:
                        raise AnalysisError("approx_percentile percentile must be a constant")
                    param = float(pe.value)
                    if not 0.0 <= param <= 1.0:
                        raise AnalysisError("percentile must be in [0, 1]")
            if fn == "map_agg":
                if arg_t.is_string is False and is_floating(arg_t):
                    raise AnalysisError(
                        "map_agg with floating-point keys is not supported")
                out_t = MapType(arg_t, arg2_t)
            elif fn == "numeric_histogram":
                out_t = MapType(DOUBLE, DOUBLE)
            elif fn == "tdigest_agg":
                out_t = TDIGEST
            elif fn == "approx_set":
                from presto_tpu.types import HYPERLOGLOG

                out_t = HYPERLOGLOG
            elif fn == "merge":
                out_t = arg_t  # tdigest or hyperloglog, checked above
            else:
                out_t = _agg_output_type(fn, arg_t, fc.is_star)
            sym = self.symbols.fresh(fn)
            agg_specs.append(AggSpec(sym, "count_star" if fc.is_star else fn,
                                     arg_sym, out_t, distinct,
                                     arg2=arg2_sym, param=param))
            repl[key.replace("agg:", "", 1)] = (sym, out_t)

        # ensure group key InputRef identities present
        seen = {s for s, _ in pre_exprs}
        pre = Project(node, pre_exprs) if pre_exprs else node

        def plan_one(gsyms: List[str], pre: PlanNode) -> PlanNode:
            hll_aggs = [a for a in agg_specs if a.fn == "approx_distinct"]
            pct_aggs = [a for a in agg_specs if a.fn == "approx_percentile"]
            distinct_aggs = [a for a in agg_specs if a.distinct]
            if hll_aggs:
                if len(agg_specs) == 1:
                    return self._plan_hll(pre, gsyms, agg_specs[0],
                                          pre_exprs, node)
                # mixed with other aggregates: the HLL lowering reshapes
                # the whole plan (registers become group rows), so fall
                # back to EXACT count-distinct on the sorted materialized
                # path — exactness trivially satisfies the approximation
                # contract; only the mergeable-sketch scaling is lost
                agg_specs_local = [
                    (AggSpec(a.symbol, "count_distinct", a.arg, a.type,
                             False) if a.fn == "approx_distinct" else a)
                    for a in agg_specs
                ]
                return Aggregate(pre, gsyms, agg_specs_local, step="single")
            if (pct_aggs and len(agg_specs) == len(pct_aggs)
                    and len({a.arg for a in pct_aggs}) == 1
                    and not any(a.distinct for a in pct_aggs)):
                # all aggregates are approx_percentile over one column → the
                # mergeable quantized-histogram sketch (distributable); mixed
                # forms fall back to the materialized exact path below
                return self._plan_qsketch(pre, gsyms, pct_aggs)
            if distinct_aggs:
                if len(agg_specs) == 1 and agg_specs[0].fn == "count":
                    # sole COUNT(DISTINCT x): two-phase dedup-then-count —
                    # both phases decomposable, so it distributes
                    a = agg_specs[0]
                    inner = Aggregate(pre, gsyms + [a.arg], [], step="single")
                    return Aggregate(
                        inner, gsyms,
                        [AggSpec(a.symbol, "count", a.arg, a.type, False)],
                        step="single",
                    )
                # mixed forms (count/sum/avg DISTINCT alongside other
                # aggregates): rewrite each DISTINCT spec to its sorted
                # order-dependent form — the materialized single-task path
                # computes decomposable and sorted aggregates in one pass
                # (reference: MarkDistinct + masked accumulators;
                # DistinctingGroupedAccumulator)
                rewritten = []
                for a in agg_specs:
                    if not a.distinct:
                        rewritten.append(a)
                        continue
                    if a.fn in ("min", "max"):  # DISTINCT is a no-op
                        rewritten.append(AggSpec(a.symbol, a.fn, a.arg,
                                                 a.type, False))
                        continue
                    if a.fn not in ("count", "sum", "avg"):
                        raise AnalysisError(
                            f"{a.fn}(DISTINCT) not supported (count/sum/avg"
                            " are)")
                    rewritten.append(AggSpec(
                        a.symbol, f"{a.fn}_distinct", a.arg, a.type, False,
                        arg2=a.arg2, param=a.param))
                return Aggregate(pre, gsyms, rewritten, step="single")
            return Aggregate(pre, gsyms, agg_specs, step="single")

        if set_asts is None:
            if grouping_calls:
                raise AnalysisError(
                    "grouping() requires GROUPING SETS / ROLLUP / CUBE")
            return plan_one(group_syms, pre), repl

        # grouping(c1, ..) → per-branch constant bitmask (bit i set when
        # ci is NOT aggregated in that branch's set — Presto semantics)
        sym_of = {ast_key(g): s for g, s in zip(group_by, group_syms)}
        grouping_syms: List[Tuple[str, List[str]]] = []
        for gkey, gc in grouping_calls.items():
            arg_syms = []
            for a in gc.args:
                k = ast_key(a)
                if k not in sym_of:
                    raise AnalysisError(
                        "grouping() arguments must be grouping columns")
                arg_syms.append(sym_of[k])
            sym = self.symbols.fresh("grouping")
            grouping_syms.append((sym, arg_syms))
            repl[gkey] = (sym, BIGINT)

        # GROUPING SETS: one aggregate per set over the shared
        # pre-projection, keys absent from a set pad as typed NULLs, then
        # UNION ALL (reference: GroupIdNode + a single multi-set
        # aggregation; the union-of-aggregates shape computes the same
        # rows and distributes through the existing set-op machinery)
        key_types = {s: e.type for s, e in pre_exprs if s in group_syms}
        out_syms = (list(group_syms) + [a.symbol for a in agg_specs]
                    + [s for s, _ in grouping_syms])
        out_types = ([key_types[s] for s in group_syms]
                     + [a.type for a in agg_specs]
                     + [BIGINT] * len(grouping_syms))
        import copy as _copy

        branches = []
        for i, s_ast in enumerate(set_asts):
            gsyms = [sym_of[ast_key(g)] for g in s_ast]
            # each branch owns its subtree: optimizer passes mutate nodes
            # in place (pruning one branch's copy of the shared
            # pre-projection must not strip columns another branch needs)
            agg_i = plan_one(gsyms, pre if i == 0 else _copy.deepcopy(pre))
            if isinstance(agg_i, Aggregate):
                agg_i.grouping_set = True
            pad = []
            for sym in group_syms:
                if sym in gsyms:
                    pad.append((sym, InputRef(key_types[sym], sym)))
                else:
                    pad.append((sym, Constant(key_types[sym], None)))
            pad.extend((a.symbol, InputRef(a.type, a.symbol))
                       for a in agg_specs)
            for gsym, arg_syms in grouping_syms:
                mask = 0
                for bit, s in enumerate(arg_syms):
                    if s not in gsyms:
                        mask |= 1 << (len(arg_syms) - 1 - bit)
                pad.append((gsym, Constant(BIGINT, mask)))
            branches.append(Project(agg_i, pad))
        agg_node = branches[0]
        for b in branches[1:]:
            agg_node = SetOp("union", True, agg_node, b,
                             list(out_syms), list(out_types))
        return agg_node, repl

    def _plan_qsketch(self, pre: PlanNode, group_syms,
                      pct_aggs: List[AggSpec]) -> PlanNode:
        """Lower approx_percentile(x, p) into a mergeable value-space
        sketch (reference: ApproximateLongPercentileAggregations over
        qdigest — here a quantized histogram over the static float64
        universe, riding the ordinary partial → exchange → final path):

          Project    qb = __qsk_bucket(x)   (order-preserving top-24-bit
                                             quantization of the monotone
                                             IEEE-754 encoding)
          Aggregate  group (keys…, qb):  cnt := count(x), mn := min(x)
                     -- decomposable: distributes and merges exactly
          Aggregate  group (keys…):  p-quantile := __approx_percentile_w
                     -- weighted-rank selection over ≤ occupied-bucket
                        rows (order-dependent, runs at the gathered task
                        like the reference's final qdigest.valueAt)

        Value-space relative error ≤ 2⁻¹² per bucket (12 mantissa bits);
        the returned value is a real data value (a bucket minimum)."""
        a0 = pct_aggs[0]
        in_types = dict(pre.output)
        arg_t = in_types[a0.arg]
        arg_ref = InputRef(arg_t, a0.arg)
        qb = self.symbols.fresh("qsk_bucket")
        lower = Project(pre, [(s, InputRef(t, s)) for s, t in pre.output] + [
            (qb, Call(BIGINT, "__qsk_bucket", (arg_ref,))),
        ])
        cnt = self.symbols.fresh("qsk_cnt")
        mn = self.symbols.fresh("qsk_min")
        inner = Aggregate(lower, group_syms + [qb], [
            AggSpec(cnt, "count", a0.arg, BIGINT),
            AggSpec(mn, "min", a0.arg, arg_t),
        ], step="single")
        outer_specs = [
            AggSpec(a.symbol, "__approx_percentile_w", mn, a.type,
                    arg2=cnt, param=a.param)
            for a in pct_aggs
        ]
        return Aggregate(inner, group_syms, outer_specs, step="single")

    def _plan_hll(self, pre: PlanNode, group_syms, a: AggSpec, pre_exprs,
                  raw_input: PlanNode) -> PlanNode:
        """Lower approx_distinct(x) into HyperLogLog over existing plan
        machinery (reference: ApproximateCountDistinctAggregations +
        HyperLogLogState — but here registers ARE group-table rows, so the
        sketch is mergeable/distributable through the ordinary partial →
        exchange → final aggregate path with a fixed m-row footprint):

          Project    reg  = __hll_reg(x)   (low bits of content hash)
                     rank = __hll_rank(x)  (1 + clz of top hash bits)
          Aggregate  group (keys…, reg):  r := max(rank)
          Project    e := 2^-r
          Aggregate  group (keys…):  c := count(r), s := sum(e)
          Project    estimate := bias-corrected harmonic mean over m
                     registers, with the small-range linear-counting
                     correction (zeros = m - c).
        """
        from presto_tpu.expr.compile import HLL_M

        if a.arg is None:
            raise AnalysisError("approx_distinct requires an argument")
        in_types = dict(pre.output)
        arg_ref = InputRef(in_types[a.arg], a.arg)
        reg = self.symbols.fresh("hll_reg")
        rank = self.symbols.fresh("hll_rank")
        lower = Project(pre, [(s, InputRef(t, s)) for s, t in pre.output] + [
            (reg, Call(BIGINT, "__hll_reg", (arg_ref,))),
            (rank, Call(BIGINT, "__hll_rank", (arg_ref,))),
        ])
        rmax = self.symbols.fresh("hll_r")
        inner = Aggregate(lower, group_syms + [reg],
                          [AggSpec(rmax, "max", rank, BIGINT)], step="single")
        e_sym = self.symbols.fresh("hll_e")
        inner_types = dict(inner.output)
        mid = Project(inner, [(s, InputRef(inner_types[s], s))
                              for s in group_syms + [rmax]] + [
            (e_sym, Call(DOUBLE, "power",
                         (Constant(DOUBLE, 2.0),
                          Call(DOUBLE, "neg",
                               (Call(DOUBLE, "cast",
                                     (InputRef(BIGINT, rmax),)),))))),
        ])
        c_sym = self.symbols.fresh("hll_c")
        s_sym = self.symbols.fresh("hll_s")
        outer = Aggregate(mid, group_syms, [
            AggSpec(c_sym, "count", rmax, BIGINT),
            AggSpec(s_sym, "sum", e_sym, DOUBLE),
        ], step="single")
        # estimator: zeros = m - c; S = s + zeros; raw = α·m²/S;
        # small range (raw ≤ 2.5m, zeros > 0): m·ln(m/zeros)
        m = float(HLL_M)
        alpha = 0.7213 / (1.0 + 1.079 / m)
        c_ref = Call(DOUBLE, "cast", (InputRef(BIGINT, c_sym),))
        zeros = Call(DOUBLE, "sub", (Constant(DOUBLE, m), c_ref))
        # empty input: sum over zero rows is SQL NULL but approx_distinct
        # must return 0 — coalesce keeps the estimator defined (all-zero
        # registers → linear counting → m·ln(m/m) = 0)
        s_safe = Call(DOUBLE, "coalesce",
                      (InputRef(DOUBLE, s_sym), Constant(DOUBLE, 0.0)))
        S = Call(DOUBLE, "add", (s_safe, zeros))
        raw = Call(DOUBLE, "div",
                   (Constant(DOUBLE, alpha * m * m), S))
        small = Call(DOUBLE, "mul",
                     (Constant(DOUBLE, m),
                      Call(DOUBLE, "ln",
                           (Call(DOUBLE, "div",
                                 (Constant(DOUBLE, m), zeros)),))))
        use_small = Call(BOOLEAN, "and", (
            Call(BOOLEAN, "le", (raw, Constant(DOUBLE, 2.5 * m))),
            Call(BOOLEAN, "gt", (zeros, Constant(DOUBLE, 0.0))),
        ))
        est = Call(BIGINT, "cast", (
            Call(DOUBLE, "round",
                 (Call(DOUBLE, "if", (use_small, small, raw)),)),))
        outer_types = dict(outer.output)
        return Project(outer, [(s, InputRef(outer_types[s], s))
                               for s in group_syms] + [(a.symbol, est)])


def _host_split(e: RowExpression):
    """Top-level host-only call → (device_input_expr, kind, param), else
    None. These produce strings over unbounded value domains, so they
    cannot be dictionary transforms; the planner runs them in a
    HostProject at the query root (plan/nodes.HostProject)."""
    if not isinstance(e, Call):
        return None
    if (e.fn == "cast" and e.type is VARCHAR and e.args
            and not e.args[0].type.is_string
            and not isinstance(e.args[0].type, (ArrayType, MapType))):
        return e.args[0], "varchar_cast", None
    if e.fn == "__host_date_format":
        return e.args[0], "date_format", str(e.args[1].value)
    return None


class _PendingCross(PlanNode):
    """Marker node: an inner join (comma, CROSS JOIN, INNER JOIN ... ON)
    whose ordering is decided by its ON conjuncts `conjs` and the WHERE
    conjuncts in _assemble_joins. Never reaches execution."""

    def __init__(self, left: RelationPlan, right: RelationPlan,
                 conjs: List[RowExpression] = ()):
        self.left = left
        self.right = right
        self.conjs = list(conjs)
        self.output = list(left.node.output) + list(right.node.output)

    def children(self):
        return [self.left.node, self.right.node]


class _PendingOuter(_PendingCross):
    """Marker node: `left` LEFT JOIN `right` ON `conjs`, planned once the
    WHERE conjuncts are known (Planner._plan_outer). Never reaches
    execution."""


def _collect_cross_leaves(rp: RelationPlan, out: List[RelationPlan],
                          on: List[RowExpression]):
    """The leaves of `rp`'s pending inner joins into `out`, their ON
    conjuncts into `on`, both in the text's order."""
    if isinstance(rp.node, _PendingCross) and \
            not isinstance(rp.node, _PendingOuter):
        _collect_cross_leaves(rp.node.left, out, on)
        _collect_cross_leaves(rp.node.right, out, on)
        on.extend(rp.node.conjs)
    else:
        out.append(rp)


def _split_ir_conjuncts(e: RowExpression) -> List[RowExpression]:
    if isinstance(e, Call) and e.fn == "and":
        out = []
        for a in e.args:
            out.extend(_split_ir_conjuncts(a))
        return out
    return [e]


def _extract_equi_keys(conjs, lsyms, rsyms):
    lkeys, rkeys, rest = [], [], []
    for c in conjs:
        if isinstance(c, Call) and c.fn == "eq":
            a, b = c.args
            if isinstance(a, InputRef) and isinstance(b, InputRef):
                if a.name in lsyms and b.name in rsyms:
                    lkeys.append(a.name)
                    rkeys.append(b.name)
                    continue
                if b.name in lsyms and a.name in rsyms:
                    lkeys.append(b.name)
                    rkeys.append(a.name)
                    continue
        rest.append(c)
    return lkeys, rkeys, rest


def _derives_unique(node: PlanNode, keys: List[str]) -> bool:
    """True if `keys` are unique on node's output (primary key of a scan,
    grouping keys of an aggregation, or either carried up the probe side
    of a join whose build is unique) — enables the single-match probe
    fast path (analog of knowing the build has no PositionLinks chains).
    A proof from the plan's structure alone: a wrong True loses rows."""
    if isinstance(node, Aggregate):
        return set(node.group_keys) <= set(keys)
    if isinstance(node, Filter):
        return _derives_unique(node.child, keys)
    if isinstance(node, Project):
        # identity-projected symbols only
        ident = {s for s, e in node.exprs if isinstance(e, InputRef) and e.name == s}
        if set(keys) <= ident:
            return _derives_unique(node.child, keys)
        return False
    if isinstance(node, TableScan):
        pk = getattr(node, "primary_key_symbols", None)
        if pk is None:
            return False
        return set(pk) <= set(keys)
    if isinstance(node, HashJoin):
        # inner/left with a unique build: a probe row survives at most
        # once, so what is unique on the probe side stays unique (FULL's
        # tail emits build rows with NULL probe columns)
        if node.kind not in ("inner", "left") or not node.build_unique:
            return False
        probe_keys = [k for k in keys if k in node.left.out_names]
        return bool(probe_keys) and _derives_unique(node.left, probe_keys)
    return False


def _contains_agg(n) -> bool:
    if isinstance(n, ast.FunctionCall) and _is_agg_fn(n.name.lower()):
        return True
    return any(_contains_agg(c) for c in _ast_children(n))


def _rewrite_aggs_to_keys(n):
    """Aggregate calls inside post-agg expressions are replaced at analysis
    time via the replacements map (keyed by ast_key); nothing to rewrite
    structurally."""
    return n


def _ast_children(n):
    if isinstance(n, ast.UnaryOp):
        return [n.operand]
    if isinstance(n, ast.BinaryOp):
        return [n.left, n.right]
    if isinstance(n, ast.Between):
        return [n.value, n.low, n.high]
    if isinstance(n, ast.InList):
        return [n.value] + n.items
    if isinstance(n, ast.Like):
        return [n.value, n.pattern]
    if isinstance(n, ast.IsNull):
        return [n.value]
    if isinstance(n, ast.FunctionCall):
        return n.args
    if isinstance(n, ast.Cast):
        return [n.value]
    if isinstance(n, ast.Case):
        out = []
        if n.operand:
            out.append(n.operand)
        for c, v in n.whens:
            out.extend([c, v])
        if n.default:
            out.append(n.default)
        return out
    if isinstance(n, ast.Extract):
        return [n.value]
    if isinstance(n, ast.WindowFunction):
        return list(n.args) + list(n.partition_by) + [o.expr for o in n.order_by]
    return []


def _derive_name(e) -> str:
    if isinstance(e, ast.Identifier):
        return e.parts[-1]
    if isinstance(e, ast.FunctionCall):
        return e.name.lower()
    if isinstance(e, ast.Extract):
        return e.field
    return "_col"


def _agg_output_type(fn: str, arg_t: Type, is_star: bool) -> Type:
    if fn in ("count", "count_if") or is_star:
        return BIGINT
    if fn == "sum":
        if isinstance(arg_t, DecimalType):
            # Presto: sum(decimal(p,s)) -> decimal(38,s), int128-backed
            return DecimalType(38, arg_t.scale)
        if is_integral(arg_t):
            return BIGINT
        return DOUBLE
    if fn == "avg":
        return DOUBLE  # deviation: Presto returns decimal for decimal args
    if fn in ("min", "max", "arbitrary", "max_by", "min_by",
              "approx_percentile"):
        if isinstance(arg_t, DecimalType) and arg_t.is_long:
            # long-decimal extremes compare on the combined float64 value
            # (deviation: Presto keeps decimal(38); exactness is preserved
            # for sums, which is where int128 matters)
            return DOUBLE
        return arg_t
    if fn in ("stddev_pop", "stddev_samp", "var_pop", "var_samp",
              "covar_pop", "covar_samp", "corr", "geometric_mean"):
        return DOUBLE
    if fn in ("bool_and", "bool_or"):
        return BOOLEAN
    if fn in ("checksum", "approx_distinct"):
        return BIGINT
    if fn == "array_agg":
        return ArrayType(arg_t)
    from presto_tpu.functions import registry as _freg

    udf = _freg().aggregate(fn)
    if udf is not None:
        return udf.result_type(arg_t)
    raise AnalysisError(f"unknown aggregate {fn}")


def plan_query(sql_or_ast, catalog: Catalog) -> QueryPlan:
    """Parse (if needed), analyze and plan a query (reference path:
    SqlQueryExecution.doAnalyzeQuery → LogicalPlanner.plan)."""
    from presto_tpu.sql.parser import parse_sql

    q = (sql_or_ast if isinstance(sql_or_ast, (ast.Query, ast.SetOp))
         else parse_sql(sql_or_ast))
    return Planner(catalog).plan(q)
