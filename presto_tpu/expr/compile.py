"""Row-expression → JAX compilation.

This is the analog of the reference's runtime bytecode generation
(presto-main/.../sql/gen/ExpressionCompiler.java, PageFunctionCompiler.java,
backed by the presto-bytecode ASM DSL): we lower the typed IR into jnp ops at
trace time and let XLA fuse the whole pipeline fragment. There is no
interpreter in the hot path.

Compiled form: fn(batch) -> (values, validity|None), vectorized over the
batch capacity. NULL semantics are SQL three-valued logic; the `live` mask is
NOT consulted here (dead lanes compute garbage harmlessly — branch-free SIMT
style, like Presto's SelectedPositions but without the compaction).

String ops: operands are dictionary codes. Literals are resolved against the
column's Dictionary at trace time (Batch carries dictionaries as static
pytree aux), so equality/range/LIKE/IN on strings become integer compares or
a precomputed boolean-table gather. See presto_tpu.dictionary.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu.batch import Batch, Column
from presto_tpu.dictionary import Dictionary
from presto_tpu.expr.ir import (
    Call,
    Constant,
    InputRef,
    LambdaExpr,
    RowExpression,
)
from presto_tpu.expr import structural as _struct
from presto_tpu.expr.structural import StructVal
from presto_tpu.types import (
    BOOLEAN,
    DOUBLE,
    ArrayType,
    DecimalType,
    MapType,
    Type,
    is_floating,
    is_integral,
)

# ---------------------------------------------------------------------------
# helpers


def _and_valid(a, b):
    if a is None:
        return b
    if b is None:
        return a
    return a & b


def _const_array(value, typ: Type):
    return jnp.asarray(value, dtype=typ.dtype)


def _round_half_away(v):
    """Half-away-from-zero rounding for floats (SQL ROUND semantics)."""
    return jnp.sign(v) * jnp.floor(jnp.abs(v) + 0.5)


def _div_half_away(v, f: int):
    """Integer divide with half-away-from-zero rounding of dropped digits."""
    av = jnp.abs(v)
    return jnp.sign(v) * ((av + f // 2) // f)


def like_table(d: Dictionary, pattern, escape: str | None = None) -> np.ndarray:
    """Which of a dictionary's values a LIKE pattern matches, indexed by
    code + 1 (slot 0, NULL, is False); memoised on the dictionary, so the
    planner's estimate (plan/stats.py) and the filter share one pass."""
    rx = re.compile(like_to_regex(str(pattern), escape))
    return d.int_lut(("like", pattern, escape),
                     lambda s: rx.match(s) is not None, dtype=np.bool_)


def like_to_regex(pattern: str, escape: str | None = None) -> str:
    """SQL LIKE pattern → anchored python regex (reference:
    operator/scalar/StringFunctions.java likePattern / LikeFunctions)."""
    out = []
    i = 0
    while i < len(pattern):
        c = pattern[i]
        if escape and c == escape and i + 1 < len(pattern):
            out.append(re.escape(pattern[i + 1]))
            i += 2
            continue
        if c == "%":
            out.append(".*")
        elif c == "_":
            out.append(".")
        else:
            out.append(re.escape(c))
        i += 1
    return "^" + "".join(out) + "$"


# string→string functions evaluated host-side over the dictionary
# (reference: operator/scalar/StringFunctions.java — but O(|dict|) instead of
# O(rows), then one device gather)
# HyperLogLog register count (2^12 → ~1.6% standard error; the reference's
# approx_distinct default standard error is 2.3% at p=11)
HLL_M = 4096

_STR_TO_STR = {
    "substr", "upper", "lower", "trim", "ltrim", "rtrim", "replace",
    "reverse", "lpad", "rpad", "concat", "split_part",
    "regexp_extract", "regexp_replace", "json_extract_scalar",
    # URL / hash / encoding family (operator/scalar/UrlFunctions,
    # VarbinaryFunctions over utf-8 text) — host dictionary transforms
    "url_extract_host", "url_extract_path", "url_extract_query",
    "url_extract_protocol", "url_extract_fragment", "url_encode",
    "url_decode", "md5", "sha1", "sha256", "sha512", "to_base64",
    "from_base64", "normalize",
    # JSON family (operator/scalar/JsonFunctions.java): JSON values are
    # VARCHAR text; every function evaluates ONCE per dictionary entry
    "json_extract", "json_array_get", "json_format", "json_parse",
    # VARBINARY family (VarbinaryFunctions.java): bytes ride the latin-1
    # bijection (types.VarbinaryType), so these are dictionary transforms
    "to_hex", "from_hex", "to_utf8", "from_utf8",
    "__vb_md5", "__vb_sha1", "__vb_sha256", "__vb_sha512", "__vb_to_base64",
    # IPADDRESS/IPPREFIX family (expr/ip.py): canonical-byte dictionary
    # entries, so casts and prefix math are dictionary transforms too
    "__to_ipaddress", "__vb_to_ipaddress", "__ip_to_varchar",
    "__ip_to_bytes", "__to_ipprefix", "__ipprefix_to_varchar",
    "__addr_to_ipprefix", "__ipprefix_to_addr",
    "ip_prefix", "ip_subnet_min", "ip_subnet_max",
    # TDIGEST entries (expr/tdigest.py)
    "scale_tdigest",
}
# string→double functions over dictionary entries (float lut + null lut):
# the TDIGEST scalar family (expr/tdigest.py)
_STR_TO_FLOAT = {"value_at_quantile", "quantile_at_value", "trimmed_mean"}
# string→int functions (code-indexed int lut)
_STR_TO_INT = {"length", "strpos", "codepoint", "json_array_length",
               "json_size", "levenshtein_distance_c", "hamming_distance_c",
               "__hll_cardinality", "bit_length", "__vb_bit_length",
               "date_parse", "from_iso8601_date", "from_iso8601_timestamp"}
# int functions whose python fn may return None = SQL NULL (absent json
# path / non-array input) — carried via a parallel null lut
_STR_INT_NULLABLE = {"json_array_length", "json_size", "__hll_cardinality",
                     "date_parse", "from_iso8601_date",
                     "from_iso8601_timestamp"}

# MySQL date format specifiers → strptime (DateTimeFunctions.java's
# date_parse uses the MySQL vocabulary, not JodaTime's)
_MYSQL_FMT = {"Y": "%Y", "y": "%y", "m": "%m", "c": "%m", "d": "%d",
              "e": "%d", "H": "%H", "k": "%H", "h": "%I", "I": "%I",
              "l": "%I", "i": "%M", "s": "%S", "S": "%S", "f": "%f",
              "p": "%p", "M": "%B", "b": "%b", "a": "%a", "W": "%A",
              "j": "%j", "T": "%H:%M:%S", "r": "%I:%M:%S %p", "%": "%%"}


def mysql_format_to_strptime(fmt: str) -> str:
    """Translate a MySQL date format to strptime; unsupported specifiers
    raise ValueError (the builder surfaces it as an AnalysisError)."""
    out = []
    i = 0
    while i < len(fmt):
        ch = fmt[i]
        if ch == "%":
            if i + 1 >= len(fmt):
                raise ValueError("trailing % in date format")
            spec = fmt[i + 1]
            if spec not in _MYSQL_FMT:
                raise ValueError(f"unsupported date format specifier %{spec}")
            out.append(_MYSQL_FMT[spec])
            i += 2
        else:
            # strptime treats bare % as special; everything else literal
            out.append(ch)
            i += 1
    return "".join(out)
# string→bool predicate functions (bool lut, like LIKE)
_STR_PRED = {"regexp_like", "starts_with", "ends_with", "contains",
             "json_array_contains", "is_json_scalar",
             "__is_subnet_of_c", "__prefix_contains_c"}


def _sql_substr(s: str, start: int, length: int | None) -> str:
    # SQL substr: 1-based; negative start counts from the end (Presto
    # StringFunctions.substr semantics)
    n = len(s)
    if start == 0:
        return ""
    if start > 0:
        i = start - 1
    else:
        i = n + start
        if i < 0:
            return ""
    if i >= n:
        return ""
    if length is None:
        return s[i:]
    if length <= 0:
        return ""
    return s[i : i + length]


def _str_xform_pyfn(fn: str, cargs: tuple):
    """Host python fn(str)->str for a string transform with constant args."""
    if fn == "substr":
        start = int(cargs[0])
        length = int(cargs[1]) if len(cargs) > 1 and cargs[1] is not None else None
        return lambda s: _sql_substr(s, start, length)
    if fn == "upper":
        return str.upper
    if fn == "lower":
        return str.lower
    if fn in ("url_extract_host", "url_extract_path", "url_extract_query",
              "url_extract_protocol", "url_extract_fragment"):
        from urllib.parse import urlparse

        attr = fn[len("url_extract_"):]
        attr = {"host": "hostname", "protocol": "scheme"}.get(attr, attr)

        def url_part(s, attr=attr):
            try:
                v = getattr(urlparse(s), attr)
            except ValueError:
                return None
            return v if v else None

        return url_part
    if fn == "url_encode":
        from urllib.parse import quote_plus

        return lambda s: quote_plus(s)
    if fn == "url_decode":
        from urllib.parse import unquote_plus

        return lambda s: unquote_plus(s)
    if fn in ("md5", "sha1", "sha256", "sha512"):
        import hashlib as _hl

        algo = fn

        def digest(s, algo=algo):
            return getattr(_hl, algo)(s.encode()).hexdigest()

        return digest
    if fn in ("__vb_md5", "__vb_sha1", "__vb_sha256", "__vb_sha512"):
        import hashlib as _hl

        algo = fn[5:]

        def vb_digest(s, algo=algo):
            raw = getattr(_hl, algo)(s.encode("latin-1")).digest()
            return raw.decode("latin-1")

        return vb_digest
    if fn == "__vb_to_base64":
        import base64 as _b64

        return lambda s: _b64.b64encode(s.encode("latin-1")).decode("ascii")
    if fn == "to_hex":
        return lambda s: s.encode("latin-1").hex().upper()
    if fn == "from_hex":
        def fh(s):
            try:
                return bytes.fromhex(s).decode("latin-1")
            except ValueError:
                return None
        return fh
    if fn == "to_utf8":
        return lambda s: s.encode("utf-8").decode("latin-1")
    if fn == "from_utf8":
        # invalid byte sequences replaced (FromUtf8Function's default)
        return lambda s: s.encode("latin-1").decode("utf-8", "replace")
    if fn == "to_base64":
        import base64 as _b64

        return lambda s: _b64.b64encode(s.encode()).decode()
    if fn == "from_base64":
        import base64 as _b64

        def fb64(s):
            try:
                return _b64.b64decode(s).decode("utf-8", "replace")
            except Exception:
                return None

        return fb64
    if fn == "normalize":
        import unicodedata as _ud

        return lambda s: _ud.normalize("NFC", s)
    if fn in ("__to_ipaddress", "__vb_to_ipaddress", "__ip_to_varchar",
              "__to_ipprefix", "__ipprefix_to_varchar", "__ip_to_bytes",
              "__addr_to_ipprefix", "__ipprefix_to_addr",
              "ip_prefix", "ip_subnet_min", "ip_subnet_max"):
        from presto_tpu.expr import ip as _ip

        if fn == "ip_prefix":
            bits = int(cargs[0])
            return lambda s, _b=bits: _ip.ip_prefix(s, _b)
        if fn == "__addr_to_ipprefix":
            # full-length prefix: /32 for v4-mapped entries, /128 for v6
            def full_pfx(s):
                b = s.encode("latin-1")
                if len(b) != 16:
                    return None
                v4 = b[:12] == bytes(10) + b"\xff\xff"
                return _ip.ip_prefix(s, 32 if v4 else 128)

            return full_pfx
        if fn == "__ipprefix_to_addr":
            return lambda s: s[:16] if len(s) == 17 else None
        if fn == "__ip_to_bytes":
            return lambda s: s  # entries ARE the 16 bytes (latin-1)
        return {"__to_ipaddress": _ip.parse_address,
                "__vb_to_ipaddress": _ip.address_from_bytes,
                "__ip_to_varchar": _ip.format_address,
                "__to_ipprefix": _ip.parse_prefix,
                "__ipprefix_to_varchar": _ip.format_prefix,
                "ip_subnet_min": _ip.subnet_min,
                "ip_subnet_max": _ip.subnet_max}[fn]
    if fn == "scale_tdigest":
        from presto_tpu.expr import tdigest as _td

        factor = float(cargs[0])
        return lambda s, _f=factor: _td.scale(s, _f)
    if fn == "trim":
        return str.strip
    if fn == "ltrim":
        return str.lstrip
    if fn == "rtrim":
        return str.rstrip
    if fn == "reverse":
        return lambda s: s[::-1]
    if fn == "replace":
        old = str(cargs[0])
        new = str(cargs[1]) if len(cargs) > 1 else ""
        return lambda s: s.replace(old, new)
    if fn == "lpad":
        n, fill = int(cargs[0]), str(cargs[1]) if len(cargs) > 1 else " "
        def lpad(s, n=n, fill=fill):
            if len(s) >= n:
                return s[:n]
            pad = (fill * n)[: n - len(s)]
            return pad + s
        return lpad
    if fn == "rpad":
        n, fill = int(cargs[0]), str(cargs[1]) if len(cargs) > 1 else " "
        def rpad(s, n=n, fill=fill):
            if len(s) >= n:
                return s[:n]
            return s + (fill * n)[: n - len(s)]
        return rpad
    if fn == "concat":
        pre, post = str(cargs[0]), str(cargs[1])
        return lambda s: pre + s + post
    if fn == "split_part":
        delim, idx = str(cargs[0]), int(cargs[1])
        def split_part(s, delim=delim, idx=idx):
            parts = s.split(delim)
            return parts[idx - 1] if 0 < idx <= len(parts) else ""
        return split_part
    if fn == "regexp_extract":
        rx = re.compile(str(cargs[0]))
        group = int(cargs[1]) if len(cargs) > 1 and cargs[1] is not None else 0
        def rex(s, rx=rx, group=group):
            m = rx.search(s)
            # Presto returns NULL on no match (and for an unmatched group)
            return m.group(group) if m else None
        return rex
    if fn == "regexp_replace":
        rx = re.compile(str(cargs[0]))
        repl = str(cargs[1]) if len(cargs) > 1 else ""
        # Presto uses $1 for backrefs; python re uses \1
        repl = re.sub(r"\$(\d+)", r"\\\1", repl)
        return lambda s: rx.sub(repl, s)
    if fn == "json_extract_scalar":
        import json as _json

        path = str(cargs[0])
        steps = _parse_json_path(path)
        def jes(s, steps=steps):
            try:
                v = _json.loads(s)
                for st in steps:
                    v = v[st]
            except Exception:
                return None
            if isinstance(v, (dict, list)) or v is None:
                return None  # non-scalar / absent → SQL NULL
            if isinstance(v, bool):
                return "true" if v else "false"
            return str(v)
        return jes
    if fn in ("json_extract", "json_array_get"):
        import json as _json

        steps = ([int(cargs[0])] if fn == "json_array_get"
                 else _parse_json_path(str(cargs[0])))

        def jex(s, steps=steps):
            try:
                v = _json.loads(s)
                for st in steps:
                    v = v[st]
            except Exception:
                return None
            return _json.dumps(v, separators=(",", ":"))
        return jex
    if fn == "json_format":
        import json as _json

        def jfmt(s):
            try:
                return _json.dumps(_json.loads(s), separators=(",", ":"))
            except Exception:
                return None
        return jfmt
    if fn == "json_parse":
        import json as _json

        def jp(s):
            try:
                _json.loads(s)
                return s  # JSON is VARCHAR text here; parse = validate
            except Exception:
                # documented deviation: the reference RAISES on malformed
                # input, but dictionary-wide evaluation visits entries
                # that may belong to filtered-out rows — NULL instead
                return None
        return jp
    raise NotImplementedError(fn)


def _parse_json_path(path: str):
    """Subset of JSONPath used by json_extract_scalar: $.a.b[0]['c']."""
    steps = []
    i = 0
    if path.startswith("$"):
        i = 1
    while i < len(path):
        ch = path[i]
        if ch == ".":
            j = i + 1
            while j < len(path) and path[j] not in ".[":
                j += 1
            steps.append(path[i + 1:j])
            i = j
        elif ch == "[":
            j = path.index("]", i)
            inner = path[i + 1:j].strip()
            if inner[:1] in ("'", '"'):
                steps.append(inner[1:-1])
            else:
                steps.append(int(inner))
            i = j + 1
        else:
            raise ValueError(f"bad json path: {path}")
    return steps


def _str_int_pyfn(fn: str, cargs: tuple):
    if fn == "length":
        return len
    if fn == "strpos":
        sub = str(cargs[0])
        return lambda s: s.find(sub) + 1
    if fn == "codepoint":
        return lambda s: ord(s[0]) if s else 0
    if fn == "json_array_length":
        import json as _json

        def jal(s):
            try:
                v = _json.loads(s)
            except Exception:
                return None
            return len(v) if isinstance(v, list) else None  # NULL
        return jal
    if fn == "json_size":
        import json as _json

        steps = _parse_json_path(str(cargs[0]))

        def jsz(s, steps=steps):
            try:
                v = _json.loads(s)
                for st in steps:
                    v = v[st]
            except Exception:
                return None  # absent path → NULL
            return len(v) if isinstance(v, (dict, list)) else 0
        return jsz
    if fn == "__hll_cardinality":
        from presto_tpu.expr.hll import cardinality as _hll_card

        return _hll_card
    if fn == "bit_length":
        return lambda s: 8 * len(s.encode("utf-8"))
    if fn == "__vb_bit_length":
        return lambda s: 8 * len(s)  # latin-1 bijection: 1 char = 1 byte
    if fn == "date_parse":
        from datetime import datetime as _dt

        raw_fmt = str(cargs[0])
        pyfmt = mysql_format_to_strptime(raw_fmt)
        # strptime defaults missing fields to 1900-01-01; the reference
        # defaults to the 1970 epoch — patch the year when the format
        # carries no year directive (month/day already default to 1)
        has_year = any(f"%{c}" in raw_fmt for c in "Yy")
        epoch = _dt(1970, 1, 1)

        def dparse(s, _fmt=pyfmt, _ep=epoch, _hy=has_year):
            try:
                dt = _dt.strptime(s, _fmt)
            except ValueError:
                return None  # unparseable → NULL (documented deviation)
            if not _hy:
                dt = dt.replace(year=1970)
            td = dt - _ep
            return (td.days * 86_400_000_000 + td.seconds * 1_000_000
                    + td.microseconds)

        return dparse
    if fn == "from_iso8601_date":
        import datetime as _d

        def iso_date(s):
            try:
                return _d.date.fromisoformat(s.strip()).toordinal() - 719163
            except ValueError:
                return None

        return iso_date
    if fn == "from_iso8601_timestamp":
        import datetime as _d

        def iso_ts(s):
            try:
                dt = _d.datetime.fromisoformat(s.strip().replace("Z", "+00:00"))
            except ValueError:
                return None
            if dt.tzinfo is not None:
                dt = dt.astimezone(_d.timezone.utc).replace(tzinfo=None)
            td = dt - _d.datetime(1970, 1, 1)
            return (td.days * 86_400_000_000 + td.seconds * 1_000_000
                    + td.microseconds)

        return iso_ts
    if fn == "levenshtein_distance_c":
        other = str(cargs[0])

        def lev(s, other=other):
            if len(s) < len(other):
                s, other = other, s
            prev = list(range(len(other) + 1))
            for i, ca in enumerate(s):
                cur = [i + 1]
                for j, cb in enumerate(other):
                    cur.append(min(prev[j + 1] + 1, cur[j] + 1,
                                   prev[j] + (ca != cb)))
                prev = cur
            return prev[-1]
        return lev
    if fn == "hamming_distance_c":
        other = str(cargs[0])
        return lambda s: sum(a != b for a, b in zip(s, other)) if len(s) == len(other) else -1
    raise NotImplementedError(fn)


def _str_float_pyfn(fn: str, cargs: tuple):
    """TDIGEST scalar family: digest entry → double (None = SQL NULL)."""
    from presto_tpu.expr import tdigest as _td

    if fn == "value_at_quantile":
        q = float(cargs[0])
        return lambda s, _q=q: _td.value_at_quantile(s, _q)
    if fn == "quantile_at_value":
        v = float(cargs[0])
        return lambda s, _v=v: _td.quantile_at_value(s, _v)
    lo, hi = float(cargs[0]), float(cargs[1])
    return lambda s, _lo=lo, _hi=hi: _td.trimmed_mean(s, _lo, _hi)


def _str_pred_pyfn(fn: str, cargs: tuple):
    if fn == "regexp_like":
        rx = re.compile(str(cargs[0]))
        return lambda s: rx.search(s) is not None
    if fn == "starts_with":
        p = str(cargs[0])
        return lambda s: s.startswith(p)
    if fn == "ends_with":
        p = str(cargs[0])
        return lambda s: s.endswith(p)
    if fn == "contains":
        p = str(cargs[0])
        return lambda s: p in s
    if fn == "json_array_contains":
        import json as _json

        want = cargs[0]

        def jac(s, want=want):
            try:
                v = _json.loads(s)
            except Exception:
                return False
            if not isinstance(v, list):
                return False
            for e in v:
                if isinstance(e, bool) or isinstance(want, bool):
                    if e is want:
                        return True
                elif isinstance(e, str) and isinstance(want, str):
                    if e == want:
                        return True
                elif isinstance(e, (int, float)) and isinstance(
                        want, (int, float)):
                    if float(e) == float(want):
                        return True
            return False
        return jac
    if fn == "__is_subnet_of_c":
        # is_subnet_of(<constant prefix>, column): cargs[0] is the
        # canonical 17-byte prefix entry (builder folds the text form)
        from presto_tpu.expr import ip as _ip

        pfx = str(cargs[0])
        return lambda s, _p=pfx: _ip.is_subnet_of(_p, s)
    if fn == "__prefix_contains_c":
        # is_subnet_of(column, <constant address/prefix>): the operand is
        # the prefix column, the constant the contained value
        from presto_tpu.expr import ip as _ip

        inner = str(cargs[0])
        return lambda s, _i=inner: _ip.is_subnet_of(s, _i)
    if fn == "is_json_scalar":
        import json as _json

        def ijs(s):
            try:
                return not isinstance(_json.loads(s), (dict, list))
            except Exception:
                return False
        return ijs
    raise NotImplementedError(fn)


def _xform_parts(e: Call):
    """Split a string-function call into (string_operand, const_args_key).
    For concat, the single non-constant operand with (prefix, suffix)."""
    if e.fn == "concat":
        pre, post, operand = [], [], None
        for a in e.args:
            if isinstance(a, Constant):
                (pre if operand is None else post).append(
                    None if a.value is None else str(a.value)
                )
            elif operand is None:
                operand = a
            else:
                raise NotImplementedError(
                    "concat of two non-constant strings (cross-product "
                    "dictionary) not supported"
                )
        if operand is None:
            raise NotImplementedError("all-constant concat should fold")
        if any(p is None for p in pre + post):
            return operand, None  # NULL operand poisons the whole concat
        return operand, ("".join(pre), "".join(post))
    consts = []
    for a in e.args[1:]:
        if not isinstance(a, Constant):
            raise NotImplementedError(
                f"{e.fn}: non-constant argument {a} not supported "
                "(dictionary transforms need plan-time constants)"
            )
        consts.append(a.value)
    return e.args[0], tuple(consts)


class CompileContext:
    """Static info the compiler needs beyond the IR: the dictionaries of the
    string columns flowing through this fragment, captured at trace time from
    the Batch itself. `out_dict` is the synthesized dictionary for
    string-valued expressions built purely from constants (e.g. CASE WHEN ..
    THEN 'promo' ELSE 'other')."""

    def __init__(self, batch: Batch, out_dict: Dictionary | None = None,
                 extra_dicts: dict | None = None):
        self.batch = batch
        self.out_dict = out_dict
        # lambda-parameter dictionaries (symbol -> Dictionary): params are
        # not batch columns, but string params carry the element dict
        self.extra_dicts = extra_dicts or {}

    def dict_for(self, e: RowExpression) -> Dictionary | None:
        if isinstance(e, InputRef):
            if e.name in self.extra_dicts:
                return self.extra_dicts[e.name]
            return self.batch.dict_of(e.name)
        if isinstance(e, Call):
            if e.fn in _STR_TO_STR:
                nd, _, _ = self.transformed(e)
                return nd
            from presto_tpu.types import ArrayType as _AT, MapType as _MT

            if (e.fn in ("subscript", "element_at") and e.args
                    and isinstance(e.args[0].type, (_AT, _MT))):
                # codes come from the structural operand's element plane
                # (for ARRAY[...] ctors that is the merged literal+column
                # dictionary — the plain arg walk below would return the
                # unmerged column dict and mis-decode)
                return _elem_dict(e.args[0], self)
            for a in e.args:
                d = self.dict_for(a)
                if d is not None:
                    return d
        return None

    def transformed(self, e: Call):
        """(new_dict, remap, operand) for a string-transform call, memoized
        on the operand's dictionary so jit retraces get identical objects.
        remap=None signals a constant-NULL result (NULL in concat)."""
        operand, cargs = _xform_parts(e)
        if cargs is None:
            return None, None, operand
        d = self.dict_for(operand)
        if d is None:
            raise ValueError(f"string function {e.fn} needs a dictionary operand")
        nd, remap = d.transform((e.fn, cargs), _str_xform_pyfn(e.fn, cargs))
        return nd, remap, operand


# ---------------------------------------------------------------------------
# main entry


def _has_string_payload(t: Type) -> bool:
    if t.is_string:
        return True
    if isinstance(t, ArrayType):
        return _has_string_payload(t.element)
    if isinstance(t, MapType):
        return t.key.is_string or t.value.is_string
    return False


def string_output_dictionary(e: RowExpression) -> Dictionary | None:
    """For an expression whose string *values* are all literals (CASE tags,
    ARRAY['a','b'] elements, map() keys and the like), build the
    dictionary those literals resolve against at plan time. Non-string
    output types still need this when structural literals appear inside
    (element_at(map(ARRAY['a'], ...), 'a') is DOUBLE-typed)."""
    if isinstance(e, InputRef):
        return None
    consts: list[str] = []

    def walk(x, value_pos: bool):
        if isinstance(x, Constant) and x.type.is_string and value_pos and x.value is not None:
            consts.append(str(x.value))
        if isinstance(x, Call):
            for i, a in enumerate(x.args):
                # string constants in comparison/LIKE/IN positions resolve
                # against column dictionaries, not the output dictionary
                in_value_pos = x.fn in (
                    "if", "coalesce", "nullif", "array_ctor", "repeat", "map"
                ) or (value_pos and x.fn == "cast")
                walk(a, in_value_pos and a.type.is_string)

    walk(e, True)
    if not consts:
        return None
    import numpy as np

    from presto_tpu.dictionary import safe_str_array

    return Dictionary(np.unique(safe_str_array(
        np.asarray(consts, dtype=object))))


def compile_expr(e: RowExpression):
    """Return fn(batch) -> (values, validity|None)."""
    out_dict = string_output_dictionary(e)

    def fn(batch: Batch):
        ctx = CompileContext(batch, out_dict)
        return _eval(e, ctx)

    fn.out_dict = None
    if isinstance(e.type, (ArrayType, MapType)) and not isinstance(e, InputRef):
        # structural output: (element_dict, key_dict) resolved at trace time
        def sdicts(batch: Batch):
            return struct_dicts(e, CompileContext(batch, out_dict))

        fn.sdicts = sdicts
    if e.type.is_string and not isinstance(e, InputRef):
        # dictionary of the output column depends on the input batch's
        # dictionaries (string transforms, structural subscripts); resolved
        # at trace time — batch dicts are static pytree aux, so this is
        # jit-cache coherent. All-literal expressions (CASE tags) fall back
        # to the plan-time literal dictionary.
        def dyn_dict(batch: Batch):
            d = CompileContext(batch, out_dict).dict_for(e)
            return d if d is not None else out_dict

        fn.dyn_dict = dyn_dict
    return fn


def compile_predicate(e: RowExpression):
    """Return fn(batch) -> bool mask (NULL → False, like Presto filters:
    operator/project/PageFilter discards non-TRUE rows)."""
    out_dict = string_output_dictionary(e)

    def fn(batch: Batch):
        ctx = CompileContext(batch, out_dict)
        v, valid = _eval(e, ctx)
        mask = v.astype(bool)
        if valid is not None:
            mask = mask & valid
        return mask

    return fn


# ---------------------------------------------------------------------------
# evaluation (at trace time)


def _eval(e: RowExpression, ctx: CompileContext):
    if isinstance(e, InputRef):
        c = ctx.batch.column(e.name)
        if c.sizes is not None:
            return StructVal(c.values, c.sizes, c.evalid, c.keys), c.validity
        if c.hi is not None:
            # long decimal (two-limb int128): expressions compute over the
            # combined float64 unscaled value — exact below 2^53, the lossy
            # escape hatch for arithmetic over aggregated sums
            return c.combined_f64(), c.validity
        return c.values, c.validity
    if isinstance(e, Constant):
        return _eval_constant(e, ctx, None)
    if isinstance(e, Call):
        return _eval_call(e, ctx)
    raise NotImplementedError(f"cannot compile {e!r}")


def _eval_constant(e: Constant, ctx: CompileContext, sibling: RowExpression | None):
    """Constants; string constants resolve against the sibling's dictionary."""
    if e.value is None:
        cap = ctx.batch.capacity
        return (
            jnp.zeros(cap, dtype=e.type.dtype),
            jnp.zeros(cap, dtype=bool),
        )
    if e.raw:
        return _const_array(e.value, e.type), None
    if e.type.is_string:
        d = ctx.dict_for(sibling) if sibling is not None else None
        if d is None:
            d = ctx.out_dict
        if d is None:
            raise ValueError("string constant without dictionary context")
        code = d.code_of(str(e.value))
        return jnp.asarray(code, dtype=jnp.int32), None
    if isinstance(e.type, DecimalType):
        unscaled = int(round(float(e.value) * (10 ** e.type.scale)))
        return _const_array(unscaled, e.type), None
    return _const_array(e.value, e.type), None


def _eval_arg(a: RowExpression, ctx, sibling=None):
    if isinstance(a, Constant):
        return _eval_constant(a, ctx, sibling)
    return _eval(a, ctx)


_CMP = {
    "eq": jnp.equal,
    "ne": jnp.not_equal,
    "lt": jnp.less,
    "le": jnp.less_equal,
    "gt": jnp.greater,
    "ge": jnp.greater_equal,
}


_STRUCT_ONLY_FNS = {
    "array_ctor", "array_position", "array_min", "array_max", "array_sum",
    "array_average", "array_distinct", "array_sort", "slice", "sequence",
    "repeat", "map", "map_keys", "map_values",
    "transform", "filter", "reduce", "any_match", "all_match", "none_match",
    "transform_values", "map_filter",
    "array_union", "array_intersect", "array_except", "arrays_overlap",
    "map_concat", "zip_with", "split", "regexp_split", "array_remove",
}
# polymorphic names: structural only when the first arg is ARRAY/MAP
_STRUCT_POLY_FNS = {"cardinality", "contains", "concat", "element_at",
                    "subscript"}


_GEO_FNS = {
    "st_geometryfromtext", "st_point", "st_x", "st_y", "st_distance",
    "st_contains", "st_intersects", "st_area", "st_perimeter", "st_length",
    "st_npoints", "st_xmin", "st_xmax", "st_ymin", "st_ymax", "st_centroid",
    "great_circle_distance",
}


def _eval_call(e: Call, ctx: CompileContext):
    fn = e.fn

    # ---- registered (plugin/user) scalars --------------------------------
    # the analyzer tags them "udf:<name>" so built-ins can never be
    # shadowed (presto_tpu/functions.py — FunctionManager analog); the
    # lowering traces straight into the surrounding fused XLA program
    if fn.startswith("udf:"):
        from presto_tpu.functions import registry as _freg

        udf = _freg().scalar(fn[4:])
        if udf is None:
            raise ValueError(f"function {fn[4:]} is no longer registered")
        cap = ctx.batch.capacity
        vals, valids = [], []
        for a in e.args:
            v, va = _eval_arg(a, ctx)
            if getattr(v, "ndim", 1) == 0:
                v = jnp.broadcast_to(v, (cap,))
            vals.append(v)
            valids.append(va)
        agg_valid = None
        for va in valids:
            agg_valid = _and_valid(agg_valid, va)
        if udf.null_propagating:
            return udf.lower(*vals), agg_valid
        return udf.lower(vals, valids)

    # ---- geospatial ------------------------------------------------------
    if fn in _GEO_FNS:
        return _eval_geo(e, ctx)

    # ---- structural (ARRAY / MAP) ---------------------------------------
    if fn in _STRUCT_ONLY_FNS or (
        fn in _STRUCT_POLY_FNS
        and e.args
        and isinstance(e.args[0].type, (ArrayType, MapType))
    ):
        return _eval_structural(e, ctx)

    # ---- comparisons (incl. dictionary-code string compares) -------------
    if fn in _CMP:
        l, r = e.args
        if l.type.is_string or r.type.is_string:
            return _string_compare(fn, l, r, ctx)
        lv, lval = _eval_arg(l, ctx, r)
        rv, rval = _eval_arg(r, ctx, l)
        lv, rv = _numeric_align(l.type, r.type, lv, rv)
        return _CMP[fn](lv, rv), _and_valid(lval, rval)

    # ---- boolean (Kleene) ------------------------------------------------
    if fn == "and":
        vals, valids = zip(*[_eval_arg(a, ctx) for a in e.args])
        v = vals[0].astype(bool)
        for x in vals[1:]:
            v = v & x.astype(bool)
        # AND is null iff no operand is definitively false and any is null
        known_false = jnp.zeros_like(v)
        any_null = None
        for x, va in zip(vals, valids):
            if va is not None:
                known_false = known_false | (~x.astype(bool) & va)
                any_null = (
                    ~va if any_null is None else (any_null | ~va)
                )
            else:
                known_false = known_false | ~x.astype(bool)
        if any_null is None:
            return v, None
        valid = known_false | ~any_null
        return v & valid, valid
    if fn == "or":
        vals, valids = zip(*[_eval_arg(a, ctx) for a in e.args])
        v = vals[0].astype(bool)
        for x in vals[1:]:
            v = v | x.astype(bool)
        known_true = jnp.zeros_like(v)
        any_null = None
        for x, va in zip(vals, valids):
            if va is not None:
                known_true = known_true | (x.astype(bool) & va)
                any_null = ~va if any_null is None else (any_null | ~va)
            else:
                known_true = known_true | x.astype(bool)
        if any_null is None:
            return v, None
        valid = known_true | ~any_null
        return v, valid
    if fn == "not":
        v, valid = _eval_arg(e.args[0], ctx)
        return ~v.astype(bool), valid

    # ---- null handling ---------------------------------------------------
    if fn == "is_null":
        v, valid = _eval_arg(e.args[0], ctx)
        if valid is None:
            return jnp.zeros(jnp.shape(v), dtype=bool), None
        return ~valid, None
    if fn == "is_not_null":
        v, valid = _eval_arg(e.args[0], ctx)
        if valid is None:
            return jnp.ones(jnp.shape(v), dtype=bool), None
        return valid, None
    if fn == "coalesce":
        out_v, out_valid = _eval_arg(e.args[0], ctx)
        out_v = out_v.astype(e.type.dtype)
        for a in e.args[1:]:
            av, avalid = _eval_arg(a, ctx)
            av = av.astype(e.type.dtype)
            if out_valid is None:
                break
            out_v = jnp.where(out_valid, out_v, av)
            out_valid = out_valid | (
                avalid if avalid is not None else jnp.ones_like(out_valid)
            )
            if avalid is None:
                out_valid = None if out_valid is None else jnp.ones_like(out_v, dtype=bool)
                # fully covered
                return out_v, None
        return out_v, out_valid
    if fn == "nullif":
        av, avalid = _eval_arg(e.args[0], ctx, e.args[1])
        bv, bvalid = _eval_arg(e.args[1], ctx, e.args[0])
        eq = av == bv
        if bvalid is not None:
            eq = eq & bvalid
        valid = avalid if avalid is not None else jnp.ones(jnp.shape(av), bool)
        return av, valid & ~eq

    # ---- control flow ----------------------------------------------------
    if fn == "if":
        cond, then, els = e.args
        cv, cvalid = _eval_arg(cond, ctx)
        cmask = cv.astype(bool)
        if cvalid is not None:
            cmask = cmask & cvalid
        tv, tvalid = _eval_arg(then, ctx, els)
        ev, evalid = _eval_arg(els, ctx, then)
        tv = tv.astype(e.type.dtype)
        ev = ev.astype(e.type.dtype)
        out = jnp.where(cmask, tv, ev)
        if tvalid is None and evalid is None:
            return out, None
        tva = tvalid if tvalid is not None else jnp.ones(jnp.shape(out), bool)
        eva = evalid if evalid is not None else jnp.ones(jnp.shape(out), bool)
        return out, jnp.where(cmask, tva, eva)

    # ---- membership ------------------------------------------------------
    if fn == "in":
        val = e.args[0]
        if val.type.is_string:
            d = ctx.dict_for(val)
            codes = [d.code_of(str(c.value)) for c in e.args[1:]]
            vv, vvalid = _eval(val, ctx)
            m = jnp.zeros(jnp.shape(vv), dtype=bool)
            for c in codes:
                m = m | (vv == c)
            return m, vvalid
        vv, vvalid = _eval_arg(val, ctx)
        m = jnp.zeros(jnp.shape(vv), dtype=bool)
        for c in e.args[1:]:
            cv, _ = _eval_arg(c, ctx, val)
            m = m | (vv == cv)
        return m, vvalid
    if fn == "between":
        v, lo, hi = e.args
        ge = _eval_call(Call(BOOLEAN, "ge", (v, lo)), ctx)
        le = _eval_call(Call(BOOLEAN, "le", (v, hi)), ctx)
        return ge[0] & le[0], _and_valid(ge[1], le[1])

    # ---- LIKE over dictionary -------------------------------------------
    if fn == "like":
        val, pat = e.args[0], e.args[1]
        escape = str(e.args[2].value) if len(e.args) > 2 else None
        d = ctx.dict_for(val)
        if d is None:
            raise ValueError("LIKE on non-dictionary column")
        table = like_table(d, pat.value, escape)
        vv, vvalid = _eval(val, ctx)
        out = jnp.asarray(table)[vv + 1]
        return out, vvalid

    # ---- string functions over dictionaries ------------------------------
    if fn in _STR_TO_STR:
        _, remap, operand = ctx.transformed(e)
        if remap is None:  # NULL constant operand → NULL result
            cap = ctx.batch.capacity
            return jnp.zeros(cap, jnp.int32), jnp.zeros(cap, bool)
        codes, valid = _eval(operand, ctx)
        out = jnp.asarray(remap)[codes + 1]
        if bool((remap[1:] < 0).any()):
            # transform produced NULLs (regexp_extract no-match, absent
            # json path): a negative new code means SQL NULL
            nullable = out >= 0
            valid = nullable if valid is None else (valid & nullable)
        return out, valid
    if fn in _STR_TO_FLOAT:
        # digest entry → double, with a parallel null lut (invalid digest
        # or out-of-domain argument → SQL NULL)
        operand, cargs = _xform_parts(e)
        d = ctx.dict_for(operand)
        if d is None:
            raise ValueError(f"{fn} needs a dictionary operand")
        pyfn = _str_float_pyfn(fn, cargs)
        fmemo: dict = {}

        def ff(s, _m=fmemo, _f=pyfn):
            if s not in _m:
                _m[s] = _f(s)
            return _m[s]

        table = d.int_lut((fn, cargs, "v"),
                          lambda s: ff(s) if ff(s) is not None else 0.0,
                          dtype=np.float64)
        nulls = d.int_lut((fn, cargs, "null"),
                          lambda s: ff(s) is None, dtype=np.bool_)
        codes, valid = _eval(operand, ctx)
        notnull = ~jnp.asarray(nulls)[codes + 1]
        valid = notnull if valid is None else valid & notnull
        return jnp.asarray(table)[codes + 1], valid
    if fn in _STR_TO_INT or fn in _STR_PRED:
        operand, cargs = _xform_parts(e)
        d = ctx.dict_for(operand)
        if d is None:
            raise ValueError(f"{fn} needs a dictionary operand")
        if fn in _STR_TO_INT:
            pyfn = _str_int_pyfn(fn, cargs)
            if fn in _STR_INT_NULLABLE:
                memo: dict = {}  # one parse per entry, not one per lut

                def pf(s, _m=memo, _f=pyfn):
                    if s not in _m:
                        _m[s] = _f(s)
                    return _m[s]

                table = d.int_lut((fn, cargs, "v"),
                                  lambda s: pf(s) or 0)
                nulls = d.int_lut((fn, cargs, "null"),
                                  lambda s: pf(s) is None, dtype=np.bool_)
                codes, valid = _eval(operand, ctx)
                notnull = ~jnp.asarray(nulls)[codes + 1]
                valid = notnull if valid is None else valid & notnull
                # e.type drives the device dtype (DATE luts are int32)
                return jnp.asarray(table)[codes + 1].astype(e.type.dtype), valid
            table = d.int_lut((fn, cargs), pyfn)
        else:
            table = d.int_lut((fn, cargs), _str_pred_pyfn(fn, cargs),
                              dtype=np.bool_)
        codes, valid = _eval(operand, ctx)
        return jnp.asarray(table)[codes + 1], valid

    # ---- HyperLogLog primitives (approx_distinct lowering) ----------------
    # __hll_reg(x): register index = low log2(m) bits of a 64-bit content
    # hash; __hll_rank(x): 1 + leading-zero count of the top 32 hash bits
    # (ranks 1..33 — counts to ~2^32 distinct). The builder lowers
    # approx_distinct into (reg, max(rank)) aggregates over these
    # (reference: ApproximateCountDistinctAggregations' HLL state; here the
    # registers ARE group-table rows so the state rides the existing
    # partial/exchange/final machinery).
    if fn in ("__hll_reg", "__hll_rank"):
        from presto_tpu.ops.hashing import splitmix64

        a = e.args[0]
        av, avalid = _eval(a, ctx)
        if a.type.is_string:
            d = ctx.dict_for(a)
            lut = jnp.asarray(d.content_hash_lut())
            h = splitmix64(lut[av.astype(jnp.int32) + 1].astype(jnp.uint64))
        elif jnp.issubdtype(av.dtype, jnp.floating):
            # hash the BIT PATTERN — astype(int64) would value-truncate and
            # collapse all sub-integer-distinct doubles onto one hash
            bits = jax.lax.bitcast_convert_type(
                av.astype(jnp.float64), jnp.int64)
            # canonicalize -0.0 → +0.0 so equal SQL values hash equal
            bits = jnp.where(av == 0.0, jnp.int64(0), bits)
            h = splitmix64(bits)
        else:
            h = splitmix64(av.astype(jnp.int64))
        if fn == "__hll_reg":
            return (h & jnp.uint64(HLL_M - 1)).astype(jnp.int64), avalid
        w = ((h >> jnp.uint64(32)) & jnp.uint64(0xFFFFFFFF)).astype(jnp.int64)
        f = jnp.maximum(w.astype(jnp.float64), 1.0)
        rank = jnp.where(w == 0, 33, 32 - jnp.floor(jnp.log2(f)))
        return rank.astype(jnp.int64), avalid

    # ---- quantile-sketch primitive (approx_percentile lowering) ----------
    # __qsk_bucket(x): order-preserving quantization of x as float64 —
    # monotone IEEE-754 integer encoding truncated to its top 24 bits
    # (sign + exponent + 12 mantissa bits → value-space relative error
    # ≤ 2⁻¹² per bucket). The sketch is a histogram over these buckets
    # (reference: qdigest's value-universe compression).
    if fn == "__qsk_bucket":
        av, avalid = _eval_arg(e.args[0], ctx)
        x = av.astype(jnp.float64)
        x = jnp.where(x == 0.0, 0.0, x)  # canonicalize -0.0
        bits = jax.lax.bitcast_convert_type(x, jnp.uint64)
        flip = jnp.where(
            bits >> jnp.uint64(63),
            jnp.uint64(0xFFFFFFFFFFFFFFFF),
            jnp.uint64(0x8000000000000000),
        )
        mono = bits ^ flip
        return (mono >> jnp.uint64(40)).astype(jnp.int64), avalid

    if fn == "__host_date_format":
        raise NotImplementedError(
            "date_format is supported in the top-level SELECT list only "
            "(it is a host finishing projection)")

    # ---- cast ------------------------------------------------------------
    if fn == "cast":
        return _eval_cast(e, ctx)

    # ---- arithmetic ------------------------------------------------------
    if fn in ("add", "sub", "mul", "div", "mod"):
        return _eval_arith(e, ctx)
    if fn == "neg":
        v, valid = _eval_arg(e.args[0], ctx)
        return -v, valid
    if fn == "abs":
        v, valid = _eval_arg(e.args[0], ctx)
        return jnp.abs(v), valid

    # ---- math ------------------------------------------------------------
    _MATH = {
        "sqrt": jnp.sqrt,
        "exp": jnp.exp,
        "ln": jnp.log,
        "floor": jnp.floor,
        "ceil": jnp.ceil,
        "sin": jnp.sin,
        "cos": jnp.cos,
        "tan": jnp.tan,
        "asin": jnp.arcsin,
        "acos": jnp.arccos,
        "atan": jnp.arctan,
        "sinh": jnp.sinh,
        "cosh": jnp.cosh,
        "tanh": jnp.tanh,
        "log2": jnp.log2,
        "log10": jnp.log10,
        "cbrt": jnp.cbrt,
        "degrees": jnp.degrees,
        "radians": jnp.radians,
        "sign": jnp.sign,
        "truncate": jnp.trunc,
    }
    if fn in _MATH:
        v, valid = _eval_arg(e.args[0], ctx)
        return _MATH[fn](v.astype(e.type.dtype)), valid
    if fn == "atan2":
        a, avalid = _eval_arg(e.args[0], ctx)
        b, bvalid = _eval_arg(e.args[1], ctx)
        return jnp.arctan2(a.astype(e.type.dtype), b.astype(e.type.dtype)), _and_valid(avalid, bvalid)
    if fn in ("greatest", "least"):
        # SQL: NULL if any argument is NULL (Presto MathFunctions.greatest)
        op = jnp.maximum if fn == "greatest" else jnp.minimum
        out_v, out_valid = _eval_arg(e.args[0], ctx)
        out_v = out_v.astype(e.type.dtype)
        for a in e.args[1:]:
            av, avalid = _eval_arg(a, ctx)
            out_v = op(out_v, av.astype(e.type.dtype))
            out_valid = _and_valid(out_valid, avalid)
        return out_v, out_valid
    if fn == "round":
        # SQL ROUND is half-away-from-zero (Presto MathFunctions.round),
        # not jnp.round's half-to-even
        v, valid = _eval_arg(e.args[0], ctx)
        if isinstance(e.type, DecimalType):
            if len(e.args) > 1:
                digits = int(e.args[1].value)
            else:
                digits = 0
            src_scale = e.args[0].type.scale
            if digits >= src_scale:
                return v, valid
            f = 10 ** (src_scale - digits)
            return _div_half_away(v, f) * f, valid
        if len(e.args) > 1:
            digits = int(e.args[1].value)
            f = 10.0 ** digits
            return _round_half_away(v * f) / f, valid
        return _round_half_away(v), valid
    if fn == "power":
        a, avalid = _eval_arg(e.args[0], ctx)
        b, bvalid = _eval_arg(e.args[1], ctx)
        return jnp.power(a.astype(e.type.dtype), b.astype(e.type.dtype)), _and_valid(avalid, bvalid)
    if fn in ("bitwise_and", "bitwise_or", "bitwise_xor",
              "bitwise_left_shift", "bitwise_right_shift"):
        a, avalid = _eval_arg(e.args[0], ctx)
        b, bvalid = _eval_arg(e.args[1], ctx)
        a = a.astype(jnp.int64)
        b = b.astype(jnp.int64)
        out = {
            "bitwise_and": lambda: a & b,
            "bitwise_or": lambda: a | b,
            "bitwise_xor": lambda: a ^ b,
            "bitwise_left_shift": lambda: a << b,
            "bitwise_right_shift": lambda: jax.lax.shift_right_logical(a, b),
        }[fn]()
        return out, _and_valid(avalid, bvalid)
    if fn == "bitwise_not":
        v, valid = _eval_arg(e.args[0], ctx)
        return ~v.astype(jnp.int64), valid
    if fn in ("is_nan", "is_finite", "is_infinite"):
        v, valid = _eval_arg(e.args[0], ctx)
        out = {"is_nan": jnp.isnan, "is_finite": jnp.isfinite,
               "is_infinite": jnp.isinf}[fn](v.astype(jnp.float64))
        return out, valid
    if fn == "from_unixtime":
        v, valid = _eval_arg(e.args[0], ctx)
        return (v.astype(jnp.float64) * 1e6).astype(jnp.int64), valid
    if fn == "to_unixtime":
        v, valid = _eval_arg(e.args[0], ctx)
        return v.astype(jnp.float64) / 1e6, valid
    if fn == "width_bucket":
        v, valid = _eval_arg(e.args[0], ctx)
        lo = float(e.args[1].value)
        hi = float(e.args[2].value)
        nb = int(e.args[3].value)
        x = v.astype(jnp.float64)
        bucket = jnp.floor((x - lo) / (hi - lo) * nb).astype(jnp.int64) + 1
        bucket = jnp.clip(bucket, 0, nb + 1)
        return bucket, valid

    # ---- date ------------------------------------------------------------
    def _as_days(a, v):
        # TIMESTAMP operands (micros since epoch) reduce to civil days;
        # DATE is already days
        if a.type.name == "timestamp":
            return jnp.floor_divide(v.astype(jnp.int64),
                                    86_400_000_000).astype(jnp.int32)
        return v.astype(jnp.int32)

    if fn in ("year", "month", "day"):
        v, valid = _eval_arg(e.args[0], ctx)
        y, m, d = _civil_from_days(_as_days(e.args[0], v))
        return {"year": y, "month": m, "day": d}[fn].astype(jnp.int64), valid
    if fn == "quarter":
        v, valid = _eval_arg(e.args[0], ctx)
        _, m, _ = _civil_from_days(_as_days(e.args[0], v))
        return ((m - 1) // 3 + 1).astype(jnp.int64), valid
    if fn in ("__time_hour", "__time_minute", "__time_second"):
        # TIME (micros-of-day) and TIMESTAMP (micros-since-epoch) both
        # reduce mod one day
        v, valid = _eval_arg(e.args[0], ctx)
        tod = jnp.mod(v.astype(jnp.int64), 86_400_000_000)
        if fn == "__time_hour":
            out = tod // 3_600_000_000
        elif fn == "__time_minute":
            out = (tod // 60_000_000) % 60
        else:
            out = (tod // 1_000_000) % 60
        return out, valid
    if fn == "day_of_week":
        # ISO: 1 = Monday … 7 = Sunday; epoch day 0 (1970-01-01) is Thursday
        v, valid = _eval_arg(e.args[0], ctx)
        return (jnp.mod(_as_days(e.args[0], v).astype(jnp.int64) + 3, 7)
                + 1), valid
    if fn == "day_of_year":
        v, valid = _eval_arg(e.args[0], ctx)
        days = _as_days(e.args[0], v)
        y, _, _ = _civil_from_days(days)
        return (days - _days_from_civil_vec(y, 1, 1) + 1).astype(jnp.int64), valid
    if fn == "date_add_days":
        v, valid = _eval_arg(e.args[0], ctx)
        dv, dvalid = _eval_arg(e.args[1], ctx)
        return v + dv.astype(v.dtype), _and_valid(valid, dvalid)
    if fn == "date_trunc":
        unit = str(e.args[0].value).lower()
        v, valid = _eval_arg(e.args[1], ctx)
        days = v.astype(jnp.int32)
        if unit == "day":
            return days, valid
        if unit == "week":
            return days - jnp.mod(days + 3, 7), valid
        y, m, _ = _civil_from_days(days)
        if unit == "month":
            return _days_from_civil_vec(y, m, 1), valid
        if unit == "quarter":
            return _days_from_civil_vec(y, ((m - 1) // 3) * 3 + 1, 1), valid
        if unit == "year":
            return _days_from_civil_vec(y, 1, 1), valid
        raise NotImplementedError(f"date_trunc unit {unit}")
    if fn == "date_diff":
        unit = str(e.args[0].value).lower()
        a, avalid = _eval_arg(e.args[1], ctx)
        b, bvalid = _eval_arg(e.args[2], ctx)
        valid = _and_valid(avalid, bvalid)
        a64, b64 = a.astype(jnp.int64), b.astype(jnp.int64)
        if unit == "day":
            return b64 - a64, valid
        if unit == "week":
            return (b64 - a64) // 7, valid
        ya, ma, da = _civil_from_days(a.astype(jnp.int32))
        yb, mb, db = _civil_from_days(b.astype(jnp.int32))
        months = (yb.astype(jnp.int64) * 12 + mb) - (ya.astype(jnp.int64) * 12 + ma)
        # truncate toward zero on the day-of-month remainder
        months = months - jnp.where((months > 0) & (db < da), 1, 0)
        months = months + jnp.where((months < 0) & (db > da), 1, 0)
        if unit == "month":
            return months, valid
        if unit == "quarter":
            return months // 3, valid
        if unit == "year":
            return months // 12, valid
        raise NotImplementedError(f"date_diff unit {unit}")
    if fn == "date_add_unit":
        unit = str(e.args[0].value).lower()
        n, nvalid = _eval_arg(e.args[1], ctx)
        v, valid = _eval_arg(e.args[2], ctx)
        valid = _and_valid(valid, nvalid)
        days = v.astype(jnp.int32)
        n = n.astype(jnp.int32)
        if unit == "day":
            return days + n, valid
        if unit == "week":
            return days + 7 * n, valid
        y, m, d = _civil_from_days(days)
        mult = {"month": 1, "quarter": 3, "year": 12}.get(unit)
        if mult is None:
            raise NotImplementedError(f"date_add unit {unit}")
        total = y * 12 + (m - 1) + n * mult
        y2 = total // 12
        m2 = jnp.mod(total, 12) + 1
        d2 = jnp.minimum(d, _days_in_month(y2, m2))
        return _days_from_civil_vec(y2, m2, d2), valid

    raise NotImplementedError(f"scalar function not implemented: {fn}")


# ---------------------------------------------------------------------------
# structural (ARRAY / MAP) evaluation


def _array_ctor_dict(e: Call, ctx: CompileContext) -> Dictionary | None:
    """Element dictionary of ARRAY[...] over string operands: the UNION of
    every operand column's dictionary and the literal elements — a literal
    absent from a column dictionary must still get a real code (operand
    codes are remapped into this union at evaluation time)."""
    import numpy as np

    d = None
    for a in e.args:
        if isinstance(a, Constant):
            continue
        ad = ctx.dict_for(a)
        if ad is not None:
            d = ad if d is None or d is ad else Dictionary.merge(d, ad)
    lits = sorted({str(a.value) for a in e.args
                   if isinstance(a, Constant) and a.value is not None})
    if lits:
        # object dtype: dtype=str would drop trailing NULs of canonical
        # VARBINARY/IPADDRESS entries (dictionary.safe_str_array)
        ld, _ = Dictionary.encode(np.asarray(lits, dtype=object))
        d = ld if d is None else Dictionary.merge(d, ld)
    return d


def _setop_elem_dict(e: Call, ctx: CompileContext) -> Dictionary | None:
    """Merged element dictionary across every operand of an array/map
    set-style function (codes must share one space to compare)."""
    from presto_tpu.types import ArrayType as _AT, MapType as _MT

    t0 = e.args[0].type
    elem = t0.element if isinstance(t0, _AT) else t0.value
    if not elem.is_string:
        return None
    d = None
    for a in e.args:
        ad = _elem_dict(a, ctx)
        if ad is not None:
            d = ad if d is None or d is ad else Dictionary.merge(d, ad)
    return d


def _setop_key_dict(e: Call, ctx: CompileContext) -> Dictionary | None:
    d = None
    for a in e.args:
        ad = _key_dict(a, ctx)
        if ad is not None:
            d = ad if d is None or d is ad else Dictionary.merge(d, ad)
    return d


def regexp_split_pieces(pattern: str):
    """Splitter matching the reference: capture groups in the pattern
    must NOT leak into the result (Python re.split interleaves them at
    positions that are not multiples of groups+1)."""
    rx = re.compile(pattern)
    if not rx.groups:
        return rx.split
    step = rx.groups + 1
    return lambda s, _rx=rx, _st=step: _rx.split(s)[::_st]


def _split_tables(d: Dictionary, fn: str, cargs: tuple):
    """split/regexp_split over a dictionary: per-entry piece lists →
    (element_dict, [len+1, W] code plane, [len+1] sizes), row 0 = NULL.
    Memoized on the dictionary like transform()."""
    key = ("__split", fn, cargs)
    hit = d._memo.get(key)
    if hit is not None:
        return hit
    if fn == "split":
        delim = str(cargs[0])
        limit = int(cargs[1]) if len(cargs) > 1 else None
        # SQL limit = max array size; the last element takes the rest
        splitter = (lambda s: s.split(delim) if limit is None
                    else s.split(delim, limit - 1))
    else:
        splitter = regexp_split_pieces(str(cargs[0]))
    pieces = [splitter(str(v)) for v in d.values]
    from presto_tpu.dictionary import safe_str_array

    uniq = sorted({p for ps in pieces for p in ps}) or [""]
    ed = Dictionary(np.unique(safe_str_array(
        np.asarray(uniq, dtype=object))))
    w = max((len(ps) for ps in pieces), default=1) or 1
    n = len(d.values)
    plane = np.zeros((n + 1, w), np.int32)
    sizes = np.zeros(n + 1, np.int32)
    for i, ps in enumerate(pieces):
        sizes[i + 1] = len(ps)
        for j, p in enumerate(ps):
            plane[i + 1, j] = ed.code_of(p)
    d._memo[key] = (ed, plane, sizes)
    return ed, plane, sizes


def _elem_dict(e: RowExpression, ctx: CompileContext) -> Dictionary | None:
    """Dictionary of a structural expression's (string) element plane."""
    if isinstance(e, InputRef):
        return ctx.batch.dict_of(e.name)
    if isinstance(e, Call):
        if e.fn == "array_ctor" and e.type.element.is_string:
            return _array_ctor_dict(e, ctx)
        if e.fn in ("split", "regexp_split"):
            operand, cargs = _xform_parts(e)
            d = ctx.dict_for(operand)
            return None if d is None else _split_tables(d, e.fn, cargs)[0]
        if e.fn == "array_remove":
            return _elem_dict(e.args[0], ctx)
        if e.fn == "map":
            return _elem_dict(e.args[1], ctx)
        if e.fn == "map_keys":
            return _key_dict(e.args[0], ctx)
        if e.fn in ("array_union", "array_intersect", "array_except",
                    "map_concat"):
            return _setop_elem_dict(e, ctx)
        if e.fn in ("transform", "transform_values"):
            # output element dict = the body's dict with the params bound
            # to the input's element/key dicts (dict transforms are
            # dictionary-level, so no element batch is needed here)
            le = e.args[1]
            bound = dict(ctx.extra_dicts)
            if e.fn == "transform":
                bound[le.params[0][0]] = _elem_dict(e.args[0], ctx)
            else:
                bound[le.params[0][0]] = _key_dict(e.args[0], ctx)
                bound[le.params[1][0]] = _elem_dict(e.args[0], ctx)
            sub = CompileContext(ctx.batch, ctx.out_dict, bound)
            return sub.dict_for(le.body)
        for a in e.args:
            if isinstance(a.type, (ArrayType, MapType)) or a.type.is_string:
                d = _elem_dict(a, ctx) if isinstance(
                    a.type, (ArrayType, MapType)) else ctx.dict_for(a)
                if d is not None:
                    return d
    return ctx.out_dict


def _key_dict(e: RowExpression, ctx: CompileContext) -> Dictionary | None:
    """Dictionary of a map expression's (string) key plane."""
    if isinstance(e, InputRef):
        return ctx.batch.dict_of(e.name + "#keys")
    if isinstance(e, Call):
        if e.fn == "map":
            return _elem_dict(e.args[0], ctx)
        if e.fn in ("transform_values", "map_filter"):
            return _key_dict(e.args[0], ctx)
        if e.fn == "map_concat":
            return _setop_key_dict(e, ctx)
        for a in e.args:
            if isinstance(a.type, MapType):
                d = _key_dict(a, ctx)
                if d is not None:
                    return d
    return None


def struct_dicts(e: RowExpression, ctx: CompileContext):
    """(element_dict, key_dict) a projected structural column should carry."""
    t = e.type
    ed = kd = None
    if isinstance(t, ArrayType) and t.element.is_string:
        ed = _elem_dict(e, ctx)
    if isinstance(t, MapType):
        if t.value.is_string:
            ed = _elem_dict(e, ctx)
        if t.key.is_string:
            kd = _key_dict(e, ctx)
    return ed, kd


def _eval_struct_const(a: Constant, ctx, d: Dictionary | None):
    """A scalar constant appearing inside a structural expression; string
    constants resolve against the element/key dictionary `d`."""
    if a.value is None:
        cap = ctx.batch.capacity
        return jnp.zeros(cap, a.type.dtype), jnp.zeros(cap, bool)
    if a.type.is_string:
        if d is None:
            d = ctx.out_dict
        if d is None:
            raise ValueError("string constant in structural expression "
                             "without a dictionary context")
        return jnp.asarray(d.code_of(str(a.value)), jnp.int32), None
    return _eval_constant(a, ctx, None)


def _eval_structural(e: Call, ctx: CompileContext):
    fn = e.fn
    cap = ctx.batch.capacity

    def scalar_arg(a: RowExpression, d: Dictionary | None = None):
        if isinstance(a, Constant):
            v, valid = _eval_struct_const(a, ctx, d)
        else:
            v, valid = _eval(a, ctx)
        return jnp.broadcast_to(v, (cap,)), valid

    if fn == "array_ctor":
        et = e.type.element
        if et.is_string:
            # unified element dictionary: operand codes remap into the
            # union so column values and literals share one code space
            d = _array_ctor_dict(e, ctx)
            parts = []
            for a in e.args:
                if isinstance(a, Constant):
                    v, valid = _eval_struct_const(a, ctx, d)
                else:
                    v, valid = _eval(a, ctx)
                    ad = ctx.dict_for(a)
                    if ad is not None and ad is not d:
                        remap = jnp.asarray(ad.map_to(d))
                        v = remap[v.astype(jnp.int32) + 1]
                parts.append((jnp.broadcast_to(v, (cap,)), valid))
            return _struct.array_ctor(parts, cap, et.dtype), None
        parts = [scalar_arg(a) for a in e.args]
        return _struct.array_ctor(parts, cap, et.dtype), None

    if fn in ("split", "regexp_split"):
        # per-dictionary-entry expansion (StringFunctions.split): pieces
        # and sizes are host tables over the operand dictionary; rows get
        # them via one 2D gather, so the device never sees text
        operand, cargs = _xform_parts(e)
        d = ctx.dict_for(operand)
        if d is None:
            raise ValueError(f"{fn} needs a dictionary operand")
        _, plane, sizes = _split_tables(d, fn, cargs)
        codes, valid = _eval(operand, ctx)
        return _struct.StructVal(
            jnp.asarray(plane)[codes.astype(jnp.int32) + 1],
            jnp.asarray(sizes)[codes.astype(jnp.int32) + 1], None), valid

    if fn == "array_remove":
        sv0, rvalid0 = _eval(e.args[0], ctx)
        d = (_elem_dict(e.args[0], ctx)
             if e.args[0].type.element.is_string else None)
        xv, xvalid = scalar_arg(e.args[1], d)
        # equality only counts for present, non-null elements; NULL
        # elements are retained (unknown ≠ element, Presto semantics).
        # Mixed numeric widths compare in float64 (truncating 1.5 to an
        # int element dtype would remove the WRONG elements)
        xb = jnp.broadcast_to(xv, (cap,))
        if xb.dtype != sv0.values.dtype:
            equal = (sv0.values.astype(jnp.float64)
                     == xb.astype(jnp.float64)[:, None])
        else:
            equal = sv0.values == xb[:, None]
        keep = sv0.present() & ~(equal & sv0.element_valid())
        out = _struct.filter_elements(sv0, keep)
        # NULL element argument → NULL result (ArrayRemoveFunction)
        return out, _and_valid(rvalid0, xvalid)

    if fn == "sequence":
        lo = int(e.args[0].value)
        hi = int(e.args[1].value)
        step = int(e.args[2].value) if len(e.args) > 2 else (
            1 if hi >= lo else -1)
        return _struct.sequence(lo, hi, step, cap), None

    if fn == "repeat":
        n = int(e.args[1].value)
        et = e.type.element
        d = _elem_dict(e, ctx) if et.is_string else None
        v, valid = scalar_arg(e.args[0], d)
        return _struct.repeat_val(v, valid, n, cap, et.dtype), None

    if fn == "map":
        ksv, kvalid = _eval(e.args[0], ctx)
        vsv, vvalid = _eval(e.args[1], ctx)
        return _struct.map_from_arrays(ksv, vsv), _and_valid(kvalid, vvalid)

    if fn == "reduce":
        return _eval_reduce(e, ctx)

    if fn == "zip_with":
        return _eval_zip_with(e, ctx)

    # remaining forms evaluate their structural operand first
    sv, rvalid = _eval(e.args[0], ctx)
    t0 = e.args[0].type

    if fn == "cardinality":
        return _struct.cardinality(sv, rvalid)
    if fn in ("subscript", "element_at"):
        if isinstance(t0, MapType):
            d = _key_dict(e.args[0], ctx) if t0.key.is_string else None
            kv, kvalid = scalar_arg(e.args[1], d)
            return _struct.map_element_at(sv, kv, kvalid, rvalid)
        iv, ivalid = scalar_arg(e.args[1])
        return _struct.subscript(sv, iv.astype(jnp.int64), ivalid, rvalid,
                                 null_oob=(fn == "element_at"))
    if fn == "contains":
        d = _elem_dict(e.args[0], ctx) if t0.element.is_string else None
        xv, xvalid = scalar_arg(e.args[1], d)
        return _struct.contains(sv, xv, xvalid, rvalid)
    if fn == "array_position":
        d = _elem_dict(e.args[0], ctx) if t0.element.is_string else None
        xv, xvalid = scalar_arg(e.args[1], d)
        return _struct.array_position(sv, xv, xvalid, rvalid)
    if fn in ("array_min", "array_max"):
        return _struct.array_minmax(sv, rvalid, fn == "array_min")
    if fn in ("array_sum", "array_average"):
        return _struct.array_sum(sv, rvalid, e.type.dtype,
                                 fn == "array_average")
    if fn == "array_sort":
        return _struct.array_sort(sv), rvalid
    if fn == "array_distinct":
        return _struct.array_distinct(sv), rvalid
    if fn == "slice":
        sv0 = sv
        s, svalid = scalar_arg(e.args[1])
        ln, lvalid = scalar_arg(e.args[2])
        out = _struct.slice_array(sv0, s.astype(jnp.int64),
                                  ln.astype(jnp.int64))
        return out, _and_valid(rvalid, _and_valid(svalid, lvalid))
    if fn == "concat":
        out, valid = sv, rvalid
        for a in e.args[1:]:
            asv, avalid = _eval(a, ctx)
            out = _struct.concat_arrays(out, asv)
            valid = _and_valid(valid, avalid)
        return out, valid
    if fn == "map_keys":
        return _struct.map_keys(sv), rvalid
    if fn == "map_values":
        return _struct.map_values(sv), rvalid
    if fn in ("array_union", "array_intersect", "array_except",
              "arrays_overlap", "map_concat"):
        t0 = e.args[0].type
        target = _setop_elem_dict(e, ctx)
        ktarget = (_setop_key_dict(e, ctx)
                   if fn == "map_concat" and t0.key.is_string else None)

        def aligned(arg, s):
            if target is not None:
                d = _elem_dict(arg, ctx)
                if d is not None and d is not target:
                    remap = jnp.asarray(d.map_to(target))
                    s = s._replace(
                        values=remap[s.values.astype(jnp.int32) + 1])
            if ktarget is not None:
                d = _key_dict(arg, ctx)
                if d is not None and d is not ktarget:
                    remap = jnp.asarray(d.map_to(ktarget))
                    s = s._replace(
                        keys=remap[s.keys.astype(jnp.int32) + 1])
            return s

        out, valid = aligned(e.args[0], sv), rvalid
        for a in e.args[1:]:
            osv, ovalid = _eval(a, ctx)
            osv = aligned(a, osv)
            valid = _and_valid(valid, ovalid)
            if fn == "array_union":
                out = _struct.array_union(out, osv)
            elif fn == "array_intersect":
                out = _struct.array_intersect(out, osv)
            elif fn == "array_except":
                out = _struct.array_except(out, osv)
            elif fn == "map_concat":
                out = _struct.map_concat(out, osv)
            else:
                return _struct.arrays_overlap(out, osv), valid
        return out, valid
    if fn in ("transform", "filter", "any_match", "all_match", "none_match"):
        return _eval_higher_order(e, ctx, sv, rvalid)
    if fn in ("transform_values", "map_filter"):
        return _eval_map_higher_order(e, ctx, sv, rvalid)
    raise NotImplementedError(f"structural function not implemented: {fn}")


def _eval_map_higher_order(e: Call, ctx: CompileContext, sv: StructVal,
                           rvalid):
    """transform_values / map_filter: the (k, v) lambda evaluates over the
    flattened key+value planes together."""
    fn = e.fn
    cap = ctx.batch.capacity
    le: LambdaExpr = e.args[1]
    (ksym, kt), (vsym, vt) = le.params
    w = sv.width
    if w == 0:
        return sv, rvalid
    present = sv.present()
    evalid = sv.element_valid()
    kdict = _key_dict(e.args[0], ctx) if kt.is_string else None
    vdict = _elem_dict(e.args[0], ctx) if vt.is_string else None
    eb, extra = _element_batch(ctx, w, [
        (ksym, kt, sv.keys.reshape(-1), present.reshape(-1), kdict),
        (vsym, vt, sv.values.reshape(-1), evalid.reshape(-1), vdict),
    ])
    bctx = CompileContext(eb, ctx.out_dict, extra)
    bv, bvalid = _eval(le.body, bctx)
    bv = jnp.broadcast_to(bv, (cap * w,)).reshape(cap, w)
    bvalid2 = (jnp.broadcast_to(bvalid, (cap * w,)).reshape(cap, w)
               if bvalid is not None else None)
    if fn == "transform_values":
        out = StructVal(bv.astype(le.type.dtype), sv.sizes, bvalid2,
                        keys=sv.keys)
        return out, rvalid
    truth = bv.astype(bool)
    if bvalid2 is not None:
        truth = truth & bvalid2
    return _struct.filter_elements(sv, truth & present), rvalid


def _repeat_column(c, w: int):
    """Row i of the outer batch → rows i*w..(i+1)*w-1 (lambda bodies may
    capture outer columns). gather() replicates every plane."""
    cap = c.values.shape[0]
    idx = jnp.repeat(jnp.arange(cap, dtype=jnp.int32), w)
    return c.gather(idx)


def _element_batch(ctx: CompileContext, w: int, param_cols) -> Batch:
    """Synthetic [cap*w]-row batch: outer columns repeated per element
    slot + the lambda parameter columns (flattened element planes). The
    lambda body compiles over it exactly like any row expression —
    vectorized over every element of every row at once."""
    b = ctx.batch
    names = list(b.names)
    types = list(b.types)
    cols = [_repeat_column(c, w) for c in b.columns]
    dicts = dict(b.dicts)
    extra = {}
    for sym, t, vals, valid, d in param_cols:
        names.append(sym)
        types.append(t)
        cols.append(Column(vals, valid))
        if d is not None:
            dicts[sym] = d
            extra[sym] = d
    live = jnp.repeat(b.live, w)
    eb = Batch(names, types, cols, live, dicts)
    return eb, extra


def _eval_higher_order(e: Call, ctx: CompileContext, sv: StructVal, rvalid):
    """transform/filter/…_match: the lambda body evaluates once over the
    flattened [cap*w] element plane (no per-element interpretation —
    LambdaDefinitionExpression codegen redesigned as plane vectorization)."""
    fn = e.fn
    cap = ctx.batch.capacity
    le: LambdaExpr = e.args[1]
    (psym, pt), = le.params
    w = sv.width
    if w == 0:
        if fn == "transform":
            return StructVal(jnp.zeros((cap, 0), le.type.dtype), sv.sizes,
                             None), rvalid
        if fn == "filter":
            return sv, rvalid
        empty = jnp.zeros(cap, bool)
        return (~empty if fn in ("all_match", "none_match") else empty), rvalid

    present = sv.present()
    evalid = sv.element_valid()
    pdict = _elem_dict(e.args[0], ctx) if pt.is_string else None
    eb, extra = _element_batch(
        ctx, w,
        [(psym, pt, sv.values.reshape(-1), evalid.reshape(-1), pdict)])
    bctx = CompileContext(eb, ctx.out_dict, extra)
    bv, bvalid = _eval(le.body, bctx)
    bv = jnp.broadcast_to(bv, (cap * w,)).reshape(cap, w)
    bvalid2 = (jnp.broadcast_to(bvalid, (cap * w,)).reshape(cap, w)
               if bvalid is not None else None)

    if fn == "transform":
        out = StructVal(bv.astype(le.type.dtype), sv.sizes, bvalid2)
        return out, rvalid
    truth = bv.astype(bool)
    if bvalid2 is not None:
        truth = truth & bvalid2  # NULL predicate counts as not-matching
    if fn == "filter":
        return _struct.filter_elements(sv, truth & present), rvalid
    if fn == "any_match":
        return jnp.any(truth & present, axis=1), rvalid
    if fn == "all_match":
        return jnp.all(truth | ~present, axis=1), rvalid
    return ~jnp.any(truth & present, axis=1), rvalid  # none_match


def _eval_zip_with(e: Call, ctx: CompileContext):
    """zip_with(a, b, (x, y) -> ...): planes pad to the longer array (the
    shorter side's missing elements are NULL params — Presto's padding);
    the lambda body evaluates once over the paired flattened planes."""
    from presto_tpu.expr.structural import pad_plane_width

    asv, avalid = _eval(e.args[0], ctx)
    bsv, bvalid = _eval(e.args[1], ctx)
    le: LambdaExpr = e.args[2]
    (xsym, xt), (ysym, yt) = le.params
    cap = ctx.batch.capacity
    w = max(asv.width, bsv.width, 1)
    av = pad_plane_width(asv.values, w)
    bv = pad_plane_width(bsv.values, w)
    aev = pad_plane_width(asv.element_valid(), w, False)
    bev = pad_plane_width(bsv.element_valid(), w, False)
    xdict = _elem_dict(e.args[0], ctx) if xt.is_string else None
    ydict = _elem_dict(e.args[1], ctx) if yt.is_string else None
    eb, extra = _element_batch(ctx, w, [
        (xsym, xt, av.reshape(-1), aev.reshape(-1), xdict),
        (ysym, yt, bv.reshape(-1), bev.reshape(-1), ydict),
    ])
    bctx = CompileContext(eb, ctx.out_dict, extra)
    ov, ovalid = _eval(le.body, bctx)
    ov = jnp.broadcast_to(ov, (cap * w,)).reshape(cap, w)
    ovalid2 = (jnp.broadcast_to(ovalid, (cap * w,)).reshape(cap, w)
               if ovalid is not None else None)
    sizes = jnp.maximum(asv.sizes, bsv.sizes)
    out = StructVal(ov.astype(le.type.dtype), sizes, ovalid2)
    return out, _and_valid(avalid, bvalid)


def _eval_reduce(e: Call, ctx: CompileContext):
    """reduce(arr, init, (state, x) -> ...): trace-time unrolled fold over
    the W element slots — each step is one vectorized body evaluation over
    all rows (W is the static plane width, typically small)."""
    sv, rvalid = _eval(e.args[0], ctx)
    iv, ivalid = _eval_arg(e.args[1], ctx)
    le: LambdaExpr = e.args[2]
    (ssym, st), (xsym, xt) = le.params
    cap = ctx.batch.capacity
    acc_v = jnp.broadcast_to(iv, (cap,)).astype(st.dtype)
    acc_valid = (jnp.broadcast_to(ivalid, (cap,)) if ivalid is not None
                 else jnp.ones(cap, bool))
    present = sv.present()
    evalid = sv.element_valid()
    xdict = _elem_dict(e.args[0], ctx) if xt.is_string else None
    for j in range(sv.width):
        eb, extra = _element_batch(ctx, 1, [
            (ssym, st, acc_v, acc_valid, None),
            (xsym, xt, sv.values[:, j], evalid[:, j], xdict),
        ])
        bctx = CompileContext(eb, ctx.out_dict, extra)
        bv, bvalid = _eval(le.body, bctx)
        bv = jnp.broadcast_to(bv, (cap,)).astype(st.dtype)
        bvalid = (jnp.broadcast_to(bvalid, (cap,))
                  if bvalid is not None else jnp.ones(cap, bool))
        active = present[:, j]
        acc_v = jnp.where(active, bv, acc_v)
        acc_valid = jnp.where(active, bvalid, acc_valid)
    valid = acc_valid
    if rvalid is not None:
        valid = valid & rvalid
    return acc_v, valid


def _days_in_month(y, m):
    base = jnp.asarray([31, 28, 31, 30, 31, 30, 31, 31, 30, 31, 30, 31])[m - 1]
    leap = ((jnp.mod(y, 4) == 0) & (jnp.mod(y, 100) != 0)) | (jnp.mod(y, 400) == 0)
    return jnp.where((m == 2) & leap, 29, base)


def _days_from_civil_vec(y, m, d):
    """Vectorized inverse of _civil_from_days (same Hinnant algorithm)."""
    y = y - (m <= 2)
    era = jnp.where(y >= 0, y, y - 399) // 400
    yoe = y - era * 400
    doy = (153 * (m + jnp.where(m > 2, -3, 9)) + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return (era * 146097 + doe - 719468).astype(jnp.int32)


def _numeric_align(lt: Type, rt: Type, lv, rv):
    """Align device representations for comparison (analyzer guarantees the
    SQL types are comparable; decimals arrive same-scale via casts)."""
    if lv.dtype != rv.dtype:
        target = jnp.result_type(lv.dtype, rv.dtype)
        lv = lv.astype(target)
        rv = rv.astype(target)
    return lv, rv


def _string_compare(op: str, l: RowExpression, r: RowExpression, ctx):
    """Dictionary-code string comparison. Order-preserving dictionaries make
    range compares on codes correct when both sides share one dictionary;
    cross-dictionary equality remaps codes via a host-built table."""
    lconst = isinstance(l, Constant)
    rconst = isinstance(r, Constant)
    if lconst and not rconst:
        flip = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le"}
        return _string_compare(flip.get(op, op), r, l, ctx)
    if rconst:
        d = ctx.dict_for(l)
        if d is None:
            raise ValueError(f"no dictionary for {l}")
        s = str(r.value)
        lv, lvalid = _eval(l, ctx)
        if op in ("eq", "ne"):
            code = d.code_of(s)
            m = lv == code
            return (m if op == "eq" else ~m), lvalid
        # range predicate via searchsorted position in the sorted dictionary
        if op == "lt":
            pos = d.range_codes(s, "left")
            return lv < pos, lvalid
        if op == "le":
            pos = d.range_codes(s, "right")
            return lv < pos, lvalid
        if op == "gt":
            pos = d.range_codes(s, "right")
            return lv >= pos, lvalid
        if op == "ge":
            pos = d.range_codes(s, "left")
            return lv >= pos, lvalid
    # column vs column
    ld = ctx.dict_for(l)
    rd = ctx.dict_for(r)
    lv, lvalid = _eval(l, ctx)
    rv, rvalid = _eval(r, ctx)
    valid = _and_valid(lvalid, rvalid)
    if ld is rd or rd is None or ld is None:
        return _CMP[op](lv, rv), valid
    if op in ("eq", "ne"):
        remap = jnp.asarray(ld.map_to(rd))
        lv2 = remap[lv + 1]
        m = (lv2 == rv) & (lv2 >= 0)
        return (m if op == "eq" else ~m), valid
    raise NotImplementedError("cross-dictionary range comparison")


def _eval_arith(e: Call, ctx):
    l, r = e.args
    lv, lvalid = _eval_arg(l, ctx, r)
    rv, rvalid = _eval_arg(r, ctx, l)
    valid = _and_valid(lvalid, rvalid)
    out_t = e.type
    ldec = isinstance(l.type, DecimalType)
    rdec = isinstance(r.type, DecimalType)
    if isinstance(out_t, DecimalType):
        # exact scaled-int64 arithmetic (reference: short-decimal paths in
        # spi/type/DecimalOperators); analyzer pre-aligned scales for add/sub
        if e.fn == "div":
            return _decimal_div(lv, rv, l.type, r.type, out_t, valid)
        lv = lv.astype(jnp.int64)
        rv = rv.astype(jnp.int64)
        if e.fn == "add":
            return lv + rv, valid
        if e.fn == "sub":
            return lv - rv, valid
        if e.fn == "mul":
            return lv * rv, valid  # scale(out) = scale(l) + scale(r)
        if e.fn == "mod":
            return jnp.mod(lv, rv), valid
        raise NotImplementedError(f"decimal {e.fn}")
    # float / integer paths
    if out_t is DOUBLE or is_floating(out_t):
        if ldec:
            lv = lv.astype(out_t.dtype) / (10.0 ** l.type.scale)
        else:
            lv = lv.astype(out_t.dtype)
        if rdec:
            rv = rv.astype(out_t.dtype) / (10.0 ** r.type.scale)
        else:
            rv = rv.astype(out_t.dtype)
    else:
        lv = lv.astype(out_t.dtype)
        rv = rv.astype(out_t.dtype)
    if e.fn == "add":
        return lv + rv, valid
    if e.fn == "sub":
        return lv - rv, valid
    if e.fn == "mul":
        return lv * rv, valid
    if e.fn == "div":
        if is_integral(out_t):
            # SQL integer division truncates toward zero
            q = jnp.sign(lv) * jnp.sign(rv) * (jnp.abs(lv) // jnp.maximum(jnp.abs(rv), 1))
            div_ok = rv != 0
            return q.astype(out_t.dtype), _and_valid(valid, div_ok)
        div_ok = rv != 0.0
        return jnp.where(div_ok, lv / jnp.where(div_ok, rv, 1.0), 0.0), _and_valid(valid, div_ok)
    if e.fn == "mod":
        safe = jnp.where(rv == 0, 1, rv)
        m = lv - jnp.trunc(lv / safe) * safe if is_floating(out_t) else jnp.sign(lv) * (jnp.abs(lv) % jnp.abs(safe))
        return m, _and_valid(valid, rv != 0)
    raise NotImplementedError(e.fn)


def _two_prod(a, b):
    """Dekker/Veltkamp exact two-product: a*b = hi + lo with hi = fl(a*b).
    Pure f64 elementwise ops — XLA preserves FP semantics (no unsafe
    reassociation), so the error term is exact."""
    p = a * b
    c = jnp.float64(134217729.0)  # 2^27 + 1 (Veltkamp splitter)
    ac = a * c
    ah = ac - (ac - a)
    al = a - ah
    bc = b * c
    bh = bc - (bc - b)
    bl = b - bh
    err = ((ah * bh - p) + ah * bl + al * bh) + al * bl
    return p, err


def _decimal_div(lv, rv, lt, rt, out_t, valid):
    """DECIMAL ÷ DECIMAL with Presto semantics (DecimalOperators.divide /
    UnscaledDecimal128Arithmetic.divideRoundUp): the numerator rescales by
    10^(s_out + s_r - s_l), the quotient rounds HALF AWAY FROM ZERO.

    Exactness ladder on TPU int64/f64 lanes (reference is int128-exact to
    38 digits):
      1. numerator fits 18 digits → pure int64, bit-exact;
      2. otherwise a Dekker two-product f64 path with exact-remainder
         correction — bit-exact while the operands are f64-exact
         (< 2^53), the rescale shift ≤ 22, and the quotient < 2^53;
      3. beyond those bounds the result is the f64 approximation
         (documented deviation — 16+ significant digit quotients).
    """
    from presto_tpu.types import DecimalType as _DT

    ls = lt.scale if isinstance(lt, _DT) else 0
    rs = rt.scale if isinstance(rt, _DT) else 0
    lp = lt.precision if isinstance(lt, _DT) else 18
    shift = out_t.scale + rs - ls
    div_ok = rv != 0
    valid = _and_valid(valid, div_ok)
    int_in = (not jnp.issubdtype(lv.dtype, jnp.floating)
              and not jnp.issubdtype(rv.dtype, jnp.floating))
    if int_in and shift >= 0 and lp + shift <= 18:
        n = lv.astype(jnp.int64) * (10 ** shift)
        d = jnp.where(div_ok, rv.astype(jnp.int64), jnp.ones((), jnp.int64))
        an, ad = jnp.abs(n), jnp.abs(d)
        q = (an + ad // 2) // ad  # round half away on |·|
        return (jnp.sign(n) * jnp.sign(d) * q).astype(jnp.int64), valid

    nf = jnp.abs(lv.astype(jnp.float64))
    da = jnp.abs(jnp.where(div_ok, rv.astype(jnp.float64), 1.0))
    sgn = jnp.sign(lv.astype(jnp.float64)) * jnp.sign(
        jnp.where(div_ok, rv.astype(jnp.float64), 1.0))
    if shift < 0 or shift > 22:  # 10^shift not f64-exact: plain f64 tail
        q = jnp.round(nf * (10.0 ** shift) / da)
        return (sgn * q).astype(jnp.int64), valid
    n_hi, n_lo = _two_prod(nf, jnp.float64(10.0 ** shift))
    qa = jnp.floor(n_hi / da)
    for _ in range(2):  # each sweep shrinks the error ~2^-52
        p_hi, p_lo = _two_prod(qa, da)
        r = ((n_hi - p_hi) - p_lo) + n_lo
        qa = qa + jnp.floor(r / da)
    p_hi, p_lo = _two_prod(qa, da)
    r = ((n_hi - p_hi) - p_lo) + n_lo  # exact remainder in [0, da)
    q = qa + (2.0 * r >= da)  # half away from zero on |·|
    return (sgn * q).astype(jnp.int64), valid


def parse_string_to(tt, s: str):
    """SQL text → the internal value of type `tt`, or None when
    unparseable (shared by varchar-cast LUTs and constant folding)."""
    from presto_tpu.types import DATE as _DATE

    def _time_micros(txt: str) -> int:
        hms, _, frac = txt.partition(".")
        parts = list(map(int, hms.split(":")))
        while len(parts) < 3:
            parts.append(0)
        hh, mm, ss = parts[:3]
        micros = (hh * 3600 + mm * 60 + ss) * 1_000_000
        if frac:
            micros += int(frac[:6].ljust(6, "0"))
        return micros

    try:
        s = s.strip()
        if tt is _DATE:
            y, m, dd = map(int, s.split("-"))
            return days_from_civil(y, m, dd)
        if tt.name == "timestamp":
            datepart, _, timepart = s.partition(" ")
            y, m, dd = map(int, datepart.split("-"))
            micros = days_from_civil(y, m, dd) * 86_400_000_000
            if timepart:
                micros += _time_micros(timepart)
            return micros
        if tt.name == "time":
            return _time_micros(s)
        if tt is BOOLEAN:
            if s.lower() in ("true", "t", "1"):
                return 1
            if s.lower() in ("false", "f", "0"):
                return 0
            return None
        if isinstance(tt, DecimalType):
            import decimal as _dec

            return int(_dec.Decimal(s).scaleb(tt.scale)
                       .to_integral_value(rounding=_dec.ROUND_HALF_UP))
        if is_floating(tt):
            return float(s)
        return int(float(s)) if "." in s or "e" in s.lower() else int(s)
    except Exception:
        return None


def _eval_cast(e: Call, ctx):
    src = e.args[0]
    st, tt = src.type, e.type
    if st.is_string and not tt.is_string:
        # varchar → numeric/date/boolean: parse each DICTIONARY value on
        # the host, one device gather (codes must never be value-cast!).
        # Unparseable values yield NULL — a documented deviation from the
        # reference's row-level cast error (no exception channel exists on
        # device; try(cast(..)) is therefore equivalent to cast(..))
        d = ctx.dict_for(src)
        if d is None:
            raise ValueError("cast from varchar requires a dictionary")
        import numpy as _np

        def val_of(s):
            v = parse_string_to(tt, s)
            return 0 if v is None else v

        def ok_of(s):
            return parse_string_to(tt, s) is not None

        npdt = _np.float64 if is_floating(tt) else _np.int64
        vlut = d.int_lut(("cast_val", tt.name), val_of, dtype=npdt)
        olut = d.int_lut(("cast_ok", tt.name), ok_of, dtype=_np.bool_)
        codes, valid = _eval(src, ctx)
        out = jnp.asarray(vlut)[codes + 1].astype(tt.dtype)
        ok = jnp.asarray(olut)[codes + 1]
        return out, ok if valid is None else (valid & ok)
    if tt.is_string and not st.is_string:
        raise NotImplementedError(
            "cast to varchar from non-string types is supported in the "
            "top-level SELECT list only (it runs as a HostProject "
            "finishing projection — no input dictionary exists to "
            "transform on the device)")
    v, valid = _eval_arg(src, ctx)
    if st == tt:
        return v, valid
    sdec = isinstance(st, DecimalType)
    tdec = isinstance(tt, DecimalType)
    if sdec and tdec:
        # rescale
        if tt.scale >= st.scale:
            return v * (10 ** (tt.scale - st.scale)), valid
        f = 10 ** (st.scale - tt.scale)
        return _div_half_away(v, f), valid
    if sdec and is_floating(tt):
        return v.astype(tt.dtype) / (10.0 ** st.scale), valid
    if sdec and is_integral(tt):
        return _div_half_away(v, 10 ** st.scale).astype(tt.dtype), valid
    if tdec and is_integral(st):
        return v.astype(jnp.int64) * (10 ** tt.scale), valid
    if tdec and is_floating(st):
        return _round_half_away(v * (10.0 ** tt.scale)).astype(jnp.int64), valid
    if tt is BOOLEAN:
        return v.astype(bool), valid
    return v.astype(tt.dtype), valid


def _civil_from_days(z):
    """days-since-epoch → (year, month, day). Howard Hinnant's algorithm,
    branch-free integer math (vectorizes on the VPU)."""
    z = z + 719468
    era = jnp.where(z >= 0, z, z - 146096) // 146097
    doe = z - era * 146097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = jnp.where(mp < 10, mp + 3, mp - 9)
    y = jnp.where(m <= 2, y + 1, y)
    return y, m, d


def days_from_civil(y: int, m: int, d: int) -> int:
    """Host-side date literal → days since epoch."""
    y -= m <= 2
    era = (y if y >= 0 else y - 399) // 400
    yoe = y - era * 400
    doy = (153 * (m + (-3 if m > 2 else 9)) + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146097 + doe - 719468


# ---------------------------------------------------------------------------
# geospatial (expr/geo.py): WKT parses once per dictionary, row ops are
# vectorized plane programs (reference: presto-geospatial GeoFunctions)

# bounded LRUs: long-running servers compile unboundedly many plans
from collections import OrderedDict as _OD

_GEO_PLANES_CACHE: "_OD" = _OD()   # id(geoms) -> (geoms, np planes)
_GEO_CONST_CACHE: "_OD" = _OD()    # wkt literal -> (geoms, ok) singleton


def _geo_planes(geoms: tuple):
    from presto_tpu.expr import geo as G

    hit = _GEO_PLANES_CACHE.get(id(geoms))
    if hit is not None and hit[0] is geoms:
        _GEO_PLANES_CACHE.move_to_end(id(geoms))
        return hit[1]
    planes = G.edge_planes(geoms)
    _GEO_PLANES_CACHE[id(geoms)] = (geoms, planes)
    while len(_GEO_PLANES_CACHE) > 128:
        _GEO_PLANES_CACHE.popitem(last=False)
    return planes


def _geo_parse_all(values):
    """Lenient WKT parse: (geoms tuple, ok ndarray). Unparseable values
    (incl. the '' null sentinel some connectors store) become invalid
    rows, not query failures."""
    from presto_tpu.expr import geo as G

    parsed, ok = [], []
    fallback = G.parse_wkt("POINT(0 0)")
    for v in values:
        try:
            parsed.append(G.parse_wkt(str(v)))
            ok.append(True)
        except G.WktError:
            parsed.append(fallback)
            ok.append(False)
    return tuple(parsed), np.asarray(ok, bool)


def _geo_lut(gv, func, dtype=jnp.float64):
    """geometry→scalar as a host table gathered by code."""
    table = jnp.asarray(np.array([func(g) for g in gv.geoms]).astype(dtype))
    return table[jnp.clip(gv.codes, 0, len(gv.geoms) - 1)]


def _geo_points(gv):
    """(x, y) coordinate arrays of a GeomVal; None when it holds
    non-point geometries."""
    from presto_tpu.expr import geo as G

    if gv.kind == "points":
        return gv.x, gv.y
    if all(G.is_point(g) for g in gv.geoms):
        return (_geo_lut(gv, lambda g: G.point_xy(g)[0]),
                _geo_lut(gv, lambda g: G.point_xy(g)[1]))
    return None


def _eval_geom_arg(a: RowExpression, ctx):
    """Evaluate a GEOMETRY-typed subexpression to (GeomVal, valid)."""
    v, valid = _eval(a, ctx)
    from presto_tpu.expr.geo import GeomVal

    if not isinstance(v, GeomVal):
        raise NotImplementedError(
            "GEOMETRY values only flow between geospatial functions")
    return v, valid


def _eval_geo(e: Call, ctx: CompileContext):
    from presto_tpu.expr import geo as G
    from presto_tpu.expr.geo import GeomVal

    fn = e.fn
    if fn == "great_circle_distance":
        vals = [_eval_arg(a, ctx) for a in e.args]
        valid = None
        for _, va in vals:
            valid = _and_valid(valid, va)
        lat1, lon1, lat2, lon2 = (v.astype(jnp.float64) for v, _ in vals)
        return G.great_circle_distance(lat1, lon1, lat2, lon2), valid

    if fn == "st_geometryfromtext":
        a = e.args[0]
        cap = ctx.batch.capacity
        if isinstance(a, Constant):
            key = str(a.value) if a.value is not None else None
            if key is None:
                geoms, ok = _geo_parse_all([""])
            else:
                hit = _GEO_CONST_CACHE.get(key)
                if hit is None:
                    hit = _geo_parse_all([key])
                    _GEO_CONST_CACHE[key] = hit
                    while len(_GEO_CONST_CACHE) > 256:
                        _GEO_CONST_CACHE.popitem(last=False)
                else:
                    _GEO_CONST_CACHE.move_to_end(key)
                geoms, ok = hit
            valid = None if bool(ok[0]) else jnp.zeros(cap, bool)
            return GeomVal("coded", jnp.zeros(cap, jnp.int32), geoms,
                           None, None), valid
        codes, valid = _eval(a, ctx)
        hit = ctx.dict_for(a)
        if hit is None:
            raise NotImplementedError(
                "ST_GeometryFromText needs a dictionary-encoded varchar")
        d = hit
        memo = d._memo.get("__geoms__")
        if memo is None:
            memo = _geo_parse_all(d.values)
            d._memo["__geoms__"] = memo
        geoms, ok = memo
        if not geoms:
            geoms, ok = _geo_parse_all([""])
            return (GeomVal("coded", jnp.zeros(cap, jnp.int32), geoms,
                            None, None), jnp.zeros(cap, bool))
        okv = jnp.asarray(ok)[jnp.clip(codes, 0, len(geoms) - 1)]
        okv = okv & (codes >= 0)
        return GeomVal("coded", codes, geoms, None, None), _and_valid(
            valid, okv)

    if fn == "st_point":
        (x, xv), (y, yv) = (_eval_arg(a, ctx) for a in e.args)

        def vec(v):
            v = v.astype(jnp.float64)
            # literal coordinates arrive 0-d; plane gathers need [rows]
            return (jnp.broadcast_to(v, (ctx.batch.capacity,))
                    if jnp.ndim(v) == 0 else v)

        return (GeomVal("points", None, None, vec(x), vec(y)),
                _and_valid(xv, yv))

    if fn in ("st_area", "st_perimeter", "st_length", "st_npoints",
              "st_xmin", "st_xmax", "st_ymin", "st_ymax", "st_x", "st_y",
              "st_centroid"):
        gv, valid = _eval_geom_arg(e.args[0], ctx)
        if gv.kind == "points":
            if fn in ("st_x", "st_xmin", "st_xmax"):
                return gv.x, valid
            if fn in ("st_y", "st_ymin", "st_ymax"):
                return gv.y, valid
            if fn == "st_centroid":
                return gv, valid
            if fn == "st_npoints":
                return jnp.ones_like(gv.x, dtype=jnp.int64), valid
            return jnp.zeros_like(gv.x), valid  # area/perimeter/length
        if fn in ("st_x", "st_y"):
            if not all(G.is_point(g) for g in gv.geoms):
                raise NotImplementedError(f"{fn} needs POINT geometries")
            i = 0 if fn == "st_x" else 1
            return _geo_lut(gv, lambda g: G.point_xy(g)[i]), valid
        if fn == "st_centroid":
            return (GeomVal("points", None, None,
                            _geo_lut(gv, lambda g: G.geom_centroid(g)[0]),
                            _geo_lut(gv, lambda g: G.geom_centroid(g)[1])),
                    valid)
        host = {"st_area": G.geom_area, "st_perimeter": G.geom_perimeter,
                "st_length": G.geom_length,
                "st_xmin": lambda g: G.geom_bbox(g)[0],
                "st_ymin": lambda g: G.geom_bbox(g)[1],
                "st_xmax": lambda g: G.geom_bbox(g)[2],
                "st_ymax": lambda g: G.geom_bbox(g)[3]}
        if fn == "st_npoints":
            return _geo_lut(gv, G.geom_npoints, jnp.int64), valid
        return _geo_lut(gv, host[fn]), valid

    # binary geometry relations
    ga, va = _eval_geom_arg(e.args[0], ctx)
    gb, vb = _eval_geom_arg(e.args[1], ctx)
    valid = _and_valid(va, vb)
    pa, pb = _geo_points(ga), _geo_points(gb)

    if fn in ("st_contains", "st_intersects"):
        def point_in(poly, px, py):
            # only area kinds enclose points (linestrings never do)
            inside = G.point_in_coded(_geo_planes(poly.geoms), poly.codes,
                                      px, py)
            area = _geo_lut(poly, lambda g: float(G.is_area(g))) > 0
            return inside & area

        # polygon side contains / intersects a point probe (even-odd)
        if ga.kind == "coded" and pb is not None and pa is None:
            return point_in(ga, pb[0], pb[1]), valid
        if (fn == "st_intersects" and gb.kind == "coded"
                and pa is not None and pb is None):
            return point_in(gb, pa[0], pa[1]), valid
        if pa is not None and pb is not None:
            eqv = (pa[0] == pb[0]) & (pa[1] == pb[1])
            return eqv, valid
        if fn == "st_contains" and pa is not None and pb is None:
            # a point never contains a polygon/linestring
            return jnp.zeros_like(pa[0], dtype=bool), valid
        raise NotImplementedError(
            f"{fn} between two non-point geometries is not supported")

    if fn == "st_distance":
        if pa is not None and pb is not None:
            return jnp.hypot(pa[0] - pb[0], pa[1] - pb[1]), valid
        poly, pt = (ga, pb) if pa is None else (gb, pa)
        if pt is None:
            raise NotImplementedError(
                "ST_Distance between two non-point geometries is not "
                "supported")
        d = G.point_seg_distance(_geo_planes(poly.geoms), poly.codes,
                                 pt[0], pt[1])
        inside = G.point_in_coded(_geo_planes(poly.geoms), poly.codes,
                                  pt[0], pt[1])
        area = _geo_lut(poly, lambda g: float(G.is_area(g))) > 0
        return jnp.where(inside & area, 0.0, d), valid

    raise NotImplementedError(f"geospatial function {fn}")
