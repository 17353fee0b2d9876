"""The comparison that decides `correct`: every statement the window ran,
as the client decoded its pages, against the plain reference's rows.

Two numbers come out, each with a limit of its own:
  wrong_statements   statements that failed, or whose rows differ from the
                     reference in row count or in any exact column (keys,
                     strings, dates, counts, decimals). Exact: limit 0.
  double_rel_err_max widest relative gap of a DOUBLE column (averages);
                     reported only where the query has such columns.
"""

from __future__ import annotations

import decimal
import math
from typing import List, Optional, Tuple


def compare_rows(columns, got, want, expected=None) -> Tuple[Optional[str], float]:
    """(first exact difference or None, widest relative gap of a double).
    `expected` is the query file's `result_columns`: a column the program
    reports under another type (a decimal turned double, say) is a
    difference, so the type cannot be changed to get under a tolerance."""
    if expected is not None and [(c["name"], c["type"]) for c in columns] != \
            [(c["name"], c["type"]) for c in expected]:
        return f"columns {columns!r} != reference {expected!r}", 0.0
    if len(got) != len(want):
        return f"row count {len(got)} != reference {len(want)}", 0.0
    worst, first = 0.0, None
    for i, (g, w) in enumerate(zip(got, want)):
        if len(g) != len(w):
            return f"row {i}: {len(g)} columns, reference {len(w)}", worst
        for col, a, b in zip(columns, g, w):
            kind = col["type"]
            if kind == "double":
                if a is None:
                    first = first or f"row {i} column {col['name']}: null"
                    continue
                gap = abs(float(a) - float(b)) / max(abs(float(b)), 1e-300)
                if not math.isfinite(gap):  # a NaN would get past `max`
                    first = first or (f"row {i} column {col['name']}: "
                                      f"got {a!r}, reference {b!r}")
                    continue
                worst = max(worst, gap)
                continue
            if kind.startswith("decimal"):
                try:
                    same = decimal.Decimal(a) == b
                except (decimal.InvalidOperation, TypeError, ValueError):
                    same = False
            else:
                same = a == b
            if not same:
                first = first or (f"row {i} column {col['name']} ({kind}): "
                                  f"got {a!r}, reference {b!r}")
    return first, worst


def judge(statements: List[dict], references: dict, limits: dict) -> dict:
    """`statements`: the window's, each {"query", "params_key", "columns",
    "rows", "error"}. `references`: {(query, params_key): (rows, the query
    file's result_columns)}. Returns
    {"correct", "compared": {name: {"value", "limit"}}, "first_difference"}."""
    wrong, worst, first = 0, 0.0, None
    has_double = any(c["type"] == "double" for _, expected in
                     references.values() for c in expected)
    for st in statements:
        if st.get("error") or st.get("columns") is None:
            wrong += 1
            first = first or f"statement {st['index']}: {st.get('error')}"
            continue
        want, expected = references[(st["query"], st["params_key"])]
        diff, gap = compare_rows(st["columns"], st["rows"], want, expected)
        worst = max(worst, gap)
        if diff:
            wrong += 1
            first = first or f"statement {st['index']} ({st['query']}): {diff}"
    compared = {"wrong_statements": {"value": wrong,
                                     "limit": limits["wrong_statements"]}}
    if has_double:
        compared["double_rel_err_max"] = {
            "value": worst, "limit": limits["double_rel_err_max"]}
    ok = bool(statements) and all(v["value"] <= v["limit"]
                                  for v in compared.values())
    return {"correct": ok, "compared": compared, "first_difference": first}
