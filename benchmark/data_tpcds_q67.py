"""TPC-DS's data module for Q67: `data_tpcds.py`'s functions, and
store_sales made the way the connector makes it.

`data_tpcds.generate` asks the generator for a table by name, and the
generator has no `store_sales()`: it draws the store channel's sales and
returns together (`store_sales_and_returns()`). Here that call makes
store_sales; every other table comes from `data_tpcds.generate`.

An engine whose generator lacks the columns TPC-DS Q67 reads beyond the rest
(`d_month_seq`, `i_class`) cannot plan the query; it is refused here, at
import and by name, before a run makes 2.9 M rows of data for nothing:
`run.py` imports the module the configuration names before it makes any.
"""

from __future__ import annotations

from typing import Dict, Iterable

from presto_tpu.catalog.tpcds import TpcdsGenerator

from benchmark import data_tpcds
from benchmark.data_tpcds import (  # noqa: F401
    column_array, install, referenced_bytes, scanned_rows, strings)

_WANTED = {"date_dim": ("d_month_seq",), "item": ("i_class",)}


def _missing():
    gen = TpcdsGenerator(1e-6)  # the two tables are the same at every scale
    return [c for table, cols in _WANTED.items()
            for c in cols if c not in getattr(gen, table)()]


if _missing():
    raise ImportError(
        "benchmark.data_tpcds_q67: this engine's TPC-DS generator has no "
        f"{', '.join(_missing())}; it cannot plan TPC-DS Q67. Not run.")


def generate(sf: float, seed: int, tables: Iterable[str]) -> Dict[str, dict]:
    """{table: {column: array | pair}} for `tables`; store_sales as the
    connector draws it, without its returns."""
    tables = list(tables)
    out = data_tpcds.generate(sf, seed, [t for t in tables if t != "store_sales"])
    if "store_sales" in tables:
        out["store_sales"], _ = TpcdsGenerator(
            sf, seed=int(seed)).store_sales_and_returns()
    return out
