"""TPC-DS's data module: `data.py`'s five functions over the program's
generator (`presto_tpu/catalog/tpcds.py`), for a configuration whose catalog
is `tpcds:sf=...`.

A generated column is a plain array (keys, counts, dates as days), an array
of strings, a `(Dictionary, codes)` pair or a `("raw72", cents)` pair (a
decimal(7,2) as its unscaled integers): `column_array` gives what the device
holds for each, `strings` the text of a string column.

An engine whose generator lacks the columns TPC-DS Q72 reads beyond the rest
(`cs_bill_cdemo_sk`, `cs_bill_hdemo_sk`, `i_item_desc`) cannot plan the
query; it is refused here, at import and by name, before a run makes 27 M
rows of data for nothing: `run.py` imports the module the configuration
names before it makes any.
"""

from __future__ import annotations

from typing import Dict, Iterable

import numpy as np

from presto_tpu.catalog.tpcds import TpcdsGenerator

_WANTED = {"catalog_sales": ("cs_bill_cdemo_sk", "cs_bill_hdemo_sk"),
           "item": ("i_item_desc",)}


def _missing():
    gen = TpcdsGenerator(1e-6)  # one catalog sale; the item table is small
    return [c for table, cols in _WANTED.items()
            for c in cols if c not in getattr(gen, table)()]


if _missing():
    raise ImportError(
        "benchmark.data_tpcds: this engine's TPC-DS generator has no "
        f"{', '.join(_missing())}; it cannot plan TPC-DS Q72. Not run.")


def generate(sf: float, seed: int, tables: Iterable[str]) -> Dict[str, dict]:
    """{table: {column: array | pair}} for `tables`; catalog_returns is
    drawn from the catalog sales this call makes, as the connector draws
    it."""
    gen = TpcdsGenerator(sf, seed=int(seed))
    out: Dict[str, dict] = {}
    for t in tables:
        if t == "catalog_sales":
            out[t] = gen._ensure_channel("cs")
        elif t == "web_sales":
            out[t] = gen._ensure_channel("ws")
        else:
            out[t] = getattr(gen, t)()
    return out


def install(catalog, sf: float, seed: int, data: Dict[str, dict]) -> None:
    """Put `data` into the catalog's TPC-DS connector, so that the catalog
    serves exactly the arrays the reference reads. Tables not in `data`
    would be generated lazily by the connector from the same seed."""
    connector = catalog.connectors["tpcds"]
    connector.gen = TpcdsGenerator(sf, seed=int(seed))
    for name, cols in data.items():
        connector._add(name, cols)


def column_array(col) -> np.ndarray:
    """The array the device holds for a generated column: the codes of a
    dictionary column, the cents of a decimal, the values of a plain one."""
    return col[1] if isinstance(col, tuple) else col


def strings(col, keep=None) -> np.ndarray:
    """A generated string column as strings, of the rows `keep` selects."""
    if isinstance(col, tuple):
        d, codes = col
        return d.decode(codes if keep is None else codes[keep])
    return col if keep is None else col[keep]


def scanned_rows(query_meta: dict, data: Dict[str, dict]) -> int:
    """Input rows of a statement: the rows its FROM tables hold, a table
    named more than once counted once."""
    return sum(len(column_array(next(iter(data[t].values()))))
               for t in query_meta["tables"])


def referenced_bytes(query_meta: dict, data: Dict[str, dict]) -> int:
    """Bytes of the columns the statement references, as the device holds
    them: rows x dtype width, from the arrays themselves; a column generated
    as strings reaches the device as int32 dictionary codes."""
    total = 0
    for table, cols in query_meta["tables"].items():
        for c in cols:
            arr = np.asarray(column_array(data[table][c]))
            total += 4 * len(arr) if arr.dtype == object else arr.nbytes
    return total
