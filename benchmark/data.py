"""Data for a run: the tables a cell's queries name, made once from the seed
and handed to both the catalog (the system under test) and the reference.

The generator is the program's (`presto_tpu/catalog/tpch.py`, dbgen-shaped);
orders and lineitem are generated in chunks of `CHUNK_ORDERS` orders on a few
threads (numpy releases the lock), which is what shortens set-up at SF10.

A configuration names the module that makes its data (`"data_module"`, this
one for TPC-H); another schema or a skewed generator brings a module of its
own with the same five functions: `generate`, `install`, `scanned_rows`,
`referenced_bytes`, `column_array`.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Iterable

import numpy as np

# tables that come out of one generator call
_PAIRED = ("orders", "lineitem")
CHUNK_ORDERS = 1_500_000


def _concat(chunks):
    """Concatenate per-chunk column dicts; a (Dictionary, codes) column keeps
    the first chunk's dictionary (every chunk builds the same vocabulary)."""
    out = {}
    for col, first in chunks[0].items():
        if isinstance(first, tuple):
            out[col] = (first[0], np.concatenate([c[col][1] for c in chunks]))
        else:
            out[col] = np.concatenate([c[col] for c in chunks])
    return out


def generate(sf: float, seed: int, tables: Iterable[str]) -> Dict[str, dict]:
    """{table: {column: array | (Dictionary, codes)}} for `tables`; orders
    and lineitem come out of one generator call, so asking for one gives
    both."""
    from presto_tpu.catalog.tpch import TpchGenerator

    gen = TpchGenerator(sf, seed=int(seed))
    tables = list(tables)
    out: Dict[str, dict] = {}
    if any(t in _PAIRED for t in tables):
        n = gen.n_orders
        starts = list(range(0, n, CHUNK_ORDERS))
        # the generator builds its shared dictionaries lazily on first use:
        # do that once here, not in a race between threads
        gen.orders_lineitem_chunk(0, 1)
        threads = min(len(starts), max(1, (os.cpu_count() or 2) - 1))

        def one(start):
            return gen.orders_lineitem_chunk(start, min(CHUNK_ORDERS, n - start))

        if len(starts) == 1:
            parts = [one(0)]
        else:
            with ThreadPoolExecutor(threads) as ex:
                parts = list(ex.map(one, starts))
        out["orders"] = _concat([p[0] for p in parts])
        out["lineitem"] = _concat([p[1] for p in parts])
        del parts
    for t in tables:
        if t not in _PAIRED:
            out[t] = getattr(gen, t)()
    return out


def install(catalog, sf: float, seed: int, data: Dict[str, dict]) -> None:
    """Put `data` into the catalog's TPC-H connector, so that the catalog
    serves exactly the arrays the reference reads. Tables not in `data`
    would be generated lazily by the connector from the same seed."""
    from presto_tpu.catalog.tpch import TpchGenerator

    connector = catalog.connectors["tpch"]
    connector.gen = TpchGenerator(sf, seed=int(seed))
    for name, cols in data.items():
        connector._add(name, cols)


def column_array(col) -> np.ndarray:
    """The array the device holds for a generated column: the codes of a
    dictionary column, the values of a plain one."""
    return col[1] if isinstance(col, tuple) else col


def strings(col, keep=None) -> np.ndarray:
    """A generated string column as strings, of the rows `keep` selects."""
    if isinstance(col, tuple):
        d, codes = col
        return d.decode(codes if keep is None else codes[keep])
    return col if keep is None else col[keep]


def scanned_rows(query_meta: dict, data: Dict[str, dict]) -> int:
    """Input rows of a statement: the rows its FROM tables hold."""
    total = 0
    for table in query_meta["tables"]:
        cols = data[table]
        total += len(column_array(next(iter(cols.values()))))
    return total


def referenced_bytes(query_meta: dict, data: Dict[str, dict]) -> int:
    """Bytes of the columns the statement references, as the device holds
    them: rows x dtype width, from the arrays themselves."""
    total = 0
    for table, cols in query_meta["tables"].items():
        for c in cols:
            arr = column_array(data[table][c])
            # a column generated as strings reaches the device as int32
            # dictionary codes, like the generator's own code columns
            total += 4 * len(arr) if arr.dtype == object else arr.nbytes
    return total
