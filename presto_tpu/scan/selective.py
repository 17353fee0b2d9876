"""Lazy column materialization — the selective reader core.

Reference: OrcSelectiveRecordReader's two-phase read: filter columns
decode first, each filter shrinks a row-index selection vector
(positions surviving so far), and payload columns decode only for
surviving rows. A batch whose selection vector empties never touches its
payload columns at all — for wide tables behind selective predicates
that is most of the IO and ALL of the host→device transfer.

The connector supplies `decode(columns_tuple) -> ({name: (values,
validity, hi)}, n)` over its host-decode cache; this module owns the
cascade, the gather, and the Batch assembly.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, Optional, Sequence

import numpy as np

from presto_tpu.batch import Batch, round_up_capacity
from presto_tpu.scan.adaptive import AdaptiveFilterOrder
from presto_tpu.scan.filters import ValueFilter


def _bytes_per_row(handle, columns: Sequence[str]) -> int:
    total = 0
    for c in columns:
        try:
            total += np.dtype(handle.column(c).type.dtype).itemsize
        except (KeyError, TypeError):
            continue
    return total


def selective_read(
    decode: Callable,
    handle,
    columns: Sequence[str],
    filters: Dict[str, ValueFilter],
    capacity: Optional[int] = None,
    dicts: Optional[dict] = None,
    adaptive: Optional[AdaptiveFilterOrder] = None,
    counters: Optional[Callable[[str, int], None]] = None,
) -> Batch:
    """Read one split selectively. `filters` may constrain columns outside
    the projection (a pruned-away predicate column still filters — that is
    pushdown, not a schema change); the returned Batch carries exactly
    `columns`, sized to the survivor count, not the split."""
    import jax.numpy as jnp

    from presto_tpu.batch import Column

    from presto_tpu.obs import trace as _obs_trace

    filter_cols = list(filters)
    order = adaptive.order(filter_cols) if adaptive is not None else filter_cols
    # filter-decode + cascade wall, before any payload materializes
    with _obs_trace.current().phase("scan_filter_cascade"):
        decoded_f, n = decode(tuple(filter_cols))
        sel = np.arange(n)
        for col in order:
            if not len(sel):
                break
            arr, valid, _ = decoded_f[col]
            t0 = time.perf_counter()
            mask = filters[col].test(
                arr[sel], valid[sel] if valid is not None else None)
            rows_in = len(sel)
            sel = sel[mask]
            if adaptive is not None:
                adaptive.update(col, rows_in, len(sel),
                                time.perf_counter() - t0)
    m = len(sel)
    if counters is not None and n > m:
        counters("rows_predecode_filtered", n - m)
        counters("bytes_skipped", (n - m) * _bytes_per_row(handle, columns))
    payload = [c for c in columns if c not in decoded_f]
    decoded_p: dict = {}
    if m and payload:
        decoded_p, n2 = decode(tuple(payload))
        if n2 != n:
            raise RuntimeError(
                f"selective read of {handle.name}: payload decode returned "
                f"{n2} rows, filter decode returned {n}")
    cap = round_up_capacity(max(m, 1))
    if capacity is not None:
        cap = min(cap, capacity)
    live = np.zeros(cap, bool)
    live[:m] = True
    names, typelist, cols = [], [], []
    dicts = dicts or {}
    for name in columns:
        st = handle.column(name).type
        if name in decoded_f:
            arr, valid, hi = decoded_f[name]
        elif name in decoded_p:
            arr, valid, hi = decoded_p[name]
        else:
            # fully-filtered split: payload never decoded — correct-schema
            # all-dead planes
            arr, valid, hi = (np.zeros(0, dtype=st.dtype), None, None)
        buf = np.zeros(cap, dtype=st.dtype)
        if m:
            buf[:m] = arr[sel]
        vcol = None
        if valid is not None:
            vb = np.zeros(cap, bool)
            if m:
                vb[:m] = valid[sel]
            vcol = jnp.asarray(vb)
        hcol = None
        if hi is not None:
            hb = np.zeros(cap, np.int64)
            if m:
                hb[:m] = hi[sel]
            hcol = jnp.asarray(hb)
        names.append(name)
        typelist.append(st)
        cols.append(Column(jnp.asarray(buf), vcol, hcol))
    return Batch(names, typelist, cols, jnp.asarray(live),
                 {c: dicts[c] for c in columns if c in dicts})
