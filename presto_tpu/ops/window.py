"""Window function kernels.

Reference: operator/WindowOperator.java:47 + operator/window/* (21 files:
RowNumberFunction, RankFunction, DenseRankFunction, NtileFunction,
LagFunction, LeadFunction, FirstValueFunction, LastValueFunction,
PercentRankFunction, CumeDistFunction, aggregate window frames).

TPU-native redesign: the reference walks each partition row-by-row with
per-function accumulators over a PagesIndex. Here the whole input is sorted
once by (partition keys, order keys) via lax.sort, then every window value
is a closed-form vectorized computation over the sorted array:

- partition/peer boundaries  → adjacent-row key-change masks
- segment start index        → cummax of boundary-marked iota
- segment id / sizes         → cumsum of boundaries + one scatter-add
- running (frame) aggregates → cumsum minus its value at segment start
- RANGE CURRENT ROW frames   → gather the running value at the last peer row
- lag/lead                   → shifted gathers with same-partition masking

No sequential per-partition loops anywhere — one O(n log n) sort plus O(n)
vector ops, all on the VPU.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from presto_tpu.batch import Batch, Column


class WindowKeys(NamedTuple):
    """Sorted-order boundary structure shared by every function over one
    (partition_by, order_by) spec."""

    is_start: jnp.ndarray      # partition boundary at i
    seg_start: jnp.ndarray     # index of partition start, per row
    seg_id: jnp.ndarray        # partition ordinal, per row
    seg_size: jnp.ndarray      # partition row count, per row
    peer_start: jnp.ndarray    # index of first peer (same order keys), per row
    peer_last: jnp.ndarray     # index of last peer, per row
    row_number: jnp.ndarray    # 1-based position within partition
    live: jnp.ndarray
    n_live: jnp.ndarray


def _change_mask(cols, live):
    """True at i where any key column differs from row i-1 (or i == 0)."""
    n = live.shape[0]
    iota = jnp.arange(n)
    change = iota == 0
    for values, validity in cols:
        prev = jnp.roll(values, 1)
        diff = values != prev
        if jnp.issubdtype(values.dtype, jnp.floating):
            # SQL total order: NaN equals NaN for grouping/peers
            diff = diff & ~(jnp.isnan(values) & jnp.isnan(prev))
        if validity is not None:
            pv = jnp.roll(validity, 1)
            # null vs null is "same" for partitioning/peers (SQL grouping
            # semantics); null vs value differs
            diff = jnp.where(validity & pv, diff, validity != pv)
        change = change | diff
    return change


def window_keys(
    part_cols: Sequence[tuple], order_cols: Sequence[tuple], live: jnp.ndarray
) -> WindowKeys:
    """All boundary structure for one spec, over batch-sorted rows (live rows
    first — sort_permutation puts dead rows last)."""
    n = live.shape[0]
    # positions in int32 (a batch has fewer than 2^31 lanes): the TPU has no
    # 64-bit integer unit, and its compiler takes minutes over an emulated
    # int64 cummax or scatter, or fails on them
    iota = jnp.arange(n, dtype=jnp.int32)
    is_start = _change_mask(part_cols, live)
    seg_start = jax.lax.cummax(jnp.where(is_start, iota, 0))
    seg_id = jnp.cumsum(is_start.astype(jnp.int32)) - 1
    ones = live.astype(jnp.int32)
    sizes = jnp.zeros(n, dtype=jnp.int32).at[seg_id].add(ones, mode="drop")
    seg_size = sizes[seg_id]
    peer_change = is_start | _change_mask(order_cols, live) if order_cols else is_start
    if not order_cols:
        # no ORDER BY: every partition row is a peer of every other
        peer_start = seg_start
        peer_last = seg_start + jnp.maximum(seg_size - 1, 0)
    else:
        peer_start = jax.lax.cummax(jnp.where(peer_change, iota, 0))
        peer_id = jnp.cumsum(peer_change.astype(jnp.int32)) - 1
        last = jnp.zeros(n, dtype=jnp.int32).at[peer_id].max(
            jnp.where(live, iota, 0), mode="drop"
        )
        peer_last = last[peer_id]
    row_number = iota - seg_start + 1
    i64 = jnp.int64
    return WindowKeys(
        is_start, seg_start.astype(i64), seg_id, seg_size.astype(i64),
        peer_start.astype(i64), peer_last, row_number.astype(i64),
        live, jnp.sum(ones, dtype=i64),
    )


# ---------------------------------------------------------------------------
# ranking functions


def row_number(k: WindowKeys):
    return k.row_number, None


def rank(k: WindowKeys):
    return (k.peer_start - k.seg_start + 1).astype(jnp.int64), None


def dense_rank(k: WindowKeys):
    n = k.live.shape[0]
    iota = jnp.arange(n)
    peer_change = iota == k.peer_start  # first row of each peer group
    cnt = jnp.cumsum(peer_change.astype(jnp.int64))
    return cnt - cnt[k.seg_start] + 1, None


def percent_rank(k: WindowKeys):
    r = (k.peer_start - k.seg_start + 1).astype(jnp.float64)
    denom = jnp.maximum(k.seg_size - 1, 1).astype(jnp.float64)
    out = jnp.where(k.seg_size > 1, (r - 1) / denom, 0.0)
    return out, None


def cume_dist(k: WindowKeys):
    covered = (k.peer_last - k.seg_start + 1).astype(jnp.float64)
    return covered / jnp.maximum(k.seg_size, 1).astype(jnp.float64), None


def ntile(k: WindowKeys, buckets: int):
    """SQL NTILE: first (size % n) buckets get one extra row."""
    size = k.seg_size
    n = jnp.asarray(buckets, dtype=jnp.int64)
    q = size // n
    r = size % n
    rn0 = k.row_number - 1
    big = r * (q + 1)  # rows covered by the larger buckets
    in_big = rn0 < big
    b = jnp.where(
        in_big,
        rn0 // jnp.maximum(q + 1, 1),
        r + (rn0 - big) // jnp.maximum(q, 1),
    )
    # more buckets than rows: bucket == row_number
    b = jnp.where(size < n, rn0, b)
    return b + 1, None


# ---------------------------------------------------------------------------
# value functions


def _shift_gather(values, validity, idx, ok, live, default=None):
    """Gather values[idx] where `ok`; out-of-frame rows are NULL, or
    `default` (lag/lead 3-arg form) when given."""
    n = values.shape[0]
    idx = jnp.clip(idx, 0, n - 1)
    v = values[idx]
    valid = jnp.ones(n, dtype=bool) if validity is None else validity[idx]
    valid = valid & ok & live
    if default is not None:
        v = jnp.where(ok, v, jnp.asarray(default, v.dtype))
        valid = valid | (~ok & live)
    return v, valid


def lag(k: WindowKeys, values, validity, offset: int = 1, default=None):
    n = values.shape[0]
    iota = jnp.arange(n)
    idx = iota - offset
    ok = idx >= k.seg_start
    return _shift_gather(values, validity, idx, ok, k.live, default)


def lead(k: WindowKeys, values, validity, offset: int = 1, default=None):
    n = values.shape[0]
    iota = jnp.arange(n)
    idx = iota + offset
    seg_end = k.seg_start + k.seg_size - 1
    ok = idx <= seg_end
    return _shift_gather(values, validity, idx, ok, k.live, default)


def first_value(k: WindowKeys, values, validity):
    return _shift_gather(values, validity, k.seg_start,
                         jnp.ones_like(k.live), k.live)


def last_value(k: WindowKeys, values, validity):
    # default frame = RANGE UNBOUNDED PRECEDING .. CURRENT ROW → last peer
    return _shift_gather(values, validity, k.peer_last,
                         jnp.ones_like(k.live), k.live)


def nth_value(k: WindowKeys, values, validity, n: int):
    idx = k.seg_start + (n - 1)
    ok = (n >= 1) & (idx <= k.peer_last)
    return _shift_gather(values, validity, idx, ok, k.live)


# ---------------------------------------------------------------------------
# aggregate window functions (default frame: whole partition without ORDER BY,
# RANGE UNBOUNDED PRECEDING..CURRENT ROW with ORDER BY)


def _running_at_peer_last(cum, k: WindowKeys):
    """Frame-inclusive value: the running total at the last peer row."""
    return cum[k.peer_last]


def agg_window(
    k: WindowKeys, fn: str, values, validity, frame: str,
    is_float: bool,
):
    """sum/avg/min/max/count over the window. frame: "whole" = whole
    partition (no ORDER BY), "range" = RANGE UNBOUNDED..CURRENT (default with
    ORDER BY — peer rows included), "rows" = ROWS UNBOUNDED..CURRENT."""
    n = values.shape[0]
    valid = k.live if validity is None else (k.live & validity)
    framed = frame in ("range", "rows")

    def frame_value(run):
        return run if frame == "rows" else _running_at_peer_last(run, k)

    if fn == "count":
        c = jnp.cumsum(valid.astype(jnp.int64))
        run = c - c[k.seg_start] + valid[k.seg_start].astype(jnp.int64)
        if framed:
            return frame_value(run), None
        total = jnp.zeros(n, jnp.int64).at[k.seg_id].add(
            valid.astype(jnp.int64), mode="drop"
        )
        return total[k.seg_id], None

    if fn in ("sum", "avg"):
        acc_dtype = values.dtype if is_float else jnp.int64
        v = jnp.where(valid, values.astype(acc_dtype), 0)
        cs = jnp.cumsum(v)
        run = cs - cs[k.seg_start] + v[k.seg_start]
        cv = jnp.cumsum(valid.astype(jnp.int64))
        runc = cv - cv[k.seg_start] + valid[k.seg_start].astype(jnp.int64)
        if framed:
            s = frame_value(run)
            c = frame_value(runc)
        else:
            s = jnp.zeros(n, acc_dtype).at[k.seg_id].add(v, mode="drop")[k.seg_id]
            c = jnp.zeros(n, jnp.int64).at[k.seg_id].add(
                valid.astype(jnp.int64), mode="drop"
            )[k.seg_id]
        out_valid = c > 0
        if fn == "sum":
            return s, out_valid
        if is_float:
            return s / jnp.maximum(c, 1).astype(s.dtype), out_valid
        # integer/decimal avg: round half away from zero, like the
        # aggregation finalizer
        av = jnp.abs(s)
        cden = jnp.maximum(c, 1)
        q = jnp.sign(s) * ((av + cden // 2) // cden)
        return q, out_valid

    if fn in ("min", "max"):
        if is_float:
            sent = jnp.inf if fn == "min" else -jnp.inf
        else:
            info = jnp.iinfo(values.dtype)
            sent = info.max if fn == "min" else info.min
        v = jnp.where(valid, values, jnp.asarray(sent, values.dtype))
        if fn == "min":
            cm = _segmented_cummin(v, k)
        else:
            cm = -_segmented_cummin(-v, k)
        cnt = jnp.cumsum(valid.astype(jnp.int64))
        runc = cnt - cnt[k.seg_start] + valid[k.seg_start].astype(jnp.int64)
        if framed:
            out = frame_value(cm)
            c = frame_value(runc)
            return out, c > 0
        total = (
            jnp.full(n, sent, dtype=v.dtype).at[k.seg_id].min(v, mode="drop")
            if fn == "min"
            else jnp.full(n, sent, dtype=v.dtype).at[k.seg_id].max(v, mode="drop")
        )
        ctot = jnp.zeros(n, jnp.int64).at[k.seg_id].add(
            valid.astype(jnp.int64), mode="drop"
        )
        return total[k.seg_id], ctot[k.seg_id] > 0

    raise NotImplementedError(f"window aggregate {fn}")


# ---------------------------------------------------------------------------
# bounded ROWS frames (ROWS BETWEEN <bound> AND <bound>)


def parse_frame_bound(tok: str):
    """'up' | 'uf' | 'cur' | 'pN' | 'fN' → (kind, offset)."""
    if tok in ("up", "uf", "cur"):
        return tok, 0
    if tok[0] == "p":
        return "p", int(tok[1:])  # lint: allow(host-sync)
    if tok[0] == "f":
        return "f", int(tok[1:])  # lint: allow(host-sync)
    raise ValueError(f"bad frame bound {tok!r}")


def frame_bounds(k: WindowKeys, frame: str):
    """'rows:<s>:<e>' → (start_idx, end_idx, nonempty) per sorted row.
    Bounds clamp to the partition; an inverted frame is empty (SQL: the
    aggregate over an empty frame is NULL / count 0)."""
    _, s_tok, e_tok = frame.split(":")
    sk, so = parse_frame_bound(s_tok)
    ek, eo = parse_frame_bound(e_tok)
    n = k.live.shape[0]
    iota = jnp.arange(n)
    seg_end = k.seg_start + jnp.maximum(k.seg_size - 1, 0)
    start = {
        "up": k.seg_start,
        "cur": iota,
        "p": iota - so,
        "f": iota + so,
        "uf": seg_end,
    }[sk]
    end = {
        "up": k.seg_start,
        "cur": iota,
        "p": iota - eo,
        "f": iota + eo,
        "uf": seg_end,
    }[ek]
    nonempty = (jnp.maximum(start, k.seg_start)
                <= jnp.minimum(end, seg_end)) & k.live
    start_c = jnp.clip(start, k.seg_start, seg_end)
    end_c = jnp.clip(end, k.seg_start, seg_end)
    return start_c.astype(jnp.int32), end_c.astype(jnp.int32), nonempty


def _range_min_table(v):
    """Sparse table for O(1) range-min queries: levels[j][i] = min over
    [i, i + 2^j). O(n log n) build, pure elementwise shifts — the
    vectorized substitute for the reference's per-row frame walk."""
    n = v.shape[0]
    levels = [v]
    j = 0
    while (1 << (j + 1)) <= n:
        prev = levels[-1]
        half = 1 << j
        shifted = jnp.concatenate([prev[half:], prev[-1:].repeat(half)])
        levels.append(jnp.minimum(prev, shifted))
        j += 1
    return jnp.stack(levels)  # [L, n]


def _range_min_query(table, start, end):
    """min over [start, end] (inclusive, start<=end) via two overlapping
    power-of-two windows."""
    n = table.shape[1]
    span = (end - start + 1).astype(jnp.int32)
    # floor(log2(span)): span >= 1
    j = (31 - jax.lax.clz(span.astype(jnp.int32))).astype(jnp.int32)
    j = jnp.clip(j, 0, table.shape[0] - 1)
    second = jnp.clip(end - (1 << j) + 1, 0, n - 1)
    a = table[j, start]
    b = table[j, second]
    return jnp.minimum(a, b)


def range_frame_bounds(k: WindowKeys, order_vals, frame: str,
                       order_valid=None, nulls_first: bool = False,
                       offset_scale: int = 1):
    """'range:<s>:<e>' with VALUE offsets over ONE ascending-ized numeric
    order key: per-row frame bounds by vectorized binary search (log n
    elementwise gather steps — no per-row loops). order_vals are the
    partition-sorted key values in their NATIVE domain (int64 for
    integral/decimal/date keys — exact past 2^53 — float64 for doubles);
    offsets are scaled by offset_scale (10^scale for decimals) so the
    comparison happens in the exact unscaled domain. NULL keys
    (order_valid False) and NaN keys are excluded from the searchable
    span; their offset bounds resolve to their peer-group edges while
    non-offset bounds (UNBOUNDED / CURRENT ROW) keep their meaning."""
    _, s_tok, e_tok = frame.split(":")
    sk, so = parse_frame_bound(s_tok)
    ek, eo = parse_frame_bound(e_tok)
    seg_end = (k.seg_start + jnp.maximum(k.seg_size - 1, 0)).astype(jnp.int32)
    seg_start = k.seg_start.astype(jnp.int32)
    v = order_vals
    iters = max(1, int(k.live.shape[0] - 1).bit_length()) + 1

    # NULL keys sit at one contiguous end of each partition (per
    # nulls_first); NaN keys always sort at the tail of the non-null run
    # (lax.sort totals NaN greatest in both directions — DESC negates,
    # and -NaN is still NaN). Shrink the searchable span so no finite
    # target ever absorbs either group — this also keeps genuine +inf
    # keys distinct from NaN keys.
    def segcount(mask):
        c = jnp.cumsum(mask.astype(jnp.int32))
        return c[seg_end] - c[seg_start] + mask[seg_start].astype(jnp.int32)

    nan_mask = (jnp.isnan(v) & k.live
                if v is not None and jnp.issubdtype(v.dtype, jnp.floating)
                else None)
    null_mask = ((~order_valid) & k.live) if order_valid is not None else None
    lo0, hi0 = seg_start, seg_end
    if null_mask is not None and nulls_first:
        lo0 = jnp.minimum(seg_start + segcount(null_mask), seg_end)
    tail = nan_mask
    if null_mask is not None and not nulls_first:
        tail = null_mask if tail is None else (tail | null_mask)
    if tail is not None:
        hi0 = jnp.maximum(seg_end - segcount(tail), seg_start)
    # rows whose key can't anchor a value search get their PEER GROUP as
    # the result of any offset bound (SQL: a NULL/NaN row's offset frame
    # edge is its peers); non-offset bounds keep their normal meaning
    over = null_mask
    if nan_mask is not None:
        over = nan_mask if over is None else (over | nan_mask)

    def shift(delta: int):
        """v + delta with saturation (int keys must not wrap past the
        extremes; float +/-inf saturates on its own)."""
        if jnp.issubdtype(v.dtype, jnp.floating):
            return v + float(delta)  # lint: allow(host-sync)
        t = v + jnp.asarray(delta, v.dtype)
        if delta > 0:
            t = jnp.where(t < v, jnp.iinfo(v.dtype).max, t)
        elif delta < 0:
            t = jnp.where(t > v, jnp.iinfo(v.dtype).min, t)
        return t

    def lower_bound(target):
        """Smallest index in [lo0, hi0] whose key >= target (keys ascend
        within the partition); hi0+1 when none."""
        lo, hi = lo0, hi0
        for _ in range(iters):
            mid = (lo + hi) // 2
            ok = v[mid] >= target
            hi = jnp.where(ok, mid, hi)
            lo = jnp.where(ok, lo, jnp.minimum(mid + 1, hi0))
        return jnp.where(v[hi] >= target, hi, hi0 + 1)

    def upper_bound(target):
        """Largest index in [lo0, hi0] whose key <= target; lo0-1 when
        none."""
        lo, hi = lo0, hi0
        for _ in range(iters):
            mid = (lo + hi + 1) // 2
            ok = v[mid] <= target
            lo = jnp.where(ok, mid, lo)
            hi = jnp.where(ok, hi, jnp.maximum(mid - 1, lo0))
        return jnp.where(v[lo] <= target, lo, lo0 - 1)

    if sk == "up":
        start = seg_start
    elif sk == "cur":
        # RANGE start at CURRENT ROW includes preceding PEERS
        start = k.peer_start.astype(jnp.int32)
    else:
        start = lower_bound(shift((-so if sk == "p" else so) * offset_scale))
        if over is not None:
            start = jnp.where(over, k.peer_start.astype(jnp.int32), start)
    if ek == "uf":
        end = seg_end
    elif ek == "cur":
        end = k.peer_last.astype(jnp.int32)
    else:
        end = upper_bound(shift((eo if ek == "f" else -eo) * offset_scale))
        if over is not None:
            end = jnp.where(over, k.peer_last.astype(jnp.int32), end)
    nonempty = (start <= end) & k.live
    start = jnp.clip(start, seg_start, seg_end)
    end = jnp.clip(end, seg_start, seg_end)
    return start, end, nonempty


def agg_window_bounded(k: WindowKeys, fn: str, values, validity,
                       frame: str, is_float: bool, order_vals=None,
                       order_valid=None, nulls_first: bool = False,
                       offset_scale: int = 1):
    """sum/avg/min/max/count over an explicit ROWS or RANGE frame.
    Prefix-sum differences for sum/count (both gather indices stay
    inside one partition, so cross-partition terms cancel); sparse-table
    range min/max for extremes."""
    if frame.startswith("range:"):
        start, end, nonempty = range_frame_bounds(
            k, order_vals, frame, order_valid, nulls_first, offset_scale)
    else:
        start, end, nonempty = frame_bounds(k, frame)
    valid = k.live if validity is None else (k.live & validity)

    def windowed_sum(x, dtype):
        xv = jnp.where(valid, x.astype(dtype), jnp.zeros((), dtype))
        cs = jnp.cumsum(xv)
        lo = jnp.where(start > 0, cs[jnp.maximum(start - 1, 0)],
                       jnp.zeros((), dtype))
        return cs[end] - lo

    cnt = windowed_sum(jnp.ones_like(k.live, dtype=jnp.int64), jnp.int64)
    cnt = jnp.where(nonempty, cnt, 0)
    if fn == "count":
        return cnt, None
    if fn in ("sum", "avg"):
        acc_dtype = values.dtype if is_float else jnp.int64
        s = jnp.where(nonempty, windowed_sum(values, acc_dtype),
                      jnp.zeros((), acc_dtype))
        out_valid = nonempty & (cnt > 0)
        if fn == "sum":
            return s, out_valid
        if is_float:
            return s / jnp.maximum(cnt, 1).astype(s.dtype), out_valid
        av = jnp.abs(s)
        cden = jnp.maximum(cnt, 1)
        return jnp.sign(s) * ((av + cden // 2) // cden), out_valid
    if fn in ("min", "max"):
        if is_float:
            sent = jnp.inf if fn == "min" else -jnp.inf
        else:
            info = jnp.iinfo(values.dtype)
            sent = info.max if fn == "min" else info.min
        v = jnp.where(valid, values, jnp.asarray(sent, values.dtype))
        if fn == "max":
            v = -v
        table = _range_min_table(v)
        out = _range_min_query(table, start, end)
        if fn == "max":
            out = -out
        return out, nonempty & (cnt > 0)
    raise NotImplementedError(f"bounded window aggregate {fn}")


def value_over_frame(k: WindowKeys, fn: str, values, validity, frame: str,
                     nth: int = 1, order_vals=None, order_valid=None,
                     nulls_first: bool = False, offset_scale: int = 1):
    """first_value/last_value/nth_value over an explicit ROWS or RANGE
    frame."""
    if frame.startswith("range:"):
        start, end, nonempty = range_frame_bounds(
            k, order_vals, frame, order_valid, nulls_first, offset_scale)
    else:
        start, end, nonempty = frame_bounds(k, frame)
    if fn == "first_value":
        idx = start
        ok = nonempty
    elif fn == "last_value":
        idx = end
        ok = nonempty
    else:
        idx = start + (nth - 1)
        ok = nonempty & (nth >= 1) & (idx <= end)
    return _shift_gather(values, validity, idx, ok, k.live)


def _segmented_cummin(v, k: WindowKeys):
    """Running minimum that resets at partition boundaries.

    Trick: order-encode (seg_id, v) into a single monotone key so a global
    cummin over the pair key restricted to the segment prefix is exact —
    implemented as an associative scan over (seg_id, v) pairs whose combine
    keeps the right-hand segment and min-merges only within a segment.
    """

    def combine(a, b):
        sa, va = a
        sb, vb = b
        take_b_only = sb != sa
        return sb, jnp.where(take_b_only, vb, jnp.minimum(va, vb))

    _, out = jax.lax.associative_scan(
        combine, (k.seg_id.astype(jnp.int32), v)
    )
    return out
