"""ORDER BY / compaction kernels.

Reference: operator/OrderByOperator.java + PagesIndex.java:75 with codegen'd
OrderingCompiler comparators; TopNOperator.java:35.

TPU-native: `lax.sort` (XLA's sort, efficient on TPU) over monotone-encoded
sort keys with a permutation payload, then gather every column through the
permutation. Descending order uses bitwise/arithmetic negation of the
encoding rather than a custom comparator. Compaction (live rows to the
front, original order preserved) is a stable sort on the dead bit — the
batch-world analog of copying selected positions into a new Page.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from presto_tpu.batch import Batch, Column


class SortKey(NamedTuple):
    values: jnp.ndarray
    validity: Optional[jnp.ndarray]
    descending: bool = False
    nulls_first: bool = False


def _encode_key(k: SortKey):
    """Monotone int/float encoding such that ascending lax.sort yields the
    requested order. Returns (null_rank, value_key)."""
    v = k.values
    if v.dtype == jnp.bool_:
        v = v.astype(jnp.int32)
    if k.descending:
        if jnp.issubdtype(v.dtype, jnp.floating):
            v = -v
        else:
            v = ~v  # two's complement bitwise-not: strictly order-reversing
    if k.validity is None:
        null_rank = None
    else:
        # nulls first → null rank 0; nulls last → null rank 1
        null_rank = jnp.where(k.validity, 1, 0) if k.nulls_first else jnp.where(k.validity, 0, 1)
        null_rank = null_rank.astype(jnp.int32)
        v = jnp.where(k.validity, v, jnp.zeros_like(v))
    return null_rank, v


def sort_permutation(keys: Sequence[SortKey], live: jnp.ndarray) -> jnp.ndarray:
    """Stable permutation ordering live rows by keys, dead rows last."""
    n = live.shape[0]
    operands = [(~live).astype(jnp.int32)]
    for k in keys:
        null_rank, v = _encode_key(k)
        if null_rank is not None:
            operands.append(null_rank)
        operands.append(v)
    perm = jnp.arange(n, dtype=jnp.int32)
    out = jax.lax.sort(operands + [perm], num_keys=len(operands), is_stable=True)
    return out[-1]


def permute_batch(b: Batch, perm: jnp.ndarray) -> Batch:
    return Batch(b.names, b.types, [c.gather(perm) for c in b.columns],
                 b.live[perm], b.dicts)


def sort_batch(b: Batch, keys: Sequence[SortKey], limit: Optional[int] = None) -> Batch:
    perm = sort_permutation(keys, b.live)
    out = permute_batch(b, perm)
    if limit is not None:
        keep = jnp.arange(out.capacity) < limit
        out = out.with_live(out.live & keep)
    return out


def compact_permutation(live: jnp.ndarray,
                        out_cap: Optional[int] = None) -> jnp.ndarray:
    """Lanes of `live` in compaction order (live lanes first, both groups
    in their original order): a stable sort on the dead bit. With a static
    `out_cap` only the first `out_cap` entries, which is all that a result
    of that many lanes is gathered through."""
    n = live.shape[0]
    perm = jax.lax.sort(
        [(~live).astype(jnp.int32), jnp.arange(n, dtype=jnp.int32)],
        num_keys=1, is_stable=True)[-1]
    return perm if out_cap is None else perm[:min(out_cap, n)]


def compact(b: Batch, out_cap: Optional[int] = None) -> Batch:
    """Move live rows to the front (stable). Dead lanes become trailing.

    With a static `out_cap` the result has `min(out_cap, capacity)` lanes:
    every plane is gathered through the head of the permutation alone, so
    what is moved is sized by what is kept and not by the input's
    capacity. Plane for plane it is the whole compaction cut to `out_cap`
    lanes; the caller gives a capacity of at least the live count, or
    live rows are dropped."""
    return permute_batch(b, compact_permutation(b.live, out_cap))


def limit_batch(b: Batch, n: int) -> Batch:
    """LIMIT without ordering: keep the first n live rows."""
    rank = jnp.cumsum(b.live.astype(jnp.int64)) - 1
    return b.with_live(b.live & (rank < n))
