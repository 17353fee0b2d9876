"""ORDER BY / compaction kernels.

Reference: operator/OrderByOperator.java + PagesIndex.java:75 with codegen'd
OrderingCompiler comparators; TopNOperator.java:35.

TPU-native: `lax.sort` (XLA's sort, efficient on TPU) over monotone-encoded
sort keys with a permutation payload, then gather every column through the
permutation. Descending order uses bitwise/arithmetic negation of the
encoding rather than a custom comparator. Compaction (live rows to the
front, original order preserved) is a stable sort on the dead bit — the
batch-world analog of copying selected positions into a new Page.

A lexicographic sort over many key operands is one `lax.sort` only up to
`ONE_SORT_MAX_KEYS` keys: the TPU's compiler lowers a sort to a bitonic
network unrolled over every operand, and its time grows steeply with them
(compiling for a described v5e at 2^17 lanes: one int32 key and the
permutation 18 s, three keys 75 s, nine keys 825 s). Past that the order
comes from a loop of stable one-key sorts, least significant key first
(`lex_sort`): one sort program of two operands, whatever the key count.
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
    operands = [(~live).astype(jnp.int32)]
    for k in keys:
        null_rank, v = _encode_key(k)
        if null_rank is not None:
            operands.append(null_rank)
        operands.append(v)
    return lex_sort(operands, stable=True, want_sorted=False)[1]


# the most key operands one lax.sort is given (module docstring)
ONE_SORT_MAX_KEYS = 6


def _word(x: jnp.ndarray):
    """(an int64 array whose signed order and equality are lax.sort's order
    and equality of `x`, whether it fits in 32 signed bits), or None for a
    dtype with no such encoding here (64-bit floats)."""
    dt = x.dtype
    if dt == jnp.bool_:
        return x.astype(jnp.int64), True
    if jnp.issubdtype(dt, jnp.floating):
        if dt.itemsize > 4:
            return None
        # lax.sort's float order: -0.0 equals 0.0, every NaN equal and last
        f = x.astype(jnp.float32)
        f = jnp.where(f == 0, jnp.zeros_like(f), f)
        f = jnp.where(jnp.isnan(f), jnp.full_like(f, jnp.nan), f)
        i = jax.lax.bitcast_convert_type(f, jnp.int32)
        i = jnp.where(i < 0, i ^ jnp.int32(0x7FFFFFFF), i)
        return i.astype(jnp.int64), True
    if jnp.issubdtype(dt, jnp.unsignedinteger):
        if dt.itemsize < 4:
            return x.astype(jnp.int64), True
        if dt.itemsize == 4:
            return x.astype(jnp.int64) - (1 << 31), True
        return (jax.lax.bitcast_convert_type(x, jnp.int64)
                ^ jnp.int64(-(1 << 63))), False
    return x.astype(jnp.int64), dt.itemsize <= 4


def _words(keys: Sequence[jnp.ndarray]):
    """The keys as int64 words, most significant first, with the same
    lexicographic order: two neighbouring keys that fit in 32 bits share a
    word (the first in its high half). None where a key has no encoding."""
    enc = [_word(k) for k in keys]
    if any(e is None for e in enc):
        return None
    words, i = [], 0
    while i < len(enc):
        v, narrow = enc[i]
        if narrow and i + 1 < len(enc) and enc[i + 1][1]:
            lo = enc[i + 1][0] + (1 << 31)
            words.append(jax.lax.shift_left(v, jnp.int64(32)) | lo)
            i += 2
        else:
            words.append(v)
            i += 1
    return words


def _lsd_permutation(words: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """Stable permutation ordering lanes by `words` lexicographically: one
    stable one-key sort a word, least significant first, in a loop whose
    body (one sort of the word and the permutation) compiles once."""
    n = words[0].shape[0]
    stack = jnp.stack(list(words)[::-1])

    def one_word(i, perm):
        return jax.lax.sort([stack[i][perm], perm], num_keys=1,
                            is_stable=True)[1]

    return jax.lax.fori_loop(0, len(words), one_word,
                             jnp.arange(n, dtype=jnp.int32))


def lex_sort(keys: Sequence[jnp.ndarray], stable: bool = False,
             want_sorted: bool = True):
    """(the keys in lexicographic order or None, the permutation) — what
    `lax.sort(keys + [iota], num_keys=len(keys))` gives. Past
    `ONE_SORT_MAX_KEYS` keys the permutation comes from `_lsd_permutation`
    (always stable) and each key is gathered through it."""
    keys = list(keys)
    n = keys[0].shape[0]
    words = _words(keys) if len(keys) > ONE_SORT_MAX_KEYS else None
    if words is None:
        out = jax.lax.sort(keys + [jnp.arange(n, dtype=jnp.int32)],
                           num_keys=len(keys), is_stable=stable)
        return (list(out[:-1]) if want_sorted else None), out[-1]
    perm = _lsd_permutation(words)
    return ([k[perm] for k in keys] if want_sorted else None), perm


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
