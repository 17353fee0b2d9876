"""Lexicographic sorts past `ONE_SORT_MAX_KEYS` key operands (ops/sort.py,
`lex_sort`): a loop of stable one-key sorts over packed int64 words gives
the order, and the keys, one `lax.sort` over every operand gives.

Held here against `lax.sort` itself on mixes of every key dtype the engine
sorts (ties, NULL ranks, float zeros and NaNs, 64-bit extremes), and through
the kernels that call it — the sort engine's GROUP BY on eight keys, ORDER
BY on ten, and a DISTINCT over wide rows — against pandas."""

import numpy as np
import pandas as pd
import pytest

import jax
import jax.numpy as jnp

from presto_tpu.ops import sort as S
from presto_tpu.ops.grouping import KeyCol, StateCol, grouped_merge

N = 2048


def _col(rng, kind, n=N):
    if kind == "i32":
        return rng.integers(-3, 3, n).astype(np.int32)
    if kind == "i64":
        return rng.choice(np.array([-2**63, -5, 0, 7, 2**62, 2**63 - 1],
                                   np.int64), n)
    if kind == "u32":
        return rng.choice(np.array([0, 1, 2**31, 2**32 - 1], np.uint32), n)
    if kind == "u64":
        return rng.choice(np.array([0, 1, 2**63, 2**64 - 1], np.uint64), n)
    if kind == "f32":
        return rng.choice(np.array([-np.inf, -1.5, -0.0, 0.0, 2.0, np.inf,
                                    np.nan, -np.nan], np.float32), n)
    if kind == "bool":
        return rng.integers(0, 2, n).astype(bool)
    if kind == "i8":
        return rng.integers(-3, 3, n).astype(np.int8)
    raise AssertionError(kind)


MIXES = [
    ["i32"] * 9,
    ["i64"] * 7,
    ["i32", "i32", "i32", "i32", "i32", "i64", "i64", "i64", "i32"],
    ["bool", "i8", "u32", "i64", "u64", "f32", "i32", "i32"],
    ["f32", "i64", "f32", "u32", "bool", "i32", "i64", "f32", "i8", "u64"],
    ["i32", "i64"] * 11,
]


@pytest.mark.parametrize("stable", [True, False])
@pytest.mark.parametrize("mix", range(len(MIXES)))
def test_lex_sort_gives_what_one_lax_sort_gives(mix, stable):
    kinds = MIXES[mix]
    assert len(kinds) > S.ONE_SORT_MAX_KEYS
    rng = np.random.default_rng(mix)
    keys = [jnp.asarray(_col(rng, k)) for k in kinds]
    ref = jax.lax.sort(keys + [jnp.arange(N, dtype=jnp.int32)],
                       num_keys=len(keys), is_stable=True)
    got, perm = jax.jit(lambda *k: S.lex_sort(list(k), stable=stable))(*keys)
    # the loop is stable whatever was asked: the permutation is the stable one
    np.testing.assert_array_equal(np.asarray(perm), np.asarray(ref[-1]))
    for g, r in zip(got, ref[:-1]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


def test_a_narrow_sort_is_one_lax_sort_and_a_wide_one_a_loop():
    def sorts_and_loops(n_keys):
        keys = [jnp.zeros(N, jnp.int32)] * n_keys
        jaxpr = str(jax.make_jaxpr(lambda *k: S.lex_sort(list(k)))(*keys))
        return jaxpr.count("sort["), jaxpr.count("scan[")

    assert sorts_and_loops(S.ONE_SORT_MAX_KEYS) == (1, 0)
    assert sorts_and_loops(S.ONE_SORT_MAX_KEYS + 1) == (1, 1)
    assert sorts_and_loops(22) == (1, 1)


def test_64_bit_floats_keep_one_lax_sort():
    keys = [jnp.zeros(N, jnp.float64)] + [jnp.zeros(N, jnp.int32)] * 8
    assert S._words(keys) is None
    jaxpr = str(jax.make_jaxpr(lambda *k: S.lex_sort(list(k)))(*keys))
    assert jaxpr.count("sort[") == 1 and "scan[" not in jaxpr


def _frame(seed, n=N):
    rng = np.random.default_rng(seed)
    df = pd.DataFrame({
        "a": rng.integers(0, 3, n).astype(np.int32),
        "b": rng.integers(0, 2, n).astype(np.int32),
        "c": rng.integers(0, 3, n).astype(np.int64),
        "d": rng.integers(-2, 2, n).astype(np.int64),
        "e": rng.integers(0, 2, n).astype(np.int32),
        "f": rng.integers(0, 2, n).astype(np.int64),
        "g": rng.integers(0, 2, n).astype(np.int32),
        "h": rng.integers(0, 3, n).astype(np.int32),
    })
    valid = {k: rng.random(n) > 0.15 for k in df.columns}
    live = rng.random(n) > 0.1
    return df, valid, live, rng.integers(1, 100, n).astype(np.int64)


def _tuples(cols, valids, sel):
    """Rows as tuples of (is NULL, value) per key: the sort engine's key
    order, NULLs of a key after its values."""
    return [tuple((0, int(v)) if ok else (1, 0) for v, ok in zip(*row))
            for row in zip(zip(*[np.asarray(c)[sel] for c in cols]),
                           zip(*[np.asarray(k)[sel] for k in valids]))]


def test_sort_engine_groups_eight_nullable_keys_as_pandas():
    """Q67's finest set: eight keys, each with NULLs, 17 sort operands."""
    df, valid, live, x = _frame(3)
    vals = [jnp.asarray(df[k].to_numpy()) for k in df.columns]
    oks = [jnp.asarray(valid[k]) for k in df.columns]

    def merge(vals, oks, x, live):
        keys = [KeyCol(v, ok) for v, ok in zip(vals, oks)]
        kout, sout, out_live, n_groups = grouped_merge(
            keys, [StateCol(x, None, "sum")], live, 4096, engine="sort")
        return ([(k.values, k.validity) for k in kout], sout[0].values,
                out_live, n_groups)

    kout, sout, out_live, n_groups = jax.jit(merge)(
        vals, oks, jnp.asarray(x), jnp.asarray(live))
    ol = np.asarray(out_live)
    got = _tuples([v for v, _ in kout], [ok for _, ok in kout], ol)
    sums = np.asarray(sout)[ol]
    want = {}
    for t, v in zip(_tuples([df[c] for c in df.columns],
                            [valid[c] for c in df.columns], live), x[live]):
        want[t] = want.get(t, 0) + int(v)
    assert int(n_groups) == len(want) == len(got)
    assert got == sorted(want)  # the sort engine hands groups in key order
    assert [int(v) for v in sums] == [want[t] for t in got]


def test_order_by_ten_keys_as_numpy():
    """ORDER BY ten keys, some descending, NULLs placed per key: the
    stable permutation of numpy's lexsort over the same encodings."""
    df, valid, live, _ = _frame(5)
    cols = list(df.columns) + ["a", "c"]
    desc = [i % 3 == 1 for i in range(len(cols))]
    nulls_first = [i % 4 == 2 for i in range(len(cols))]
    def order(vals, oks, live):
        return S.sort_permutation(
            [S.SortKey(v, ok, d, nf)
             for v, ok, d, nf in zip(vals, oks, desc, nulls_first)], live)

    perm = np.asarray(jax.jit(order)(
        [jnp.asarray(df[c].to_numpy()) for c in cols],
        [jnp.asarray(valid[c]) for c in cols], jnp.asarray(live)))
    ops = [(~live).astype(np.int64)]
    for c, d, nf in zip(cols, desc, nulls_first):
        v, ok = df[c].to_numpy().astype(np.int64), valid[c]
        ops.append(np.where(ok, 1, 0) if nf else np.where(ok, 0, 1))
        ops.append(np.where(ok, -v if d else v, 0))
    want = np.lexsort(ops[::-1])  # lexsort's last key is its primary one
    np.testing.assert_array_equal(perm, want)


def test_distinct_over_wide_rows_as_pandas():
    from presto_tpu.batch import Batch, Column
    from presto_tpu.exec.runtime import _distinct_rows
    from presto_tpu.types import BIGINT, INTEGER

    df, valid, live, _ = _frame(7)
    types = [INTEGER if df[c].dtype == np.int32 else BIGINT for c in df.columns]
    b = Batch(list(df.columns), types,
              [Column(jnp.asarray(df[c].to_numpy()), jnp.asarray(valid[c]))
               for c in df.columns], jnp.asarray(live), {})
    out = jax.jit(_distinct_rows)(b)
    ol = np.asarray(out.live)
    got = _tuples([out.column(c).values for c in df.columns],
                  [out.column(c).validity for c in df.columns], ol)
    want = set(_tuples([df[c] for c in df.columns],
                       [valid[c] for c in df.columns], live))
    assert len(got) == len(set(got)) == len(want) and set(got) == want
