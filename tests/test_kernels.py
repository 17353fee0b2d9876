"""Kernel unit tests against numpy oracles (the tier-1 analog of
presto-main's per-operator tests, e.g. operator/TestHashAggregationOperator,
TestHashJoinOperator — SURVEY §4 tier 1)."""

import numpy as np
import jax
import jax.numpy as jnp
import pandas as pd
import pytest

from presto_tpu.batch import Batch, Column
from presto_tpu.types import BIGINT, DOUBLE, INTEGER
from presto_tpu.ops.grouping import grouped_merge, KeyCol, StateCol
from presto_tpu.ops import join as join_ops
from presto_tpu.ops.join import build_side, probe_unique, probe_counts, probe_expand
from presto_tpu.ops.partition import partition_for_exchange
from presto_tpu.ops.sort import sort_batch, SortKey, compact, limit_batch
from presto_tpu.ops.hashing import hash_columns


def make_batch(rng, n=1000, live_frac=0.9, nkeys=7):
    k = rng.integers(0, nkeys, n)
    v = rng.normal(size=n)
    live = rng.random(n) < live_frac
    b = Batch.from_numpy({"k": k, "v": v}, {"k": BIGINT, "v": DOUBLE})
    pad = np.zeros(b.capacity, bool)
    pad[:n] = live
    return b.with_live(b.live & jnp.asarray(pad)), k, v, live


class TestGrouping:
    def test_sum_count(self, rng):
        b, k, v, live = make_batch(rng)
        keys, states, out_live, ng = grouped_merge(
            [KeyCol(b.column("k").values, None)],
            [StateCol(b.column("v").values, None, "sum")],
            b.live, 64,
        )
        df = pd.DataFrame({"k": k[live], "v": v[live]})
        exp = df.groupby("k")["v"].sum().sort_index()
        lv = np.asarray(out_live)
        got_k = np.asarray(keys[0].values)[lv]
        got_s = np.asarray(states[0].values)[lv]
        order = np.argsort(got_k)
        assert int(ng) == len(exp)
        np.testing.assert_array_equal(got_k[order], exp.index.values)
        np.testing.assert_allclose(got_s[order], exp.values)

    def test_min_max_with_nulls(self, rng):
        n = 500
        k = rng.integers(0, 5, n)
        v = rng.integers(-1000, 1000, n)
        valid = rng.random(n) < 0.8
        b = Batch.from_numpy({"k": k, "v": v}, {"k": BIGINT, "v": BIGINT})
        vcol = np.zeros(b.capacity, bool)
        vcol[:n] = valid
        from presto_tpu.batch import Column

        col = Column(b.column("v").values, jnp.asarray(vcol))
        b = b.with_column("v", BIGINT, col)
        keys, states, out_live, ng = grouped_merge(
            [KeyCol(b.column("k").values, None)],
            [
                StateCol(col.values, col.validity, "min"),
                StateCol(col.values, col.validity, "max"),
            ],
            b.live, 64,
        )
        df = pd.DataFrame({"k": k, "v": np.where(valid, v, np.nan)})
        exp_min = df.groupby("k")["v"].min().sort_index()
        exp_max = df.groupby("k")["v"].max().sort_index()
        lv = np.asarray(out_live)
        got_k = np.asarray(keys[0].values)[lv]
        order = np.argsort(got_k)
        got_min = np.asarray(states[0].values)[lv][order]
        got_max = np.asarray(states[1].values)[lv][order]
        np.testing.assert_allclose(got_min, exp_min.values)
        np.testing.assert_allclose(got_max, exp_max.values)

    def test_null_keys_group_together(self, rng):
        n = 100
        k = rng.integers(0, 3, n)
        valid = rng.random(n) < 0.7
        b = Batch.from_numpy({"k": k}, {"k": BIGINT})
        vk = np.zeros(b.capacity, bool)
        vk[:n] = valid
        keys, states, out_live, ng = grouped_merge(
            [KeyCol(b.column("k").values, jnp.asarray(vk))],
            [StateCol(jnp.ones(b.capacity, jnp.int64), None, "count_add")],
            b.live, 16,
        )
        # distinct live key values + one null group
        expected_groups = len(np.unique(k[valid])) + (1 if (~valid).any() else 0)
        assert int(ng) == expected_groups

    def test_capacity_overflow_reported(self, rng):
        b, k, v, live = make_batch(rng, nkeys=50)
        _, _, _, ng = grouped_merge(
            [KeyCol(b.column("k").values, None)],
            [StateCol(b.column("v").values, None, "sum")],
            b.live, 8,
        )
        assert int(ng) == len(np.unique(k[live]))  # true count reported


class TestJoin:
    def test_unique_probe(self, rng):
        nb, npr = 64, 500
        bk = np.arange(nb)
        bv = rng.normal(size=nb)
        bb = Batch.from_numpy({"id": bk, "x": bv}, {"id": BIGINT, "x": DOUBLE})
        tbl = build_side(bb, ("id",))
        pk = rng.integers(0, 100, npr)
        pb = Batch.from_numpy({"id": pk}, {"id": BIGINT})
        idx, matched = probe_unique(tbl, pb, ("id",), ("id",))
        exp = pk < nb
        np.testing.assert_array_equal(np.asarray(matched)[:npr], exp)
        got_x = np.asarray(tbl.batch.column("x").values)[np.asarray(idx)[:npr]]
        np.testing.assert_allclose(got_x[exp], bv[pk[exp]])

    def test_fanout_expand(self, rng):
        bk = rng.integers(0, 10, 200)
        bb = Batch.from_numpy({"id": bk, "y": np.arange(200)}, {"id": BIGINT, "y": BIGINT})
        tbl = build_side(bb, ("id",))
        pk = rng.integers(0, 12, 100)
        pb = Batch.from_numpy({"id": pk}, {"id": BIGINT})
        lo, counts, offsets, total, _, _ovf = probe_counts(tbl, pb, ("id",), ("id",), max_fanout_scan=4)
        pr, bi, ol = probe_expand(tbl, pb, ("id",), ("id",), lo, counts, offsets, 0, 8192)
        got = set()
        y = np.asarray(tbl.batch.column("y").values)
        prn, bin_, oln = np.asarray(pr), np.asarray(bi), np.asarray(ol)
        for i in range(8192):
            if oln[i]:
                got.add((int(prn[i]), int(y[bin_[i]])))
        exp = {(i, int(j)) for i, x in enumerate(pk) for j in np.where(bk == x)[0]}
        assert got == exp

    def test_null_keys_never_match(self, rng):
        bk = np.arange(10)
        bb = Batch.from_numpy({"id": bk}, {"id": BIGINT})
        tbl = build_side(bb, ("id",))
        pk = np.arange(10)
        pb = Batch.from_numpy({"id": pk}, {"id": BIGINT})
        from presto_tpu.batch import Column

        valid = np.zeros(pb.capacity, bool)
        valid[:5] = True  # rows 5..9 have NULL keys
        pb = pb.with_column("id", BIGINT, Column(pb.column("id").values, jnp.asarray(valid)))
        _, matched = probe_unique(tbl, pb, ("id",), ("id",))
        m = np.asarray(matched)[:10]
        assert m[:5].all() and not m[5:].any()


def _key_batch(keys, live=None, valid=None):
    """A batch of exactly len(keys) lanes (from_numpy would round it up to
    a power of two) with one BIGINT key column `id`."""
    keys = np.asarray(keys, np.int64)
    live = np.ones(len(keys), bool) if live is None else np.asarray(live, bool)
    col = Column(jnp.asarray(keys),
                 None if valid is None else jnp.asarray(np.asarray(valid, bool)))
    return Batch(["id"], [BIGINT], [col], jnp.asarray(live), {})


_SENT = np.iinfo(np.int64).max


def _few_hashes(batch, key_names):
    """join_hash with forced collisions: every key lands on one of three
    hashes, two of them in one bucket of any directory."""
    v = batch.column(key_names[0]).values
    return jnp.asarray(np.array([5, 6, 1 << 61], np.int64))[v % 3]


def _edge_hashes(batch, key_names):
    """join_hash that hands live rows the two values the kernels reserve:
    the build's dead-lane sentinel and the probe's."""
    v = batch.column(key_names[0]).values
    return jnp.asarray(np.array([_SENT, _SENT - 1, 7, 0], np.int64))[v % 4]


def _pair_hashes(batch, key_names):
    """join_hash under which keys 2i and 2i + 1 share bucket and fingerprint
    (every bit from 44 up) and differ in the lowest bit alone: a unique
    probe verifies two lanes to find the second."""
    v = batch.column(key_names[0]).values.astype(jnp.int64)
    return ((v // 2) << 44) | (v % 2)


def _shifted_hashes(batch, key_names):
    """join_hash that keeps the keys' order: one bucket, and a key above the
    live ones searches to the first dead lane."""
    return batch.column(key_names[0]).values.astype(jnp.int64) << 50


def _double_batch(keys):
    """A batch of exactly len(keys) lanes with one DOUBLE key column `id`."""
    keys = np.asarray(keys, np.float64)
    return Batch(["id"], [DOUBLE], [Column(jnp.asarray(keys), None)],
                 jnp.ones(len(keys), bool), {})


def _range_cases():
    r = np.random.default_rng(28)
    some = lambda n, p: r.random(n) < p  # noqa: E731
    nb, npr = 1024, 256  # one pair of shapes for most cases: one compile
    return {
        # name: (build batch, probe batch, join_hash to patch in or None)
        "long_runs_of_one_key": (
            _key_batch(r.integers(0, 3, nb)),
            _key_batch(r.integers(0, 5, npr)), None),
        "every_row_one_key": (
            _key_batch(np.full(nb, 42)),
            _key_batch(r.integers(41, 44, npr)), None),
        "forced_collisions": (
            _key_batch(r.integers(0, 50, nb), some(nb, .7)),
            _key_batch(r.integers(0, 60, npr)), _few_hashes),
        "dead_lanes_and_null_keys_both_sides": (
            _key_batch(r.integers(0, 400, nb), some(nb, .6), some(nb, .8)),
            _key_batch(r.integers(0, 500, npr), some(npr, .6),
                       some(npr, .7)), None),
        "distinct_keys_full_build": (
            _key_batch(r.permutation(nb)),
            _key_batch(r.integers(0, 2 * nb, npr)), None),
        "empty_build": (
            _key_batch(np.arange(nb), np.zeros(nb, bool)),
            _key_batch(np.arange(npr)), None),
        "all_dead_build_by_null_keys": (
            _key_batch(np.arange(nb), valid=np.zeros(nb, bool)),
            _key_batch(np.arange(npr)), None),
        "all_dead_probe": (
            _key_batch(r.integers(0, 30, nb)),
            _key_batch(np.arange(npr), np.zeros(npr, bool)), None),
        "reserved_hashes_on_live_rows": (
            _key_batch(r.integers(0, 4, nb), some(nb, .5)),
            _key_batch(r.integers(0, 4, npr), some(npr, .8)), _edge_hashes),
        "capacity_1_hit": (_key_batch([7]), _key_batch([7, 8, 7, 7]), None),
        "capacity_1_dead": (
            _key_batch([7], [False]), _key_batch([7, 8, 9, 7]), None),
        "capacity_not_a_power_of_two": (
            _key_batch(r.integers(0, 900, 3000), some(3000, .9)),
            _key_batch(r.integers(0, 1000, 777), some(777, .9)), None),
        # distinct keys whose hashes differ below the fingerprint alone
        "pairs_share_bucket_and_fingerprint": (
            _key_batch(r.permutation(nb)),
            _key_batch(r.integers(0, nb + 64, npr)), _pair_hashes),
        # distinct keys with one full hash: a float hashes by its integer part
        "floats_share_a_hash_by_truncation": (
            _double_batch(r.permutation(np.arange(nb)) / 10),
            _double_batch(np.round(r.uniform(-5, nb / 10 + 5, npr), 1)), None),
        # the dead lanes, where a search past the live ones ends, hold the key
        "dead_lanes_hold_a_key_past_the_live_ones": (
            _key_batch(np.r_[0:4, np.full(nb - 4, 5)], np.arange(nb) < 4),
            _key_batch(r.integers(0, 7, npr)), _shifted_hashes),
        # what set-op membership sends: one key, its every row
        "one_key_repeated_1000_times": (
            _key_batch(np.full(1000, 42)),
            _key_batch(r.integers(40, 45, npr), some(npr, .9)), None),
    }


_RANGE_CASES = _range_cases()


@pytest.mark.parametrize("case", sorted(_RANGE_CASES))
def test_probe_ranges_are_numpy_searchsorted_left_and_right(case, monkeypatch):
    """The bucket directory's [lo, hi) against a binary search of the whole
    sorted build, dead lanes' sentinels included: bit for bit."""
    build, probe, patched_hash = _RANGE_CASES[case]
    if patched_hash is not None:
        monkeypatch.setattr(join_ops, "join_hash", patched_hash)
    tbl = build_side(build, ("id",))
    h, lo, hi, live = join_ops._probe_ranges(tbl, probe, ("id",))
    hashes, h = np.asarray(tbl.hashes), np.asarray(h)
    n = int(tbl.n_rows)
    assert (np.diff(hashes) >= 0).all()
    if patched_hash is not _edge_hashes:
        assert (hashes[n:] == _SENT).all() and (hashes[:n] < _SENT).all()
    np.testing.assert_array_equal(lo, np.searchsorted(hashes, h, "left"))
    np.testing.assert_array_equal(hi, np.searchsorted(hashes, h, "right"))
    # the directory itself: bucket b is [dir[b], dir[b + 1]) of the live lanes
    d = np.asarray(tbl.dir)
    k = max(build.capacity - 1, 0).bit_length()
    assert d.shape == (2 ** k + 1,) and d[0] == 0 and d[-1] == n
    np.testing.assert_array_equal(
        np.diff(d), np.bincount(hashes[:n] >> (63 - k), minlength=2 ** k))
    largest = int(np.diff(d).max())
    assert int(tbl.search_steps) == largest.bit_length()
    dead = ~np.asarray(live)
    assert (h[dead] == _SENT - 1).all()
    if case == "every_row_one_key":  # one bucket holds the build
        assert int(tbl.search_steps) == 11 and (hi - lo).max() == 1024


@pytest.mark.parametrize("case", sorted(_RANGE_CASES))
def test_probe_unique_is_a_numpy_lookup_among_the_live_build_keys(
        case, monkeypatch):
    """(idx, matched) against the live build keys, `idx` wherever a row
    matched; `fp` and `verify_width` against the sorted hashes: the widest
    run of live lanes sharing bucket and fingerprint."""
    build, probe, patched_hash = _RANGE_CASES[case]
    if patched_hash is not None:
        monkeypatch.setattr(join_ops, "join_hash", patched_hash)
    tbl = build_side(build, ("id",))
    idx, matched = probe_unique(tbl, probe, ("id",), ("id",))
    assert idx.dtype == jnp.int32 and matched.dtype == jnp.bool_

    def live_keys(b):
        c = b.column("id")
        ok = np.asarray(b.live)
        if c.validity is not None:
            ok = ok & np.asarray(c.validity)
        return np.asarray(c.values), ok

    bkeys, blive = live_keys(build)
    pkeys, plive = live_keys(probe)
    want = plive & np.isin(pkeys, bkeys[blive])
    if patched_hash is _edge_hashes:
        # a live row hashed to the dead lanes' sentinel is not told from them
        want &= np.asarray(patched_hash(probe, ("id",))) != _SENT
        matched = np.asarray(matched) & want
    np.testing.assert_array_equal(matched, want)
    n = int(tbl.n_rows)
    idx = np.asarray(idx)[want]
    assert (idx < n).all()
    np.testing.assert_array_equal(
        np.asarray(tbl.batch.column("id").values)[idx], pkeys[want])
    hashes = np.asarray(tbl.hashes)
    shift = 63 - max(build.capacity - 1, 0).bit_length()
    np.testing.assert_array_equal(
        tbl.fp, ((hashes >> (shift - 32)) & 0xFFFFFFFF).astype(np.uint32))
    _, runs = np.unique(hashes[:n] >> (shift - 32), return_counts=True)
    assert int(tbl.verify_width) == (runs.max() if n else 0)
    widths = {"distinct_keys_full_build": 1, "one_key_repeated_1000_times": 1000,
              "pairs_share_bucket_and_fingerprint": 2,
              "floats_share_a_hash_by_truncation": 10}
    if case in widths:
        assert int(tbl.verify_width) == widths[case]


def _scanned_counts(table, probe, probe_keys, build_keys, max_fanout_scan=8):
    """The counting pass as it was before it read run widths alone: it
    verified up to `max_fanout_scan` candidates of each run against the key
    columns, then gave every row its run's width all the same."""
    _, lo, hi, live = join_ops._probe_ranges(table, probe, probe_keys)
    width = hi - lo
    counts = jnp.zeros(width.shape, dtype=jnp.int64)
    cap = table.hashes.shape[0]
    for j in range(max_fanout_scan):
        idx = jnp.clip(lo + j, 0, cap - 1).astype(jnp.int32)
        ok = (j < width) & join_ops._keys_equal(table, idx, probe, probe_keys,
                                                build_keys)
        counts = counts + ok.astype(jnp.int64)
    counts = jnp.where(counts == width, counts, width)
    widened = live & (width > max_fanout_scan)
    counts = jnp.where(width > max_fanout_scan, width, counts)
    counts = jnp.where(live, counts, 0)
    offsets = jnp.cumsum(counts) - counts
    total = jnp.sum(counts)
    overflow = jnp.sum(widened.astype(jnp.int64))
    return lo.astype(jnp.int32), counts, offsets, total, live, overflow


def _pair_batch(a, b, live=None):
    """A batch of exactly len(a) lanes with two BIGINT key columns."""
    live = np.ones(len(a), bool) if live is None else np.asarray(live, bool)
    return Batch(["id", "id2"], [BIGINT, BIGINT],
                 [Column(jnp.asarray(np.asarray(k, np.int64)), None)
                  for k in (a, b)], jnp.asarray(live), {})


def _runs(widths, n):
    """Keys 0, 1, ... repeated widths[0], widths[1], ... times, cut to n
    lanes (live only where a key landed)."""
    keys = np.repeat(np.arange(len(widths)), widths)[:n]
    return _key_batch(np.r_[keys, np.zeros(n - len(keys))],
                      np.arange(n) < len(keys))


def _count_cases():
    r = np.random.default_rng(38)
    some = lambda n, p: r.random(n) < p  # noqa: E731
    nb, npr = 1024, 256
    one = ("id",)
    past_8 = _runs(np.arange(100) % 20 + 1, nb)  # runs of 1 to 20
    return {
        # name: (build batch, probe batch, key names, max_fanout_scan)
        "unique_keys": (_key_batch(r.permutation(nb)),
                        _key_batch(r.integers(0, 2 * nb, npr)), one, 8),
        "fanout_1_to_7": (_runs(np.arange(300) % 7 + 1, nb),
                          _key_batch(r.integers(0, 320, npr)), one, 8),
        "fanout_past_8": (past_8, _key_batch(r.integers(0, 110, npr)), one, 8),
        "fanout_past_8_scan_4": (
            past_8, _key_batch(r.integers(0, 110, npr)), one, 4),
        "fanout_past_8_scan_16": (
            past_8, _key_batch(r.integers(0, 110, npr)), one, 16),
        # a quarter step: four values share each integer part and so a hash,
        # and each repeats, so a run's matches are not contiguous
        "doubles_share_a_hash_by_truncation": (
            _double_batch(r.integers(0, 300, nb) / 4),
            _double_batch(r.integers(-4, 320, npr) / 4), one, 8),
        "two_keys": (_pair_batch(r.integers(0, 40, nb), r.integers(0, 4, nb)),
                     _pair_batch(r.integers(0, 45, npr), r.integers(0, 5, npr)),
                     ("id", "id2"), 8),
        "null_keys_both_sides": (
            _key_batch(r.integers(0, 100, nb), valid=some(nb, .8)),
            _key_batch(r.integers(0, 120, npr), valid=some(npr, .7)), one, 8),
        "dead_probe_lanes": (
            _key_batch(r.integers(0, 100, nb)),
            _key_batch(r.integers(0, 120, npr), some(npr, .5)), one, 8),
        "empty_build": (_key_batch(np.arange(nb), np.zeros(nb, bool)),
                        _key_batch(np.arange(npr)), one, 8),
    }


_COUNT_CASES = _count_cases()


@pytest.mark.parametrize("case", sorted(_COUNT_CASES))
def test_probe_counts_are_what_the_candidate_scan_gave(case):
    """The six outputs of the counting pass, lane for lane, against the pass
    that verified `max_fanout_scan` candidates first; `overflow` is the
    live rows whose run of equal hashes is wider than that."""
    build, probe, keys, scan = _COUNT_CASES[case]
    tbl = build_side(build, keys)
    got = probe_counts(tbl, probe, keys, keys, max_fanout_scan=scan)
    want = _scanned_counts(tbl, probe, keys, keys, scan)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g, w)
    _, counts, _, total, live, overflow = got
    counts, live = np.asarray(counts), np.asarray(live)
    assert int(total) == counts.sum()
    assert int(overflow) == int((live & (counts > scan)).sum())
    assert (counts[~live] == 0).all()
    if case.startswith("fanout_past_8"):
        assert int(overflow) > 0
    if case == "empty_build":
        assert int(total) == 0


def _ends_cases():
    r = np.random.default_rng(29)
    n, cap = 256, 128  # one pair of shapes for most cases: one compile

    def padded(*parts):
        c = np.concatenate([np.atleast_1d(p) for p in parts]).astype(int)
        return np.concatenate([c, np.zeros(n - len(c), int)])

    return {
        # name: (counts of a probe batch, chunk_base, out_capacity)
        "first_chunk": (r.integers(0, 4, n), 0, cap),
        "chunk_base_past_one_chunk": (r.integers(0, 9, n), 4 * cap, cap),
        "last_partial_chunk": (r.integers(0, 3, n) // 2, cap, cap),
        "chunk_base_at_total": (np.full(n, 2), 2 * n, cap),
        "zero_counts_at_both_ends_of_a_chunk": (
            padded(np.zeros(5), cap, 0, 0, 5, np.zeros(7), cap - 5, 0, 0, 9),
            cap, cap),
        "zero_counts_lead_and_trail": (
            padded(np.zeros(40), r.integers(0, 3, 50)), 0, cap),
        "no_row_matches": (np.zeros(n, int), 0, cap),
        "one_row_emits_everything": (
            padded(np.zeros(9), 1000), 2 * cap, cap),
        "every_row_once": (np.ones(n, int), 100, cap),
        "sparse_matches": ((r.random(n) < .03).astype(int), 0, cap),
        "one_probe_lane": (np.array([3]), 0, 8),
        "capacities_not_powers_of_two": (r.integers(0, 9, 777), 333, 3000),
    }


_ENDS_CASES = _ends_cases()


@pytest.mark.parametrize("case", sorted(_ENDS_CASES))
def test_slot_rows_is_numpy_searchsorted_right_of_the_ends(case):
    counts, base, out_cap = _ENDS_CASES[case]
    ends = np.cumsum(np.asarray(counts, np.int64))
    got = join_ops._slot_rows(jnp.asarray(ends), jnp.int64(base), out_cap)
    want = np.searchsorted(ends, np.arange(out_cap) + base, "right")
    np.testing.assert_array_equal(got, np.clip(want, 0, len(ends) - 1))
    assert got.dtype == jnp.int32


def _eqns(jaxpr, primitive):
    """`primitive`'s equations in a jaxpr, at any depth."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _eqns(sub, primitive)
    return found


@pytest.mark.parametrize("program,loops", [
    ("probe_counts", 1), ("probe_unique", 2), ("probe_expand", 0)])
def test_probe_programs_loop_only_in_the_bounded_finish(program, loops):
    """No binary search of the whole build or of the prefix sums is left:
    a probe's loops are the halving inside a bucket, bounded by the table's
    `search_steps`, and, in the unique probe, the verification of the lanes
    from there, bounded by its `verify_width`; mapping slots to rows has
    none."""
    build = _key_batch(np.arange(1000) % 300)
    probe = _key_batch(np.arange(256))
    tbl = build_side(build, ("id",))
    keys = ("id",)
    if program == "probe_expand":
        lo, counts, offsets, *_ = probe_counts(tbl, probe, keys, keys)
        jaxpr = jax.make_jaxpr(lambda t, p, l, c, o, b: probe_expand(
            t, p, keys, keys, l, c, o, b, 512))(
                tbl, probe, lo, counts, offsets, jnp.int64(512))
    else:
        fn = {"probe_counts": probe_counts, "probe_unique": probe_unique}[program]
        jaxpr = jax.make_jaxpr(lambda t, p: fn(t, p, keys, keys))(tbl, probe)
    # a loop with a static trip count would be a `scan`: a `while` here is
    # one bounded by a device scalar of the table
    assert len(_eqns(jaxpr.jaxpr, "while")) == loops
    assert "sort" not in str(jaxpr).replace("indices_are_sorted", "")
    if program == "probe_counts":
        # a count is its run's width: a second key gathers nothing more
        pairs = build_side(_pair_batch(np.arange(1000) % 300,
                                       np.arange(1000) % 7), ("id", "id2"))
        probe = _pair_batch(np.arange(256), np.arange(256) % 7)
        gathers = [len(_eqns(jax.make_jaxpr(
            lambda t, p: probe_counts(t, p, k, k))(pairs, probe).jaxpr,
            "gather")) for k in (("id",), ("id", "id2"))]
        assert gathers[0] == gathers[1]


class TestSortCompact:
    def test_multi_key_desc_nulls(self, rng):
        n = 300
        a = rng.integers(0, 5, n)
        v = rng.normal(size=n)
        b = Batch.from_numpy({"a": a, "v": v}, {"a": BIGINT, "v": DOUBLE})
        out = sort_batch(
            b,
            [
                SortKey(b.column("a").values, None, descending=False),
                SortKey(b.column("v").values, None, descending=True),
            ],
        )
        d = out.to_pydict()
        df = pd.DataFrame({"a": a, "v": v}).sort_values(
            ["a", "v"], ascending=[True, False], ignore_index=True
        )
        np.testing.assert_array_equal(d["a"], df["a"].values)
        np.testing.assert_allclose(d["v"], df["v"].values)

    def test_limit(self, rng):
        b, k, v, live = make_batch(rng)
        out = limit_batch(b, 17)
        assert out.num_live() == 17

    def test_compact_preserves_order(self, rng):
        b, k, v, live = make_batch(rng)
        out = compact(b)
        d = out.to_pydict()
        np.testing.assert_allclose(d["v"], v[live])


CAP = 256


def planes_batch(rng, n):
    """A batch of capacity 512 with `n` live rows scattered over it and one
    column of every kind of plane: validity, a long decimal's `hi`, an
    array's 2-D `values` and `evalid` with `sizes`, a map's `keys`."""
    from presto_tpu.types import ArrayType, DecimalType, MapType

    cap = 512
    live = np.zeros(cap, bool)
    live[rng.choice(cap, n, replace=False)] = True
    ints = lambda *shape: jnp.asarray(rng.integers(0, 1 << 30, shape))  # noqa: E731
    bools = lambda *shape: jnp.asarray(rng.random(shape) < 0.7)  # noqa: E731
    cols = [Column(ints(cap), None),
            Column(ints(cap), bools(cap)),
            Column(ints(cap), bools(cap), hi=ints(cap)),
            Column(ints(cap, 3), bools(cap), sizes=ints(cap) % 4,
                   evalid=bools(cap, 3)),
            Column(ints(cap, 3), None, sizes=ints(cap) % 4, keys=ints(cap, 3))]
    types = [BIGINT, BIGINT, DecimalType(30, 2), ArrayType(BIGINT),
             MapType(BIGINT, BIGINT)]
    return Batch(["a", "v", "d", "arr", "m"], types, cols, jnp.asarray(live), {})


@pytest.mark.parametrize("n,cap", [(0, 128), (1, 128), (CAP, CAP), (40, CAP),
                                   (300, 1024)])
def test_compact_at_an_output_capacity_is_the_whole_compaction_cut(rng, n, cap):
    """`compact(b, out_cap)` gathers `out_cap` lanes of every plane: what
    comes out is `_truncate(compact(b), out_cap)` plane for plane, for no
    live row, one, exactly `out_cap`, fewer, and a capacity past the
    batch's own (which keeps the batch's)."""
    from presto_tpu.exec.runtime import _JIT_COMPACT, _truncate

    b = planes_batch(rng, n)
    got, want = _JIT_COMPACT(b, out_cap=cap), _truncate(compact(b), cap)
    assert got.capacity == want.capacity == min(cap, b.capacity)
    assert int(got.num_live()) == n
    np.testing.assert_array_equal(np.asarray(got.live), np.asarray(want.live))
    for name in b.names:
        g, w = got.column(name), want.column(name)
        for plane in Column.__slots__:
            gp, wp = getattr(g, plane), getattr(w, plane)
            assert (gp is None) == (wp is None), (name, plane)
            if gp is not None:
                np.testing.assert_array_equal(np.asarray(gp), np.asarray(wp),
                                              err_msg=f"{name}.{plane}")


class TestPartition:
    def test_counts_and_overflow(self, rng):
        n = 2000
        k = rng.integers(0, 1000, n)
        b = Batch.from_numpy({"k": k}, {"k": BIGINT})
        out, counts, ovf = partition_for_exchange(b, ["k"], 8, 1024)
        assert int(ovf) == 0
        assert int(np.asarray(counts).sum()) == n
        # same key → same partition
        d = out.to_pydict()
        from presto_tpu.ops.partition import partition_ids

        pid = np.asarray(partition_ids(b, ["k"], 8))[:n]
        got_rows = np.asarray(out.live).reshape(8, -1).sum(axis=1)
        exp_rows = np.bincount(pid, minlength=8)
        np.testing.assert_array_equal(got_rows, exp_rows)

    def test_hash_stability(self):
        a = jnp.asarray(np.arange(100, dtype=np.int64))
        h1 = hash_columns([a])
        h2 = hash_columns([a])
        np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))
        assert (np.asarray(h1) >= 0).all()


class TestPallasGroupedSums:
    """MXU one-hot grouped-sum kernel (ops/pallas_groupby) in interpreter
    mode: int64 limb exactness and float two-split accuracy vs numpy."""

    def test_int64_exact_including_negative_and_large(self, rng):
        from presto_tpu.ops.pallas_groupby import grouped_sums

        n, g = 1000, 6
        gid = jnp.asarray(rng.integers(0, g, n), jnp.int32)
        big = rng.integers(-(1 << 44), 1 << 44, n)
        small = rng.integers(-5, 6, n)
        iouts = grouped_sums(
            gid, [jnp.asarray(big), jnp.asarray(small)], g,
            interpret=True)
        for arr, out in ((big, iouts[0]), (small, iouts[1])):
            exp = np.array([arr[np.asarray(gid) == i].sum()
                            for i in range(g)])
            np.testing.assert_array_equal(np.asarray(out), exp)

    def test_dead_rows_ignored(self, rng):
        from presto_tpu.ops.pallas_groupby import grouped_sums

        n, g = 500, 4
        gid = np.asarray(rng.integers(0, g + 1, n), np.int32)  # g = dead
        vals = rng.integers(0, 1000, n)
        masked = np.where(gid < g, vals, 0)
        iouts = grouped_sums(jnp.asarray(gid), [jnp.asarray(masked)],
                             g, interpret=True)
        exp = np.array([masked[gid == i].sum() for i in range(g)])
        np.testing.assert_array_equal(np.asarray(iouts[0]), exp)

    def test_direct_merge_pallas_path_matches_portable(self, rng):
        """The full _pallas_direct_merge (sums + counts + min/max fallback
        + validity) against the portable masked path."""
        from presto_tpu.ops.grouping import (
            KeyCol,
            StateCol,
            _direct_grouped_merge,
            _pallas_direct_merge,
        )

        n, cap = 800, 16
        k = rng.integers(0, 3, n)
        live = jnp.asarray(rng.random(n) < 0.9)
        dec = jnp.asarray(rng.integers(-10_000, 10_000, n))
        dbl = jnp.asarray(rng.normal(size=n))
        validity = jnp.asarray(rng.random(n) < 0.8)
        keys = [KeyCol(jnp.asarray(k), None, 3)]
        states = [
            StateCol(dec, validity, "sum"),
            StateCol(jnp.ones(n, jnp.int64), None, "count_add"),
            StateCol(dbl, None, "sum"),
            StateCol(dec, None, "min"),
        ]
        gid = jnp.where(live, jnp.asarray(k, jnp.int32), 3)
        kp, sp, lp, np_ = _pallas_direct_merge(
            keys, states, live, cap, [3], gid, 3, interpret=True)
        km, sm, lm, nm = _direct_grouped_merge(keys, states, live, cap, [3])
        assert int(np_) == int(nm)
        np.testing.assert_array_equal(np.asarray(lp), np.asarray(lm))
        for a, b in zip(kp, km):
            np.testing.assert_array_equal(np.asarray(a.values),
                                          np.asarray(b.values))
        for a, b in zip(sp, sm):
            np.testing.assert_allclose(np.asarray(a.values),
                                       np.asarray(b.values), rtol=1e-12)
            if a.validity is not None or b.validity is not None:
                np.testing.assert_array_equal(np.asarray(a.validity),
                                              np.asarray(b.validity))
