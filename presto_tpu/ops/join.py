"""Hash join kernels: sorted build + bucket-directory probe.

Reference: operator/HashBuilderOperator.java (build), PagesHash.java:34,152 /
JoinHash + PositionLinks chains (probe), LookupJoinOperator.java:392-460
(probe loop with yielding output builder).

TPU-native redesign: no pointer chains. The build side is *sorted by a
64-bit key hash* and carries a directory over the hash's top bits: one
bucket per slot of capacity, `dir[b]` = sorted position where bucket b
starts. A probe row reads its bucket's two ends, finishes with a halving
search *inside the bucket* (as many rounds as the build's fullest bucket
needs — a handful for uniform hashes, whatever the build's size) and reads
the end of its run of equal hashes from `run_end`: its candidate range
[lo, hi), the same one two binary searches over the whole build would
give. Range semantics replace PositionLinks. Because we join on the hash,
candidates are verified against the actual key columns (exact semantics
even under hash collisions).

The single-match probe needs no range end: it searches its bucket on `fp`,
the 32 hash bits below the bucket's (one 32-bit gather a round where the
64-bit hash costs the TPU two), and verifies lanes from there while any row
is unresolved, at most `verify_width` of them: the widest run of lanes
sharing bucket and `fp`, which a build of distinct keys holds to one.

Fanout handling (the LookupJoinPageBuilder analog): a counts pass gives
each probe row the width of its run of equal hashes, read from the
directory alone, and a prefix sum; materialization maps each output slot i
back to (probe_row, ordinal) by counting the prefix sums' ends at or below
i — one scatter-add of the ends and a running sum — and verifies every
pair against the key columns, fully vectorized, chunked by the prober when
total matches exceed the output capacity. Where distinct keys share a hash
(float keys hash by integer truncation) a run holds more lanes than match:
the output capacity widens, the results do not.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from presto_tpu.batch import Batch, Column, round_up_capacity
from presto_tpu.ops import pallas_hash
from presto_tpu.ops.hashing import hash_columns
from presto_tpu.ops.radix import slot_hash
from presto_tpu.ops.sort import permute_batch


class BuildTable(NamedTuple):
    """Sorted-by-hash build side. `batch` holds payload + key columns,
    compacted so live rows occupy [0, n_rows); `hashes` aligned with it.
    `orig_live` preserves input liveness BEFORE NULL-key rows were killed —
    FULL OUTER must still emit those rows in its build remainder (a NULL
    key never matches, but the row exists)."""

    hashes: jnp.ndarray  # int64[cap], sorted; dead lanes = int64.max
    batch: Batch
    n_rows: jnp.ndarray  # device scalar
    orig_live: jnp.ndarray  # bool[cap], aligned with batch
    # bucket(h) = h >> (63 - k), k = ceil(log2(cap)): monotone in the sort
    # order. dir[b] = live sorted hashes whose bucket is below b, so bucket
    # b is [dir[b], dir[b + 1]) and dir[2^k] = n_rows (dead lanes kept out)
    dir: jnp.ndarray  # int32[2^k + 1]
    # run_end[i] = first position past i whose hash differs from hashes[i]
    run_end: jnp.ndarray  # int32[cap]
    # halving rounds the fullest bucket needs, ceil(log2(largest + 1)): the
    # probe's loop bound, a device scalar the host reads only where it
    # reads n_rows (table_stats)
    search_steps: jnp.ndarray
    # fp[i] = the 32 bits of hashes[i] just below its bucket's k, unsigned:
    # ordered within a bucket as hashes are
    fp: jnp.ndarray  # uint32[cap]
    # the widest run of live lanes sharing bucket and fp: lanes a unique
    # probe may verify (table_stats reads it beside search_steps)
    verify_width: jnp.ndarray


_SENTINEL = jnp.iinfo(jnp.int64).max


def join_hash(batch: Batch, key_names: Sequence[str]) -> jnp.ndarray:
    cols = [batch.column(k).values for k in key_names]
    valids = [batch.column(k).validity for k in key_names]
    return hash_columns(cols, valids)


def align_probe_strings(
    probe: Batch, probe_keys: Sequence[str], table: "BuildTable",
    build_keys: Sequence[str],
) -> Batch:
    """Equi-join on varchar compares dictionary codes, so probe-side codes
    must be remapped into the build side's dictionary code space (analog of
    DictionaryBlock id canonicalization before PagesHash compare). Codes with
    no build-side entry become -1, which never equals a valid build code.
    Host builds the remap table at trace time; device does one gather."""
    out = probe
    for pk, bk in zip(probe_keys, build_keys):
        if not probe.type_of(pk).is_string:
            continue
        pd_ = probe.dict_of(pk)
        bd = table.batch.dict_of(bk)
        if pd_ is None or bd is None or pd_ is bd:
            continue
        remap = jnp.asarray(pd_.map_to(bd))
        c = out.column(pk)
        from presto_tpu.batch import Column

        out = out.with_column(
            pk, probe.type_of(pk), Column(remap[c.values + 1], c.validity),
            dictionary=bd,
        )
    return out


@jax.named_scope("join_build")
def build_side(batch: Batch, key_names: Sequence[str]) -> BuildTable:
    """Sort the (concatenated, still masked) build input by key hash; dead
    rows sink to the end via a sentinel hash."""
    h = join_hash(batch, key_names)
    # rows with NULL in any key never match an equi-join: kill them now
    live = batch.live
    for k in key_names:
        v = batch.column(k).validity
        if v is not None:
            live = live & v
    h = jnp.where(live, h, _SENTINEL)
    perm = jnp.arange(batch.capacity, dtype=jnp.int32)
    sorted_h, sperm = jax.lax.sort([h, perm], num_keys=1)
    sorted_batch = permute_batch(batch.with_live(live), sperm)
    n = jnp.sum(live.astype(jnp.int64))
    return BuildTable(sorted_h, sorted_batch, n, batch.live[sperm],
                      *_bucket_directory(sorted_h, n))


def _bucket_shift(cap: int) -> int:
    """63 - k for a build of `cap` lanes: k = ceil(log2(cap)) top bits of a
    non-negative hash name its bucket."""
    return 63 - (cap - 1).bit_length()


def _running_sum(v: jnp.ndarray, width: int = 1024) -> jnp.ndarray:
    """jnp.cumsum of a 1-D integer vector, as rows of `width` lanes summed
    along with the rows' totals carried over: the same numbers in a form
    the TPU's compiler takes in a second, where the flat scan of a build's
    2^21 lanes costs it 5-10 s and a cummin 30-55 s (CHANGES.md, PR 28)."""
    n = v.shape[0]
    if n <= width:
        return jnp.cumsum(v)
    rows = jnp.pad(v, (0, -n % width)).reshape(-1, width)
    within = jnp.cumsum(rows, axis=1)
    before = jnp.cumsum(within[:, -1]) - within[:, -1]
    return (within + before[:, None]).reshape(-1)[:n]


def _fingerprint(h: jnp.ndarray, shift: int) -> jnp.ndarray:
    """The 32 bits of a hash just below its bucket's: shift >= 32 for every
    capacity below 2^31."""
    return ((h >> (shift - 32)) & 0xFFFFFFFF).astype(jnp.uint32)


def _run_ids(v: jnp.ndarray):
    """(first, run) of a sorted vector: whether a lane starts a run of equal
    values, and its run's number, counted from 1."""
    first = jnp.concatenate([jnp.ones((1,), bool), v[1:] != v[:-1]])
    return first, _running_sum(first.astype(jnp.int32))


def _bucket_directory(sorted_h: jnp.ndarray, n_rows):
    """(dir, run_end, search_steps, fp, verify_width) of BuildTable over
    hashes already sorted: histograms of the lanes' bucket and run ids and
    running sums — no search."""
    cap = sorted_h.shape[0]
    shift = _bucket_shift(cap)
    pos = jnp.arange(cap, dtype=jnp.int32)
    live = (pos < n_rows).astype(jnp.int32)
    bucket = (sorted_h >> shift).astype(jnp.int32)
    sizes = jnp.zeros(1 << (63 - shift), jnp.int32).at[bucket].add(
        live, indices_are_sorted=True, mode="promise_in_bounds")
    steps = 32 - jax.lax.clz(jnp.max(sizes))
    dir_ = jnp.concatenate([jnp.zeros(1, jnp.int32), _running_sum(sizes)])
    # runs of equal hashes: a run ends where the next one starts (slot 0
    # takes the writes of the lanes that start none)
    first, run = _run_ids(sorted_h)
    starts = jnp.full(cap + 2, cap, jnp.int32).at[
        jnp.where(first, run, 0)].set(pos, mode="promise_in_bounds")
    # runs of equal bucket and fingerprint, their live lanes counted
    _, fp_run = _run_ids(sorted_h >> (shift - 32))
    widths = jnp.zeros(cap + 1, jnp.int32).at[fp_run].add(
        live, indices_are_sorted=True, mode="promise_in_bounds")
    return (dir_, starts[run + 1], steps, _fingerprint(sorted_h, shift),
            jnp.max(widths))


def _probe_hash(probe: Batch, key_names: Sequence[str]):
    """(hash, live) of the probe rows: a row with a NULL key is not live."""
    h = join_hash(probe, key_names)
    live = probe.live
    for k in key_names:
        v = probe.column(k).validity
        if v is not None:
            live = live & v
    return h, live


def _probe_ranges(table: BuildTable, probe: Batch, key_names: Sequence[str]):
    """Candidate range [lo, hi) of every probe row in the sorted build:
    what searching `table.hashes` for the row's hash from the left and from
    the right would give."""
    h, live = _probe_hash(probe, key_names)
    h = jnp.where(live, h, _SENTINEL - 1)  # never matches a real hash*
    hashes = table.hashes
    cap = hashes.shape[0]
    bucket = (h >> _bucket_shift(cap)).astype(jnp.int32)

    def halve(_, ends):
        lo, hi = ends
        mid = (lo + hi) >> 1
        below = (lo < hi) & (hashes[mid] < h)
        return jnp.where(below, mid + 1, lo), jnp.where(below, hi, mid)

    lo, _ = jax.lax.fori_loop(
        0, table.search_steps, halve,
        (table.dir[bucket], table.dir[bucket + 1]))
    at = jnp.minimum(lo, cap - 1)
    hi = jnp.where(hashes[at] == h, table.run_end[at], lo)
    return h, lo, hi, live


def _slot_rows(ends: jnp.ndarray, chunk_base, out_capacity: int):
    """Probe row of each output slot chunk_base + s, s < out_capacity: the
    number of inclusive prefix-sum `ends` at or below the slot (rows that
    emit nothing share their end with the row before and are skipped),
    clipped to a row. The ends inside the chunk are scattered onto their
    slots and summed along; those at or below its base are counted."""
    rel = ends - chunk_base
    inside = (rel > 0) & (rel < out_capacity)
    hist = jnp.zeros(out_capacity, jnp.int32).at[
        jnp.where(inside, rel, out_capacity).astype(jnp.int32)].add(
            1, mode="drop")
    row = jnp.sum(rel <= 0).astype(jnp.int32) + _running_sum(hist)
    return jnp.minimum(row, ends.shape[0] - 1)


def _keys_equal(table: BuildTable, build_idx, probe: Batch,
                probe_keys: Sequence[str], build_keys: Sequence[str]):
    """Verify actual key equality at gathered build positions."""
    ok = jnp.ones(build_idx.shape, dtype=bool)
    for pk, bk in zip(probe_keys, build_keys):
        pv = probe.column(pk).values
        bv = table.batch.column(bk).values[build_idx]
        if pv.dtype != bv.dtype:
            t = jnp.result_type(pv.dtype, bv.dtype)
            pv, bv = pv.astype(t), bv.astype(t)
        ok = ok & (pv == bv)
    return ok


@jax.named_scope("join_probe")
def probe_unique(
    table: BuildTable,
    probe: Batch,
    probe_keys: Sequence[str],
    build_keys: Sequence[str],
):
    """Fast path: build keys are unique (dimension tables — the dominant
    TPC-H shape). Each probe row matches <= 1 build row.

    The halving search inside the row's bucket runs on `fp`: `lo` is the
    bucket's first lane whose fingerprint is not below the row's. Lanes
    lo, lo + 1, ... are then verified against the key columns: a row is
    resolved once it matched or once a lane's fingerprint differs from its
    own, and the loop stops when every live row is resolved or after
    `verify_width` lanes, past which no lane shares the row's bucket and
    fingerprint. One round in a build of distinct keys; exact wherever
    distinct keys share a hash (float keys hash by integer truncation) or
    a key repeats (set-op membership).

    Returns (build_idx int32[cap], matched bool[cap]).
    """
    h, live = _probe_hash(probe, probe_keys)
    cap = table.hashes.shape[0]
    shift = _bucket_shift(cap)
    bucket = (h >> shift).astype(jnp.int32)
    f = _fingerprint(h, shift)
    fp = table.fp

    def halve(_, ends):
        lo, hi = ends
        mid = (lo + hi) >> 1
        below = (lo < hi) & (fp[mid] < f)
        return jnp.where(below, mid + 1, lo), jnp.where(below, hi, mid)

    lo, _ = jax.lax.fori_loop(
        0, table.search_steps, halve,
        (table.dir[bucket], table.dir[bucket + 1]))

    def unresolved(state):
        j, _, _, open_ = state
        return (j < table.verify_width) & jnp.any(open_)

    def verify(state):
        j, idx, matched, open_ = state
        at = lo + j
        cand = jnp.minimum(at, cap - 1)
        inside = at < table.n_rows
        ok = open_ & inside & _keys_equal(table, cand, probe, probe_keys,
                                          build_keys)
        open_ = open_ & ~ok & inside & (fp[cand] == f)
        return (j + 1, jnp.where(ok, cand, idx), matched | ok, open_)

    _, idx, matched, _ = jax.lax.while_loop(
        unresolved, verify,
        (jnp.int32(0), jnp.minimum(lo, cap - 1), jnp.zeros(lo.shape, bool),
         live))
    return idx, matched


@jax.named_scope("join_probe")
def probe_counts(
    table: BuildTable,
    probe: Batch,
    probe_keys: Sequence[str],
    build_keys: Sequence[str],
    max_fanout_scan: int = 8,
):
    """General path, pass 1: per-probe-row candidate ranges and counts.

    A live row's count is the width of its run of equal hashes, hi - lo,
    read from the directory: no key column is gathered, so `build_keys` is
    unused (kept for the callers' common signature). probe_expand verifies
    every pair against the key columns, so where distinct keys share a
    hash (float keys hash by integer truncation, and their verified
    matches need not be contiguous) the output capacity widens and the
    results do not. `overflow` counts the live rows whose run is wider
    than `max_fanout_scan`, for the prober to surface as a counter.

    Returns (lo int32[cap], counts, offsets, total, live, overflow).
    """
    _, lo, hi, live = _probe_ranges(table, probe, probe_keys)
    width = (hi - lo).astype(jnp.int64)
    counts = jnp.where(live, width, 0)
    offsets = jnp.cumsum(counts) - counts  # exclusive prefix sum
    total = jnp.sum(counts)
    overflow = jnp.sum((live & (width > max_fanout_scan)).astype(jnp.int64))
    return lo.astype(jnp.int32), counts, offsets, total, live, overflow


@jax.named_scope("join_probe")
def probe_expand(
    table: BuildTable,
    probe: Batch,
    probe_keys: Sequence[str],
    build_keys: Sequence[str],
    lo: jnp.ndarray,
    counts: jnp.ndarray,
    offsets: jnp.ndarray,
    chunk_base,
    out_capacity: int,
):
    """General path, pass 2: materialize output slots
    [chunk_base, chunk_base + out_capacity).

    Each output slot i maps to probe_row = the number of inclusive ends at
    or below i (_slot_rows) and ordinal = i - offsets[probe_row]; the build
    row is lo[probe_row] + ordinal, verified against real keys.

    Returns (probe_idx int32[out_capacity], build_idx int32[out_capacity],
    out_live bool[out_capacity]).
    """
    total = offsets + counts  # inclusive ends
    i = jnp.arange(out_capacity, dtype=jnp.int64) + chunk_base
    probe_row = _slot_rows(total, chunk_base, out_capacity)
    ordinal = i - offsets[probe_row]
    in_range = (i < total[-1]) & (ordinal >= 0) & (ordinal < counts[probe_row])
    build_idx = (lo[probe_row] + ordinal).astype(jnp.int32)
    build_idx = jnp.clip(build_idx, 0, table.hashes.shape[0] - 1)
    # verify real keys at the expanded pairs (covers hash collisions and the
    # wide-range counting fallback)
    pk_ok = jnp.ones(out_capacity, dtype=bool)
    for pk, bk in zip(probe_keys, build_keys):
        pv = probe.column(pk).values[probe_row]
        bv = table.batch.column(bk).values[build_idx]
        if pv.dtype != bv.dtype:
            t = jnp.result_type(pv.dtype, bv.dtype)
            pv, bv = pv.astype(t), bv.astype(t)
        pk_ok = pk_ok & (pv == bv)
    return probe_row, build_idx, in_range & pk_ok


# ---------------------------------------------------------------------------
# linear-probing hash-table engine (ops/pallas_hash) — the alternative to the
# sorted build above, selected per breaker by plan/stats.choose_breaker_engine


class HashJoinTable(NamedTuple):
    """Linear-probing build side. Unlike BuildTable there is NO sort: the
    build batch keeps input row order and `slot_row` maps probe-chain
    slots to build ROW indices (-1 = empty); duplicate keys occupy
    consecutive chain slots. `planes` are the pairwise-promoted encoded
    key planes (pallas_hash.encode_plane), reused by every probe batch.
    `hashes`/`orig_live` keep BuildTable's shape contract so the FULL
    OUTER remainder path is engine-agnostic."""

    hashes: jnp.ndarray       # int64[cap_b], per-row content hash
    batch: Batch              # NULL-key rows live-killed, input order
    n_rows: jnp.ndarray       # device scalar
    orig_live: jnp.ndarray    # bool[cap_b]
    slot_row: jnp.ndarray     # int32[tcap], tcap = 2 * pow2(cap_b)
    planes: jnp.ndarray       # int64[K, cap_b]


def join_compare_dtypes(build_batch: Batch, build_keys: Sequence[str],
                        probe_dtypes: Sequence) -> tuple:
    """Pairwise-promoted compare dtype per key position — the dtype at
    which _keys_equal would compare, applied at ENCODE time so plane
    equality matches the sort engine's `==` (identical rounding for
    int→float promotions)."""
    return tuple(
        jnp.result_type(build_batch.column(k).values.dtype, jnp.dtype(d))
        for k, d in zip(build_keys, probe_dtypes))


def _encode_join_planes(batch: Batch, key_names: Sequence[str],
                        compare_dtypes: Sequence):
    """Encode one side's key columns at the promoted compare dtypes.

    Returns (planes int64[K, cap], live, matchable): `live` kills
    NULL-key rows (an equi-join never matches NULL — same as
    build_side/_probe_ranges); `matchable` additionally excludes rows
    with a NaN float key, because the hash table would make equal NaN
    bit patterns match while IEEE `==` (the sort engine) never does."""
    planes = []
    live = batch.live
    matchable = batch.live
    for k, dt in zip(key_names, compare_dtypes):
        c = batch.column(k)
        if c.validity is not None:
            live = live & c.validity
        v = c.values
        dt = jnp.dtype(dt)
        if v.dtype != dt:
            v = v.astype(dt)
        if jnp.issubdtype(dt, jnp.floating):
            matchable = matchable & jnp.logical_not(jnp.isnan(v))
        planes.append(pallas_hash.encode_plane(v, canonicalize_nan=False))
    return jnp.stack(planes), live, live & matchable


@jax.named_scope("join_build")
def hash_build_side(batch: Batch, key_names: Sequence[str],
                    probe_dtypes: Sequence) -> HashJoinTable:
    """Build-side insert on the Pallas linear-probing kernel. The table
    holds 2× the batch capacity (load ≤ 50%), so every live row claims a
    slot. `probe_dtypes` are the probe side's key dtypes (from the plan),
    fixing the pairwise-promoted encoding before any probe batch exists."""
    compare = join_compare_dtypes(batch, key_names, probe_dtypes)
    planes, live, ins_live = _encode_join_planes(batch, key_names, compare)
    h = hash_columns(list(planes))
    tcap = 2 * round_up_capacity(batch.capacity, minimum=64)
    slot_row = pallas_hash.join_insert(
        slot_hash(h, tcap), ins_live, tcap,
        interpret=pallas_hash.use_interpret())
    n = jnp.sum(live.astype(jnp.int64))
    return HashJoinTable(h, batch.with_live(live), n, batch.live,
                         slot_row, planes)


def _hash_probe(table: HashJoinTable, probe: Batch,
                probe_keys: Sequence[str], compare_dtypes: Sequence,
                fanout: int):
    planes, live, matchable = _encode_join_planes(
        probe, probe_keys, compare_dtypes)
    h = hash_columns(list(planes))
    slot0 = slot_hash(h, table.slot_row.shape[0])
    mm, cnt, ovf = pallas_hash.join_probe(
        slot0, planes, matchable, table.slot_row, table.planes, fanout,
        interpret=pallas_hash.use_interpret())
    return mm, cnt, ovf, live


@jax.named_scope("join_probe")
def hash_probe_unique(table: HashJoinTable, probe: Batch,
                      probe_keys: Sequence[str], compare_dtypes: Sequence):
    """Unique-build fast path: first (only) match per probe row.
    Same contract as probe_unique: (build_idx int32[cap], matched)."""
    mm, cnt, _ovf, _live = _hash_probe(
        table, probe, probe_keys, compare_dtypes, 1)
    idx = jnp.clip(mm[:, 0], 0, table.batch.capacity - 1).astype(jnp.int32)
    return idx, cnt > 0


@jax.named_scope("join_probe")
def hash_probe_counts(table: HashJoinTable, probe: Batch,
                      probe_keys: Sequence[str], compare_dtypes: Sequence,
                      max_fanout_scan: int = 8):
    """General path, pass 1. Counts are EXACT (the kernel keeps counting
    past the match-matrix width), so offsets/total never inflate;
    overflow = #rows with more matches than the matrix holds — the
    driver re-runs ONLY this probe with the fanout doubled.

    Returns (mm int32[n, F], counts int64, offsets, total, live,
    overflow)."""
    mm, cnt, ovf, live = _hash_probe(
        table, probe, probe_keys, compare_dtypes, max_fanout_scan)
    counts = cnt.astype(jnp.int64)
    offsets = jnp.cumsum(counts) - counts
    total = jnp.sum(counts)
    return mm, counts, offsets, total, live, ovf.astype(jnp.int64)


@jax.named_scope("join_probe")
def hash_probe_expand(table: HashJoinTable, mm: jnp.ndarray,
                      counts: jnp.ndarray, offsets: jnp.ndarray,
                      chunk_base, out_capacity: int):
    """General path, pass 2 — pure XLA (no kernel): slot i maps back to
    (probe_row, ordinal) by _slot_rows over the inclusive ends and
    the build row is mm[probe_row, ordinal]. Precondition: counts <= F
    everywhere (the driver widened the probe on overflow), so no key
    re-verification is needed — the kernel matched exact planes.

    Returns (probe_idx, build_idx, out_live), like probe_expand."""
    ends = offsets + counts
    i = jnp.arange(out_capacity, dtype=jnp.int64) + chunk_base
    probe_row = _slot_rows(ends, chunk_base, out_capacity)
    ordinal = i - offsets[probe_row]
    in_range = (i < ends[-1]) & (ordinal >= 0) & (ordinal < counts[probe_row])
    fanout = mm.shape[1]
    oc = jnp.clip(ordinal, 0, fanout - 1).astype(jnp.int32)
    build_idx = mm[probe_row, oc]
    out_live = in_range & (build_idx >= 0)
    build_idx = jnp.clip(build_idx, 0, table.batch.capacity - 1)
    return probe_row, build_idx, out_live


# ---------------------------------------------------------------------------
# N-ary multiway probe (plan/nodes.MultiwayJoin): N resident build tables,
# one probe batch walked through all N probes in a single traced pass —
# no intermediate batch materialization between legs (PAPERS.md
# 1905.13376). Output row = probe row × one (match | left-null) per leg,
# decomposed mixed-radix over the per-leg match counts.


class MwSpec(NamedTuple):
    """Static description of one leg of a multiway probe. Drivers close
    over it (it is NOT a traced value), so every field must be hashable.
    `sources[k]` locates probe-side key k: -1 = the probe batch itself,
    j >= 0 = the payload of earlier UNIQUE build j, gathered at that
    leg's matched row (snowflake chains). Non-unique legs probe through
    the pallas kernel (`hash_engine`, exact counts) or the sorted engine
    (counts may widen — inner kinds only; expand re-verifies keys)."""

    probe_keys: tuple
    build_keys: tuple
    sources: tuple
    kind: str                # inner | left
    unique: bool             # single-match sorted-engine probe
    hash_engine: bool        # fanout leg probes through the pallas kernel
    compare_dtypes: tuple    # hash-engine encode dtypes (else ())


def _mw_key_batch(probe: Batch, tables, spec: "MwSpec", idxs, matcheds):
    """Key batch for one leg: key columns assembled from the probe batch
    and/or earlier unique legs' payloads, with rows unmatched in the
    source leg invalidated — a NULL key never equi-matches, which is
    exactly the binary chain's semantics for that row."""
    names, types, cols, dicts = [], [], [], {}
    for sym, src in zip(spec.probe_keys, spec.sources):
        if src < 0:
            c = probe.column(sym)
            t = probe.type_of(sym)
            d = probe.dicts.get(sym)
        else:
            tb = tables[src].batch
            c = tb.column(sym).gather(idxs[src])
            v = matcheds[src] if c.validity is None else \
                (c.validity & matcheds[src])
            c = Column(c.values, v, c.hi, c.sizes, c.evalid, c.keys)
            t = tb.type_of(sym)
            d = tb.dicts.get(sym)
        names.append(sym)
        types.append(t)
        cols.append(c)
        if d is not None:
            dicts[sym] = d
    return Batch(names, types, cols, probe.live, dicts)


def _mw_unique_state(specs, state):
    """(idxs, matcheds) maps for the unique legs — key sources for later
    snowflake legs."""
    idxs, matcheds = {}, {}
    for i, spec in enumerate(specs):
        if spec.unique:
            idxs[i], matcheds[i] = state[i]
    return idxs, matcheds


@jax.named_scope("join_probe")
def multiway_counts(tables, probe: Batch, specs, fanouts):
    """Pass 1 of the N-ary probe: per-leg match state, per-leg effective
    counts (left legs floor at 1 — the null-extension row), the combined
    per-probe-row product T and its exclusive prefix sum. Counts are
    exact for unique and hash legs; sorted-engine fanout legs may widen
    (probe_counts contract) — expand re-verifies keys, so only capacity
    inflates. ``ovfs[i]`` > 0 means hash leg i truncated its match
    matrix: the driver doubles that leg's fanout and re-runs (the
    widening-replay ladder).

    Returns (state, chats, offsets, T, total, ovfs)."""
    state, chats, ovfs = [], [], []
    idxs, matcheds = {}, {}
    for i, spec in enumerate(specs):
        kb = _mw_key_batch(probe, tables, spec, idxs, matcheds)
        kb = align_probe_strings(kb, spec.probe_keys, tables[i],
                                 spec.build_keys)
        if spec.unique:
            idx, matched = probe_unique(tables[i], kb, spec.probe_keys,
                                        spec.build_keys)
            idxs[i], matcheds[i] = idx, matched
            c = matched.astype(jnp.int64)
            state.append((idx, matched))
            ovfs.append(jnp.zeros((), jnp.int64))
        elif spec.hash_engine:
            mm, c, _off, _tot, _live, ovf = hash_probe_counts(
                tables[i], kb, spec.probe_keys, spec.compare_dtypes,
                fanouts[i])
            state.append((mm, c))
            ovfs.append(ovf)
        else:
            lo, c, _off, _tot, _live, ovf = probe_counts(
                tables[i], kb, spec.probe_keys, spec.build_keys,
                fanouts[i])
            state.append((lo, c))
            ovfs.append(jnp.zeros((), jnp.int64))
        chats.append(jnp.maximum(c, 1) if spec.kind == "left" else c)
    T = probe.live.astype(jnp.int64)
    for chat in chats:
        T = T * chat
    offsets = jnp.cumsum(T) - T
    total = jnp.sum(T)
    return (tuple(state), tuple(chats), offsets, T, total,
            jnp.stack(ovfs))


@jax.named_scope("join_probe")
def multiway_expand(tables, probe: Batch, specs, state, chats, offsets,
                    T, chunk_base, out_capacity: int, probe_cols,
                    build_cols):
    """Pass 2: materialize output slots [chunk_base, chunk_base +
    out_capacity). _slot_rows over the inclusive ends of T maps a
    slot to its probe row; the residual ordinal decomposes mixed-radix
    across legs (last leg fastest). Left legs emit their null-extension
    at digit 0 when unmatched. ``build_cols[i]`` are leg i's payload
    symbols; probe columns gather at probe_row."""
    N = len(specs)
    ends = offsets + T
    i = jnp.arange(out_capacity, dtype=jnp.int64) + chunk_base
    probe_row = _slot_rows(ends, chunk_base, out_capacity)
    r = i - offsets[probe_row]
    in_range = (i < ends[-1]) & (r >= 0) & (r < T[probe_row])
    digits = [None] * N
    for t in range(N - 1, -1, -1):
        c = jnp.maximum(chats[t][probe_row], 1)
        digits[t] = r % c
        r = r // c
    idxs, matcheds = _mw_unique_state(specs, state)
    out_live = in_range
    bidx, bvalid = [], []
    for t, spec in enumerate(specs):
        d = digits[t]
        if spec.unique:
            idx, matched = state[t]
            bi = idx[probe_row]
            ok = matched[probe_row]
        elif spec.hash_engine:
            mm, c = state[t]
            oc = jnp.clip(d, 0, mm.shape[1] - 1).astype(jnp.int32)
            bi = mm[probe_row, oc]
            ok = (d < c[probe_row]) & (bi >= 0)
            bi = jnp.clip(bi, 0,
                          tables[t].batch.capacity - 1).astype(jnp.int32)
        else:
            lo, c = state[t]
            bi = (lo[probe_row] + d).astype(jnp.int32)
            bi = jnp.clip(bi, 0, tables[t].hashes.shape[0] - 1)
            ok = d < c[probe_row]
            # re-verify real keys in the leg's aligned code space (covers
            # collisions and the widened counting fallback)
            kb = align_probe_strings(
                _mw_key_batch(probe, tables, spec, idxs, matcheds),
                spec.probe_keys, tables[t], spec.build_keys)
            for pk, bk in zip(spec.probe_keys, spec.build_keys):
                pv = kb.column(pk).values[probe_row]
                bv = tables[t].batch.column(bk).values[bi]
                if pv.dtype != bv.dtype:
                    pt = jnp.result_type(pv.dtype, bv.dtype)
                    pv, bv = pv.astype(pt), bv.astype(pt)
                ok = ok & (pv == bv)
        if spec.kind == "inner":
            out_live = out_live & ok
        bidx.append(bi)
        bvalid.append(ok)
    names, types, cols, dicts = [], [], [], {}
    for sym in probe_cols:
        names.append(sym)
        types.append(probe.type_of(sym))
        cols.append(probe.column(sym).gather(probe_row))
        if sym in probe.dicts:
            dicts[sym] = probe.dicts[sym]
    for t in range(N):
        tb = tables[t].batch
        for sym in build_cols[t]:
            names.append(sym)
            types.append(tb.type_of(sym))
            c = tb.column(sym).gather(bidx[t])
            v = bvalid[t] if c.validity is None else \
                (c.validity & bvalid[t])
            cols.append(Column(c.values, v, c.hi, c.sizes, c.evalid,
                               c.keys))
            if sym in tb.dicts:
                dicts[sym] = tb.dicts[sym]
    return Batch(names, types, cols, out_live, dicts)


@jax.named_scope("join_probe")
def multiway_probe_unique(tables, probe: Batch, specs, probe_cols,
                          build_cols):
    """All-unique fast path — the dominant star-schema shape: every leg
    matches at most one build row, so the output is row-aligned with the
    probe batch. Probe columns pass through untouched, each leg costs
    one probe + one payload gather, and the whole N-way join is ONE
    compiled program with no expansion pass.

    Returns (out, n_probe, n_leg0): the probe's live row count and leg
    0's binary-equivalent output row count ride along for the HBO
    probe-selectivity observation (one extra reduction each, no extra
    program)."""
    out_live = probe.live
    idxs, matcheds = {}, {}
    for i, spec in enumerate(specs):
        kb = _mw_key_batch(probe, tables, spec, idxs, matcheds)
        kb = align_probe_strings(kb, spec.probe_keys, tables[i],
                                 spec.build_keys)
        idx, matched = probe_unique(tables[i], kb, spec.probe_keys,
                                    spec.build_keys)
        idxs[i], matcheds[i] = idx, matched
        if spec.kind == "inner":
            out_live = out_live & matched
    n_probe = jnp.sum(probe.live).astype(jnp.int64)
    if specs[0].kind == "inner":
        n_leg0 = jnp.sum(probe.live & matcheds[0]).astype(jnp.int64)
    else:
        n_leg0 = n_probe
    names, types, cols, dicts = [], [], [], {}
    for sym in probe_cols:
        names.append(sym)
        types.append(probe.type_of(sym))
        cols.append(probe.column(sym))
        if sym in probe.dicts:
            dicts[sym] = probe.dicts[sym]
    for t in range(len(specs)):
        tb = tables[t].batch
        for sym in build_cols[t]:
            names.append(sym)
            types.append(tb.type_of(sym))
            c = tb.column(sym).gather(idxs[t])
            v = matcheds[t] if c.validity is None else \
                (c.validity & matcheds[t])
            cols.append(Column(c.values, v, c.hi, c.sizes, c.evalid,
                               c.keys))
            if sym in tb.dicts:
                dicts[sym] = tb.dicts[sym]
    return Batch(names, types, cols, out_live, dicts), n_probe, n_leg0


def gather_join_output(
    probe: Batch,
    table: BuildTable,
    probe_row: Optional[jnp.ndarray],
    build_idx: jnp.ndarray,
    out_live: jnp.ndarray,
    probe_cols: Sequence[str],
    build_cols: Sequence[str],
    build_prefix: str = "",
) -> Batch:
    """Materialize an inner-join output batch from index vectors.
    `probe_row` None: the output is row-aligned with the probe batch, whose
    columns are handed on as they are."""
    names, types, cols = [], [], []
    dicts = {}
    for c in probe_cols:
        names.append(c)
        types.append(probe.type_of(c))
        # Column.gather preserves validity AND the long-decimal hi limb
        col = probe.column(c)
        cols.append(col if probe_row is None else col.gather(probe_row))
        if c in probe.dicts:
            dicts[c] = probe.dicts[c]
    for c in build_cols:
        out_name = build_prefix + c
        names.append(out_name)
        types.append(table.batch.type_of(c))
        cols.append(table.batch.column(c).gather(build_idx))
        if c in table.batch.dicts:
            dicts[out_name] = table.batch.dicts[c]
    return Batch(names, types, cols, out_live, dicts)


def table_stats(table):
    """Host-synced (live row count, search steps, verify width) of a built
    join table: ``n_rows`` of a BuildTable or HashJoinTable and, in the same
    transfer, a BuildTable's ``search_steps`` and ``verify_width`` (None for
    the hash engine's table, which has no bucket search). One sync; the HBO
    observation path calls it after the build phase has already
    materialized the table, so the transfer is of ready scalars."""
    rows, steps, width = jax.device_get(
        (table.n_rows, getattr(table, "search_steps", None),
         getattr(table, "verify_width", None)))
    if steps is None:
        return int(rows), None, None  # lint: allow(host-sync)
    return int(rows), int(steps), int(width)  # lint: allow(host-sync)
