"""Pallas linear-probing hash tables — the second breaker engine.

Reference hot loops: operator/MultiChannelGroupByHash.java:228 (group-by
open addressing over flat long[]) and PagesHash.java:34 (join build/probe
with PositionLinks chains). The sort engine (ops/grouping.py, ops/join.py)
replaced both with argsort + searchsorted; this module puts the hash table
back as a *selectable* engine, because neither wins everywhere — group
count, skew, and payload width set the crossover ("Global Hash Tables
Strike Back", arXiv 2505.04153; the hash-vs-sort group-by study,
arXiv 2411.13245). plan/stats.choose_breaker_engine makes the call.

Design:

- Keys are pre-encoded into int64 *planes* (`encode_plane`): plane
  equality ⇔ SQL group/join-key equality. Floats are bit-cast with
  -0.0 → +0.0 canonicalized; GROUP BY additionally canonicalizes NaN so
  all NaNs form ONE group (Presto semantics; the sort engine's `!=`
  boundary detection gives each NaN row its own group — a documented
  deviation, irrelevant to equi-joins where NaN keys are excluded from
  matching on both sides, mirroring the sort engine's IEEE `==`).
- The physical table is 2× the logical capacity (load factor ≤ 50%), so
  probe chains stay short even when the logical table is full and the
  overflow signal stays *exact*: inserts stop at `cap` distinct keys, so
  overflow > 0 ⇔ the input holds more than `cap` distinct keys — the
  same n_groups > cap contract the sort engine's drivers already replay
  on (capacity-growth replay, ops/grouping.grouped_merge docstring).
- Kernels are serial per-row loops (grid=(1,)) — the table lives in one
  ref and rows chain through `lax.while_loop` probes. They run ONLY
  under the Pallas interpreter, on CPU (`use_interpret()`): the TPU
  compiler refuses all three (`TPU_REFUSAL`), so on a TPU backend the
  hash engine is not selectable (`tpu_refusal()`, enforced by
  plan/stats.choose_breaker_engine) and nothing here is ever interpreted
  on a chip.
- Join probe returns a bounded-fanout match matrix mm[n, F] plus EXACT
  per-row match counts; rows with more than F matches set the overflow
  counter and the driver re-probes with F doubled (counts, offsets and
  totals are already exact, so only the probe kernel reruns).
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def use_interpret() -> bool:
    """Interpret kernels off-TPU: tier-1/CI and the verifier sweeps then
    exercise the hash engine on CPU with the exact kernel semantics. A
    CPU-test device only — never true on a TPU backend."""
    return jax.default_backend() != "tpu"


# The v5e compiler's verdict on this module (jax 0.9.0 / libtpu 0.0.34,
# compiled for a described v5e:2x2 at n = 2^17): the kernels walk whole
# unblocked int64 refs with scalar loops. As written they are refused
# while lowering; with 32-bit refs in scalar memory the design compiles
# only while every ref fits 1 MiB of SMEM (n = 2^13 yes, n = 2^17 no).
TPU_REFUSAL = (
    "ops/pallas_hash kernels are refused by the TPU compiler: "
    "group_insert and join_probe 'Only arrays with 32-bit element types "
    "can be converted to scalars, but got: float64', join_insert "
    "RecursionError while lowering; with 32-bit refs in SMEM 'Ran out of "
    "memory in memory space smem. Used 3.00M of 1.00M' at 2^17 rows")


def tpu_refusal() -> Optional[str]:
    """Why the hash engine cannot run on this backend, or None if it can
    (off-TPU it runs interpreted)."""
    return TPU_REFUSAL if jax.default_backend() == "tpu" else None


# ---------------------------------------------------------------------------
# key-plane encoding

_NAN64_BITS = 0x7FF8000000000000  # canonical quiet-NaN bit patterns
_NAN32_BITS = 0x7FC00000


def encode_plane(values: jnp.ndarray,
                 target_dtype=None,
                 canonicalize_nan: bool = True) -> jnp.ndarray:
    """One key column → an int64 plane where plane equality matches SQL
    equality under `target_dtype` (the pairwise-promoted compare dtype for
    joins; the column's own dtype for GROUP BY).

    Floats bit-cast (f32 via its int32 pattern — reversible); -0.0 is
    canonicalized to +0.0 first so `-0.0 = 0.0` holds like the sort
    engine's `==`. With canonicalize_nan all NaNs share one plane value
    (GROUP BY); join callers exclude NaN-key rows instead."""
    v = values
    if target_dtype is not None and v.dtype != jnp.dtype(target_dtype):
        v = v.astype(target_dtype)
    if v.dtype == jnp.bool_:
        return v.astype(jnp.int64)
    if jnp.issubdtype(v.dtype, jnp.floating):
        if v.dtype != jnp.float32:
            v = v.astype(jnp.float64)
        v = v + jnp.zeros((), v.dtype)  # -0.0 + 0.0 == +0.0
        if v.dtype == jnp.float32:
            bits = jax.lax.bitcast_convert_type(v, jnp.int32).astype(jnp.int64)
            nan = jnp.int64(_NAN32_BITS)
        else:
            bits = jax.lax.bitcast_convert_type(v, jnp.int64)
            nan = jnp.int64(_NAN64_BITS)
        if canonicalize_nan:
            bits = jnp.where(jnp.isnan(v), nan, bits)
        return bits
    return v.astype(jnp.int64)


def decode_plane(plane: jnp.ndarray, dtype) -> jnp.ndarray:
    """Reverse `encode_plane` for GROUP BY key materialization."""
    dtype = jnp.dtype(dtype)
    if dtype == jnp.bool_:
        return plane != 0
    if jnp.issubdtype(dtype, jnp.floating):
        if dtype == jnp.dtype(jnp.float32):
            return jax.lax.bitcast_convert_type(
                plane.astype(jnp.int32), jnp.float32)
        return jax.lax.bitcast_convert_type(
            plane, jnp.float64).astype(dtype)
    return plane.astype(dtype)


def encode_group_keys(
    cols: Sequence[Tuple[jnp.ndarray, Optional[jnp.ndarray]]],
) -> Tuple[jnp.ndarray, bool]:
    """GROUP BY keys → stacked planes [K', n]. Nullable keys zero their
    plane on NULL and set a bit in a shared trailing nullbits plane, so
    (NULL group) ≠ (value-0 group) and NULLs form one group per key —
    exactly the sort engine's (nullbit, zeroed value) operand pair.

    Returns (planes, has_null_plane)."""
    planes = []
    nullbits = None
    for j, (v, valid) in enumerate(cols):
        p = encode_plane(v)
        if valid is not None:
            p = jnp.where(valid, p, jnp.int64(0))
            nb = jnp.where(valid, jnp.int64(0), jnp.int64(1) << jnp.int64(j))
            nullbits = nb if nullbits is None else nullbits | nb
        planes.append(p)
    if nullbits is not None:
        planes.append(nullbits)
    return jnp.stack(planes), nullbits is not None


# ---------------------------------------------------------------------------
# group-by insert kernel


def _group_insert_kernel(slot0_ref, keys_ref, live_ref,
                         gid_ref, table_ref, occ_ref, stat_ref,
                         *, tcap: int, fill_max: int):
    """Serial linear-probing insert: one pass over the rows, table state
    in refs. Probe walks (slot0 + j) & (tcap - 1) until it sees the key
    (match) or an empty slot (claim, while under fill_max distinct)."""
    n = slot0_ref.shape[0]
    occ_ref[...] = jnp.zeros_like(occ_ref)
    table_ref[...] = jnp.zeros_like(table_ref)
    gid_ref[...] = jnp.full_like(gid_ref, tcap)
    mask = tcap - 1

    def row(i, carry):
        ngroups, ovf = carry
        lv = live_ref[i]
        s0 = slot0_ref[i]
        ki = keys_ref[:, i]

        # kind: 0 = searching, 1 = key found at slot, 2 = empty at slot
        def cond(st):
            j, kind, _slot = st
            return (kind == 0) & (j < tcap)

        def body(st):
            j, _kind, _slot = st
            s = (s0 + j) & mask
            o = occ_ref[s]
            stored = table_ref[:, s]
            is_empty = o == 0
            is_match = jnp.logical_not(is_empty) & jnp.all(stored == ki)
            kind = jnp.where(is_match, 1, jnp.where(is_empty, 2, 0))
            return j + 1, kind, s

        init_kind = jnp.where(lv, 0, 1)  # dead rows skip the probe
        _, kind, slot = jax.lax.while_loop(
            cond, body, (jnp.int32(0), init_kind, jnp.int32(0)))

        do_insert = lv & (kind == 2) & (ngroups < fill_max)
        cur = table_ref[:, slot]
        table_ref[:, slot] = jnp.where(do_insert, ki, cur)
        occ_ref[slot] = jnp.where(do_insert, 1, occ_ref[slot])
        placed = lv & ((kind == 1) | do_insert)
        gid_ref[i] = jnp.where(placed, slot, tcap)
        ovf_inc = (lv & jnp.logical_not(placed)).astype(jnp.int32)
        return ngroups + do_insert.astype(jnp.int32), ovf + ovf_inc

    ngroups, ovf = jax.lax.fori_loop(
        0, n, row, (jnp.int32(0), jnp.int32(0)))
    stat_ref[0] = ngroups
    stat_ref[1] = ovf


def group_insert(planes: jnp.ndarray, slot0: jnp.ndarray,
                 live: jnp.ndarray, cap: int,
                 interpret: bool = False):
    """Assign linear-probing group ids for GROUP BY.

    planes: int64[K, n] encoded key planes; slot0: int32[n] initial probe
    slot in [0, 2*cap) (low bits of the key hash — see radix.slot_hash for
    the top-bits/low-bits disjointness contract under radix); cap: the
    driver's logical pow2 group budget. The physical table is tcap=2*cap.

    Returns (gid int32[n], table int64[K, tcap], occ int32[tcap],
    n_groups int32, overflow int32). gid == tcap marks dead or unplaced
    rows. Inserts stop at cap distinct keys, so overflow > 0 ⇔ more than
    cap distinct keys — the driver's regrow-replay trigger; unplaced rows
    each count once, so cap + overflow upper-bounds the true distinct
    count (callers clamp before feeding round_up_capacity)."""
    if cap <= 0 or cap & (cap - 1):
        raise ValueError(f"cap must be a positive power of two, got {cap}")
    K, n = planes.shape
    tcap = 2 * cap
    gid, table, occ, stat = pl.pallas_call(
        functools.partial(_group_insert_kernel, tcap=tcap, fill_max=cap),
        out_shape=(
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((K, tcap), jnp.int64),
            jax.ShapeDtypeStruct((tcap,), jnp.int32),
            jax.ShapeDtypeStruct((2,), jnp.int32),
        ),
        interpret=interpret,
    )(slot0.astype(jnp.int32), planes, live)
    return gid, table, occ, stat[0], stat[1]


# ---------------------------------------------------------------------------
# join build insert kernel


def _join_insert_kernel(slot0_ref, live_ref, slot_row_ref, *, tcap: int):
    """Claim one slot per live build row (duplicate keys occupy separate
    slots along the probe chain; the probe kernel walks to the first empty
    slot, collecting every row whose key verifies)."""
    n = slot0_ref.shape[0]
    slot_row_ref[...] = jnp.full_like(slot_row_ref, -1)
    mask = tcap - 1

    def row(i, _):
        lv = live_ref[i]
        s0 = slot0_ref[i]

        def cond(st):
            j, done, _slot = st
            return jnp.logical_not(done) & (j < tcap)

        def body(st):
            j, _done, _slot = st
            s = (s0 + j) & mask
            done = slot_row_ref[s] < 0
            return j + 1, done, s

        init_done = jnp.logical_not(lv)
        _, done, slot = jax.lax.while_loop(
            cond, body, (jnp.int32(0), init_done, jnp.int32(0)))
        claim = lv & done
        cur = slot_row_ref[slot]
        slot_row_ref[slot] = jnp.where(claim, i, cur)
        return 0

    jax.lax.fori_loop(0, n, row, 0)


def join_insert(slot0: jnp.ndarray, live: jnp.ndarray, tcap: int,
                interpret: bool = False) -> jnp.ndarray:
    """Build-side insert: → slot_row int32[tcap], the build ROW index
    occupying each slot (-1 = empty). tcap must be a pow2 ≥ 2× the live
    row count so the load factor stays ≤ 50% and every row finds a slot."""
    if tcap <= 0 or tcap & (tcap - 1):
        raise ValueError(f"tcap must be a positive power of two, got {tcap}")
    return pl.pallas_call(
        functools.partial(_join_insert_kernel, tcap=tcap),
        out_shape=jax.ShapeDtypeStruct((tcap,), jnp.int32),
        interpret=interpret,
    )(slot0.astype(jnp.int32), live)


# ---------------------------------------------------------------------------
# join probe kernel


def _join_probe_kernel(slot0_ref, pkeys_ref, plive_ref, slot_row_ref,
                       bkeys_ref, mm_ref, cnt_ref, stat_ref,
                       *, tcap: int, fanout: int):
    """Walk each probe row's chain to the first empty slot, verifying the
    stored row's key planes. The first `fanout` matching build rows land
    in mm[i, :]; the count keeps going past fanout so counts/offsets stay
    exact and stat[0] reports rows needing a wider matrix."""
    n = slot0_ref.shape[0]
    mask = tcap - 1

    def row(i, ovf):
        lv = plive_ref[i]
        s0 = slot0_ref[i]
        ki = pkeys_ref[:, i]

        def cond(st):
            j, cont, _cnt, _mm = st
            return cont & (j < tcap)

        def body(st):
            j, _cont, cnt, mmrow = st
            s = (s0 + j) & mask
            r = slot_row_ref[s]
            occupied = r >= 0
            rc = jnp.maximum(r, 0)
            stored = bkeys_ref[:, rc]
            m = occupied & jnp.all(stored == ki)
            rec = m & (cnt < fanout)
            pos = jnp.minimum(cnt, fanout - 1)
            mmrow = mmrow.at[pos].set(jnp.where(rec, r, mmrow[pos]))
            return j + 1, occupied, cnt + m.astype(jnp.int32), mmrow

        init = (jnp.int32(0), lv, jnp.int32(0),
                jnp.full((fanout,), -1, jnp.int32))
        _, _, cnt, mmrow = jax.lax.while_loop(cond, body, init)
        mm_ref[i, :] = mmrow
        cnt_ref[i] = cnt
        return ovf + (cnt > fanout).astype(jnp.int32)

    ovf = jax.lax.fori_loop(0, n, row, jnp.int32(0))
    stat_ref[0] = ovf


def join_probe(slot0: jnp.ndarray, pkeys: jnp.ndarray, plive: jnp.ndarray,
               slot_row: jnp.ndarray, bkeys: jnp.ndarray, fanout: int,
               interpret: bool = False):
    """Probe-side lookup.

    slot0: int32[n] initial probe slots; pkeys: int64[K, n] probe planes;
    bkeys: int64[K, cap_b] build planes indexed by build ROW; slot_row:
    int32[tcap] from join_insert. Returns (mm int32[n, fanout] build rows
    of the first `fanout` matches (-1 padded), counts int32[n] EXACT match
    counts, overflow int32 scalar = rows with counts > fanout)."""
    if fanout <= 0 or fanout & (fanout - 1):
        raise ValueError(
            f"fanout must be a positive power of two, got {fanout}")
    n = slot0.shape[0]
    tcap = slot_row.shape[0]
    mm, cnt, stat = pl.pallas_call(
        functools.partial(_join_probe_kernel, tcap=tcap, fanout=fanout),
        out_shape=(
            jax.ShapeDtypeStruct((n, fanout), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
        ),
        interpret=interpret,
    )(slot0.astype(jnp.int32), pkeys, plive, slot_row, bkeys)
    return mm, cnt, stat[0]
