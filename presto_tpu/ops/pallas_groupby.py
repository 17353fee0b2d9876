"""Pallas TPU kernel: fused small-domain grouped aggregation on the MXU.

Reference hot loop: MultiChannelGroupByHash.java:228 + the per-function
accumulators of InMemoryHashAggregationBuilder — a row-at-a-time
open-addressing hash table. The engine's portable path
(ops/grouping._direct_grouped_merge) replaces that with a [G, n]
masked-broadcast reduction per state on the VPU: O(G·n·S) elementwise work
and one pass over the batch per state.

This kernel instead feeds the MXU: per 256-row block, build a one-hot
[B, G] group-membership matrix once and compute ALL state partials as one
[G, B] × [B, S'] matmul — the systolic array does the segmented reduction.
One pass over the input, S-independent membership cost, 128×128 MAC
throughput.

Exactness (the engine's aggregates are money sums — lossy f32 MACs are
not acceptable):
- int64 states (decimal unscaled values, counts) split into four 16-bit
  limbs of the two's-complement bits. A limb is < 2¹⁶ and a 256-row block
  keeps each per-block limb partial < 2²⁴ — exactly representable in f32,
  so the MXU matmul is exact. Each block writes its OWN output slot (no
  cross-block f32 accumulation); the final reduction runs outside the
  kernel in int64, and Σ limbsum_k · 2¹⁶ᵏ in wrapping int64 arithmetic
  equals the true int64 sum for ANY inputs (mod-2⁶⁴ congruence).
- float64 states stay OFF this kernel: the MXU's f32 MACs round each
  accumulation step (~1e-6 relative after 256 addends — measured), and
  no splitting trick fixes rounding inside the systolic array. Float
  sums keep the portable f64 VPU path; the kernel covers the integer
  states (decimal money sums, counts, validity counts) where exactness
  is achievable AND required.

The kernel runs when PRESTO_TPU_PALLAS=1 on a TPU backend (the portable
XLA path stays the default); unit tests validate it bit-for-bit against
numpy in interpreter mode on CPU.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

BLOCK_ROWS = 256  # keeps 16-bit limb block-partials exact in f32 (< 2^24)
_LIMB = 4         # 4 × 16-bit limbs cover int64


def enabled() -> bool:
    return (os.environ.get("PRESTO_TPU_PALLAS", "0") == "1"
            and jax.default_backend() == "tpu")


def _kernel(gid_ref, vals_ref, out_ref, *, n_groups: int):
    """One grid step = one row block → one [G, S] output slot.

    gid_ref:  [1, 1, B] int32 group ids (>= n_groups → masked/dead row)
    vals_ref: [B, S] f32 state contributions (limbs already split)
    out_ref:  [1, G, S] this block's partials
    """
    gid = gid_ref[0]                                       # [1, B]
    onehot_t = (jax.lax.broadcasted_iota(jnp.int32, (n_groups, 1), 0)
                == gid).astype(jnp.float32)                # [G, B]
    # HIGHEST: a 16-bit limb does not survive a single bf16 MXU pass
    out_ref[0, :, :] = jnp.dot(onehot_t, vals_ref[...],    # [G, S]
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _blocked_call(gid: jnp.ndarray, vals: jnp.ndarray, n_groups: int,
                  interpret: bool) -> jnp.ndarray:
    """→ [nb, G, S] per-block partials (reduced by the caller).

    gid rides as [nb, 1, B]: a 1-D int32 operand would have to be blocked
    at XLA's 1024-element tile, and B must stay 256 (exactness, above);
    with the block's last two dimensions equal to the array's the TPU
    compiler takes any B. The package turns x64 on, so index-map
    constants are spelled int32 — Mosaic refuses an i64 block index.
    """
    n, s = vals.shape
    nb = -(-n // BLOCK_ROWS)
    pad = nb * BLOCK_ROWS - n
    if pad:
        gid = jnp.pad(gid, (0, pad), constant_values=n_groups)
        vals = jnp.pad(vals, ((0, pad), (0, 0)))
    z = np.int32(0)
    return pl.pallas_call(
        functools.partial(_kernel, n_groups=n_groups),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, 1, BLOCK_ROWS), lambda i: (i, z, z)),
            pl.BlockSpec((BLOCK_ROWS, s), lambda i: (i, z)),
        ],
        out_specs=pl.BlockSpec((1, n_groups, s), lambda i: (i, z, z)),
        out_shape=jax.ShapeDtypeStruct((nb, n_groups, s), jnp.float32),
        interpret=interpret,
        name="grouped_sums",
    )(gid.reshape(nb, 1, BLOCK_ROWS), vals)


def grouped_sums(gid, int_states, n_groups: int,
                 interpret: bool = False):
    """Fused multi-state EXACT grouped int64 sums.

    gid:        int32[n]; values >= n_groups are ignored (dead rows)
    int_states: list of int64[n] (masked to 0 on dead rows by caller)
    Returns a list of int64[G], exact for any inputs.
    """
    planes = []
    for v in int_states:
        u = v.astype(jnp.uint64)
        for k in range(_LIMB):
            planes.append(((u >> jnp.uint64(16 * k))
                           & jnp.uint64(0xFFFF)).astype(jnp.float32))
    if not planes:
        return []
    vals = jnp.stack(planes, axis=1)  # [n, S']
    out = _blocked_call(gid.astype(jnp.int32), vals, n_groups, interpret)

    int_out = []
    col = 0
    for _ in int_states:
        total = jnp.zeros(n_groups, jnp.int64)
        for k in range(_LIMB):
            # per-block limb partials are exact integers in f32; sum across
            # blocks in int64, then the shifted wrapping-int64 combine is
            # congruent mod 2^64 to the true sum — i.e. the exact int64 sum
            limb_sum = jnp.sum(
                jnp.round(out[:, :, col + k]).astype(jnp.int64), axis=0)
            total = total + (limb_sum << jnp.int64(16 * k))
        int_out.append(total)
        col += _LIMB
    return int_out
