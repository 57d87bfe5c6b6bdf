"""Pallas kernel: fused η hash-threshold + outlier-index membership (§6.2).

The skewed-workload sample predicate is ``hash(pk) ≤ m OR pk ∈
outlier_keys`` with pinned rows flagged ``__outlier`` (weight 1, Def. 5).
The seed implementation answered the membership half with a Python loop
unrolled over the whole index capacity — O(N·K) dispatches for multi-column
keys.  This kernel answers both halves in ONE pass over the key-column
tile:

  1. fold the composite key columns through the shared splitmix32 mixer
     (imported from core/hashing — bit-identical to hash_threshold) THREE
     ways at once: the η hash, and the (hi, lo) lanes of the 64-bit
     membership digest.  One ``mix(col)`` per column feeds all three folds
     — pure VPU elementwise work;
  2. η: u(h) < m, exactly the hash_threshold compare;
  3. membership: broadcast-compare the row digests against the (2, Kp)
     sorted-digest table resident in VMEM — the (BLOCK_R, Kp) equality tile
     never leaves VMEM (the TPU shape of the sorted-search idea: the table
     is scanned once per row tile instead of per key);
  4. emit an int32 code per row: bit0 = keep (η ∨ member), bit1 = member
     (the ``__outlier`` flag source).

Shapes: cols (R, C) int32 composite key panel (SENTINEL_KEY marks invalid
probe rows); keys (8, Kp) uint32 digest table (row 0 = hi, row 1 = lo,
rows 2.. padding); out (R, 1) int32.  Grid walks row tiles; the key table
is revisited every step (sequential TPU grid ⇒ safe).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.hashing import splitmix32, u01
from repro.relational.relation import SENTINEL_KEY

BLOCK_R = 256
LANE = 128
KEY_ROWS = 8  # digest table sublane padding (uint32 tile multiple)


def _outlier_member_kernel(C, seed_eta, seed_hi, seed_lo, thresh,
                           col_ref, keys_ref, out_ref):
    """``seed_*``/``thresh`` are Python constants baked at trace time (the
    sampling ratio and seeds are plan-static in SVC)."""
    cols = col_ref[...]  # (BLOCK_R, C) int32
    shape = (cols.shape[0], 1)
    h_eta = jnp.full(shape, jnp.uint32(seed_eta), jnp.uint32)
    h_hi = jnp.full(shape, jnp.uint32(seed_hi), jnp.uint32)
    h_lo = jnp.full(shape, jnp.uint32(seed_lo), jnp.uint32)
    for c in range(C):
        mc = splitmix32(cols[:, c:c + 1].astype(jnp.uint32))
        h_eta = splitmix32(h_eta ^ mc)
        h_hi = splitmix32(h_hi ^ mc)
        h_lo = splitmix32(h_lo ^ mc)
    u = u01(h_eta)
    eta = u < jnp.float32(thresh)

    khi = keys_ref[0:1, :]  # (1, Kp)
    klo = keys_ref[1:2, :]
    match = (h_hi == khi) & (h_lo == klo)  # (BLOCK_R, Kp) broadcast compare
    member = jnp.sum(match.astype(jnp.float32), axis=1, keepdims=True) > 0.0
    member = member & (cols[:, 0:1] != jnp.int32(SENTINEL_KEY))
    keep = eta | member
    out_ref[...] = keep.astype(jnp.int32) + 2 * member.astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("seed_eta", "seed_hi", "seed_lo", "thresh", "interpret")
)
def outlier_member_tiles(
    cols: jnp.ndarray,
    keys: jnp.ndarray,
    seed_eta: int,
    seed_hi: int,
    seed_lo: int,
    thresh: float,
    interpret: bool = True,
) -> jnp.ndarray:
    """cols (R, C) int32 (R % BLOCK_R == 0), keys (8, Kp) uint32
    (Kp % 128 == 0, padded with sentinel-tuple digests); out (R, 1) int32
    codes (bit0 keep, bit1 member)."""
    R, C = cols.shape
    Kp = keys.shape[1]
    br = min(BLOCK_R, R)
    return pl.pallas_call(
        functools.partial(_outlier_member_kernel, C, seed_eta, seed_hi, seed_lo, thresh),
        out_shape=jax.ShapeDtypeStruct((R, 1), jnp.int32),
        grid=(max(1, R // BLOCK_R),),
        in_specs=[
            pl.BlockSpec((br, C), lambda r: (r, 0)),
            pl.BlockSpec((KEY_ROWS, Kp), lambda r: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, 1), lambda r: (r, 0)),
        interpret=interpret,
    )(cols, keys)
