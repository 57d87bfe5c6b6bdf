"""Pallas kernel: η_{a,m} hashing + threshold (§4.4).

Layout: key columns are padded/reshaped to (R, 128) so rows map onto VPU
lanes; the grid walks row-tiles of shape (BLOCK_R, 128) held in VMEM.  The
splitmix32 finalizer is pure elementwise uint32 arithmetic — ideal VPU work
— and the threshold compare emits an int8 mask (bool stores are awkward in
VMEM; int8 keeps the tile dense).

The kernel hashes up to ``n_cols`` key columns (composite keys) by folding
each column through the mixer, seeded identically to the jnp reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# the ONE splitmix32 mixer (core/hashing): Prop. 2's bit-identical-hash
# invariant is structural, not a copied constant block
from repro.core.hashing import splitmix32, u01

LANES = 128
BLOCK_R = 64  # (64, 128) uint32 tile = 32 KiB in VMEM per column


def _hash_threshold_kernel(seed_mix: int, thresh: float, *refs):
    """refs = (col_ref_0, ..., col_ref_{k-1}, out_ref).

    ``seed_mix``/``thresh`` are Python constants baked at trace time (the
    sampling ratio and seed are plan-static in SVC).
    """
    col_refs, out_ref = refs[:-1], refs[-1]
    h = jnp.full(col_refs[0].shape, jnp.uint32(seed_mix), jnp.uint32)
    for r in col_refs:
        c = r[...].astype(jnp.uint32)
        h = splitmix32(h ^ splitmix32(c))
    u = u01(h)
    out_ref[...] = (u < jnp.float32(thresh)).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("seed_mix", "thresh", "n_cols", "interpret"))
def hash_threshold_tiles(
    cols2d: tuple, seed_mix: int, thresh: float, n_cols: int, interpret: bool = True
) -> jnp.ndarray:
    """cols2d: n_cols arrays of identical shape (R, 128) int32/uint32."""
    rows = cols2d[0].shape[0]
    grid = (max(1, rows // BLOCK_R),)
    block = (min(BLOCK_R, rows), LANES)
    spec = pl.BlockSpec(block, lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_hash_threshold_kernel, seed_mix, thresh),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.int8),
        grid=grid,
        in_specs=[spec] * n_cols,
        out_specs=spec,
        interpret=interpret,
    )(*cols2d)
