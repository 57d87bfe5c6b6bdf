"""Pallas kernel: fused η-filter + group-by sum/count over delta rows.

The SVC hot loop (§4.5) is "hash the delta row's view key, keep it if it
falls under the sample threshold, then fold it into its group's partial
aggregates".  The unfused pipeline runs that as two kernels with a full
materialized intermediate (hash_threshold mask → masked relation →
segment_aggsum); this kernel does both in ONE pass over the delta tile:

  1. splitmix32 the group-key column (bit-identical to hash_threshold) and
     compare against the threshold — VPU elementwise work;
  2. OR in the outlier-pin membership mask (Def. 5 rows enter the sample
     with weight 1 regardless of their hash);
  3. fold the keep-mask into the one-hot matrix and accumulate
     ``out[g, :] += onehotᵀ @ [1 | vals]`` on the MXU — column 0 of the
     output is the kept-row count, columns 1.. are the masked column sums.
     A row tile runs one contraction per occurrence rank (a group's k-th
     row in the tile lands in pass k), so every group sums its rows one
     at a time in row order: bit-equal to the executor's segment sum.

No filtered intermediate ever exists: the keep decision lives only in the
one-hot tile in VMEM.  Grid and accumulation discipline follow
segment_aggsum: (group_tiles × row_tiles), the out block revisited across
row tiles (sequential TPU grid ⇒ safe accumulation).

Shapes: gid (R, 1) int32 (−1 ⇒ invalid/padded row, ≥ num_groups ⇒ dropped
like segment_sum's out-of-range rule); pin (R, 1) int8; vals (R, 1 + C)
f32 with a leading ones column; out (G, 1 + C) f32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# The ONE splitmix32 mixer (core/hashing): importing it makes the
# bit-identical-hash invariant behind Prop. 2 structural — this kernel
# cannot drift from hash_threshold/the jnp oracle by copy-edit.
from repro.core.hashing import splitmix32, u01

BLOCK_R = 256
BLOCK_G = 128


def _fused_clean_kernel(seed_mix, thresh, gid_ref, gidrow_ref, pin_ref,
                        val_ref, out_ref):
    """``seed_mix``/``thresh`` are baked at trace time (plan-static in SVC)."""
    gi = pl.program_id(0)  # group tile
    ri = pl.program_id(1)  # row tile

    @pl.when(ri == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    gid = gid_ref[...]  # (BLOCK_R, 1) int32
    # η_{a,m}: the shared mixer + compare of kernels/hash_threshold
    h = splitmix32(jnp.uint32(seed_mix) ^ splitmix32(gid.astype(jnp.uint32)))
    u = u01(h)
    keep = (u < jnp.float32(thresh)) | (pin_ref[...] != 0)
    keep = keep & (gid >= 0)

    g0 = gi * BLOCK_G
    local = gid - g0  # group index within this tile
    cols = jax.lax.broadcasted_iota(jnp.int32, (gid.shape[0], BLOCK_G), 1)
    # the η decision folds into the one-hot: kept rows scatter, dropped
    # rows vanish — this is the "no materialized filtered intermediate"
    hit = (cols == local) & keep  # (BLOCK_R, BLOCK_G)
    # rank of each row among the earlier rows of its key in this tile:
    # pass k adds every group's k-th row, so a group's rows accumulate
    # one at a time in row order — the float order of the plan
    # executor's segment sum, which a multi-row MXU contraction would
    # reassociate
    n = gid.shape[0]
    earlier = ((gidrow_ref[...] == gid)
               & (jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
                  < jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)))
    rank = jnp.sum(earlier.astype(jnp.int32), axis=1, keepdims=True)
    in_tile = keep & (local >= 0) & (local < BLOCK_G)
    passes = jnp.max(jnp.where(in_tile, rank + 1, 0))
    vals = val_ref[...]

    def one_pass(k, carry):
        onehot = (hit & (rank == k)).astype(jnp.float32)
        # one row per group per pass: HIGHEST makes 1·v exact on the MXU
        out_ref[...] += jax.lax.dot_general(
            onehot, vals, (((0,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
        return carry

    jax.lax.fori_loop(0, passes, one_pass, 0)


@functools.partial(jax.jit, static_argnames=("seed_mix", "thresh", "num_groups", "interpret"))
def fused_clean_tiles(
    gid: jnp.ndarray,
    pin: jnp.ndarray,
    vals: jnp.ndarray,
    seed_mix: int,
    thresh: float,
    num_groups: int,
    interpret: bool = True,
) -> jnp.ndarray:
    """gid (R,1) int32, pin (R,1) int8, vals (R, 1+C) f32 (R % BLOCK_R == 0);
    out (num_groups, 1+C) f32 with count in column 0.

    num_groups must be a multiple of BLOCK_G (ops.py pads).
    """
    R, C1 = vals.shape
    grid = (num_groups // BLOCK_G, max(1, R // BLOCK_R))
    br = min(BLOCK_R, R)
    return pl.pallas_call(
        functools.partial(_fused_clean_kernel, seed_mix, thresh),
        out_shape=jax.ShapeDtypeStruct((num_groups, C1), jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, 1), lambda g, r: (r, 0)),
            pl.BlockSpec((1, br), lambda g, r: (0, r)),
            pl.BlockSpec((br, 1), lambda g, r: (r, 0)),
            pl.BlockSpec((br, C1), lambda g, r: (r, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_G, C1), lambda g, r: (g, 0)),
        interpret=interpret,
    )(gid, gid.reshape(1, R), pin, vals)
