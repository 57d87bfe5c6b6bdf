"""Pallas kernel: group-by partial aggregation as one-hot × values matmul.

The TPU-native adaptation of hash-partitioned group-by (DESIGN.md §2):
instead of scattering rows into buckets (pointer-chasing, serial on TPU),
each row-tile builds a one-hot matrix ``onehot[r, g] = (gid[r] == g)`` and
accumulates ``out[g, c] += onehotᵀ @ vals[r, c]`` on the MXU.  The group
axis is tiled to keep the one-hot block in VMEM; the grid walks
(row_tiles × group_tiles) with the output block revisited across row tiles
(sequential TPU grid ⇒ safe accumulation).

Shapes: gid (R,) int32; vals (R, C) f32; out (G, C) f32.  Grid:
(G // BLOCK_G, R // BLOCK_R); out block (BLOCK_G, C) indexed by g only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_R = 256
BLOCK_G = 128


def _segsum_kernel(gid_ref, val_ref, out_ref):
    gi = pl.program_id(0)  # group tile
    ri = pl.program_id(1)  # row tile

    @pl.when(ri == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    gid = gid_ref[...]  # (BLOCK_R, 1) int32
    vals = val_ref[...]  # (BLOCK_R, C) f32
    g0 = gi * BLOCK_G
    local = gid - g0  # group index within this tile
    # one-hot on the MXU: (BLOCK_G, BLOCK_R) @ (BLOCK_R, C)
    cols = jax.lax.broadcasted_iota(jnp.int32, (gid.shape[0], BLOCK_G), 1)
    onehot = (cols == local).astype(jnp.float32)  # (BLOCK_R, BLOCK_G)
    out_ref[...] += jax.lax.dot_general(
        onehot, vals, (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit, static_argnames=("num_groups", "interpret"))
def segment_sum_tiles(
    gid: jnp.ndarray, vals: jnp.ndarray, num_groups: int, interpret: bool = True
) -> jnp.ndarray:
    """gid (R,1) int32 (R % BLOCK_R == 0); vals (R, C); out (num_groups, C).

    num_groups must be a multiple of BLOCK_G (ops.py pads).
    """
    R, C = vals.shape
    grid = (num_groups // BLOCK_G, max(1, R // BLOCK_R))
    br = min(BLOCK_R, R)
    return pl.pallas_call(
        _segsum_kernel,
        out_shape=jax.ShapeDtypeStruct((num_groups, C), jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, 1), lambda g, r: (r, 0)),
            pl.BlockSpec((br, C), lambda g, r: (r, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_G, C), lambda g, r: (g, 0)),
        interpret=interpret,
    )(gid, vals)
