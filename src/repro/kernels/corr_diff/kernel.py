"""Pallas kernel: fused SVC+CORR inner loop (Def. 4 + §5.2.1 moments).

Computes, in one pass over the correspondence-joined row space:

    d_i   = t_new_i − t_old_i          (correspondence subtract, Ø→0)
    out   = [Σ d_i,  Σ d_i²,  Σ mask_i]

which is everything svc_corr needs for the estimate and its CLT interval
(mean/variance are derived on the host from the three moments).  Fusing the
subtract with the moment accumulation avoids materializing the diff column
in HBM — the CORR estimation path becomes a single streaming reduction.

Tiles: inputs reshaped to (R, 128); grid walks row tiles; the (8, 128)
output accumulator block is revisited by every grid step (sequential TPU
grid ⇒ safe).  Slots [0,0..2] hold the three moments.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
BLOCK_R = 64


def _corr_diff_kernel(t_new_ref, t_old_ref, mask_ref, out_ref):
    ri = pl.program_id(0)

    @pl.when(ri == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    m = mask_ref[...].astype(jnp.float32)
    d = (t_new_ref[...] - t_old_ref[...]) * m
    s1 = jnp.sum(d)
    s2 = jnp.sum(d * d)
    s0 = jnp.sum(m)
    # slots [0, 0..2] built with iota/where (Mosaic lowers no scatter)
    row = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    first = row == 0
    acc = jnp.where(first & (col == 0), s1, 0.0)
    acc = jnp.where(first & (col == 1), s2, acc)
    acc = jnp.where(first & (col == 2), s0, acc)
    out_ref[...] += acc


@functools.partial(jax.jit, static_argnames=("interpret",))
def corr_diff_tiles(
    t_new: jnp.ndarray, t_old: jnp.ndarray, mask: jnp.ndarray, interpret: bool = True
) -> jnp.ndarray:
    """t_new/t_old (R, 128) f32, mask (R, 128) int8 → (8, 128) accumulator."""
    rows = t_new.shape[0]
    grid = (max(1, rows // BLOCK_R),)
    br = min(BLOCK_R, rows)
    spec = pl.BlockSpec((br, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        _corr_diff_kernel,
        out_shape=jax.ShapeDtypeStruct((8, LANES), jnp.float32),
        grid=grid,
        in_specs=[spec, spec, spec],
        out_specs=pl.BlockSpec((8, LANES), lambda i: (0, 0)),
        interpret=interpret,
    )(t_new, t_old, mask)
