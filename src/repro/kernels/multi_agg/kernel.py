"""Pallas kernel: one pass over sample rows answers Q queries at once.

The SVC query hot loop evaluates, per query, a predicate mask and a
§5.2.1 trans table over the clean sample, the stale sample, and their
correspondence diff, then reduces each to a handful of moments.  Answered
one query at a time that is ~4Q scans of the same rows (AQP trans, CORR
trans × 2 sides, break-even check).  This kernel tiles the
correspondence-aligned row panel ONCE and accumulates, for all Q queries
simultaneously, every moment the estimators need:

  1. select each query's value/predicate columns from the row tile with
     one-hot matrices on the MXU — ``v = X @ sel`` — so the per-query
     (rows × queries) trans tables exist only in VMEM;
  2. apply the encoded interval bounds (ge/gt/le/lt per term; ±inf for
     unused sides) and the sum/count/avg op codes to form t and the row
     mask per query;
  3. reduce each row tile to its moments, out[tile, moment, q] = Σ_rows:
     counts, Σt, Σt², Σ(1−π)t² per side plus Σd, Σd² and the pin-aware
     Σ min(1−π_new, 1−π_old)·d² (HT_D, §6.3) for d = t_new−t_old.

Each grid step writes its own (16, Q) partial block; ops.py sums the
partials with one XLA reduction.  Accumulating 8k row tiles into one f32
block in grid order would cost ~8k·2⁻²⁴ ≈ 5e-4 relative error at 2M rows
— wider than a CORR interval whose diff is small, where the exact side
of the estimate must be exact to float rounding.

Shapes: x (R, Cp) f32 panels; valid/w/ompi (R, 1) f32 row vectors;
sel ((1+P)·Cp, Qp) f32; meta (Mp, Qp) f32; out (R/BLOCK_R, 16, Qp) f32
with the moment-row layout of ref.py (rows 12..15 zero padding).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.multi_agg.ref import META_IS_AVG, META_IS_COUNT, META_PER_PRED, META_PRED0

BLOCK_R = 256
LANE = 128
N_OUT_ROWS = 16  # 12 moments padded to the f32 sublane multiple


def _dot(a, b):
    # HIGHEST: the one-hot column select must return the column's f32
    # values exactly (the MXU's default bf16 pass would round predicate
    # operands and move rows across range bounds)
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _tile_trans(x, valid, w, sel, meta, C, P):
    """(BLOCK_R, Qp) trans table t and f32 row mask for one panel side."""
    v = _dot(x, sel[0:C, :])
    is_count = meta[META_IS_COUNT:META_IS_COUNT + 1, :]
    is_avg = meta[META_IS_AVG:META_IS_AVG + 1, :]
    v = jnp.where(is_count > 0, 1.0, v)
    cond = jnp.broadcast_to(valid > 0, v.shape)
    for p in range(P):
        tv = _dot(x, sel[(1 + p) * C:(2 + p) * C, :])
        b0 = META_PRED0 + META_PER_PRED * p
        cond = (cond
                & (tv >= meta[b0:b0 + 1, :]) & (tv > meta[b0 + 1:b0 + 2, :])
                & (tv <= meta[b0 + 2:b0 + 3, :]) & (tv < meta[b0 + 3:b0 + 4, :]))
    w_eff = jnp.where(is_avg > 0, 1.0, w)
    t = jnp.where(cond, v, 0.0) * w_eff
    rowmask = jnp.where(
        is_avg > 0, cond.astype(jnp.float32),
        jnp.broadcast_to((valid > 0).astype(jnp.float32), v.shape),
    )
    return t, rowmask


def _side_rows(t, rowmask, ompi):
    return (
        jnp.sum(rowmask, axis=0),
        jnp.sum(t, axis=0),
        jnp.sum(t * t, axis=0),
        jnp.sum(ompi * t * t, axis=0),
    )


def _multi_agg_kernel_two(C, P, xn_ref, vn_ref, wn_ref, on_ref,
                          xo_ref, vo_ref, wo_ref, oo_ref,
                          sel_ref, meta_ref, out_ref):
    sel = sel_ref[...]
    meta = meta_ref[...]
    vn, vo = vn_ref[...], vo_ref[...]
    tn, mn = _tile_trans(xn_ref[...], vn, wn_ref[...], sel, meta, C, P)
    to, mo = _tile_trans(xo_ref[...], vo, wo_ref[...], sel, meta, C, P)
    kn, sn, ssn, htn = _side_rows(tn, mn, on_ref[...])
    ko, so, sso, hto = _side_rows(to, mo, oo_ref[...])
    d = tn - to
    joined = ((vn > 0) | (vo > 0)).astype(jnp.float32)
    kd = jnp.zeros_like(kn) + jnp.sum(joined)
    sd = jnp.sum(d, axis=0)
    ssd = jnp.sum(d * d, axis=0)
    # §6.3: rows pinned on either side (ompi = 0) have an exact diff —
    # their 1−π factor for the CORR HT term is the per-side minimum
    od = jnp.minimum(on_ref[...], oo_ref[...])
    htd = jnp.sum(od * d * d, axis=0)
    z = jnp.zeros_like(kn)
    out_ref[0] = jnp.stack(
        [kn, sn, ssn, htn, ko, so, sso, hto, kd, sd, ssd, htd, z, z, z, z]
    )


def _multi_agg_kernel_one(C, P, xn_ref, vn_ref, wn_ref, on_ref,
                          sel_ref, meta_ref, out_ref):
    tn, mn = _tile_trans(xn_ref[...], vn_ref[...], wn_ref[...],
                         sel_ref[...], meta_ref[...], C, P)
    kn, sn, ssn, htn = _side_rows(tn, mn, on_ref[...])
    z = jnp.zeros_like(kn)
    out_ref[0] = jnp.stack([kn, sn, ssn, htn] + [z] * 12)


@functools.partial(jax.jit, static_argnames=("C", "P", "interpret"))
def multi_agg_tiles_two(xn, vn, wn, on, xo, vo, wo, oo, sel, meta,
                        C: int, P: int, interpret: bool = True) -> jnp.ndarray:
    """Two-sided scan (clean ∥ stale ∥ diff).  R % BLOCK_R == 0, C = Cp,
    Q = Qp multiples of 128; meta rows a multiple of 8.  Out
    (R/BLOCK_R, 16, Qp) per-tile partials."""
    R = xn.shape[0]
    Qp = sel.shape[1]
    Mp = meta.shape[0]
    row = lambda r: (r, 0)
    full = lambda r: (0, 0)
    return pl.pallas_call(
        functools.partial(_multi_agg_kernel_two, C, P),
        out_shape=jax.ShapeDtypeStruct((R // BLOCK_R, N_OUT_ROWS, Qp),
                                       jnp.float32),
        grid=(R // BLOCK_R,),
        in_specs=[
            pl.BlockSpec((BLOCK_R, C), row),
            pl.BlockSpec((BLOCK_R, 1), row),
            pl.BlockSpec((BLOCK_R, 1), row),
            pl.BlockSpec((BLOCK_R, 1), row),
            pl.BlockSpec((BLOCK_R, C), row),
            pl.BlockSpec((BLOCK_R, 1), row),
            pl.BlockSpec((BLOCK_R, 1), row),
            pl.BlockSpec((BLOCK_R, 1), row),
            pl.BlockSpec(((1 + P) * C, Qp), full),
            pl.BlockSpec((Mp, Qp), full),
        ],
        out_specs=pl.BlockSpec((1, N_OUT_ROWS, Qp), lambda r: (r, 0, 0)),
        interpret=interpret,
    )(xn, vn, wn, on, xo, vo, wo, oo, sel, meta)


@functools.partial(jax.jit, static_argnames=("C", "P", "interpret"))
def multi_agg_tiles_one(xn, vn, wn, on, sel, meta,
                        C: int, P: int, interpret: bool = True) -> jnp.ndarray:
    """One-sided scan (e.g. exact batch over the full materialized view)."""
    R = xn.shape[0]
    Qp = sel.shape[1]
    Mp = meta.shape[0]
    row = lambda r: (r, 0)
    full = lambda r: (0, 0)
    return pl.pallas_call(
        functools.partial(_multi_agg_kernel_one, C, P),
        out_shape=jax.ShapeDtypeStruct((R // BLOCK_R, N_OUT_ROWS, Qp),
                                       jnp.float32),
        grid=(R // BLOCK_R,),
        in_specs=[
            pl.BlockSpec((BLOCK_R, C), row),
            pl.BlockSpec((BLOCK_R, 1), row),
            pl.BlockSpec((BLOCK_R, 1), row),
            pl.BlockSpec((BLOCK_R, 1), row),
            pl.BlockSpec(((1 + P) * C, Qp), full),
            pl.BlockSpec((Mp, Qp), full),
        ],
        out_specs=pl.BlockSpec((1, N_OUT_ROWS, Qp), lambda r: (r, 0, 0)),
        interpret=interpret,
    )(xn, vn, wn, on, sel, meta)
