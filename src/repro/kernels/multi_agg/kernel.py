"""Pallas kernel: one pass over sample rows answers Q queries at once.

The SVC query hot loop evaluates, per query, a predicate mask and a
§5.2.1 trans table over the clean sample, the stale sample, and their
correspondence diff, then reduces each to a handful of moments.  Answered
one query at a time that is ~4Q scans of the same rows (AQP trans, CORR
trans × 2 sides, break-even check).  This kernel tiles the
correspondence-aligned row panel ONCE and accumulates, for all Q queries
simultaneously, every moment the estimators need.

Layout: lane-dense.  Each panel side arrives as one (S, Rp) f32 slab with
ROWS ON LANES — sublanes 0..C-1 the value columns, then the row mask,
the 1/π weights and the 1−π factors, zero rows up to S = pad8(C + 3) —
and the queries sit on SUBLANES: every per-query trans table is a
(Qp, BLOCK) array, Qp = pad8(Q).  A 4-column panel is an 8-sublane slab,
so the scan reads its logical bytes and nothing padded to 128 lanes.  Per
tile:

  1. select each query's value/predicate column with a VPU select by the
     query's column index (exact f32: no MXU pass rounds a predicate
     operand across a range bound);
  2. apply the encoded interval bounds (ge/gt/le/lt per term; ±inf for
     unused sides) and the sum/count/avg op codes to form t and the row
     mask per query;
  3. reduce along lanes to per-query moments: counts, Σt, Σt², Σ(1−π)t²
     per side plus Σd, Σd² and the pin-aware Σ min(1−π_new, 1−π_old)·d²
     (HT_D, §6.3) for d = t_new−t_old.

Each grid step writes its own (Qp, 128) partial block, moment m in lane
m; the jitted pass in ops.py sums the partials in the same program.
Accumulating hundreds of row tiles into one f32 block in grid order
would cost ~n·2⁻²⁴ relative error at 2M rows — wider than a CORR
interval whose diff is small, where the exact side of the estimate must
be exact to float rounding.

Lane tile (``lane_block``), reckoned for a v5e core (128 MiB of VMEM, a
16 MiB default scoped limit): per grid step the pipeline holds each
side's (S, BLOCK) input twice (double buffering), and the body keeps
about six (Qp, BLOCK) f32 tables live per side.  The tile is the largest
power of two up to MAX_BLOCK whose footprint
``4·BLOCK·(2·sides·S + 6·sides·Qp)`` stays within VMEM_BUDGET (8 MiB,
half the scoped limit): S = 8, Qp = 8 two-sided gives 512 B a lane, so
BLOCK = 8192 and a 2^21-row panel takes 256 grid steps.  Wide batches
(Q = 130) shrink the tile instead of spilling.

Shapes: slabs (S, Rp) f32 with Rp % BLOCK == 0; qtab (Qp, 128) f32 — per
query its 1 + P column indices (−1 for none) then the (2 + 4P) meta rows
of ref.py; out (Rp/BLOCK, Qp, 128) f32 with ref.py's moment order in
lanes 0..11.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.multi_agg.ref import META_IS_AVG, META_IS_COUNT, META_PER_PRED, META_PRED0

LANE = 128
SUBLANE = 8
N_ROW_VECS = 3  # valid, w, ompi under the value columns of a slab
MAX_BLOCK = 8192
VMEM_BUDGET = 8 << 20
TABLES_PER_SIDE = 6


def pad_to(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


def slab_rows(C: int) -> int:
    """S: sublanes of one side's slab for a C-column panel."""
    return pad_to(C + N_ROW_VECS, SUBLANE)


def lane_block(S: int, Qp: int, R: int, sides: int) -> int:
    """Rows (lanes) per grid step — see the module docstring's reckoning."""
    per_lane = 4 * (2 * sides * S + TABLES_PER_SIDE * sides * Qp)
    block = LANE
    while (2 * block <= MAX_BLOCK and 2 * block * per_lane <= VMEM_BUDGET
           and block < R):
        block *= 2
    return block


def _pick(x, k, C):
    """(Qp, BLOCK) values of column k[q] (−1: none → 0) for every query."""
    out = jnp.zeros((k.shape[0], x.shape[1]), jnp.float32)
    for c in range(C):
        out = jnp.where(k == c, x[c:c + 1, :], out)
    return out


def _tile_trans(x, qt, C, P):
    """(Qp, BLOCK) trans table t and f32 row mask for one slab tile."""
    m0 = 1 + P  # meta row j of ref.py sits in qtab column m0 + j
    col = lambda j: qt[:, m0 + j:m0 + j + 1]
    valid = x[C:C + 1, :] > 0
    is_count = col(META_IS_COUNT) > 0
    is_avg = col(META_IS_AVG) > 0
    v = jnp.where(is_count, 1.0, _pick(x, qt[:, 0:1], C))
    cond = jnp.broadcast_to(valid, v.shape)
    for p in range(P):
        tv = _pick(x, qt[:, 1 + p:2 + p], C)
        b0 = META_PRED0 + META_PER_PRED * p
        cond = (cond
                & (tv >= col(b0)) & (tv > col(b0 + 1))
                & (tv <= col(b0 + 2)) & (tv < col(b0 + 3)))
    w_eff = jnp.where(is_avg, 1.0, x[C + 1:C + 2, :])
    t = jnp.where(cond, v, 0.0) * w_eff
    rowmask = jnp.where(is_avg, cond.astype(jnp.float32),
                        valid.astype(jnp.float32))
    return t, rowmask


def _lane_sum(a):
    return jnp.sum(a, axis=1, keepdims=True)


def _side_moments(t, rowmask, ompi):
    return [_lane_sum(rowmask), _lane_sum(t), _lane_sum(t * t),
            _lane_sum(ompi * t * t)]


def _moment_block(moments, Qp):
    """(Qp, 128) block with moment m — a (Qp, 1) or (1, 1) column — in lane m."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (Qp, LANE), 1)
    out = jnp.zeros((Qp, LANE), jnp.float32)
    for m, val in enumerate(moments):
        out = jnp.where(lane == m, val, out)
    return out


def _multi_agg_kernel_two(C, P, n_ref, o_ref, q_ref, out_ref):
    qt = q_ref[...]
    xn, xo = n_ref[...], o_ref[...]
    tn, mn = _tile_trans(xn, qt, C, P)
    to, mo = _tile_trans(xo, qt, C, P)
    on, oo = xn[C + 2:C + 3, :], xo[C + 2:C + 3, :]
    d = tn - to
    joined = ((xn[C:C + 1, :] > 0) | (xo[C:C + 1, :] > 0)).astype(jnp.float32)
    # §6.3: rows pinned on either side (ompi = 0) have an exact diff —
    # their 1−π factor for the CORR HT term is the per-side minimum
    od = jnp.minimum(on, oo)
    moments = (_side_moments(tn, mn, on) + _side_moments(to, mo, oo)
               + [_lane_sum(joined), _lane_sum(d), _lane_sum(d * d),
                  _lane_sum(od * d * d)])
    out_ref[0] = _moment_block(moments, qt.shape[0])


def _multi_agg_kernel_one(C, P, n_ref, q_ref, out_ref):
    qt = q_ref[...]
    xn = n_ref[...]
    tn, mn = _tile_trans(xn, qt, C, P)
    out_ref[0] = _moment_block(_side_moments(tn, mn, xn[C + 2:C + 3, :]),
                               qt.shape[0])


def multi_agg_scan(slabs, qtab, C: int, P: int, block: int,
                   interpret: bool) -> jnp.ndarray:
    """One or two (S, Rp) slabs and the (Qp, 128) query table →
    (Rp/block, Qp, 128) per-tile moment partials."""
    S, Rp = slabs[0].shape
    Qp = qtab.shape[0]
    body = _multi_agg_kernel_two if len(slabs) == 2 else _multi_agg_kernel_one
    tile = pl.BlockSpec((S, block), lambda r: (0, r))
    return pl.pallas_call(
        functools.partial(body, C, P),
        out_shape=jax.ShapeDtypeStruct((Rp // block, Qp, LANE), jnp.float32),
        grid=(Rp // block,),
        in_specs=[tile] * len(slabs) + [pl.BlockSpec((Qp, LANE), lambda r: (0, 0))],
        out_specs=pl.BlockSpec((1, Qp, LANE), lambda r: (r, 0, 0)),
        interpret=interpret,
    )(*slabs, qtab)
