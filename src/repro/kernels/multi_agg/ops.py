"""jit wrapper: one jitted program per batched moment pass.

``multi_agg_moments`` is the op the batched query engine (repro.query)
calls for its fused single-scan moment pass.  On the Pallas path each
call is ONE compiled program (``multi_agg_tiles_two`` / ``_one``): it
packs each side's (R, C) panel and its three row vectors into the kernel's
lane-dense (S, Rp) slab, turns the one-hot selectors and bounds into the
(Qp, 128) query table, runs the kernel and sums its per-tile partials.
Shapes come from the inputs alone, so a steady dashboard workload hits
the jit cache instead of retracing per query batch.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.multi_agg.kernel import (
    LANE,
    SUBLANE,
    lane_block,
    multi_agg_scan,
    pad_to,
    slab_rows,
)
from repro.kernels.multi_agg.ref import N_MOMENTS, multi_agg_ref
from repro.kernels.platform import interpret, use_pallas as _use_pallas
from repro.obs.kprof import profiled

# Pallas interpret mode walks the grid step by step and is slower than XLA
# on CPU, so off-TPU the op compiles the reference math instead — the same
# single logical pass (one-hot column select → mask → moment accumulation),
# just lowered by XLA.  Tests force the Pallas path with ``use_pallas=True``
# to check the kernel itself.

_ref_two = jax.jit(multi_agg_ref)
_ref_one = jax.jit(
    lambda x, v, w, o, sel, meta: multi_agg_ref(x, v, w, o, sel, meta)
)


def _slab(x, valid, w, ompi, S, Rp):
    """(S, Rp) f32 slab, rows on lanes: x's columns, valid, w, ompi, zeros."""
    R, C = x.shape
    rows = jnp.concatenate([
        jnp.asarray(x, jnp.float32).T,
        jnp.asarray(valid, jnp.float32)[None],
        jnp.asarray(w, jnp.float32)[None],
        jnp.asarray(ompi, jnp.float32)[None],
    ])
    return jnp.pad(rows, ((0, S - rows.shape[0]), (0, Rp - R)))


def _query_table(sel, meta, C, Qp):
    """(Qp, 128) f32: per query the column index of each selector block
    (−1 where the block selects nothing), then its meta rows."""
    Q = sel.shape[1]
    blocks = jnp.asarray(sel, jnp.float32).reshape(-1, C, Q)
    idx = jnp.where(jnp.any(blocks != 0, axis=1),
                    jnp.argmax(blocks, axis=1), -1).astype(jnp.float32)
    tab = jnp.concatenate([idx, jnp.asarray(meta, jnp.float32)]).T
    return jnp.pad(tab, ((0, Qp - Q), (0, LANE - tab.shape[1])))


def _geometry(R, C, Q, sides):
    """(S, Qp, lane tile, Rp) of a pass over an (R, C) panel."""
    S = slab_rows(C)
    Qp = pad_to(Q, SUBLANE)
    block = lane_block(S, Qp, R, sides)
    return S, Qp, block, pad_to(R, block)


def _pass(sides, sel, meta, interpret):
    R, C = sides[0][0].shape
    Q = sel.shape[1]
    P = sel.shape[0] // C - 1
    if 3 + 5 * P > LANE:
        raise ValueError(f"{P} predicate terms do not fit one query-table row")
    S, Qp, block, Rp = _geometry(R, C, Q, len(sides))
    out = multi_agg_scan([_slab(*side, S, Rp) for side in sides],
                         _query_table(sel, meta, C, Qp), C, P, block, interpret)
    return jnp.sum(out, axis=0)[:Q, :N_MOMENTS].T


@functools.partial(jax.jit, static_argnames=("interpret",))
def multi_agg_tiles_two(xn, vn, wn, on, xo, vo, wo, oo, sel, meta,
                        interpret: bool = True) -> jnp.ndarray:
    """Two-sided pass (clean ∥ stale ∥ diff) → (12, Q) f32."""
    return _pass([(xn, vn, wn, on), (xo, vo, wo, oo)], sel, meta, interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def multi_agg_tiles_one(xn, vn, wn, on, sel, meta,
                        interpret: bool = True) -> jnp.ndarray:
    """One-sided pass (e.g. exact batch over the full view) → (12, Q) f32;
    the OLD and D rows are zero."""
    return _pass([(xn, vn, wn, on)], sel, meta, interpret)


def multi_agg_moments(
    x_new: jnp.ndarray,
    valid_new: jnp.ndarray,
    w_new: jnp.ndarray,
    ompi_new: jnp.ndarray,
    sel: jnp.ndarray,
    meta: jnp.ndarray,
    x_old: Optional[jnp.ndarray] = None,
    valid_old: Optional[jnp.ndarray] = None,
    w_old: Optional[jnp.ndarray] = None,
    ompi_old: Optional[jnp.ndarray] = None,
    use_pallas: Optional[bool] = None,
) -> jnp.ndarray:
    """Fused batched-query moment pass; returns (12, Q) f32.

    x_* (R, C) f32 column panels (row-aligned when two-sided — the
    correspondence cache provides the alignment); valid_* (R,) row masks;
    w_* (R,) inverse-inclusion-probability weights; ompi_* (R,) 1−π HT
    factors; sel ((1+P)·C, Q) stacked one-hot column selectors; meta
    (2+4P, Q) op codes + predicate bounds (see repro.query.batch).
    Row layout of the result is ref.py's K/S/SS/HT_{NEW,OLD} + K/S/SS_D.
    """
    two = x_old is not None
    nrows, C = x_new.shape
    if not _use_pallas(use_pallas):
        if two:
            return profiled(
                "multi_agg", _ref_two,
                jnp.asarray(x_new, jnp.float32), jnp.asarray(valid_new, bool),
                jnp.asarray(w_new, jnp.float32), jnp.asarray(ompi_new, jnp.float32),
                sel, meta,
                jnp.asarray(x_old, jnp.float32), jnp.asarray(valid_old, bool),
                jnp.asarray(w_old, jnp.float32), jnp.asarray(ompi_old, jnp.float32),
                fallback=True, rows=nrows, padded=nrows,
            )
        return profiled(
            "multi_agg", _ref_one,
            jnp.asarray(x_new, jnp.float32), jnp.asarray(valid_new, bool),
            jnp.asarray(w_new, jnp.float32), jnp.asarray(ompi_new, jnp.float32),
            sel, meta,
            fallback=True, rows=nrows, padded=nrows,
        )
    padded = _geometry(nrows, C, sel.shape[1], 2 if two else 1)[3]
    if two:
        return profiled(
            "multi_agg", multi_agg_tiles_two,
            x_new, valid_new, w_new, ompi_new,
            x_old, valid_old, w_old, ompi_old, sel, meta,
            rows=nrows, padded=padded, interpret=interpret(),
        )
    return profiled(
        "multi_agg", multi_agg_tiles_one,
        x_new, valid_new, w_new, ompi_new, sel, meta,
        rows=nrows, padded=padded, interpret=interpret(),
    )
