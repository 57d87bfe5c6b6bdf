"""jit wrapper: pad panels/tables to tile multiples and dispatch.

``multi_agg_moments`` is the op the batched query engine (repro.query)
calls for its fused single-scan moment pass.  Shapes are padded to stable
tile multiples, so a steady dashboard workload hits the jit cache instead
of retracing per query batch.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.multi_agg.kernel import BLOCK_R, LANE, multi_agg_tiles_one, multi_agg_tiles_two
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


def _pad_to(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


def _pad_side(x, valid, w, ompi, Rp, Cp):
    R, C = x.shape
    x = jnp.pad(jnp.asarray(x, jnp.float32), ((0, Rp - R), (0, Cp - C)))
    v = jnp.pad(jnp.asarray(valid, jnp.float32), (0, Rp - R))[:, None]
    w = jnp.pad(jnp.asarray(w, jnp.float32), (0, Rp - R))[:, None]
    o = jnp.pad(jnp.asarray(ompi, jnp.float32), (0, Rp - R))[:, None]
    return x, v, w, o


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
    if not _use_pallas(use_pallas):
        nrows = x_new.shape[0]
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

    R, C = x_new.shape
    Q = sel.shape[1]
    P = sel.shape[0] // C - 1
    Rp = _pad_to(max(R, BLOCK_R), BLOCK_R)
    Cp = _pad_to(C, LANE)
    Qp = _pad_to(Q, LANE)
    Mp = _pad_to(meta.shape[0], 8)

    sel3 = jnp.asarray(sel, jnp.float32).reshape(1 + P, C, Q)
    sel_p = jnp.pad(sel3, ((0, 0), (0, Cp - C), (0, Qp - Q))).reshape((1 + P) * Cp, Qp)
    meta_p = jnp.pad(jnp.asarray(meta, jnp.float32), ((0, Mp - meta.shape[0]), (0, Qp - Q)))

    xn, vn, wn, on = _pad_side(x_new, valid_new, w_new, ompi_new, Rp, Cp)
    if two:
        xo, vo, wo, oo = _pad_side(x_old, valid_old, w_old, ompi_old, Rp, Cp)
        out = profiled(
            "multi_agg", multi_agg_tiles_two,
            xn, vn, wn, on, xo, vo, wo, oo, sel_p, meta_p,
            rows=R, padded=Rp, C=Cp, P=P, interpret=interpret(),
        )
    else:
        out = profiled(
            "multi_agg", multi_agg_tiles_one,
            xn, vn, wn, on, sel_p, meta_p,
            rows=R, padded=Rp, C=Cp, P=P, interpret=interpret(),
        )
    return jnp.sum(out, axis=0)[:N_MOMENTS, :Q]
