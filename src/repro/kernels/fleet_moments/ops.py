"""jit wrapper: pad the fleet panel to tile multiples and dispatch.

``fleet_moments`` is the op the planner cost model calls once per epoch:
every view's §5.2.2 moment snapshot comes out of ONE compiled call over
the stacked (V, R) channel panels instead of a per-view
``variance_comparison`` trace.  A fixed fleet keeps one stable panel
shape, so every epoch after the first hits the jit cache.

Off-TPU the op compiles the reference math (the same single reduction
pass, lowered by XLA) instead of walking the Pallas grid in interpret
mode; tests force the Pallas path with ``use_pallas=True`` to check the
kernel itself.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.fleet_moments.kernel import (
    BLOCK_R,
    BLOCK_V,
    fleet_moments_tiles,
)
from repro.kernels.fleet_moments.ref import N_MOMENTS, fleet_moments_ref
from repro.kernels.platform import interpret, use_pallas as _use_pallas
from repro.obs.kprof import profiled

_ref_jit = jax.jit(fleet_moments_ref)


def _pad_to(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


def fleet_moments(
    x_new, valid_new, w_new, ompi_new,
    x_old, valid_old, w_old, ompi_old,
    use_pallas: Optional[bool] = None,
) -> jnp.ndarray:
    """Eight (V, R) channel panels → (V, N_MOMENTS) per-view moments.

    Padding rows/views must carry all-zero channels (the fleet panel's
    contract) so they reduce to zero on every moment.
    """
    args = [jnp.asarray(a, jnp.float32) for a in (
        x_new, valid_new, w_new, ompi_new,
        x_old, valid_old, w_old, ompi_old,
    )]
    V, R = args[0].shape
    for a in args:
        if a.shape != (V, R):
            raise ValueError(f"ragged channel panel: {a.shape} != {(V, R)}")
    if V == 0:
        return jnp.zeros((0, N_MOMENTS), jnp.float32)
    if not _use_pallas(use_pallas):
        return profiled("fleet_moments", _ref_jit, *args,
                        fallback=True, rows=V, padded=V)
    # Views ride the lane axis.  A fleet narrower than one lane tile folds
    # its rows into the spare lanes — view v's row chunk f becomes lane
    # v·fold + f, and the chunks' moments are summed after the pass — so
    # padding stays bounded (unfolded, V = 2 views of 2M rows would pad
    # eight panels to 128 lanes: 8 GiB).
    fold = 1
    while 2 * V * fold <= BLOCK_V:
        fold *= 2
    Rp = _pad_to(max(R, BLOCK_R * fold), BLOCK_R * fold)
    Vf = V * fold
    Vp = _pad_to(max(Vf, BLOCK_V), BLOCK_V)
    padded = [
        jnp.pad(jnp.pad(a, ((0, 0), (0, Rp - R))).reshape(Vf, Rp // fold),
                ((0, Vp - Vf), (0, 0))).T
        for a in args
    ]
    out = profiled("fleet_moments", fleet_moments_tiles, *padded,
                   rows=Vf, padded=Vp, interpret=interpret())
    return out[:N_MOMENTS, :Vf].reshape(N_MOMENTS, V, fold).sum(axis=2).T
