"""Pallas kernel: the fleet's stale-row upsert, views on the lane axis.

The merge remainder splits into two halves.  The *upsert* half — every
stale row picks up its matching insert/delete delta group and applies
``(stale + ins) − del`` — is the matching stage and lives here: the stale
key panel arrives TRANSPOSED as ``(Rp, Vp)`` with views on lanes (the
fleet_moments layout), the dense delta panels as ``(Gp, Vp)``, and each
grid step matches one ``(BLOCK_R, BLOCK_V)`` key tile against one
``BLOCK_G`` slab of groups.  A per-lane dynamic gather does not map to
the TPU's vector unit, so the gather is computed as dense one-hot
matching: for each group row ``g`` the tile-wide mask ``keys == g``
selects the (at most one) stale row per lane that upserts that group —
the same trick kernels/fused_clean uses for its scatter.

Float order is preserved exactly: the accumulator initializes to the
stale values at the first group slab, and the single matching group adds
its insert value THEN subtracts its delete value inside one loop
iteration (non-matching iterations contribute exact ``0.0``), so the
result is ``(stale + ins) − del`` bit-for-bit.

The other half — delta-only rows (groups with no stale partner) and the
final key sort — is cheap O(R + G) work and stays in XLA inside ops.py's
single dispatch for BOTH paths.

Padding contract: invalid stale rows carry key SENTINEL_KEY (never
matches a group id) and zero values; padded group rows carry zero
liveness.  Grid: (A, Vp/BLOCK_V, Rp/BLOCK_R, n_slabs) with the slab axis
innermost — each output block is revisited only across the sequential
innermost dimension (safe accumulation).

Only the group slabs a key tile can hit are visited.  Two scalar-prefetch
vectors give, per row tile, its first slab and its slab count (ops.py
derives them from the tile's smallest and largest in-range key); the
group-slab index maps read them, so a row tile of sorted stale keys
streams the few slabs its key span covers instead of all Gp/BLOCK_G — at
200k groups the full sweep would be ~25M grid steps per dispatch.  A
tile whose keys hit no group visits nothing but its init.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_R = 256  # stale rows per tile
BLOCK_V = 128  # views (lanes) per tile
BLOCK_G = 128  # delta groups per slab


def _fleet_merge_kernel(slab0_ref, nslab_ref, skeys_ref, svals_ref,
                        ivalid_ref, ivals_ref, dvalid_ref, dvals_ref, out_ref):
    rj = pl.program_id(2)
    s = pl.program_id(3)

    @pl.when(s == 0)
    def _init():
        out_ref[...] = svals_ref[...]

    @pl.when(s < nslab_ref[rj])
    def _upsert():
        keys = skeys_ref[...]  # (BLOCK_R, BLOCK_V) int32
        g0 = (slab0_ref[rj] + s) * BLOCK_G

        def body(g, acc):
            gabs = g0 + g
            hit = (keys == gabs).astype(jnp.float32)      # (BLOCK_R, BLOCK_V)
            iv = ivalid_ref[pl.ds(g, 1), :]               # (1, BLOCK_V)
            dv = dvalid_ref[pl.ds(g, 1), :]
            ival = ivals_ref[0, pl.ds(g, 1), :]
            dval = dvals_ref[0, pl.ds(g, 1), :]
            # exact executor float order: (stale + ins) − del — the one
            # matching group applies both signs inside ONE iteration
            acc = acc + hit * (iv * ival)
            acc = acc - hit * (dv * dval)
            return acc

        out_ref[...] = jax.lax.fori_loop(0, BLOCK_G, body, out_ref[...])


@functools.partial(jax.jit, static_argnames=("n_slabs", "interpret"))
def fleet_merge_tiles(
    slab0: jnp.ndarray,   # (Rp/BLOCK_R,) int32 first group slab per row tile
    nslab: jnp.ndarray,   # (Rp/BLOCK_R,) int32 slabs per row tile (≤ n_slabs)
    skeys: jnp.ndarray,   # (Rp, Vp) int32, SENTINEL on invalid rows
    svals: jnp.ndarray,   # (A, Rp, Vp) f32, zero on invalid rows
    ivalid: jnp.ndarray,  # (Gp, Vp) f32 0/1
    ivals: jnp.ndarray,   # (A, Gp, Vp) f32
    dvalid: jnp.ndarray,  # (Gp, Vp) f32 0/1
    dvals: jnp.ndarray,   # (A, Gp, Vp) f32
    n_slabs: int,
    interpret: bool = True,
) -> jnp.ndarray:
    """→ (A, Rp, Vp) f32 upserted stale aggregate panels.  ``n_slabs`` is
    the static slab-axis extent, at least ``max(nslab)``."""
    A, Rp, Vp = svals.shape

    def slab(rj, s, slab0_ref, nslab_ref):
        # past its span a tile re-names its last slab, so the pipeline
        # fetches nothing new for the steps it skips
        return slab0_ref[rj] + jnp.minimum(s, jnp.maximum(nslab_ref[rj] - 1, 0))

    grid = (A, Vp // BLOCK_V, Rp // BLOCK_R, n_slabs)
    return pl.pallas_call(
        _fleet_merge_kernel,
        out_shape=jax.ShapeDtypeStruct((A, Rp, Vp), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((BLOCK_R, BLOCK_V),
                             lambda ai, vi, rj, s, lo, n: (rj, vi)),
                pl.BlockSpec((1, BLOCK_R, BLOCK_V),
                             lambda ai, vi, rj, s, lo, n: (ai, rj, vi)),
                pl.BlockSpec((BLOCK_G, BLOCK_V),
                             lambda ai, vi, rj, s, lo, n: (slab(rj, s, lo, n), vi)),
                pl.BlockSpec((1, BLOCK_G, BLOCK_V),
                             lambda ai, vi, rj, s, lo, n: (ai, slab(rj, s, lo, n), vi)),
                pl.BlockSpec((BLOCK_G, BLOCK_V),
                             lambda ai, vi, rj, s, lo, n: (slab(rj, s, lo, n), vi)),
                pl.BlockSpec((1, BLOCK_G, BLOCK_V),
                             lambda ai, vi, rj, s, lo, n: (ai, slab(rj, s, lo, n), vi)),
            ],
            out_specs=pl.BlockSpec(
                (1, BLOCK_R, BLOCK_V), lambda ai, vi, rj, s, lo, n: (ai, rj, vi)
            ),
        ),
        interpret=interpret,
    )(slab0, nslab, skeys, svals, ivalid, ivals, dvalid, dvals)
