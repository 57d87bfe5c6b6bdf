"""Public entry point for the fleet-wide merge remainder.

``fleet_merge(...)`` applies every view's merge remainder — upsert of
dense fused-groupby deltas into the padded stale-sample panel with
delete-cancellation — in one dispatch and returns the merged rows sorted
by group key (valid rows first, ascending; padding last), matching the
stable lexsort order ``relational.ops.compact`` gives the per-view path.

Backends (same convention as kernels/fleet_moments):

  * XLA (default off-TPU): jits the ref.py oracle plus the key sort.
  * Pallas (default on TPU, ``use_pallas=True`` elsewhere runs the
    interpreter): kernel.py computes the stale-row upsert with views on
    lanes, each row tile visiting only the group slabs its keys span;
    the O(R+G) delta-only rows and the sort are shared XLA glue inside
    the same jitted program.

Padding contract on outputs: invalid rows are key SENTINEL_KEY, values
0.0, valid False — callers may slice or re-pad without re-masking.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.platform import interpret, use_pallas as _use_pallas
from repro.obs.kprof import profiled
from repro.relational.relation import SENTINEL_KEY, next_pow2

from .kernel import BLOCK_G, BLOCK_R, BLOCK_V, fleet_merge_tiles
from .ref import delta_only_rows, fleet_merge_ref


def _pad_to(n: int, mult: int) -> int:
    return ((max(n, 1) + mult - 1) // mult) * mult


def _sort_by_key(keys, vals, valid):
    """Stable ascending sort on SENTINEL-masked keys per view.

    Valid keys are unique per view (group keys), so this reproduces the
    stable lexsort ordering of ``relational.ops.compact`` on valid rows
    and pushes all padding (SENTINEL_KEY) to the tail.
    """
    masked = jnp.where(valid, keys, SENTINEL_KEY)
    order = jnp.argsort(masked, axis=1, stable=True)
    keys = jnp.take_along_axis(masked, order, axis=1)
    vals = jnp.take_along_axis(vals, order[..., None], axis=1)
    valid = jnp.take_along_axis(valid, order, axis=1)
    return keys, vals, valid


@jax.jit
def _ref_sorted(stale_keys, stale_valid, stale_vals,
                ins_valid, ins_vals, del_valid, del_vals):
    out = fleet_merge_ref(
        stale_keys, stale_valid, stale_vals,
        ins_valid, ins_vals, del_valid, del_vals,
    )
    return _sort_by_key(*out)


@functools.partial(jax.jit, static_argnames=("gp",))
def _slab_ranges(skeys_t, gp: int):
    """Per BLOCK_R row tile of the (Rp, Vp) key panel: the first group slab
    its in-range keys can hit, and how many slabs their span covers (0
    when no key is in range) — the kernel's scalar-prefetch vectors."""
    t = skeys_t.reshape(-1, BLOCK_R * skeys_t.shape[1])
    inr = (t >= 0) & (t < gp)
    lo = jnp.min(jnp.where(inr, t, gp), axis=1) // BLOCK_G
    hi = jnp.max(jnp.where(inr, t, -1), axis=1) // BLOCK_G
    some = jnp.any(inr, axis=1)
    slab0 = jnp.where(some, lo, 0).astype(jnp.int32)
    nslab = jnp.where(some, hi - lo + 1, 0).astype(jnp.int32)
    return slab0, nslab


@functools.partial(jax.jit,
                   static_argnames=("v", "r", "g", "n_slabs", "interpret"))
def _pallas_sorted(slab0, nslab, skeys_t, svals_t, ivalid_t, ivals_t,
                   dvalid_t, dvals_t, stale_keys, stale_valid,
                   ins_valid, ins_vals, del_valid, del_vals,
                   v: int, r: int, g: int, n_slabs: int, interpret: bool):
    # upsert on the padded transposed panels: each row tile visits only
    # the group slabs its keys span
    upd = fleet_merge_tiles(
        slab0, nslab, skeys_t, svals_t, ivalid_t, ivals_t, dvalid_t, dvals_t,
        n_slabs=n_slabs, interpret=interpret,
    )
    upd_vals = jnp.transpose(upd, (2, 1, 0))[:v, :r]      # (V, R, A)
    upd_keys = jnp.where(stale_valid, stale_keys.astype(jnp.int32), SENTINEL_KEY)
    # O(R+G) tail shared with the oracle.
    only_keys, only_vals, only = delta_only_rows(
        stale_keys, stale_valid, ins_valid, ins_vals, del_valid, del_vals
    )
    keys = jnp.concatenate([upd_keys, only_keys], axis=1)
    vals = jnp.concatenate([upd_vals, only_vals], axis=1)
    valid = jnp.concatenate([stale_valid.astype(bool), only], axis=1)
    vals = jnp.where(valid[..., None], vals, 0.0)
    return _sort_by_key(keys, vals, valid)


def fleet_merge(
    stale_keys: jnp.ndarray,   # (V, R) int32 group keys
    stale_valid: jnp.ndarray,  # (V, R) bool
    stale_vals: jnp.ndarray,   # (V, R, A) f32 aggregate columns
    ins_valid: jnp.ndarray,    # (V, G) bool insert-delta group liveness
    ins_vals: jnp.ndarray,     # (V, G, A) f32 dense insert aggregates
    del_valid: jnp.ndarray | None = None,  # (V, G) bool delete-delta liveness
    del_vals: jnp.ndarray | None = None,   # (V, G, A) f32
    use_pallas: bool | None = None,
):
    """Batched merge remainder for a fleet panel.

    → ``(keys (V, R+G) i32, vals (V, R+G, A) f32, valid (V, R+G) bool)``
    sorted by key per view, padding last.  ``del_*=None`` means no
    delete side (views without ``with_deletes``).
    """
    if stale_keys.ndim != 2 or stale_vals.ndim != 3 or ins_vals.ndim != 3:
        raise ValueError("fleet_merge expects (V, R[, A]) / (V, G[, A]) panels")
    V, R = stale_keys.shape
    G = ins_valid.shape[1]
    A = stale_vals.shape[2]
    if stale_valid.shape != (V, R) or stale_vals.shape != (V, R, A):
        raise ValueError("ragged stale panel shapes")
    if ins_valid.shape != (V, G) or ins_vals.shape != (V, G, A):
        raise ValueError("ragged insert-delta panel shapes")
    if del_valid is None:
        del_valid = jnp.zeros((V, G), bool)
        del_vals = jnp.zeros((V, G, A), jnp.float32)
    if del_valid.shape != (V, G) or del_vals.shape != (V, G, A):
        raise ValueError("ragged delete-delta panel shapes")
    if V == 0 or G == 0 or A == 0:
        n = R + G
        return (
            jnp.full((V, n), SENTINEL_KEY, jnp.int32),
            jnp.zeros((V, n, A), jnp.float32),
            jnp.zeros((V, n), bool),
        )

    if not _use_pallas(use_pallas):
        return profiled(
            "fleet_merge", _ref_sorted,
            stale_keys, stale_valid, stale_vals,
            ins_valid, ins_vals, del_valid, del_vals,
            fallback=True, rows=V * R, padded=V * R,
        )

    Vp = _pad_to(V, BLOCK_V)
    Rp = _pad_to(R, BLOCK_R)
    Gp = _pad_to(G, BLOCK_G)
    sv = stale_valid.astype(bool)
    skeys = jnp.where(sv, stale_keys.astype(jnp.int32), SENTINEL_KEY)
    skeys_t = jnp.pad(skeys, ((0, Vp - V), (0, Rp - R)),
                      constant_values=SENTINEL_KEY).T          # (Rp, Vp)
    svals = jnp.where(sv[..., None], stale_vals.astype(jnp.float32), 0.0)
    svals_t = jnp.transpose(
        jnp.pad(svals, ((0, Vp - V), (0, Rp - R), (0, 0))), (2, 1, 0)
    )                                                          # (A, Rp, Vp)
    iv = ins_valid.astype(jnp.float32)
    dv = del_valid.astype(jnp.float32)
    ivalid_t = jnp.pad(iv, ((0, Vp - V), (0, Gp - G))).T       # (Gp, Vp)
    dvalid_t = jnp.pad(dv, ((0, Vp - V), (0, Gp - G))).T
    ivals_t = jnp.transpose(
        jnp.pad(ins_vals.astype(jnp.float32), ((0, Vp - V), (0, Gp - G), (0, 0))),
        (2, 1, 0),
    )                                                          # (A, Gp, Vp)
    dvals_t = jnp.transpose(
        jnp.pad(del_vals.astype(jnp.float32), ((0, Vp - V), (0, Gp - G), (0, 0))),
        (2, 1, 0),
    )
    slab0, nslab = _slab_ranges(skeys_t, Gp)
    # the slab axis is static: one host read of the widest tile span,
    # bucketed to a power of two so steady epochs reuse the compile
    n_slabs = min(next_pow2(max(int(jnp.max(nslab)), 1)), Gp // BLOCK_G)
    return profiled(
        "fleet_merge", _pallas_sorted,
        slab0, nslab, skeys_t, svals_t, ivalid_t, ivals_t, dvalid_t, dvals_t,
        stale_keys, sv, ins_valid, ins_vals, del_valid, del_vals,
        rows=V * R, padded=Vp * Rp,
        v=V, r=R, g=G, n_slabs=n_slabs, interpret=interpret(),
    )
