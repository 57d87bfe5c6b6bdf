"""jit wrapper: mask/pad delta rows to tile multiples and dispatch.

``fused_clean_groupby`` is the op `core/maintenance.clean_sample` dispatches
to when the cleaning plan's delta sub-aggregation has the canonical SVC
shape (group-by-sum/count over η-filtered delta rows on a dense int key).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.hashing import seed_mix as _seed_mix
from repro.kernels.fused_clean.kernel import BLOCK_G, BLOCK_R, fused_clean_tiles
from repro.kernels.platform import interpret, use_pallas as _use_pallas
from repro.obs.kprof import profiled

# Pallas interpret mode walks the grid step by step and is slower than XLA
# on CPU, so off-TPU the fused op compiles the reference math instead — the
# same single pass (hash → mask → segmented accumulation, no sort, no
# materialized filtered relation), just lowered by XLA.  Tests force the
# Pallas path with ``use_pallas=True`` to check the kernel itself.


@functools.partial(jax.jit, static_argnames=("m", "seed", "num_groups"))
def _fused_ref_path(gid, vals, valid, pin_mask, m, seed, num_groups):
    from repro.kernels.fused_clean.ref import fused_clean_ref

    return fused_clean_ref(gid, vals, valid, m, seed, num_groups, pin_mask=pin_mask)


@functools.partial(jax.jit, static_argnames=("num_groups",))
def _fleet_path(gid, vals, valid, thresh, seed_mixes, num_groups):
    from repro.core.hashing import splitmix32, u01

    V = gid.shape[0]
    h = splitmix32(seed_mixes[:, None] ^ splitmix32(gid.astype(jnp.uint32)))
    u = u01(h)
    keep = (u < thresh[:, None]) & valid
    g = jnp.where(keep, gid, num_groups)  # per-view overflow slot
    nseg = num_groups + 1
    gg = (g + nseg * jnp.arange(V, dtype=jnp.int32)[:, None]).reshape(-1)
    counts = jax.ops.segment_sum(
        keep.astype(jnp.float32).reshape(-1), gg, num_segments=V * nseg
    ).reshape(V, nseg)[:, :num_groups]
    sums = jax.ops.segment_sum(
        jnp.where(keep[:, :, None], vals, 0.0).reshape(V * gid.shape[1], -1),
        gg, num_segments=V * nseg,
    ).reshape(V, nseg, -1)[:, :num_groups, :]
    return counts, sums


def fused_clean_groupby_fleet(
    gid: jnp.ndarray,
    vals: jnp.ndarray,
    valid: jnp.ndarray,
    ms: Tuple[float, ...],
    seeds: Tuple[int, ...],
    num_groups: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One dispatch cleans a whole fleet's delta aggregations (pin-free).

    gid (V, R) int32 per-view group keys; vals (V, R, C) f32 value columns;
    valid (V, R) bool; ``ms``/``seeds`` the per-view sampling ratios and η
    seeds (the per-view seed folds exactly as in ``hash_threshold_ref``, so
    each view's slice is identical to its own ``fused_clean_groupby`` call).
    Returns (counts (V, G), sums (V, G, C)).  One batched segment pass —
    the offset-segment trick keeps V views in a single accumulator — lowers
    through XLA on every backend; the per-view Pallas kernel remains the
    single-view fast path.
    """
    thresh = jnp.asarray([float(m) for m in ms], jnp.float32)
    mixes = jnp.asarray([_seed_mix(int(s)) for s in seeds], jnp.uint32)
    V, R = gid.shape[0], gid.shape[1]
    return profiled(
        "fused_clean_fleet", _fleet_path,
        jnp.asarray(gid, jnp.int32), jnp.asarray(vals, jnp.float32),
        jnp.asarray(valid, bool), thresh, mixes, int(num_groups),
        rows=V * R, padded=V * R,
    )


def fused_clean_groupby(
    gid: jnp.ndarray,
    vals: jnp.ndarray,
    valid: jnp.ndarray,
    m: float,
    seed: int,
    num_groups: int,
    pin_mask: Optional[jnp.ndarray] = None,
    use_pallas: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused η_{gid,m} filter + per-group count/sum in one kernel pass.

    gid (R,) int32 group keys (must be < num_groups for rows that should
    land; others drop like segment_sum); vals (R, C) value columns; valid
    (R,) row mask; pin_mask (R,) optional outlier-pin membership (kept with
    weight 1 regardless of hash).  Returns (counts (G,), sums (G, C)).
    """
    squeeze = vals.ndim == 1
    if not _use_pallas(use_pallas):
        if squeeze:
            vals = vals[:, None]
        counts, sums = profiled(
            "fused_clean", _fused_ref_path,
            jnp.asarray(gid, jnp.int32), jnp.asarray(vals, jnp.float32),
            jnp.asarray(valid, bool),
            None if pin_mask is None else jnp.asarray(pin_mask, bool),
            float(m), int(seed), int(num_groups),
            fallback=True, rows=vals.shape[0], padded=vals.shape[0],
        )
        return counts, (sums[:, 0] if squeeze else sums)
    if squeeze:
        vals = vals[:, None]
    R, C = vals.shape
    Rp = ((R + BLOCK_R - 1) // BLOCK_R) * BLOCK_R
    Gp = ((num_groups + BLOCK_G - 1) // BLOCK_G) * BLOCK_G

    gid_m = jnp.where(jnp.asarray(valid, bool), jnp.asarray(gid, jnp.int32), -1)
    gid_p = jnp.pad(gid_m, (0, Rp - R), constant_values=-1)[:, None]
    if pin_mask is None:
        pin_p = jnp.zeros((Rp, 1), jnp.int8)
    else:
        pin_p = jnp.pad(jnp.asarray(pin_mask, jnp.int8), (0, Rp - R))[:, None]
    ones = jnp.ones((R, 1), jnp.float32)
    vals_ext = jnp.concatenate([ones, jnp.asarray(vals, jnp.float32)], axis=1)
    vals_p = jnp.pad(vals_ext, ((0, Rp - R), (0, 0)))

    out = profiled(
        "fused_clean", fused_clean_tiles,
        gid_p, pin_p, vals_p, seed_mix=_seed_mix(seed), thresh=float(m),
        num_groups=Gp, rows=R, padded=Rp, interpret=interpret(),
    )
    out = out[:num_groups]
    counts, sums = out[:, 0], out[:, 1:]
    return counts, (sums[:, 0] if squeeze else sums)
