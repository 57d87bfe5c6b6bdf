"""jit wrapper: pad the fleet panel to tile multiples and dispatch.

``fleet_scores`` is the op the budgeted scheduler (repro.planner) calls
once per epoch: the whole fleet's action scores come out of ONE jitted
call over the stacked feature matrix — no per-view Python loop.  A fixed
fleet keeps one stable (V, N_FEATURES) shape, so every epoch after the
first hits the jit cache.

Off-TPU the op compiles the reference math (the same one-pass elementwise
decision, lowered by XLA) instead of walking the Pallas grid in interpret
mode; tests force the Pallas path with ``use_pallas=True`` to check the
kernel itself.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.fleet_score.kernel import BLOCK_V, FEAT_ROWS, fleet_score_tiles
from repro.kernels.fleet_score.ref import N_FEATURES, N_SCORES, fleet_score_ref
from repro.kernels.platform import interpret, use_pallas as _use_pallas
from repro.obs.kprof import profiled

_ref_jit = jax.jit(fleet_score_ref)


def fleet_scores(features, use_pallas: Optional[bool] = None) -> jnp.ndarray:
    """(V, N_FEATURES) per-view features → (V, N_SCORES) action scores.

    Padded lanes carry all-zero features, which score 0 on every action
    (no spurious NaN from the guarded divisors) and are sliced off.
    """
    feats = jnp.asarray(features, jnp.float32)
    if feats.ndim != 2 or feats.shape[1] != N_FEATURES:
        raise ValueError(f"expected (V, {N_FEATURES}) features, got {feats.shape}")
    V = feats.shape[0]
    if not _use_pallas(use_pallas):
        return profiled("fleet_score", _ref_jit, feats,
                        fallback=True, rows=V, padded=V)
    Vp = max(BLOCK_V, ((V + BLOCK_V - 1) // BLOCK_V) * BLOCK_V)
    panel = jnp.pad(feats, ((0, Vp - V), (0, FEAT_ROWS - N_FEATURES))).T
    out = profiled("fleet_score", fleet_score_tiles, panel,
                   rows=V, padded=Vp, interpret=interpret())
    return out[:N_SCORES, :V].T


_sharded_cache = {}


def _make_sharded_score(mesh, axis: str):
    """One shard_map program: each shard scores ITS (1, Vmax, F) slice
    locally, then one all_gather closes the global (S, Vmax, N_SCORES)
    panel — the only cross-shard traffic is the scored decision panel,
    never the raw features' provenance (rows stay put, §7.5)."""
    from jax.sharding import PartitionSpec as P

    from repro.compat import shard_map

    def per_shard(feats):  # (1, Vmax, F) local slice
        scores = fleet_score_ref(feats[0])
        return jax.lax.all_gather(scores, axis)

    return jax.jit(shard_map(
        per_shard, mesh,
        in_specs=(P(axis),),
        out_specs=P(),
    ))


def fleet_scores_sharded(stacked, mesh=None, axis: str = "data",
                         shard_views=None) -> jnp.ndarray:
    """(S, Vmax, N_FEATURES) per-shard feature panels → (S, Vmax, N_SCORES).

    With a mesh whose ``axis`` size equals S, each device scores its own
    shard's panel in place and a single all_gather returns the global
    score panel to every shard (the psum-closed planner input).  Without a
    mesh (host fallback — e.g. a single-device test process) the same
    math runs as one vmapped reference call; ``fleet_score_ref`` is
    elementwise per view, so both paths are bit-equal.

    ``shard_views`` (optional per-shard real view counts) feeds the
    profiler's per-shard occupancy ledger; padded lanes carry all-zero
    features and score 0.
    """
    feats = jnp.asarray(stacked, jnp.float32)
    if feats.ndim != 3 or feats.shape[2] != N_FEATURES:
        raise ValueError(
            f"expected (S, Vmax, {N_FEATURES}) stacked features, got "
            f"{feats.shape}")
    S, Vmax = feats.shape[0], feats.shape[1]
    rows = [int(v) for v in shard_views] if shard_views is not None \
        else [Vmax] * S
    prof = dict(shards=list(range(S)), shard_rows=rows,
                shard_padded=[Vmax] * S,
                rows=sum(rows), padded=S * Vmax)
    if mesh is not None and mesh.shape.get(axis, 1) == S and S > 1:
        key = (id(mesh), axis)
        fn = _sharded_cache.get(key)
        if fn is None:
            fn = _sharded_cache[key] = _make_sharded_score(mesh, axis)
        return profiled("fleet_score_sharded", fn, feats, **prof)
    return profiled("fleet_score_sharded", _sharded_ref_jit, feats,
                    fallback=True, **prof)


_sharded_ref_jit = jax.jit(jax.vmap(fleet_score_ref))
