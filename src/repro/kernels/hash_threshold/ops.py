"""jit wrapper: pad/reshape 1-D key columns to VPU tiles and dispatch."""

from __future__ import annotations

from typing import Sequence

import jax.numpy as jnp

from repro.core.hashing import seed_mix as _seed_mix
from repro.kernels.hash_threshold.kernel import BLOCK_R, LANES, hash_threshold_tiles
from repro.kernels.platform import interpret
from repro.obs.kprof import profiled


def hash_threshold(cols: Sequence[jnp.ndarray], m: float, seed: int = 0) -> jnp.ndarray:
    """η_{a,m} keep-mask over 1-D (composite) key columns."""
    n = cols[0].shape[0]
    tile = BLOCK_R * LANES
    padded = ((n + tile - 1) // tile) * tile
    rows = padded // LANES

    def pad2d(c):
        c = jnp.asarray(c)
        c = jnp.pad(c, (0, padded - n))
        return c.reshape(rows, LANES)

    cols2d = tuple(pad2d(c) for c in cols)
    out = profiled(
        "hash_threshold", hash_threshold_tiles,
        cols2d, _seed_mix(seed), float(m), n_cols=len(cols2d),
        rows=n, padded=padded, interpret=interpret(),
    )
    return out.reshape(padded)[:n].astype(bool)
