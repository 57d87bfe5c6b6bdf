"""jit wrapper: pad/reshape 1-D diff inputs and reduce the accumulator."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.corr_diff.kernel import BLOCK_R, LANES, corr_diff_tiles
from repro.kernels.platform import interpret
from repro.obs.kprof import profiled


def corr_moments(t_new: jnp.ndarray, t_old: jnp.ndarray, mask: jnp.ndarray):
    """Fused (Σd, Σd², count) for d = (t_new − t_old)·mask over 1-D inputs."""
    n = t_new.shape[0]
    tile = BLOCK_R * LANES
    padded = ((n + tile - 1) // tile) * tile
    rows = padded // LANES

    def pad2d(x, dtype):
        x = jnp.asarray(x, dtype)
        return jnp.pad(x, (0, padded - n)).reshape(rows, LANES)

    acc = profiled(
        "corr_diff", corr_diff_tiles,
        pad2d(t_new, jnp.float32),
        pad2d(t_old, jnp.float32),
        pad2d(mask.astype(jnp.int8), jnp.int8),
        rows=n, padded=padded, interpret=interpret(),
    )
    return acc[0, 0], acc[0, 1], acc[0, 2]
