"""jit wrapper: pad rows/groups to tile multiples and dispatch."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.segment_aggsum.kernel import BLOCK_G, BLOCK_R, segment_sum_tiles
from repro.kernels.platform import interpret
from repro.obs.kprof import profiled


def segment_sum(gid: jnp.ndarray, vals: jnp.ndarray, num_groups: int) -> jnp.ndarray:
    """Segment sum: out[g, c] = Σ_{i: gid[i]==g} vals[i, c].

    Out-of-range gids (e.g. the group-by overflow slot) are dropped, matching
    jax.ops.segment_sum semantics.
    """
    squeeze = vals.ndim == 1
    if squeeze:
        vals = vals[:, None]
    R, C = vals.shape
    Rp = ((R + BLOCK_R - 1) // BLOCK_R) * BLOCK_R
    Gp = ((num_groups + BLOCK_G - 1) // BLOCK_G) * BLOCK_G
    gid_p = jnp.pad(jnp.asarray(gid, jnp.int32), (0, Rp - R), constant_values=-1)[:, None]
    vals_p = jnp.pad(jnp.asarray(vals, jnp.float32), ((0, Rp - R), (0, 0)))
    out = profiled(
        "segment_aggsum", segment_sum_tiles, gid_p, vals_p,
        rows=R, padded=Rp, num_groups=Gp, interpret=interpret(),
    )
    out = out[:num_groups]
    return out[:, 0] if squeeze else out
