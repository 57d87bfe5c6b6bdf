"""The SVC hashing operator η_{a,m} (§4.4).

Deterministic uniform hashing of (composite) primary keys to [0,1); rows with
h(a) ≤ m form the sample.  Determinism is what yields the Correspondence
property (§4.6, Prop. 2): hashing the same key in the stale and the
up-to-date view makes the two samples correspond, for free.

The paper uses MD5/SHA1 on a CPU and argues any near-uniform hash suffices
(SUHA, §12.3).  On TPU we use the splitmix32/64 finalizer family — integer
avalanche mixing that vectorizes on the VPU.  The hot path is implemented as
a Pallas kernel (repro/kernels/hash_threshold); this module provides the
reference jnp implementation and the dispatch switch.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax.numpy as jnp
import numpy as np

# Toggled by repro.kernels at import time if the Pallas path is requested.
_USE_PALLAS = False

# Golden-ratio seed-fold constant shared by every η kernel and oracle.  The
# kernels import ``seed_mix``/``splitmix32`` from here so the
# bit-identical-hash invariant behind Prop. 2 is structural, not copied.
SEED_GAMMA = 0x9E3779B9

# Seeds of the two independent splitmix32 folds that form the 64-bit
# membership digest (key_digest below; kernels/outlier_member).
DIGEST_SEED_HI = 0x0D1D
DIGEST_SEED_LO = 0x10CA


def use_pallas(flag: bool) -> None:
    global _USE_PALLAS
    _USE_PALLAS = flag


def seed_mix(seed: int) -> int:
    """Fold a user seed into the mixer's initial state (Python int; baked
    into kernels at trace time — the seed is plan-static in SVC)."""
    return (SEED_GAMMA * (int(seed) + 1)) & 0xFFFFFFFF


def splitmix32(x: jnp.ndarray) -> jnp.ndarray:
    """32-bit avalanche finalizer (uint32 in, uint32 out)."""
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def u01(h: jnp.ndarray) -> jnp.ndarray:
    """uint32 hash → float32 in [0,1) (~2^-24 resolution).

    Mosaic, the TPU kernel compiler, has no uint32 → float32 cast, so each
    16-bit half converts exactly through int32 and one float32 add rounds
    the exact value to nearest-even: the same bits as XLA's convert.  The
    Pallas kernels and ``hash_u01`` share this function, so every η
    decision is bit-identical across paths (Prop. 2)."""
    h = h.astype(jnp.uint32)
    hi = (h >> 16).astype(jnp.int32).astype(jnp.float32)
    lo = (h & jnp.uint32(0xFFFF)).astype(jnp.int32).astype(jnp.float32)
    return (hi * jnp.float32(65536.0) + lo) * jnp.float32(1.0 / 4294967296.0)


def hash_columns(cols: Sequence[jnp.ndarray], seed: int = 0) -> jnp.ndarray:
    """Mix (composite) key columns into one uint32 hash per row."""
    h = jnp.full(cols[0].shape, np.uint32(seed_mix(seed)), jnp.uint32)
    for c in cols:
        h = splitmix32(h ^ splitmix32(c.astype(jnp.uint32)))
    return h


def key_digest(cols: Sequence[jnp.ndarray], seed: int = 0) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """64-bit composite-key digest as two uint32 lanes (hi, lo).

    Two independently seeded splitmix32 folds of the same key tuple — a
    64-bit identity for multi-column keys that stays in 32-bit arrays (jax
    x64 is disabled).  Collision probability for an N-row probe against a
    K-entry index is ~N·K/2^64; kernels/outlier_member answers membership
    on this digest instead of comparing every key column pairwise.
    """
    return (
        hash_columns(cols, DIGEST_SEED_HI + seed),
        hash_columns(cols, DIGEST_SEED_LO + seed),
    )


def hash_u01(cols: Sequence[jnp.ndarray], seed: int = 0) -> jnp.ndarray:
    """Uniform [0,1) value per row (float32; ~2^-24 resolution)."""
    return u01(hash_columns(cols, seed))


def hash_threshold_mask(
    cols: Sequence[jnp.ndarray], m: float, seed: int = 0
) -> jnp.ndarray:
    """η_{a,m}: boolean keep-mask, True where h(a) ≤ m."""
    if _USE_PALLAS:
        from repro.kernels.hash_threshold import ops as _k

        return _k.hash_threshold(tuple(cols), float(m), int(seed))
    return hash_u01(cols, seed) < jnp.float32(m)


def hash_threshold_mask_ref(cols: Sequence[jnp.ndarray], m: float, seed: int = 0):
    """Pure-jnp oracle (never dispatches to Pallas)."""
    return hash_u01(cols, seed) < jnp.float32(m)


def apply_hash(rel, cols: Tuple[str, ...], m: float, seed: int = 0, pin=None):
    """Apply η to a Relation: narrow validity to the hash sample.

    ``pin`` (a Relation of key values, or None) pins outlier-index rows into
    the sample with weight 1 (flagged in ``__outlier``; Def. 5 / §6.2).  The
    pinned form is one fused scan (η ∨ digest membership, flag, validity) via
    kernels/outlier_member — see outliers.apply_hash_with_outliers.
    """
    if pin is None:
        arrays = [rel.columns[c] for c in cols]
        mask = hash_threshold_mask(arrays, m, seed)
        return rel.replace(valid=rel.valid & mask)

    from repro.core.outliers import apply_hash_with_outliers
    from repro.relational.relation import SENTINEL_KEY

    pin_keys = tuple(
        jnp.where(pin.valid, pin.col(c), jnp.asarray(SENTINEL_KEY, pin.col(c).dtype))
        for c in pin.schema.pk
    )
    return apply_hash_with_outliers(rel, cols, m, seed, pin_keys)
