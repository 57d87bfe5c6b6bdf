"""jit wrapper: digest the key table, pad tiles, and dispatch.

``fused_hash_member`` is the op core/outliers dispatches to for the §6.2
sample predicate (η ∨ outlier membership + ``__outlier`` flag) and
``outlier_member`` is the membership-only probe behind
``member_keys``/``flag_outliers`` for multi-column keys.

Off-TPU the op compiles the sorted-digest binary search instead of running
the Pallas body in interpret mode: key digests are lexsorted once per call
(K log K, K = index capacity ≪ N) and every probe row then resolves in
log₂ K branchless descent steps — O(N log K) instead of the seed's O(N·K)
unrolled loop.  Tests force the Pallas path with ``use_pallas=True`` to
check the kernel itself.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.hashing import (
    DIGEST_SEED_HI,
    DIGEST_SEED_LO,
    hash_u01,
    key_digest,
    seed_mix,
)
from repro.kernels.outlier_member.kernel import (
    BLOCK_R,
    KEY_ROWS,
    LANE,
    outlier_member_tiles,
)
from repro.kernels.platform import interpret as _interpret
from repro.kernels.platform import use_pallas as _use_pallas
from repro.obs.kprof import profiled
from repro.relational.relation import SENTINEL_KEY, next_pow2

# Largest key table the kernel keeps resident in VMEM ((BLOCK_R, Kp) f32
# match tile ≈ 2 MiB at the cap); larger indices take the XLA binary-search
# path, which is the better asymptotic shape there anyway.
MAX_KERNEL_KEYS = 2048


def _sorted_digests(key_cols: Sequence[jnp.ndarray]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Lexsorted (hi, lo) digest lanes of the index key tuples."""
    hi, lo = key_digest(key_cols)
    order = jnp.lexsort((lo, hi))
    return hi[order], lo[order]


def _bsearch_member(phi, plo, shi, slo) -> jnp.ndarray:
    """probe digest ∈ sorted digests, branchless log₂ K descent.

    Finds the last index whose (hi, lo) pair is lexicographically ≤ the
    probe digest — the predicate is monotone along the sorted table, so a
    power-of-two descent needs no data-dependent control flow (jit-safe).
    """
    K = shi.shape[0]
    Kp = next_pow2(max(K, 2))
    if Kp != K:  # pad with the max digest: ≥ everything, never descended into
        shi = jnp.pad(shi, (0, Kp - K), constant_values=jnp.uint32(0xFFFFFFFF))
        slo = jnp.pad(slo, (0, Kp - K), constant_values=jnp.uint32(0xFFFFFFFF))
    pos = jnp.full(phi.shape, -1, jnp.int32)
    step = Kp  # step sizes Kp, Kp/2, …, 1 reach every index up to Kp−1
    while step >= 1:
        cand = pos + step
        safe_c = jnp.minimum(cand, Kp - 1)
        chi, clo = shi[safe_c], slo[safe_c]
        le = (cand < Kp) & ((chi < phi) | ((chi == phi) & (clo <= plo)))
        pos = jnp.where(le, cand, pos)
        step //= 2
    safe = jnp.clip(pos, 0, Kp - 1)
    return (pos >= 0) & (shi[safe] == phi) & (slo[safe] == plo)


@functools.partial(jax.jit, static_argnames=("m", "seed", "with_eta"))
def _fused_xla(cols, key_cols, m: float, seed: int, with_eta: bool):
    shi, slo = _sorted_digests(key_cols)
    phi, plo = key_digest(cols)
    member = _bsearch_member(phi, plo, shi, slo) & (cols[0] != SENTINEL_KEY)
    if not with_eta:
        return member, member
    keep = (hash_u01(cols, seed) < jnp.float32(m)) | member
    return keep, member


def _fused_pallas(cols, key_cols, m: float, seed: int):
    R = cols[0].shape[0]
    C = len(cols)
    Rp = max(BLOCK_R, ((R + BLOCK_R - 1) // BLOCK_R) * BLOCK_R)
    panel = jnp.stack(
        [jnp.pad(jnp.asarray(c, jnp.int32), (0, Rp - R),
                 constant_values=jnp.int32(SENTINEL_KEY)) for c in cols],
        axis=1,
    )
    K = key_cols[0].shape[0]
    Kp = max(LANE, ((K + LANE - 1) // LANE) * LANE)
    kcols = tuple(
        jnp.pad(jnp.asarray(c, jnp.int32), (0, Kp - K),
                constant_values=jnp.int32(SENTINEL_KEY))
        for c in key_cols
    )
    khi, klo = key_digest(kcols)
    keys = jnp.zeros((KEY_ROWS, Kp), jnp.uint32).at[0].set(khi).at[1].set(klo)
    code = profiled(
        "outlier_member", outlier_member_tiles,
        panel, keys,
        seed_eta=seed_mix(seed),
        seed_hi=seed_mix(DIGEST_SEED_HI),
        seed_lo=seed_mix(DIGEST_SEED_LO),
        thresh=float(m),
        rows=R, padded=Rp,
        interpret=_interpret(),
    )[:R, 0]
    return (code & 1) > 0, (code & 2) > 0


def fused_hash_member(
    cols: Sequence[jnp.ndarray],
    m: float,
    seed: int,
    key_cols: Sequence[jnp.ndarray],
    use_pallas: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(η ∨ membership, membership) in one fused pass.

    cols: 1-D composite key columns of the probe rows (sentinel marks
    invalid); key_cols: index key columns, same arity, sentinel-masked.
    Returns two (R,) bool masks: keep = hash ≤ m ∨ member, and member (the
    ``__outlier`` flag source).  Membership of the padded / sentinel key
    slots can only fire on a 64-bit digest collision.
    """
    cols = tuple(jnp.asarray(c) for c in cols)
    key_cols = tuple(jnp.asarray(c) for c in key_cols)
    if _use_pallas(use_pallas) and key_cols[0].shape[0] <= MAX_KERNEL_KEYS:
        return _fused_pallas(cols, key_cols, m, seed)
    R = cols[0].shape[0]
    return profiled("outlier_member", _fused_xla,
                    cols, key_cols, float(m), int(seed), True,
                    fallback=True, rows=R, padded=R)


def outlier_member(
    probe_cols: Sequence[jnp.ndarray],
    key_cols: Sequence[jnp.ndarray],
    use_pallas: Optional[bool] = None,
) -> jnp.ndarray:
    """Membership-only probe: probe tuple ∈ key tuples (digest identity)."""
    probe_cols = tuple(jnp.asarray(c) for c in probe_cols)
    key_cols = tuple(jnp.asarray(c) for c in key_cols)
    if _use_pallas(use_pallas) and key_cols[0].shape[0] <= MAX_KERNEL_KEYS:
        return _fused_pallas(probe_cols, key_cols, 0.0, 0)[1]
    R = probe_cols[0].shape[0]
    return profiled("outlier_member", _fused_xla,
                    probe_cols, key_cols, 0.0, 0, False,
                    fallback=True, rows=R, padded=R)[1]
