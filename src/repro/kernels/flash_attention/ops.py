"""jit wrapper: GQA-aware flash attention over (B, S, H, hd) tensors."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention.kernel import BLOCK_K, BLOCK_Q, flash_tiles
from repro.kernels.platform import interpret


def flash_attention(q, k, v, causal: bool = True):
    """q (B,S,H,hd); k/v (B,T,K,hd) with H % K == 0 → (B,S,H,hd).

    KV heads are repeated to H (grouped-query attention) and the (B,H)
    pairs map onto the kernel grid.  S/T are padded to block multiples;
    padded keys are masked via ``t_valid``.
    """
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    if H != K:
        k = jnp.repeat(k, H // K, axis=2)
        v = jnp.repeat(v, H // K, axis=2)
    Sp = ((S + BLOCK_Q - 1) // BLOCK_Q) * BLOCK_Q
    Tp = ((T + BLOCK_K - 1) // BLOCK_K) * BLOCK_K

    def to_bh(x, P):
        x = jnp.pad(x, ((0, 0), (0, P - x.shape[1]), (0, 0), (0, 0)))
        return jnp.moveaxis(x, 2, 1).reshape(B * H, P, hd)

    o = flash_tiles(
        to_bh(q, Sp), to_bh(k, Tp), to_bh(v, Tp),
        sm_scale=1.0 / float(np.sqrt(hd)), causal=causal, t_valid=T,
        interpret=interpret(),
    )
    o = o.reshape(B, H, Sp, hd)[:, :, :S]
    return jnp.moveaxis(o, 1, 2)
