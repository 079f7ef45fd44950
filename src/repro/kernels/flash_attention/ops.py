"""jit'd wrapper: GQA-aware flash attention over (B, S, H, hd) layouts."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import platform
from repro.kernels.flash_attention.kernel import flash_attention_pallas


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k"))
def flash_attention(q, k, v, *, causal=True, window=None, block_q=256,
                    block_k=256):
    """q: (B, S, H, hd); k, v: (B, S, Hkv, hd) with H % Hkv == 0.
    Returns (B, S, H, hd)."""
    B, S, H, hd = q.shape
    Hkv = k.shape[2]
    rep = H // Hkv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    to_bh = lambda t: t.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    out = flash_attention_pallas(
        to_bh(q), to_bh(k), to_bh(v), causal=causal, window=window,
        block_q=block_q, block_k=block_k,
        interpret=platform.interpret())
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
