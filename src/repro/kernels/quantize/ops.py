"""jit'd wrappers: flat-gradient <-> (int8 blocks, scales), plus the KV-cache
quantization primitives used by the paged serving pools."""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import platform
from repro.kernels.quantize.kernel import quantize_pallas


@partial(jax.jit, static_argnames=("bits", "block", "mode"))
def quantize_blocks(flat, key=None, *, bits=8, block=256, mode="stochastic"):
    """flat: (n,) f32 gradient; returns (q (rows, block) int8, scales (rows,),
    n) — padded to a block multiple. mode="nearest" is deterministic (no key
    needed); "stochastic" keeps E[dequant(quant(g))] = g for gradients."""
    n = flat.shape[0]
    pad = (-n) % block
    x = jnp.pad(flat.astype(jnp.float32), (0, pad)).reshape(-1, block)
    rows = x.shape[0]
    block_rows = 256
    while rows % block_rows:           # largest power-of-two divisor ≤ 256
        block_rows //= 2
    if mode == "nearest":
        noise = None
    else:
        if key is None:
            raise ValueError("stochastic mode needs a PRNG key")
        noise = jax.random.uniform(key, x.shape)
    q, s = quantize_pallas(x, noise, bits=bits, block_rows=block_rows,
                           mode=mode, interpret=platform.interpret())
    return q, s


@partial(jax.jit, static_argnames=("n",))
def dequantize_blocks(q, scales, n=None):
    flat = (q.astype(jnp.float32) * scales[:, None]).reshape(-1)
    return flat if n is None else flat[:n]


@dataclasses.dataclass(frozen=True)
class KVQuantConfig:
    """Paged-KV pool quantization: int8 values + one f32 scale per
    (block-slot, kv-head) vector over head_dim. Hashable so it can live in
    frozen engine/provider configs and jit compile keys."""
    bits: int = 8

    def __post_init__(self):
        if self.bits != 8:
            raise ValueError(f"only int8 KV quantization supported, got bits={self.bits}")


def quantize_kv(x, *, bits=8):
    """x: (..., hd) f32 K or V vectors. Returns (q int8 same shape, scale f32
    (...,)) with one scale per vector — nearest-even rounding so every write
    path (prefill chunk, decode token, verify drafts, dense reference) stores
    bit-identical values for the same input vector."""
    maxq = float(2 ** (bits - 1) - 1)
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(amax == 0.0, 1.0, amax / maxq)
    q = jnp.round(xf / scale[..., None])
    return jnp.clip(q, -maxq - 1, maxq).astype(jnp.int8), scale


def dequantize_kv(q, scale):
    """Inverse of quantize_kv (up to rounding): (..., hd) int8 × (...,) f32."""
    return q.astype(jnp.float32) * scale[..., None]
