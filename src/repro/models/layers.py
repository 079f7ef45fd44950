"""Shared layer primitives: norms, MLPs, embeddings, RoPE (standard + M-RoPE).

Everything is functional: `init_*` builds a params pytree, `*_apply` is pure.
Parameters are stored in bf16 (config.dtype); math runs in f32 where it
matters (norms, softmax, rope) — survey §6.3 quantization discussion applies
to gradients, not forward numerics.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def dtype_of(cfg):
    return jnp.dtype(cfg.dtype)


# ----------------------------------------------------------------- init utils
def dense_init(key, shape, dtype, scale=None):
    fan_in = shape[0] if len(shape) >= 2 else 1
    scale = scale if scale is not None else 1.0 / np.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ----------------------------------------------------------------------- norm
def init_rmsnorm(d):
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm(params, x, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * params["scale"]
    return out.astype(x.dtype)


# ------------------------------------------------------------------------ mlp
def init_swiglu(key, d, d_ff, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, (d, d_ff), dtype),
        "w_in": dense_init(k2, (d, d_ff), dtype),
        "w_out": dense_init(k3, (d_ff, d), dtype),
    }


def swiglu(params, x):
    with jax.named_scope("mlp"):
        g = jnp.einsum("...d,df->...f", x, params["w_gate"])
        h = jnp.einsum("...d,df->...f", x, params["w_in"])
        act = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * h
        return jnp.einsum("...f,fd->...d", act, params["w_out"])


# ----------------------------------------------------------------------- rope
def rope_freqs(head_dim, theta):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))


def apply_rope(x, positions, theta=10_000.0):
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(hd, theta), jnp.float32)       # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs     # (..., S, hd/2)
    angles = angles[..., None, :]                                 # (..., S, 1, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions3, theta=10_000.0, sections=(16, 24, 24)):
    """Qwen2-VL multimodal RoPE. positions3: (3, ..., S) temporal/h/w ids.

    The hd/2 frequency slots are split into 3 sections; each section uses the
    corresponding positional stream. sections must sum to hd/2.
    """
    hd = x.shape[-1]
    half = hd // 2
    secs = np.asarray(sections)
    if secs.sum() != half:  # rescale sections for reduced head dims
        secs = np.round(secs * half / secs.sum()).astype(int)
        secs[-1] = half - secs[:-1].sum()
    freqs = jnp.asarray(rope_freqs(hd, theta), jnp.float32)       # (half,)
    # pick, per frequency slot, which positional stream drives it
    sel = np.concatenate([np.full(s, i) for i, s in enumerate(secs)])
    streams = jnp.stack([positions3[i] for i in range(3)], axis=-1)  # (..., S, 3)
    pos = streams[..., sel]                                          # (..., S, half)
    angles = pos.astype(jnp.float32) * freqs                      # (..., S, half)
    angles = angles[..., None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------------ embedding
def init_embedding(key, vocab, d, dtype):
    return {"table": embed_init(key, (vocab, d), dtype)}


def embed(params, ids):
    return jnp.take(params["table"], ids, axis=0)


def unembed(params, x):
    """Tied unembedding: (..., d) @ (vocab, d)^T."""
    return jnp.einsum("...d,vd->...v", x, params["table"])
