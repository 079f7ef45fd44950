"""jit'd wrapper: paged decode attention over block-pooled KV layouts.

Full mode attends the whole logical prefix through the block table; ring
mode (window/positions/ring_pages set) attends the sliding window
(position - window, position] through a fixed ring of `ring_pages` blocks.
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import platform
from repro.kernels.paged_attention.kernel import (
    paged_attention_pallas, paged_attention_verify_pallas)
from repro.kernels.paged_attention.ref import paged_attention_ref


@partial(jax.jit, static_argnames=("window", "ring_pages"))
def paged_attention(q, k_pool, v_pool, block_tables, seq_lens, *,
                    layer=None, window=None, positions=None, ring_pages=None,
                    k_scale=None, v_scale=None):
    """q: (B, H, hd); k_pool/v_pool: every layer's pool stacked
    (L, N, block_size, Hkv, hd) and read at ``layer`` (a traced scalar), or
    one layer's pool (N, block_size, Hkv, hd) with ``layer=None``;
    block_tables: (B, P) int32; seq_lens: (B,) int32 — valid tokens per
    sequence including the current one (0 marks an inactive slot). Ring
    mode: `window` and `ring_pages` are static, `positions` (B,) carries
    each sequence's current absolute position. k_scale/v_scale: f32 dequant
    scales shaped like the pools without hd, when the pools are int8.
    Returns (B, H, hd)."""
    return paged_attention_pallas(q, k_pool, v_pool, block_tables, seq_lens,
                                  layer=layer, window=window,
                                  positions=positions,
                                  ring_pages=ring_pages, k_scale=k_scale,
                                  v_scale=v_scale,
                                  interpret=platform.interpret())


@partial(jax.jit, static_argnames=("window", "ring_pages"))
def paged_attention_verify(q, k_pool, v_pool, block_tables, seq_lens, *,
                           layer=None, window=None, positions=None,
                           ring_pages=None, k_scale=None, v_scale=None):
    """Multi-query verify mode for speculative decoding. q: (B, K, H, hd) —
    K draft queries per sequence, all K/V already written. ``seq_lens``
    counts tokens INCLUDING the K drafts; query j attends causally up to
    position ``seq_lens - K + j``. Ring mode: ``positions = seq_lens - 1``
    and the ring sized with ``draft = K - 1`` slack. Pools, ``layer`` and
    k_scale/v_scale as in :func:`paged_attention`. Returns (B, K, H, hd)."""
    return paged_attention_verify_pallas(
        q, k_pool, v_pool, block_tables, seq_lens, layer=layer, window=window,
        positions=positions, ring_pages=ring_pages, k_scale=k_scale,
        v_scale=v_scale, interpret=platform.interpret())
