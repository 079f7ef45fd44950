"""GQA attention: full / sliding-window / local-global, training and decode.

Two XLA execution strategies (the Pallas flash kernel in repro.kernels is the
TPU-native third):

* ``naive``   — materialize (S, S) scores; fine for smoke tests.
* ``chunked`` — lax.scan over query chunks with online softmax
  (flash-attention recurrence in pure jnp); bounds activation memory to
  O(chunk · S) per head and is the oracle for the Pallas kernel.

Decode: one query token against a KV cache laid out (B, S_max, Hkv, hd).
Sliding-window layers keep a ring-buffer cache of size window.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.quantize import dequantize_kv, quantize_kv
from repro.models import layers as L

NEG_INF = -1e30


def _quantize_pair(k, v):
    """Quantize a K/V write for an int8 pool/cache: per-vector nearest-even
    rounding, so every path (dense cache, paged prefill/decode/verify,
    re-prefill after preemption) stores bit-identical values for the same
    input vector — the invariant the engine's replay-equality tests rely on."""
    qk, sk = quantize_kv(k)
    qv, sv = quantize_kv(v)
    return qk, sk, qv, sv


# ------------------------------------------------------------------ params
def init_attention(key, cfg):
    d, h, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    dt = L.dtype_of(cfg)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": L.dense_init(k1, (d, h * hd), dt),
        "wk": L.dense_init(k2, (d, hkv * hd), dt),
        "wv": L.dense_init(k3, (d, hkv * hd), dt),
        "wo": L.dense_init(k4, (h * hd, d), dt),
    }


def _split_heads(x, n, hd):
    return x.reshape(x.shape[:-1] + (n, hd))


def _repeat_kv(k, n_rep):
    if n_rep == 1:
        return k
    return jnp.repeat(k, n_rep, axis=2)


def _mask(q_pos, k_pos, window):
    """causal (+ optional sliding window) mask: True = attend."""
    m = k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        m &= k_pos[None, :] > (q_pos[:, None] - window)
    return m


def _project_qkv(params, x, positions, cfg, window):
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = _split_heads(jnp.einsum("bsd,dk->bsk", x, params["wq"]), h, hd)
    k = _split_heads(jnp.einsum("bsd,dk->bsk", x, params["wk"]), hkv, hd)
    v = _split_heads(jnp.einsum("bsd,dk->bsk", x, params["wv"]), hkv, hd)
    if cfg.rope_mode == "standard":
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
    elif cfg.rope_mode == "mrope":
        q = L.apply_mrope(q, positions, cfg.rope_theta)
        k = L.apply_mrope(k, positions, cfg.rope_theta)
    return q, k, v


def _attend_full(q, k, v, n_rep, scale, chunk, window):
    """Full-sequence causal(+window) attention, dispatching naive/chunked
    (chunked needs S % chunk == 0; odd lengths take the naive path). Runs
    under the ``attn`` named scope."""
    S = q.shape[1]
    with jax.named_scope("attn"):
        if S <= chunk or S % chunk != 0:
            kk = _repeat_kv(k, n_rep)
            vv = _repeat_kv(v, n_rep)
            qpos = jnp.arange(S)
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, kk).astype(jnp.float32) * scale
            mask = _mask(qpos, qpos, window)
            scores = jnp.where(mask[None, None], scores, NEG_INF)
            probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
            return jnp.einsum("bhqk,bkhd->bqhd", probs, vv)
        return _chunked_attention(q, k, v, n_rep, scale, chunk, window)


def attention_train(params, x, positions, cfg, *, window=None, impl="chunked"):
    """Self-attention over a full sequence. x: (B,S,D); positions (B,S) or (3,B,S)."""
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q, k, v = _project_qkv(params, x, positions, cfg, window)
    n_rep = h // hkv
    scale = 1.0 / np.sqrt(hd)
    B, S = x.shape[0], x.shape[1]

    chunk = S if impl == "naive" else cfg.attn_chunk
    out = _attend_full(q, k, v, n_rep, scale, chunk, window)

    out = out.reshape(B, S, h * hd)
    return jnp.einsum("bsk,kd->bsd", out, params["wo"])


def _chunked_attention(q, k, v, n_rep, scale, chunk, window):
    """Online-softmax attention, scanning over query chunks (flash-style).

    For sliding-window layers each query chunk only reads the KV slice
    [chunk_start - window, chunk_end) — sub-quadratic work.
    """
    B, S, H, hd = q.shape
    nq = S // chunk
    kk = _repeat_kv(k, n_rep)          # (B, S, H, hd)
    vv = _repeat_kv(v, n_rep)
    kpos_all = jnp.arange(S)

    if window is not None:
        span = int(min(S, chunk * int(np.ceil(window / chunk)) + chunk))
    else:
        span = None

    @jax.checkpoint
    def one_chunk(qi, q_chunk):
        # rematted: per-chunk scores/probs are recomputed in the backward
        # pass — peak live memory stays O(one chunk), not O(all chunks)
        q_start = qi * chunk
        qpos = q_start + jnp.arange(chunk)
        if span is None:
            keys, vals, kpos = kk, vv, kpos_all
        else:
            k_start = jnp.maximum(q_start + chunk - span, 0)
            keys = jax.lax.dynamic_slice_in_dim(kk, k_start, span, axis=1)
            vals = jax.lax.dynamic_slice_in_dim(vv, k_start, span, axis=1)
            kpos = k_start + jnp.arange(span)
        s = jnp.einsum("bqhd,bkhd->bhqk", q_chunk, keys).astype(jnp.float32) * scale
        m = _mask(qpos, kpos, window)
        s = jnp.where(m[None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", p, vals)

    q_chunks = q.reshape(B, nq, chunk, H, hd).swapaxes(0, 1)   # (nq,B,chunk,H,hd)
    out = jax.lax.map(lambda args: one_chunk(*args),
                      (jnp.arange(nq), q_chunks))
    return out.swapaxes(0, 1).reshape(B, S, H, hd)


# ------------------------------------------------------------------- decode
def init_kv_cache(cfg, batch, max_len, window=None, kv_quant=None):
    from repro.models.state_providers import alloc_kv_pool
    hkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    size = min(max_len, window) if window is not None else max_len
    return alloc_kv_pool((batch, size), hkv, hd, L.dtype_of(cfg), kv_quant)


def attention_prefill(params, x, cache, cfg, *, window=None):
    """Batched prefill: full-sequence causal attention AND cache fill in ONE
    pass (vs. the O(S) sequential decode loop). x: (B,S,D) starting at
    position 0. Writes K/V into the decode cache (ring-aware for
    sliding-window layers: only the last `window` positions land, at their
    ring slots). Returns (out (B,S,D), new_cache)."""
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    B, S = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    if cfg.rope_mode == "mrope":
        positions = jnp.broadcast_to(positions[None], (3, B, S))
    q, k, v = _project_qkv(params, x, positions, cfg, window)
    quant = "k_scale" in cache
    if quant:
        # attend the ROUND-TRIPPED values: the paged prefill reads its keys
        # back from the int8 pool, so the dense reference must see the same
        # quantization error for token-level parity
        qk, sk, qv, sv = _quantize_pair(k, v)
        k = dequantize_kv(qk, sk).astype(k.dtype)
        v = dequantize_kv(qv, sv).astype(v.dtype)
    n_rep = h // hkv
    scale = 1.0 / np.sqrt(hd)
    out = _attend_full(q, k, v, n_rep, scale, cfg.attn_chunk, window)
    out = out.reshape(B, S, h * hd)
    out = jnp.einsum("bsk,kd->bsd", out, params["wo"])

    Sc = cache["k"].shape[1]
    keep = min(S, Sc)                       # ring slots are unique for the
    slots = (jnp.arange(S - keep, S)) % Sc  # last `keep` positions only
    if quant:
        new_cache = {
            "k": cache["k"].at[:, slots].set(qk[:, S - keep:]),
            "v": cache["v"].at[:, slots].set(qv[:, S - keep:]),
            "k_scale": cache["k_scale"].at[:, slots].set(sk[:, S - keep:]),
            "v_scale": cache["v_scale"].at[:, slots].set(sv[:, S - keep:]),
        }
    else:
        new_cache = {
            "k": cache["k"].at[:, slots].set(k[:, S - keep:]),
            "v": cache["v"].at[:, slots].set(v[:, S - keep:]),
        }
    return out, new_cache


# ------------------------------------------------------------ paged decode
# The paged steps (models.transformer) carry every layer's pool stacked,
# (L, N, bs, Hkv, hd), through their layer scan and pass `layer`, the
# traced index of the layer at hand. Writes scatter only the new tokens at
# (layer, block, offset) — in place, the pool being donated — and reads
# gather only the blocks a table names, at the same index. No function here
# slices a whole layer out of the stack.
def _kv_values(kv, k, v):
    """What a K/V write stores: the vectors themselves, or for an int8 pool
    (with "k_scale"/"v_scale") their int8 values and per-vector scales."""
    if "k_scale" in kv:
        qk, sk, qv, sv = _quantize_pair(k, v)
        return {"k": qk, "v": qv, "k_scale": sk, "v_scale": sv}
    return {"k": k, "v": v}


def _scatter_kv(kv, layer, bids, offs, vals):
    """Write ``vals`` (see :func:`_kv_values`) at (layer, bids, offs) of
    the stacked pool; rows whose block id is out of range are dropped."""
    return {n: kv[n].at[layer, bids, offs].set(vals[n], mode="drop")
            for n in kv}


def paged_write(kv, layer, k_new, v_new, block_tables, positions, active, *,
                ring_pages=None):
    """Scatter one token's K/V per sequence into layer ``layer`` of the
    stacked block pool.

    kv: {"k","v"}: (L, N, bs, Hkv, hd); k_new/v_new: (B, Hkv, hd);
    block_tables: (B, P); positions: (B,) absolute token position;
    active: (B,) bool — inactive rows are dropped (OOB block id).
    ring_pages: sliding-window layers write page (pos // bs) % ring_pages
    so the sequence never touches more than ring_pages blocks. An int8 pool
    (with "k_scale"/"v_scale") quantizes on write, scattering the scales at
    the same (block, offset)."""
    with jax.named_scope("kv_write"):
        N, bs = kv["k"].shape[1:3]
        B = positions.shape[0]
        pages = positions // bs
        if ring_pages is not None:
            pages = pages % ring_pages
        bids = block_tables[jnp.arange(B), pages]
        bids = jnp.where(active, bids, N)       # OOB => mode="drop"
        offs = positions % bs
        return _scatter_kv(kv, layer, bids, offs,
                           _kv_values(kv, k_new, v_new))


def attention_decode_paged(params, x, kv, layer, block_tables, positions,
                           attn_lens, cfg, *, impl=None, window=None,
                           ring_pages=None):
    """One-token decode against layer ``layer`` of the stacked paged pool.
    x: (B,1,D); kv k/v pools (L, N, bs, Hkv, hd); block_tables (B, P);
    positions (B,) absolute position of the incoming token; attn_lens (B,)
    tokens to attend over INCLUDING the new one (0 marks an inactive slot —
    its write is dropped and its output is garbage the engine ignores).
    window/ring_pages switch sliding-window layers to the ring layout (write
    modulo the ring, attend the last `window` positions). ``impl``
    ("kernel" | "ref") defaults to the platform's choice
    (``repro.kernels.platform``). Returns (out (B,1,D), new kv)."""
    from repro.kernels import platform
    from repro.kernels.paged_attention import paged_attention, paged_attention_ref
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    B = x.shape[0]
    pos_b1 = positions[:, None]
    if cfg.rope_mode == "mrope":
        pos_b1 = jnp.broadcast_to(pos_b1[None], (3, B, 1))
    q, k_new, v_new = _project_qkv(params, x, pos_b1, cfg, window)
    kv = paged_write(kv, layer, k_new[:, 0], v_new[:, 0], block_tables,
                     positions, attn_lens > 0, ring_pages=ring_pages)
    with jax.named_scope("attn"):
        scales = dict(k_scale=kv.get("k_scale"), v_scale=kv.get("v_scale"))
        attend = (paged_attention
                  if (impl or platform.paged_attn_impl()) == "kernel"
                  else paged_attention_ref)
        out = attend(q[:, 0], kv["k"], kv["v"], block_tables, attn_lens,
                     layer=layer, window=window, positions=positions,
                     ring_pages=ring_pages, **scales)
    out = out.reshape(B, 1, h * hd)
    return jnp.einsum("bsk,kd->bsd", out, params["wo"]), kv


def paged_write_multi(kv, layer, k_new, v_new, block_tables, positions,
                      valid, *, ring_pages=None):
    """Scatter K draft tokens' K/V per sequence into layer ``layer`` of the
    stacked block pool.

    kv: {"k","v"}: (L, N, bs, Hkv, hd); k_new/v_new: (B, K, Hkv, hd);
    block_tables: (B, P); positions: (B, K) absolute token positions;
    valid: (B, K) bool — invalid (rejected-horizon or inactive) writes are
    dropped (OOB block id) so pool contents stay canonical. ring_pages:
    sliding-window layers write page (pos // bs) % ring_pages. Int8 pools
    quantize on write as in :func:`paged_write`."""
    with jax.named_scope("kv_write"):
        N, bs = kv["k"].shape[1:3]
        pages = positions // bs
        if ring_pages is not None:
            pages = pages % ring_pages
        bids = jnp.take_along_axis(block_tables, pages, axis=1)       # (B, K)
        bids = jnp.where(valid, bids, N)        # OOB => mode="drop"
        offs = positions % bs
        return _scatter_kv(kv, layer, bids, offs,
                           _kv_values(kv, k_new, v_new))


def attention_verify_paged(params, x, kv, layer, block_tables, base, qlims,
                           cfg, *, impl=None, window=None, ring_pages=None):
    """Multi-query speculative verify against layer ``layer`` of the stacked
    paged pool. x: (B,K,D) — K draft tokens per sequence, draft j at
    absolute position base[b] + j. qlims (B,): number of draft positions
    whose K/V may be written this step (0 marks an inactive slot); queries
    at or past qlims produce garbage the engine discards, and their writes
    are dropped so rejected-horizon KV never lands in the pool.
    window/ring_pages switch sliding-window layers to the ring layout — the
    ring must be sized with `draft = K - 1` slack (see
    state_providers.ring_pages). ``impl`` as in
    :func:`attention_decode_paged`. Returns (out (B,K,D), new kv)."""
    from repro.kernels import platform
    from repro.kernels.paged_attention import (paged_attention_verify,
                                               paged_attention_verify_ref)
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    B, K = x.shape[0], x.shape[1]
    positions = base[:, None] + jnp.arange(K)[None, :]            # (B, K)
    pos_in = positions
    if cfg.rope_mode == "mrope":
        pos_in = jnp.broadcast_to(pos_in[None], (3, B, K))
    q, k_new, v_new = _project_qkv(params, x, pos_in, cfg, window)
    write = jnp.arange(K)[None, :] < qlims[:, None]               # (B, K)
    kv = paged_write_multi(kv, layer, k_new, v_new, block_tables, positions,
                           write, ring_pages=ring_pages)
    attn_lens = jnp.where(qlims > 0, base + K, 0)
    newest = attn_lens - 1
    with jax.named_scope("attn"):
        scales = dict(k_scale=kv.get("k_scale"), v_scale=kv.get("v_scale"))
        attend = (paged_attention_verify
                  if (impl or platform.paged_attn_impl()) == "kernel"
                  else paged_attention_verify_ref)
        out = attend(q, kv["k"], kv["v"], block_tables, attn_lens,
                     layer=layer, window=window, positions=newest,
                     ring_pages=ring_pages, **scales)
    out = out.reshape(B, K, h * hd)
    return jnp.einsum("bsk,kd->bsd", out, params["wo"]), kv


def attention_prefill_paged(params, x, kv, layer, table_rows, starts, valids,
                            cfg):
    """Segment-masked packed prefill against layer ``layer`` of the stacked
    paged pool. x: (G,C,D) — one prompt chunk per segment, segment g
    starting at absolute position `starts[g]`, of which the first
    `valids[g]` tokens are real (the rest padding; `valids[g] == 0` marks an
    all-padding segment whose writes are dropped and whose output rows the
    caller ignores). Segments own disjoint block tables (shared prefix
    blocks are read-only and not written here), so the combined scatter
    plus per-segment gathers are race-free. Writes each segment's chunk K/V
    into the pool, then attends causally over each segment's own prefix
    gathered via its table row. Returns (out (G,C,D), new kv)."""
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    G, C = x.shape[0], x.shape[1]
    pos = starts[:, None] + jnp.arange(C)[None, :]                # (G, C)
    positions = pos
    if cfg.rope_mode == "mrope":
        positions = jnp.broadcast_to(positions[None], (3, G, C))
    q, k, v = _project_qkv(params, x, positions, cfg, None)

    N, bs = kv["k"].shape[1:3]
    with jax.named_scope("kv_write"):
        valid = jnp.arange(C)[None, :] < valids[:, None]              # (G, C)
        bids = jnp.where(
            valid, jnp.take_along_axis(table_rows, pos // bs, axis=1), N)
        kv = _scatter_kv(kv, layer, bids, pos % bs, _kv_values(kv, k, v))

    # the gather-back below reads the (possibly quantized) pool contents, so
    # every query attends the same values the decode kernel will later see
    from repro.kernels.paged_attention.ref import _gather_pool
    with jax.named_scope("attn"):
        P = table_rows.shape[1]
        n_rep = h // hkv
        if "k_scale" in kv:
            kk = _repeat_kv(_gather_pool(kv["k"], kv["k_scale"], table_rows,
                                         P * bs, layer), n_rep)
            vv = _repeat_kv(_gather_pool(kv["v"], kv["v_scale"], table_rows,
                                         P * bs, layer), n_rep)
        else:
            kk = _repeat_kv(kv["k"][layer, table_rows].reshape(
                G, P * bs, hkv, hd), n_rep)
            vv = _repeat_kv(kv["v"][layer, table_rows].reshape(
                G, P * bs, hkv, hd), n_rep)
        scale = 1.0 / np.sqrt(hd)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kk).astype(jnp.float32) * scale
        mask = jnp.arange(P * bs)[None, None, :] <= pos[:, :, None]  # G,C,P*bs
        s = jnp.where(mask[:, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", p, vv).reshape(G, C, h * hd)
    return jnp.einsum("bsk,kd->bsd", out, params["wo"]), kv


def attention_prefill_ring(params, x, kv, layer, table_rows, starts, valids,
                           cfg, *, window, ring_pages):
    """Segment-masked packed prefill against layer ``layer`` of a stacked
    RING-paged pool. x: (G,C,D) — one chunk per segment starting at
    `starts[g]`, first `valids[g]` tokens real. Each segment owns only
    `ring_pages` blocks; its position p lives at
    `table_rows[g, (p // bs) % ring_pages]`, offset `p % bs`.

    Unlike the full-attention path (write, then gather everything back),
    the pre-chunk ring content is gathered BEFORE the chunk's writes: on
    wraparound the chunk overwrites pages that early queries still need, so
    read-then-write is required for correctness. Each query t attends the
    union of {its segment's pre-chunk ring keys} ∪ {its segment's chunk},
    masked to its window (t - window, t]. Returns (out (G,C,D), new kv)."""
    from repro.kernels.paged_attention.ref import (_gather_pool,
                                                   ring_key_positions)
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    G, C = x.shape[0], x.shape[1]
    pos = starts[:, None] + jnp.arange(C)[None, :]                # (G, C)
    positions = pos
    if cfg.rope_mode == "mrope":
        positions = jnp.broadcast_to(positions[None], (3, G, C))
    q, k, v = _project_qkv(params, x, positions, cfg, window)
    quant = "k_scale" in kv
    vals = _kv_values(kv, k, v)
    if quant:
        # chunk keys are attended from registers (never re-read from the
        # pool), so round-trip them explicitly for parity with the decode
        # steps that WILL read them back quantized
        k = dequantize_kv(vals["k"], vals["k_scale"]).astype(k.dtype)
        v = dequantize_kv(vals["v"], vals["v_scale"]).astype(v.dtype)

    N, bs = kv["k"].shape[1:3]
    R = ring_pages

    # 1) gather each segment's ring as of starts-1 (before this chunk's
    # writes)
    with jax.named_scope("attn"):
        ring_rows = table_rows[:, :R]                                 # (G, R)
        if quant:
            old_k = _gather_pool(kv["k"], kv["k_scale"], ring_rows, R * bs,
                                 layer)
            old_v = _gather_pool(kv["v"], kv["v_scale"], ring_rows, R * bs,
                                 layer)
        else:
            old_k = kv["k"][layer, ring_rows].reshape(G, R * bs, hkv, hd)
            old_v = kv["v"][layer, ring_rows].reshape(G, R * bs, hkv, hd)
    old_pos = ring_key_positions(starts - 1, R, bs)               # (G, R*bs)
    # entries the pre-chunk ring never held: pages < 0 entirely, and the
    # current page's offsets past (start-1) % bs (previous-lap leftovers,
    # reconstructed as > start-1)
    old_ok = (old_pos >= 0) & (old_pos <= (starts - 1)[:, None])

    # 2) write the chunk's K/V at their ring slots. Padding rows are
    # dropped, and so is any position lapped by a LATER valid position in
    # this same chunk (C can exceed the ring capacity R*bs): `.at[].set`
    # leaves duplicate-index order undefined, so only each (slot, offset)'s
    # newest lap may write. Skipped positions are > R*bs > window older
    # than the chunk's last token — nothing downstream can attend them.
    with jax.named_scope("kv_write"):
        last_valid = (starts + valids - 1)[:, None]                   # (G, 1)
        write = ((jnp.arange(C)[None, :] < valids[:, None])
                 & (pos > last_valid - R * bs))
        bids = jnp.where(
            write, jnp.take_along_axis(table_rows, (pos // bs) % R, axis=1), N)
        kv = _scatter_kv(kv, layer, bids, pos % bs, vals)

    # 3) attend: keys = each segment's pre-chunk ring ∪ its own chunk
    with jax.named_scope("attn"):
        n_rep = h // hkv
        kk = _repeat_kv(jnp.concatenate([old_k, k], axis=1), n_rep)
        vv = _repeat_kv(jnp.concatenate([old_v, v], axis=1), n_rep)
        kpos = jnp.concatenate([old_pos, pos], axis=1)          # (G, R*bs+C)
        kok = jnp.concatenate([old_ok, jnp.ones((G, C), bool)], axis=1)
        scale = 1.0 / np.sqrt(hd)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kk).astype(jnp.float32) * scale
        mask = (kok[:, None, :]
                & (kpos[:, None, :] <= pos[:, :, None])
                & (kpos[:, None, :] > pos[:, :, None] - window))  # (G, C, K)
        s = jnp.where(mask[:, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", p, vv).reshape(G, C, h * hd)
    return jnp.einsum("bsk,kd->bsd", out, params["wo"]), kv


def attention_decode(params, x, cache, index, cfg, *, window=None):
    """One-token decode. x: (B,1,D); cache k/v: (B,Sc,Hkv,hd); index: scalar
    current absolute position. Returns (out, new_cache)."""
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    B = x.shape[0]
    positions = jnp.full((B, 1), index, jnp.int32)
    if cfg.rope_mode == "mrope":
        positions = jnp.broadcast_to(positions, (3, B, 1))
    q, k_new, v_new = _project_qkv(params, x, positions, cfg, window)
    Sc = cache["k"].shape[1]
    slot = index % Sc if window is not None else index      # ring buffer
    if "k_scale" in cache:
        qk, sk, qv, sv = _quantize_pair(k_new[:, 0], v_new[:, 0])
        new_cache = {
            "k": cache["k"].at[:, slot].set(qk),
            "v": cache["v"].at[:, slot].set(qv),
            "k_scale": cache["k_scale"].at[:, slot].set(sk),
            "v_scale": cache["v_scale"].at[:, slot].set(sv),
        }
        k = dequantize_kv(new_cache["k"], new_cache["k_scale"]).astype(x.dtype)
        v = dequantize_kv(new_cache["v"], new_cache["v_scale"]).astype(x.dtype)
    else:
        k = cache["k"].at[:, slot].set(k_new[:, 0])
        v = cache["v"].at[:, slot].set(v_new[:, 0])
        new_cache = {"k": k, "v": v}
    n_rep = h // hkv
    kk = _repeat_kv(k, n_rep)
    vv = _repeat_kv(v, n_rep)
    scale = 1.0 / np.sqrt(hd)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk).astype(jnp.float32) * scale
    kpos = jnp.arange(Sc)
    if window is not None:
        # ring buffer: valid entries are those written within the last
        # `window` steps; absolute position of slot j is reconstructed below.
        age = (slot - kpos) % Sc
        valid = age < jnp.minimum(index + 1, Sc)
    else:
        valid = kpos <= index
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vv).reshape(B, 1, h * hd)
    out = jnp.einsum("bsk,kd->bsd", out, params["wo"])
    return out, new_cache
