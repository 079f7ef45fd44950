"""Pure-jnp oracle for paged decode attention (full and ring/sliding-window).

One query token per sequence attends over KV stored in a block pool via a
per-sequence block table. Semantics:

  * ``seq_lens[b]`` counts the valid tokens of sequence ``b`` INCLUDING the
    current one — the caller writes the current token's K/V into the pool
    *before* calling (same write-then-attend order as
    ``models.attention.attention_decode``).
  * ``seq_lens[b] == 0`` marks an inactive slot: the output row is all zeros.
  * Table entries past the sequence's last page may point anywhere inside the
    pool; their contents are masked out.

Ring mode (``window`` + ``positions`` + ``ring_pages`` set): the sequence
only owns ``ring_pages`` blocks and token at absolute position p was written
at ``table[(p // bs) % ring_pages]``, offset ``p % bs``. The oracle inverts
that mapping — ring slot r currently holds absolute page
``q_cur - ((q_cur % R - r) % R)`` where ``q_cur = position // bs`` — and
attends exactly the window ``(position - window, position]``. Offsets past
``position % bs`` in the current page still hold the previous lap's keys;
their reconstructed positions exceed ``position`` so the causal bound masks
them.
"""
import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _masked_gqa_attend(q, k, v, valid, scale):
    """q: (B, H, hd); k/v: (B, K, Hkv, hd); valid: (B, K) bool mask.
    Max-subtracted softmax with a guarded denominator so fully-masked rows
    (inactive slots) produce zeros instead of NaN. Returns (B, H, hd)."""
    B, H, hd = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    qg = q.reshape(B, Hkv, g, hd)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale                 # (B,Hkv,g,K)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - jnp.maximum(m, NEG_INF / 2))
    p = jnp.where(valid[:, None, None, :], p, 0.0)
    denom = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bhgk,bkhd->bhgd", p / denom, v.astype(jnp.float32))
    return out.reshape(B, H, hd).astype(q.dtype)


def _masked_gqa_attend_multi(q, k, v, valid, scale):
    """Multi-query variant: q: (B, K, H, hd); k/v: (B, Kk, Hkv, hd);
    valid: (B, K, Kk) bool, one key mask per query row. Each row runs the
    exact elementwise ops of :func:`_masked_gqa_attend`, so a verify row is
    bit-identical to the single-query reference at the same position.
    Returns (B, K, H, hd)."""
    B, K, H, hd = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    qg = q.reshape(B, K, Hkv, g, hd)
    s = jnp.einsum("bqhgd,bkhd->bqhgk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale            # (B,K,Hkv,g,Kk)
    mask = valid[:, :, None, None, :]
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - jnp.maximum(m, NEG_INF / 2))
    p = jnp.where(mask, p, 0.0)
    denom = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bqhgk,bkhd->bqhgd", p / denom, v.astype(jnp.float32))
    return out.reshape(B, K, H, hd).astype(q.dtype)


def _blocks(pool, tables, layer=None):
    """Pool blocks through a block table: (B, P, bs, ...). ``pool`` is one
    layer's (N, bs, ...) with ``layer=None``, else the layers' stack
    (L, N, bs, ...), read at ``layer`` in the same gather — no layer is
    sliced out of the stack first."""
    return pool[tables] if layer is None else pool[layer, tables]


def _gather_pool(pool, scl, tables, T, layer=None):
    """Gather pool blocks through a block table into (B, T, Hkv, hd) f32.
    When ``scl`` (N, bs, Hkv) is given the pool is int8 and each vector is
    dequantized with the same per-(slot, head) multiply as the Pallas
    kernel's `_load_kv` — so ref-with-scales is bitwise identical to the ref
    run on a pre-dequantized f32 pool. ``layer`` as in :func:`_blocks`."""
    B = tables.shape[0]
    Hkv, hd = pool.shape[-2:]
    x = _blocks(pool, tables, layer).astype(jnp.float32)  # (B, P, bs, Hkv, hd)
    if scl is not None:
        x = x * _blocks(scl, tables, layer)[..., None]
    return x.reshape(B, T, Hkv, hd)


def ring_key_positions(positions, ring_pages, block_size):
    """Absolute position of every (ring slot, offset) pair, per sequence.
    positions: (B,) current absolute position. Returns (B, R*bs) int32;
    entries may be negative (page not yet written) or > positions (stale
    previous-lap offsets) — callers mask both."""
    R, bs = ring_pages, block_size
    q_cur = positions // bs                                       # (B,)
    r_cur = q_cur % R
    page = q_cur[:, None] - ((r_cur[:, None] - jnp.arange(R)[None, :]) % R)
    kpos = page[:, :, None] * bs + jnp.arange(bs)[None, None, :]  # (B, R, bs)
    return kpos.reshape(positions.shape[0], R * bs)


def paged_attention_ref(q, k_pool, v_pool, block_tables, seq_lens, *,
                        layer=None, scale=None, window=None, positions=None,
                        ring_pages=None, k_scale=None, v_scale=None):
    """q: (B, H, hd); k_pool/v_pool: (N, bs, Hkv, hd), or the layers' stack
    (L, N, bs, Hkv, hd) read at ``layer``; block_tables: (B, P) int32;
    seq_lens: (B,) int32. Returns (B, H, hd).

    window/positions/ring_pages switch on ring mode (all three required):
    attend the sliding window (positions - window, positions] through the
    ring block layout. k_scale/v_scale: int8-pool dequant scales, f32,
    shaped like the pools without hd."""
    B, H, hd = q.shape
    bs = k_pool.shape[-3]
    scale = scale if scale is not None else hd ** -0.5

    if window is None:
        P = block_tables.shape[1]
        k = _gather_pool(k_pool, k_scale, block_tables, P * bs, layer)
        v = _gather_pool(v_pool, v_scale, block_tables, P * bs, layer)
        valid = jnp.arange(P * bs)[None, :] < seq_lens[:, None]
        return _masked_gqa_attend(q, k, v, valid, scale)

    if positions is None or ring_pages is None:
        raise ValueError("ring mode needs window, positions AND ring_pages")
    R = ring_pages
    tables = block_tables[:, :R]
    k = _gather_pool(k_pool, k_scale, tables, R * bs, layer)
    v = _gather_pool(v_pool, v_scale, tables, R * bs, layer)
    kpos = ring_key_positions(positions, R, bs)                   # (B, R*bs)
    valid = ((kpos >= 0)
             & (kpos <= positions[:, None])
             & (kpos > positions[:, None] - window)
             & (seq_lens[:, None] > 0))
    return _masked_gqa_attend(q, k, v, valid, scale)


def paged_attention_verify_ref(q, k_pool, v_pool, block_tables, seq_lens, *,
                               layer=None, scale=None, window=None,
                               positions=None, ring_pages=None, k_scale=None,
                               v_scale=None):
    """Multi-query verify oracle for speculative decoding.

    q: (B, K, H, hd) — K draft queries per sequence. ``seq_lens[b]`` counts
    valid tokens INCLUDING all K draft tokens (their K/V already written,
    write-then-attend), so query j of sequence b sits at absolute position
    ``seq_lens[b] - K + j`` and attends keys causally up to and including
    itself. ``seq_lens[b] == 0`` marks an inactive slot (zero output).

    Ring mode (window/positions/ring_pages set): ``positions[b]`` is the
    NEWEST draft position ``seq_lens[b] - 1``; each query attends its own
    sliding window ``(qpos - window, qpos]`` through the ring layout. The
    caller is responsible for sizing the ring so that the oldest query's
    window is still resident (``ring_pages(window, bs, draft=K-1)``).
    Pools, ``layer`` and scales as in :func:`paged_attention_ref`. Returns
    (B, K, H, hd)."""
    B, K, H, hd = q.shape
    bs = k_pool.shape[-3]
    scale = scale if scale is not None else hd ** -0.5
    qpos = seq_lens[:, None] - K + jnp.arange(K)[None, :]         # (B, K)

    if window is None:
        P = block_tables.shape[1]
        k = _gather_pool(k_pool, k_scale, block_tables, P * bs, layer)
        v = _gather_pool(v_pool, v_scale, block_tables, P * bs, layer)
        kpos = jnp.arange(P * bs)
        valid = kpos[None, None, :] <= qpos[:, :, None]           # (B, K, P*bs)
        return _masked_gqa_attend_multi(q, k, v, valid, scale)

    if positions is None or ring_pages is None:
        raise ValueError("ring mode needs window, positions AND ring_pages")
    R = ring_pages
    tables = block_tables[:, :R]
    k = _gather_pool(k_pool, k_scale, tables, R * bs, layer)
    v = _gather_pool(v_pool, v_scale, tables, R * bs, layer)
    kpos = ring_key_positions(positions, R, bs)                   # (B, R*bs)
    valid = ((kpos[:, None, :] >= 0)
             & (kpos[:, None, :] <= qpos[:, :, None])
             & (kpos[:, None, :] > qpos[:, :, None] - window)
             & (seq_lens[:, None, None] > 0))
    return _masked_gqa_attend_multi(q, k, v, valid, scale)
