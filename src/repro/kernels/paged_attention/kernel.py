"""Paged decode attention Pallas TPU kernel.

The serving engine stores KV in fixed-size blocks of a shared pool; each
sequence owns a list of block ids (its *block table*). At decode time one
query token per sequence must attend over its logically-contiguous KV, which
is physically scattered across the pool.

The kernel uses `PrefetchScalarGridSpec`: the layer index, the block table
and the sequence lengths are scalar-prefetched so the BlockSpec index maps
can address the *physical* KV blocks — block ``table[b, p]`` of layer
``layer`` in the layers' stacked pool (L, N, bs, Hkv, hd). The DMA engine
walks the block table; no gather materializes the sequence, and the model's
layer scan hands the kernel its whole carried pool instead of a slice of
one layer. Running online-softmax statistics (m, l, acc) live in VMEM
scratch that persists across the page steps of one sequence, exactly like
the flash_attention kernel's kv axis.

Grid: (B, ceil(P / ppb)) with the page axis innermost ("arbitrary"
semantics). Each grid step reads ``ppb`` pages (``PAGES_PER_STEP``, fewer
for a shorter table) through ppb BlockSpecs per pool, so 2 x ppb block DMAs
(K and V) are in flight and the pipeline fetches the next step's pages
while this step computes (the blocks sit in HBM; on a v5e 8 pages a step
take 8 % less time than one). The pages of a step are folded into the
running softmax one at a time, in page order.
Pages at or beyond seq_len are skipped (`pl.when`), and their table
entries (0 past a sequence's end) repeat, so the pipeline fetches no block
for them: the work per sequence is O(seq_len), not O(P * block_size).

Ring mode (`window` + `ring_pages` set, `positions` prefetched as a fourth
scalar array): sliding-window layers keep a fixed ring of `ring_pages`
blocks per sequence — token at absolute position p lives at
`table[(p // bs) % R]`, offset `p % bs`. The grid's page axis covers R
ring slots and each reconstructs the absolute page it currently holds
(`q_cur - ((q_cur % R - r) % R)`), masking keys outside
`(position - window, position]`. Stale previous-lap offsets in the current
page reconstruct to positions > position, so the causal bound masks them;
pages wholly outside the window (or not yet written) are skipped.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
PAGES_PER_STEP = 8


def _load_kv(ref, sref):
    """Load one pool block (1, bs, Hkv, hd) as f32 (Hkv, bs, hd). When the
    pool is int8 (`sref` holds per-(slot, head) scales, block (1, bs, Hkv)),
    the dequant multiply happens here — inside the kernel, after the DMA — so
    HBM traffic on the decode hot path is the int8 bytes, not f32."""
    x = ref[0].astype(jnp.float32)
    if sref is not None:
        x = x * sref[0][..., None]                   # (bs, Hkv, 1) broadcast
    return x.swapaxes(0, 1)


def paged_kernel(layer_ref, tables_ref, lens_ref, *refs, scale, block_size,
                 pages, ppb, groups, window=None, n_q=None, quant=False):
    """One body for the four modes; grid (B, ceil(pages / ppb)).

    ``refs``: [pos_ref (ring mode)], q_ref, then ppb K blocks, ppb V blocks
    [, ppb K scale and ppb V scale blocks (int8)], the output and the (m,
    l, acc) scratch. Decode (``n_q`` None): q block (1, H, hd), softmax
    rows (Hkv, groups). Verify: q block (1, n_q, H, hd); ``lens_ref[b]``
    counts tokens INCLUDING the n_q draft tokens, so query row j sits at
    absolute position ``lens - n_q + j`` and is masked to keys up to it —
    causal among the draft positions and over the committed prefix. Rows
    are laid out (Hkv, n_q*groups) so each runs exactly the decode
    schedule; fully-masked pages leave (m, l, acc) bit-unchanged. Ring
    mode: ``pages`` is the ring length R, ``pos_ref[b]`` the newest query's
    position (``lens - 1`` in verify) and each query is masked to its own
    window; the caller sizes the ring with ``draft = n_q - 1`` slack so the
    oldest query's window is still resident."""
    del layer_ref                       # read by the index maps only
    ring = window is not None
    if ring:
        pos_ref, q_ref, *refs = refs
    else:
        q_ref, *refs = refs
    nkv = 4 if quant else 2
    k_refs, v_refs = refs[:ppb], refs[ppb:2 * ppb]
    ks_refs, vs_refs = ((refs[2 * ppb:3 * ppb], refs[3 * ppb:4 * ppb])
                        if quant else ((None,) * ppb, (None,) * ppb))
    o_ref, m_ref, l_ref, acc_ref = refs[nkv * ppb:]
    verify = n_q is not None
    nq = n_q or 1
    H, hd = q_ref.shape[-2:]
    Hkv = H // groups
    rows = nq * groups
    b = pl.program_id(0)
    c = pl.program_id(1)
    seq_len = lens_ref[b]
    newest = pos_ref[b] if ring else seq_len - 1

    @pl.when(c == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def load_q():
        q = q_ref[0].astype(jnp.float32)
        if not verify:
            return q.reshape(Hkv, groups, hd)
        # (n_q, H, hd) -> (Hkv, n_q*groups, hd): kv-head-major rows
        return (q.reshape(nq, Hkv, groups, hd).transpose(1, 0, 2, 3)
                .reshape(Hkv, rows, hd))

    for j in range(ppb):
        r = c * ppb + j                 # table page (ring slot in ring mode)
        if ring:
            q_cur = newest // block_size
            # absolute page held by ring slot r (negative: never written)
            base = (q_cur - ((q_cur % pages - r) % pages)) * block_size
            # live iff the page meets the union of the queries' windows:
            # keys in (newest - (nq - 1) - window, newest]
            live = ((r < pages) & (seq_len > 0) & (base >= 0)
                    & (base <= newest)
                    & (base + block_size - 1 > newest - (nq - 1) - window))
        else:
            base = r * block_size
            live = (r < pages) & (base < seq_len)

        @pl.when(live)
        def _compute():
            q = load_q()
            k = _load_kv(k_refs[j], ks_refs[j])                    # (Hkv, bs, hd)
            v = _load_kv(v_refs[j], vs_refs[j])
            # batched over kv heads: (Hkv, rows, hd) x (Hkv, bs, hd)
            s = jax.lax.dot_general(
                q, k, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32) * scale        # (Hkv, rows, bs)
            kpos = base + jax.lax.broadcasted_iota(
                jnp.int32, (Hkv, rows, block_size), 2)
            row = jax.lax.broadcasted_iota(jnp.int32, (Hkv, rows, block_size), 1)
            qpos = newest - (nq - 1) + row // groups
            # stale previous-lap offsets in a ring's current page have
            # kpos > qpos
            keep = kpos <= qpos
            if ring:
                keep &= kpos > qpos - window
            s = jnp.where(keep, s, NEG_INF)

            m_prev = m_ref[...]                                    # (Hkv, rows, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
            prob = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[...] = l_ref[...] * corr + jnp.sum(prob, axis=2, keepdims=True)
            acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
                prob, v, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)                # (Hkv, rows, hd)
            m_ref[...] = m_new

    @pl.when(c == pl.num_programs(1) - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        acc = acc_ref[...] / denom
        if verify:
            acc = acc.reshape(Hkv, nq, groups, hd).transpose(1, 0, 2, 3)
        o_ref[0] = acc.reshape(o_ref.shape[1:]).astype(o_ref.dtype)


def _stacked(k_pool, v_pool, k_scale, v_scale, layer):
    """The pools as a stack of layers, and the layer to read as a (1,)
    int32 scalar-prefetch operand. A single layer's pool (N, bs, Hkv, hd)
    is a stack of one, read at layer 0."""
    if layer is None:
        lead = lambda a: None if a is None else a[None]
        k_pool, v_pool, k_scale, v_scale = map(
            lead, (k_pool, v_pool, k_scale, v_scale))
        layer = 0
    return (k_pool, v_pool, k_scale, v_scale,
            jnp.asarray(layer, jnp.int32).reshape(1))


def _paged_call(q, k_pool, v_pool, block_tables, seq_lens, *, layer, scale,
                window, positions, ring_pages, k_scale, v_scale, n_q,
                interpret):
    """The pallas_call of every mode. Scalar-prefetch operands
    ``(layer, tables, lens[, positions])``; grid (B, ceil(pages / ppb)).
    Pool BlockSpec j of grid step (b, c) reads block
    ``tables[b, c * ppb + j]`` of layer ``layer[0]`` (the layer axis
    squeezed; pages past the table repeat its last entry and are skipped),
    so each DMA moves one block and never a layer. q and the output are one
    sequence's (1, ...) block."""
    B, H, hd = q.shape[0], q.shape[-2], q.shape[-1]
    Hkv = k_pool.shape[-2]
    groups = H // Hkv
    quant = k_scale is not None
    if quant != (v_scale is not None):
        raise ValueError("k_scale and v_scale must be passed together")
    k_pool, v_pool, k_scale, v_scale, layer = _stacked(
        k_pool, v_pool, k_scale, v_scale, layer)
    bs = k_pool.shape[2]
    prefetch = (layer, block_tables, seq_lens)
    if window is not None:
        if positions is None or ring_pages is None:
            raise ValueError("ring mode needs window, positions AND ring_pages")
        prefetch += (positions.astype(jnp.int32),)
        pages = ring_pages
    else:
        pages = block_tables.shape[1]
    ppb = min(PAGES_PER_STEP, pages)
    kern = functools.partial(
        paged_kernel, scale=scale if scale is not None else hd ** -0.5,
        block_size=bs, pages=pages, ppb=ppb, groups=groups, window=window,
        n_q=n_q, quant=quant)

    def page_spec(j, block):            # block: (bs, Hkv[, hd])
        def index(b, c, layer, tbl, *_):
            page = tbl[b, jnp.minimum(c * ppb + j, pages - 1)]
            return (layer[0], page) + (0,) * len(block)
        return pl.BlockSpec((pl.squeezed, 1) + block, index)

    zeros = (0,) * (q.ndim - 1)
    q_spec = pl.BlockSpec((1,) + q.shape[1:], lambda b, c, *_: (b,) + zeros)
    pools = [k_pool, v_pool] + ([k_scale, v_scale] if quant else [])
    in_specs, operands = [q_spec], [q]
    for pool in pools:
        in_specs += [page_spec(j, pool.shape[2:]) for j in range(ppb)]
        operands += [pool] * ppb
    rows = (n_q or 1) * groups
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B, -(-pages // ppb)),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((Hkv, rows, 1), jnp.float32),
            pltpu.VMEM((Hkv, rows, 1), jnp.float32),
            pltpu.VMEM((Hkv, rows, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(*prefetch, *operands)


def paged_attention_pallas(q, k_pool, v_pool, block_tables, seq_lens, *,
                           layer=None, scale=None, window=None,
                           positions=None, ring_pages=None, k_scale=None,
                           v_scale=None, interpret=False):
    """q: (B, H, hd); k_pool/v_pool: the layers' stacked pools
    (L, N, bs, Hkv, hd), read at ``layer`` (a traced scalar), or one
    layer's pool (N, bs, Hkv, hd) with ``layer=None``; H % Hkv == 0;
    block_tables: (B, P) int32; seq_lens: (B,) int32 (0 = inactive slot,
    current token already written to the pool). Returns (B, H, hd).

    window/positions/ring_pages (all three) switch to ring mode: the page
    grid axis covers `ring_pages` slots and keys are masked to the sliding
    window (positions - window, positions].

    k_scale/v_scale (both or neither): int8 pools with per-(slot, head) f32
    scales (L, N, bs, Hkv) or (N, bs, Hkv), dequantized inside the kernel —
    the scale BlockSpecs walk the same block table as the pools."""
    return _paged_call(q, k_pool, v_pool, block_tables, seq_lens,
                       layer=layer, scale=scale, window=window,
                       positions=positions, ring_pages=ring_pages,
                       k_scale=k_scale, v_scale=v_scale, n_q=None,
                       interpret=interpret)


def paged_attention_verify_pallas(q, k_pool, v_pool, block_tables, seq_lens,
                                  *, layer=None, scale=None, window=None,
                                  positions=None, ring_pages=None,
                                  k_scale=None, v_scale=None,
                                  interpret=False):
    """Multi-query verify: q: (B, K, H, hd) — K draft queries per sequence,
    K/V already written (write-then-attend). ``seq_lens`` counts tokens
    INCLUDING the K draft tokens; query j attends keys up to position
    ``seq_lens - K + j``. Active slots must satisfy ``seq_lens >= K``.
    Ring mode: ``positions = seq_lens - 1`` (newest draft position) and the
    ring must be sized with ``draft = K - 1`` slack. Returns (B, K, H, hd).
    Pools, ``layer`` and k_scale/v_scale as in paged_attention_pallas."""
    return _paged_call(q, k_pool, v_pool, block_tables, seq_lens,
                       layer=layer, scale=scale, window=window,
                       positions=positions, ring_pages=ring_pages,
                       k_scale=k_scale, v_scale=v_scale, n_q=q.shape[1],
                       interpret=interpret)
