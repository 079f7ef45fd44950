"""Generic decoder assembled from a ModelConfig.

Layers are grouped into *superblocks* and scanned (`jax.lax.scan`) so the
lowered HLO is O(1) in depth — essential for compiling 48-layer models with
512 placeholder devices on one CPU core:

  family                superblock
  ------                ----------
  dense/vlm/audio/moe   1 layer (attn + mlp|moe)
  local_global (gemma3) ratio local layers + 1 global layer
  ssm (rwkv6)           time-mix + channel-mix
  hybrid (zamba2)       N mamba2 layers + 1 *shared-weight* attention layer

Entry points:
  init_params(cfg, key)
  forward(cfg, params, inputs)                  -> hidden (B,S,D), aux
  loss_fn(cfg, params, batch)                   -> scalar loss (chunked CE)
  init_decode_state(cfg, batch, max_len)        -> stacked per-superblock caches
  decode_step(cfg, params, state, inputs, idx)  -> logits (B,1,V), new state
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.parallelism import constrain
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S
from repro.models import state_providers as SP

# superblock layout / kind lists live in state_providers so the engine's
# host-side accounting derives the SAME static structure (no import cycle)
superblock_layout = SP.superblock_layout
_layer_kinds = SP.layer_kinds


# ------------------------------------------------------------------ param init
def _init_attn_layer(key, cfg, with_moe=False):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "ln1": L.init_rmsnorm(cfg.d_model),
        "attn": A.init_attention(k1, cfg),
        "ln2": L.init_rmsnorm(cfg.d_model),
    }
    if with_moe:
        p["moe"] = M.init_moe(k2, cfg)
    else:
        p["mlp"] = L.init_swiglu(k3, cfg.d_model, cfg.d_ff, L.dtype_of(cfg))
    return p


def _init_superblock(key, cfg):
    kinds = _layer_kinds(cfg)
    keys = jax.random.split(key, len(kinds))
    out = {}
    for i, (kind, k) in enumerate(zip(kinds, keys)):
        if kind in ("attn", "local", "global"):
            out[f"l{i}"] = _init_attn_layer(k, cfg, with_moe=False)
        elif kind == "moe_attn":
            out[f"l{i}"] = _init_attn_layer(k, cfg, with_moe=True)
        elif kind == "rwkv":
            out[f"l{i}"] = {
                "ln1": L.init_rmsnorm(cfg.d_model),
                "rwkv": S.init_rwkv6(k, cfg),
                "ln2": L.init_rmsnorm(cfg.d_model),
            }
        elif kind == "mamba":
            k1, k2 = jax.random.split(k)
            out[f"l{i}"] = {
                "ln1": L.init_rmsnorm(cfg.d_model),
                "mamba": S.init_mamba2(k1, cfg),
                "ln2": L.init_rmsnorm(cfg.d_model),
                "mlp": L.init_swiglu(k2, cfg.d_model, cfg.d_ff, L.dtype_of(cfg)),
            }
        elif kind == "shared_attn":
            out[f"l{i}"] = {}  # weights live in params["shared_attn"]
    return out


def init_params(cfg: ModelConfig, key):
    n_sb, _ = superblock_layout(cfg)
    k_embed, k_blocks, k_head, k_shared = jax.random.split(key, 4)
    dt = L.dtype_of(cfg)
    params = {
        "embed": L.init_embedding(k_embed, cfg.vocab_size, cfg.d_model, dt),
        "blocks": jax.vmap(lambda k: _init_superblock(k, cfg))(
            jax.random.split(k_blocks, n_sb)),
        "final_norm": L.init_rmsnorm(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"w": L.dense_init(k_head, (cfg.d_model, cfg.vocab_size), dt)}
    if cfg.family == "hybrid":
        params["shared_attn"] = _init_attn_layer(k_shared, cfg, with_moe=False)
    return params


# -------------------------------------------------------------------- forward
def _apply_layer_train(kind, lp, x, positions, cfg, shared):
    aux = jnp.float32(0.0)
    if kind in ("attn", "local", "global", "moe_attn", "shared_attn"):
        p = shared if kind == "shared_attn" else lp
        window = None
        if kind == "local" or (cfg.attention_type == "sliding" and kind in ("attn", "moe_attn")):
            window = cfg.window_size
        h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
        x = x + A.attention_train(p["attn"], h, positions, cfg, window=window)
        h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
        if kind == "moe_attn":
            from repro.core.parallelism import current_plan
            plan = current_plan()
            if M.ep_applicable(cfg, plan):
                x = x + M.moe_apply_ep(lp["moe"], h, cfg, plan)
            else:
                x = x + M.moe_apply(lp["moe"], h, cfg, constrain=constrain)
            aux = M.load_balance_loss(lp["moe"], h, cfg)
        else:
            x = x + L.swiglu(p["mlp"], h)
    elif kind == "rwkv":
        h = L.rmsnorm(lp["ln1"], x, cfg.norm_eps)
        y, _ = S.rwkv6_mix(lp["rwkv"], h, cfg)
        x = x + y
        h = L.rmsnorm(lp["ln2"], x, cfg.norm_eps)
        y, _ = S.rwkv6_channel_mix(lp["rwkv"], h, cfg)
        x = x + y
    elif kind == "mamba":
        h = L.rmsnorm(lp["ln1"], x, cfg.norm_eps)
        y, _ = S.mamba2_mix(lp["mamba"], h, cfg)
        x = x + y
        h = L.rmsnorm(lp["ln2"], x, cfg.norm_eps)
        x = x + L.swiglu(lp["mlp"], h)
    else:
        raise ValueError(kind)
    return x, aux


def forward(cfg: ModelConfig, params, inputs):
    """inputs: {"tokens": (B,S)} or {"embeds": (B,S,D)}, optional "positions".
    Returns (hidden (B,S,D), aux_loss)."""
    if "embeds" in inputs:
        x = inputs["embeds"].astype(L.dtype_of(cfg))
    else:
        x = L.embed(params["embed"], inputs["tokens"])
        if cfg.family != "ssm":
            x = x * float(np.sqrt(cfg.d_model))
    B, Sq = x.shape[0], x.shape[1]
    positions = inputs.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(Sq)[None], (B, Sq))
        if cfg.rope_mode == "mrope":
            positions = jnp.broadcast_to(positions[None], (3, B, Sq))
    x = constrain(x, ("batch", "seq", None))
    kinds = _layer_kinds(cfg)
    shared = params.get("shared_attn")

    layer_fn = _apply_layer_train
    if cfg.remat and len(kinds) > 1:
        # nested remat: the superblock checkpoint stores only its input; each
        # inner layer is checkpointed again so the superblock's backward pass
        # holds one layer's intermediates at a time, not all of them (§Perf)
        layer_fn = jax.checkpoint(_apply_layer_train, static_argnums=(0, 4))

    def sb_fn(x, sb_params):
        aux = jnp.float32(0.0)
        for i, kind in enumerate(kinds):
            x, a = layer_fn(kind, sb_params[f"l{i}"], x, positions, cfg, shared)
            aux = aux + a
        x = constrain(x, ("batch", "seq", None))
        return x, aux

    if cfg.remat:
        sb_fn = jax.checkpoint(sb_fn)

    def scan_body(x, sb_params):
        return sb_fn(x, sb_params)

    x, auxs = jax.lax.scan(scan_body, x, params["blocks"])
    with jax.named_scope("head"):
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return x, jnp.sum(auxs)


def logits(cfg: ModelConfig, params, hidden):
    if cfg.tie_embeddings:
        return L.unembed(params["embed"], hidden)
    return jnp.einsum("...d,dv->...v", hidden, params["lm_head"]["w"])


def loss_fn(cfg: ModelConfig, params, batch):
    """Chunked cross-entropy: scans over sequence chunks so the (B,S,V) logits
    tensor is never materialized (vocabs up to 262k)."""
    hidden, aux = forward(cfg, params, batch)
    labels = batch["labels"]
    B, Sq, D = hidden.shape
    chunk = min(cfg.loss_chunk, Sq)
    nc = Sq // chunk
    head = params["embed"]["table"] if cfg.tie_embeddings else params["lm_head"]["w"].T
    # head: (V, D)

    hc = hidden.reshape(B, nc, chunk, D).swapaxes(0, 1)
    lc = labels.reshape(B, nc, chunk).swapaxes(0, 1)

    @jax.checkpoint
    def step(tot, inp):
        # rematerialized: the (B, chunk, V) logits block is recomputed in the
        # backward pass instead of being stored (vocab up to 262k).
        # gold logit via one-hot contraction, NOT take_along_axis: the gather
        # would force an all-gather of the vocab-sharded logits (§Perf).
        h, lab = inp
        lg = jnp.einsum("bcd,vd->bcv", h, head).astype(jnp.float32)
        lse = jax.nn.logsumexp(lg, axis=-1)
        onehot = (lab[..., None] == jnp.arange(lg.shape[-1])).astype(jnp.float32)
        gold = jnp.sum(lg * onehot, axis=-1)
        return tot + jnp.sum(lse - gold), None

    with jax.named_scope("head"):
        total, _ = jax.lax.scan(step, jnp.float32(0.0), (hc, lc))
    ce = total / (B * Sq)
    return ce + 0.01 * aux


# --------------------------------------------------------------------- decode
def _layer_cache(kind, cfg, batch, max_len, kv_quant=None):
    if kind in ("attn", "moe_attn", "global", "shared_attn"):
        window = cfg.window_size if cfg.attention_type == "sliding" else None
        return A.init_kv_cache(cfg, batch, max_len, window=window,
                               kv_quant=kv_quant)
    if kind == "local":
        return A.init_kv_cache(cfg, batch, max_len, window=cfg.window_size,
                               kv_quant=kv_quant)
    if kind == "rwkv":
        return S.init_rwkv6_state(cfg, batch)
    if kind == "mamba":
        return S.init_mamba2_state(cfg, batch)
    raise ValueError(kind)


def init_decode_state(cfg: ModelConfig, batch, max_len, kv_quant=None):
    n_sb, _ = superblock_layout(cfg)
    kinds = _layer_kinds(cfg)
    one = {f"l{i}": _layer_cache(k, cfg, batch, max_len, kv_quant)
           for i, k in enumerate(kinds)}
    # stack per superblock
    return jax.tree.map(lambda a: jnp.broadcast_to(a[None], (n_sb,) + a.shape), one)


def _apply_layer_decode(kind, lp, cache, x, index, cfg, shared):
    if kind in ("attn", "local", "global", "moe_attn", "shared_attn"):
        p = shared if kind == "shared_attn" else lp
        window = None
        if kind == "local" or (cfg.attention_type == "sliding" and kind in ("attn", "moe_attn")):
            window = cfg.window_size
        h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
        y, cache = A.attention_decode(p["attn"], h, cache, index, cfg, window=window)
        x = x + y
        h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
        if kind == "moe_attn":
            x = x + M.moe_apply(lp["moe"], h, cfg)
        else:
            x = x + L.swiglu(p["mlp"], h)
    elif kind == "rwkv":
        h = L.rmsnorm(lp["ln1"], x, cfg.norm_eps)
        y, new = S.rwkv6_mix(lp["rwkv"], h, cfg,
                             state={"S": cache["S"], "prev": cache["prev"]})
        x = x + y
        h = L.rmsnorm(lp["ln2"], x, cfg.norm_eps)
        y, prev_cm = S.rwkv6_channel_mix(lp["rwkv"], h, cfg, state=cache["prev_cm"])
        x = x + y
        cache = {"S": new["S"], "prev": new["prev"], "prev_cm": prev_cm}
    elif kind == "mamba":
        h = L.rmsnorm(lp["ln1"], x, cfg.norm_eps)
        y, cache = S.mamba2_mix(lp["mamba"], h, cfg, state=cache)
        x = x + y
        h = L.rmsnorm(lp["ln2"], x, cfg.norm_eps)
        x = x + L.swiglu(lp["mlp"], h)
    else:
        raise ValueError(kind)
    return x, cache


_ATTN_KINDS = ("attn", "local", "global", "moe_attn", "shared_attn")


def supports_batched_prefill(cfg: ModelConfig) -> bool:
    """True when every layer kind has a one-shot prefill path (attention
    families; recurrent ssm/hybrid states still prefill token-by-token)."""
    return all(k in _ATTN_KINDS for k in _layer_kinds(cfg))


def _embed_tokens(cfg, params, tokens):
    x = L.embed(params["embed"], tokens)
    if cfg.family != "ssm":
        x = x * float(np.sqrt(cfg.d_model))
    return x


def prefill_step(cfg: ModelConfig, params, state, inputs):
    """Batched prefill: run the WHOLE prompt through every layer in one jitted
    call, filling the decode cache (vs. the O(S) sequential reference loop).
    inputs: {"tokens": (B, S0)}. Returns (logits (B,V) of the last prompt
    token, new state)."""
    if not supports_batched_prefill(cfg):
        raise NotImplementedError(
            f"batched prefill needs attention-only layers, got {_layer_kinds(cfg)}")
    x = _embed_tokens(cfg, params, inputs["tokens"])
    kinds = _layer_kinds(cfg)
    shared = params.get("shared_attn")

    def scan_body(x, sb):
        sb_params, sb_cache = sb
        new_cache = {}
        for i, kind in enumerate(kinds):
            p = shared if kind == "shared_attn" else sb_params[f"l{i}"]
            lp = sb_params[f"l{i}"]
            window = None
            if kind == "local" or (cfg.attention_type == "sliding"
                                   and kind in ("attn", "moe_attn")):
                window = cfg.window_size
            h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
            y, c = A.attention_prefill(p["attn"], h, sb_cache[f"l{i}"], cfg,
                                       window=window)
            x = x + y
            h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
            if kind == "moe_attn":
                x = x + M.moe_apply(lp["moe"], h, cfg)
            else:
                x = x + L.swiglu(p["mlp"], h)
            new_cache[f"l{i}"] = c
        return x, new_cache

    x, new_caches = jax.lax.scan(scan_body, x, (params["blocks"], state))
    with jax.named_scope("head"):
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        lg = logits(cfg, params, x[:, -1:])[:, 0]
    return lg, new_caches


# -------------------------------------------------------------- paged decode
def init_paged_state(cfg: ModelConfig, num_blocks: int, block_size: int,
                     max_slots: int = None, kv_quant=None):
    """Per-superblock, per-layer sequence state, built by the layer's state
    provider (see models.state_providers):

      full / ring layers — paged KV pools (n_sb, num_blocks, bs, Hkv, hd);
        all layers share ONE block table per sequence, each layer owns its
        pool storage. Ring layers reuse the table's first ring_pages entries
        modulo the ring.
      rwkv / mamba layers — per-slot recurrent slabs (n_sb, max_slots, ...);
        no block accounting at all.

    The paged steps below carry this whole stacked state through their
    layer scan and address superblock i's storage by the loop index: writes
    scatter only the new tokens at [i, block, offset], reads gather only
    the blocks a table names at [i, ...] (the Pallas kernel takes the stack
    and the index), and recurrent slabs are read and written back at [i].
    With the state donated, XLA updates it in place. No hot path may slice
    a whole layer out of it: such a slice, or a scan over the state as
    ``xs``, copies the pool on every step.

    `max_slots` is required whenever the config has recurrent layers.
    `kv_quant` (KVQuantConfig) switches the paged pools to int8 values with
    per-vector f32 scales; the dict structure carries the mode so the jitted
    steps dispatch statically."""
    skinds = SP.state_kinds(cfg)
    if any(k in ("rwkv", "mamba") for k in skinds) and max_slots is None:
        raise ValueError("recurrent layers need max_slots for their state slab")
    n_sb, _ = superblock_layout(cfg)
    providers = SP.providers_for(cfg, num_blocks=num_blocks,
                                 block_size=block_size,
                                 max_slots=max_slots or 0,
                                 kv_quant=kv_quant)
    one = {f"l{i}": p.init_layer_state() for i, p in enumerate(providers)}
    return jax.tree.map(lambda a: jnp.broadcast_to(a[None], (n_sb,) + a.shape), one)


def _scan_pool(body, x, params, pool):
    """Scan the superblocks with (x, pool) as the carry (see
    :func:`init_paged_state`). ``body(x, pool, sb_params, i)`` gets the
    whole stacked pool and the superblock index ``i`` and returns
    ``(x, pool, y)``, the pool changed only where it wrote. Returns
    ``(x, pool, ys)``, ys stacked over superblocks."""
    n_sb = jax.tree.leaves(params["blocks"])[0].shape[0]

    def step(carry, xs):
        x, pool, y = body(*carry, *xs)
        return (x, pool), y

    (x, pool), ys = jax.lax.scan(
        step, (x, pool), (params["blocks"], jnp.arange(n_sb, dtype=jnp.int32)))
    return x, pool, ys


def _slab_at(slab, i):
    """Superblock i's rows of a stacked recurrent slab (small: per-slot
    state, not a pool)."""
    return jax.tree.map(lambda a: a[i], slab)


def _attn_block(kind, p, lp, h_in, cfg, attn_out):
    """Residual + MLP/MoE tail shared by every attention-layer dispatch."""
    x = h_in + attn_out
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    if kind == "moe_attn":
        return x + M.moe_apply(lp["moe"], h, cfg)
    return x + L.swiglu(p["mlp"], h)


def paged_decode_step(cfg: ModelConfig, params, pool, inputs, block_tables,
                      positions, attn_lens, *, impl=None, draft=0):
    """One-token decode for a continuous batch of slots, dispatching each
    layer to its state kind. inputs: {"token": (B,)}; block_tables: (B, P);
    positions: (B,) absolute position of each incoming token; attn_lens:
    (B,) tokens to attend over including the new one (0 = inactive slot).
    The layer scan carries ``pool`` and addresses superblock i by index
    (:func:`init_paged_state`): each paged layer scatters one token per
    slot in place and its attention reads the stack at i. Recurrent slabs
    are per-slot (B == max_slots) and their updates are masked for inactive
    slots, so slots mid-prefill are never corrupted by the batched decode.
    ``draft`` must match the engine's speculative K-1 (0 when speculation
    is off) so ring layers use the same enlarged ring as the verify step.
    ``impl`` picks the paged attention path (None: the platform's, see
    ``repro.kernels.platform``). Returns (logits (B,V), new pool)."""
    x = _embed_tokens(cfg, params, inputs["token"][:, None])
    kinds = _layer_kinds(cfg)
    skinds = SP.state_kinds(cfg)
    shared = params.get("shared_attn")
    active = attn_lens > 0

    def body(x, pool, sb_params, i):
        pool = dict(pool)
        for j, (kind, skind) in enumerate(zip(kinds, skinds)):
            name = f"l{j}"
            lp = sb_params[name]
            st = pool[name]
            if skind in ("full", "ring"):
                p = shared if kind == "shared_attn" else lp
                window = cfg.window_size if skind == "ring" else None
                rp = (SP.ring_pages(window, st["k"].shape[2], draft=draft)
                      if skind == "ring" else None)
                h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
                y, pool[name] = A.attention_decode_paged(
                    p["attn"], h, st, i, block_tables, positions, attn_lens,
                    cfg, impl=impl, window=window, ring_pages=rp)
                x = _attn_block(kind, p, lp, x, cfg, y)
            else:
                old = _slab_at(st, i)
                x, new = _apply_layer_decode(kind, lp, old, x, jnp.int32(0),
                                             cfg, shared)
                new = jax.tree.map(
                    lambda n, o: jnp.where(
                        active.reshape((-1,) + (1,) * (n.ndim - 1)), n, o),
                    new, old)
                pool[name] = jax.tree.map(lambda a, n: a.at[i].set(n), st,
                                          new)
        return x, pool, None

    x, pool, _ = _scan_pool(body, x, params, pool)
    with jax.named_scope("head"):
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        lg = logits(cfg, params, x)[:, 0]
    return lg, pool


def _recurrent_verify_layer(kind, lp, slab, x, cfg, shared):
    """Speculative verify through a recurrent layer: a K-step token scan of
    the decode path that CAPTURES every intermediate state. slab leaves:
    (max_slots, ...); x: (B, K, D) with B == max_slots. Returns
    (y (B, K, D), checkpoints) where checkpoint leaves are (K, max_slots,
    ...) — checkpoint j is the state after processing draft tokens 0..j, so
    the caller can roll rejected drafts back exactly by selecting
    checkpoint `k_accepted - 1` (state_providers.select_checkpoint)."""
    def body(st, t):
        xt = jax.lax.dynamic_slice_in_dim(x, t, 1, axis=1)        # (B,1,D)
        yt, new = _apply_layer_decode(kind, lp, st, xt, t, cfg, shared)
        return new, (yt[:, 0], new)

    _, (ys, cps) = jax.lax.scan(body, slab, jnp.arange(x.shape[1]))
    return ys.swapaxes(0, 1), cps


def paged_verify_step(cfg: ModelConfig, params, pool, tokens, block_tables,
                      base, qlims, *, impl=None):
    """Multi-query speculative verify for a continuous batch of slots.
    tokens: (B, K) — K draft tokens per slot, draft j at absolute position
    `base[b] + j`; qlims: (B,) number of draft positions that may commit
    K/V this step (0 = inactive slot). The layer scan carries ``pool`` and
    addresses superblock i by index (:func:`init_paged_state`). Paged
    layers write the first qlims[b] drafts' K/V in place (write-then-attend)
    and attend causally among the draft positions; recurrent layers read
    their slab at i and scan the K tokens capturing per-step checkpoint
    states for exact rollback. Returns (logits (B, K, V), new pool) where
    recurrent entries hold stacked checkpoints (n_sb, K, max_slots, ...) —
    the caller selects the accepted checkpoint via
    state_providers.select_checkpoint."""
    x = _embed_tokens(cfg, params, tokens)                        # (B, K, D)
    K = tokens.shape[1]
    kinds = _layer_kinds(cfg)
    skinds = SP.state_kinds(cfg)
    shared = params.get("shared_attn")

    def body(x, pool, sb_params, i):
        pool, cps = dict(pool), {}
        for j, (kind, skind) in enumerate(zip(kinds, skinds)):
            name = f"l{j}"
            lp = sb_params[name]
            st = pool[name]
            if skind in ("full", "ring"):
                p = shared if kind == "shared_attn" else lp
                window = cfg.window_size if skind == "ring" else None
                rp = (SP.ring_pages(window, st["k"].shape[2], draft=K - 1)
                      if skind == "ring" else None)
                h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
                y, pool[name] = A.attention_verify_paged(
                    p["attn"], h, st, i, block_tables, base, qlims, cfg,
                    impl=impl, window=window, ring_pages=rp)
                x = _attn_block(kind, p, lp, x, cfg, y)
            else:
                x, cps[name] = _recurrent_verify_layer(
                    kind, lp, _slab_at(st, i), x, cfg, shared)
        return x, pool, cps

    x, pool, cps = _scan_pool(body, x, params, pool)
    with jax.named_scope("head"):
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        lg = logits(cfg, params, x)                               # (B, K, V)
    return lg, {**pool, **cps}


def _recurrent_prefill_layer(kind, lp, slab, i, x, valids, slots, cfg,
                             shared):
    """Packed chunked prefill through a recurrent layer: a token scan of the
    decode path (recurrent state has no one-shot prefill), with per-segment
    state updates masked past `valids[g]` so each slab row ends at exactly
    its last real token. slab leaves: stacked (n_sb, max_slots, ...), read
    and written at superblock ``i``; x: (G, C, D); slots: (G,) slab row per
    segment — `slots[g] >= max_slots` marks a padded segment (its gather
    clamps to an arbitrary row and its write-back is dropped). Returns
    (y (G,C,D), new slab)."""
    max_slots = jax.tree.leaves(slab)[0].shape[1]
    st0 = jax.tree.map(lambda a: a[i, jnp.minimum(slots, max_slots - 1)],
                       slab)

    def body(st, t):
        xt = jax.lax.dynamic_slice_in_dim(x, t, 1, axis=1)        # (G,1,D)
        yt, new = _apply_layer_decode(kind, lp, st, xt, t, cfg, shared)
        keep = t < valids                                         # (G,)
        st = jax.tree.map(
            lambda n, o: jnp.where(
                keep.reshape((-1,) + (1,) * (n.ndim - 1)), n, o), new, st)
        return st, yt[:, 0]

    stf, ys = jax.lax.scan(body, st0, jnp.arange(x.shape[1]))
    y = ys.swapaxes(0, 1)                                         # (G, C, D)
    slab = jax.tree.map(lambda a, s: a.at[i, slots].set(s, mode="drop"),
                        slab, stf)
    return y, slab


def paged_prefill_packed(cfg: ModelConfig, params, pool, tokens, tables,
                         starts, valids, slots, *, draft=0):
    """Segment-masked packed prefill: one prompt chunk per segment, all
    segments in ONE device call. tokens: (G, C) int32 — segment g's chunk
    starts at absolute position `starts[g]` with the first `valids[g]`
    tokens real; tables: (S, P) block-table rows indexed by `slots` (the
    engine passes its full device table so the rows are gathered in-jit).
    `slots[g] >= S` marks an all-padding segment: its table gather clamps,
    its paged writes drop (valids[g] == 0) and its recurrent-slab write-back
    drops, so padded segments never touch sequence state. Segments' block
    tables are disjoint where written, so packing G chunks is bit-identical
    to G separate calls. The layer scan carries ``pool`` and addresses
    superblock i by index (:func:`init_paged_state`): chunk K/V is
    scattered in place and attention gathers the segments' blocks at i.
    Returns (logits (G, V) of each segment's last valid token, new pool)."""
    x = _embed_tokens(cfg, params, tokens)
    kinds = _layer_kinds(cfg)
    skinds = SP.state_kinds(cfg)
    shared = params.get("shared_attn")
    rows = jnp.take(tables, jnp.minimum(slots, tables.shape[0] - 1), axis=0)

    def body(x, pool, sb_params, i):
        pool = dict(pool)
        for j, (kind, skind) in enumerate(zip(kinds, skinds)):
            name = f"l{j}"
            lp = sb_params[name]
            st = pool[name]
            if skind in ("full", "ring"):
                p = shared if kind == "shared_attn" else lp
                h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
                if skind == "ring":
                    rp = SP.ring_pages(cfg.window_size, st["k"].shape[2],
                                       draft=draft)
                    y, pool[name] = A.attention_prefill_ring(
                        p["attn"], h, st, i, rows, starts, valids, cfg,
                        window=cfg.window_size, ring_pages=rp)
                else:
                    y, pool[name] = A.attention_prefill_paged(
                        p["attn"], h, st, i, rows, starts, valids, cfg)
                x = _attn_block(kind, p, lp, x, cfg, y)
            else:
                x, pool[name] = _recurrent_prefill_layer(
                    kind, lp, st, i, x, valids, slots, cfg, shared)
        return x, pool, None

    x, pool, _ = _scan_pool(body, x, params, pool)
    with jax.named_scope("head"):
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        idx = jnp.maximum(valids - 1, 0)                          # (G,)
        last = jnp.take_along_axis(x, idx[:, None, None], axis=1)  # (G,1,D)
        lg = logits(cfg, params, last)[:, 0]
    return lg, pool


def paged_prefill_step(cfg: ModelConfig, params, pool, tokens, table_row,
                       start, valid_len, slot, *, draft=0):
    """Chunked prefill of ONE sequence into its per-kind state (a G=1
    packed call). tokens: (1, C) chunk starting at absolute position
    `start`, first `valid_len` real. `slot` locates the sequence's
    recurrent slab rows; paged layers use `table_row` (P,). Returns
    (logits (1,V) of the chunk's last valid token, new pool)."""
    return paged_prefill_packed(
        cfg, params, pool, tokens, table_row[None],
        jnp.asarray(start, jnp.int32)[None],
        jnp.asarray(valid_len, jnp.int32)[None],
        jnp.asarray(slot, jnp.int32)[None], draft=draft)


def decode_step(cfg: ModelConfig, params, state, inputs, index):
    """One-token decode. inputs: {"token": (B,)} or {"embed": (B,D)}.
    index: scalar int32 absolute position. Returns (logits (B,V), new_state)."""
    if "embed" in inputs:
        x = inputs["embed"][:, None, :].astype(L.dtype_of(cfg))
    else:
        x = L.embed(params["embed"], inputs["token"][:, None])
        if cfg.family != "ssm":
            x = x * float(np.sqrt(cfg.d_model))
    kinds = _layer_kinds(cfg)
    shared = params.get("shared_attn")

    def scan_body(x, sb):
        sb_params, sb_cache = sb
        new_cache = {}
        for i, kind in enumerate(kinds):
            x, c = _apply_layer_decode(kind, sb_params[f"l{i}"], sb_cache[f"l{i}"],
                                       x, index, cfg, shared)
            new_cache[f"l{i}"] = c
        return x, new_cache

    x, new_caches = jax.lax.scan(scan_body, x, (params["blocks"], state))
    with jax.named_scope("head"):
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        lg = logits(cfg, params, x)[:, 0]
    return lg, new_caches
