"""Mixture-of-Experts layer: top-k router + capacity-based sort dispatch.

Survey mapping: experts are the survey's "model parallelism by neurons"
pushed to its modern extreme — expert weights are sharded over the 'model'
mesh axis (expert parallelism) and token dispatch manifests as all-to-all /
all-gather collectives in the lowered HLO (§5.2's all-to-all analysis).

Dispatch algorithm (memory-feasible for 128 experts, unlike one-hot combine):
  1. top-k expert ids per token, flatten to (T*k,) assignments
  2. position-in-expert via sort + segment arithmetic
  3. scatter tokens into an (E, C, D) buffer (capacity C, overflow dropped)
  4. per-expert SwiGLU via batched einsum
  5. scatter-add back weighted by router probs
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L


def init_moe(key, cfg):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    dt = L.dtype_of(cfg)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "router": L.dense_init(k1, (d, e), jnp.float32),
        "w_gate": L.dense_init(k2, (e, d, f), dt),
        "w_in": L.dense_init(k3, (e, d, f), dt),
        "w_out": L.dense_init(k4, (e, f, d), dt),
    }


@jax.named_scope("mlp")
def moe_apply(params, x, cfg, constrain=None):
    """x: (B, S, D) -> (B, S, D). constrain: optional fn(tensor, names) that
    applies sharding constraints on the dispatch buffers."""
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    T = B * S
    C = int(np.ceil(T * K / E * cfg.capacity_factor))
    C = max(C, 1)
    xt = x.reshape(T, D)

    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, K)                       # (T, K)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)       # renormalize

    flat_e = top_e.reshape(-1)                                   # (T*K,)
    flat_p = top_p.reshape(-1)

    # position of each assignment within its expert (stable w.r.t. token order)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    # rank within run of equal expert ids
    counts = jnp.bincount(flat_e, length=E)                      # (E,)
    starts = jnp.cumsum(counts) - counts                         # (E,)
    rank_sorted = jnp.arange(T * K) - starts[sorted_e]
    pos = jnp.zeros(T * K, jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))

    keep = pos < C
    safe_e = jnp.where(keep, flat_e, 0)
    safe_pos = jnp.where(keep, pos, C)                           # C = drop slot

    # §Perf: the token stream is NOT gathered by index — flat_t is just
    # repeat(arange(T), K), so jnp.repeat keeps the data-sharding local.
    # (The baseline's xt[flat_t] gather lowered to a full (T·K, D) f32
    # all-reduce per layer: 13.2e12 B/device, the dominant collective.)
    xt_rep = jnp.repeat(xt, K, axis=0) * keep[:, None].astype(x.dtype)
    buf = jnp.zeros((E, C + 1, D), x.dtype)
    buf = buf.at[safe_e, safe_pos].add(xt_rep)
    buf = buf[:, :C]                                             # (E, C, D)
    if constrain is not None:
        buf = constrain(buf, ("expert", "capacity", None))

    g = jnp.einsum("ecd,edf->ecf", buf, params["w_gate"])
    h = jnp.einsum("ecd,edf->ecf", buf, params["w_in"])
    act = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * h
    y = jnp.einsum("ecf,efd->ecd", act, params["w_out"])         # (E, C, D)
    if constrain is not None:
        y = constrain(y, ("expert", "capacity", None))

    # combine as a scatter-add keyed by an inverse (expert, slot) -> token
    # map, so the reduction over expert shards happens on the (T, D) output
    # in bf16 — not on a gathered (T·K, D) f32 intermediate (§Perf).
    tok_ids = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)
    token_of = jnp.full((E, C + 1), T, jnp.int32).at[safe_e, safe_pos].set(tok_ids)
    w_of = jnp.zeros((E, C + 1), jnp.float32).at[safe_e, safe_pos].set(flat_p * keep)
    weighted = y * w_of[:, :C, None].astype(y.dtype)             # (E, C, D)
    # batched 2-D scatter indices: no reshape of the sharded (E, C) dims
    # (a flat reshape would all-gather the capacity-sharded buffer, §Perf)
    out = jnp.zeros((T + 1, D), x.dtype).at[token_of[:, :C]].add(weighted)
    return out[:T].reshape(B, S, D)


@jax.named_scope("mlp")
def moe_apply_ep(params, x, cfg, plan):
    """Expert-parallel fast path (survey §5.2 made communication-optimal).

    Preconditions: num_experts % |model axis| == 0 and the plan shards
    experts over 'model' (qwen3: 128/16 = 8 experts per device).

    Insight: under the dp_tp plan the token activations are *replicated
    across the model axis* (they are sharded over batch axes only), so every
    device already holds the tokens its local experts need — dispatch is
    communication-free. Each device routes its local tokens to its local
    expert slice and the only collective is ONE bf16 psum of the (T_loc, D)
    partial output over 'model' per layer. The XLA-auto baseline instead
    all-gathered (T·K, D) scatter operands (§Perf pair 3: 294s → see
    EXPERIMENTS); this path moves ~1000× fewer bytes.

    Capacity semantics: per-(data-shard × expert) capacity
    C_loc = ceil(T_loc·K/E·capacity_factor) — drops can differ marginally
    from the global-capacity reference (documented approximation).
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = plan.mesh
    model_axes = plan.tensor_axes            # ("model",)
    batch_axes = plan.batch_axes
    E = cfg.num_experts
    ep = int(np.prod([mesh.shape[a] for a in model_axes]))
    E_loc = E // ep

    x_spec = P(batch_axes or None, None, None)
    p_spec = {
        "router": P(),
        "w_gate": P(model_axes, None, None),
        "w_in": P(model_axes, None, None),
        "w_out": P(model_axes, None, None),
    }

    def local(params_loc, x_loc):
        B, S, D = x_loc.shape
        T = B * S
        K = cfg.experts_per_token
        C = max(int(np.ceil(T * K / E * cfg.capacity_factor)), 1)
        xt = x_loc.reshape(T, D)

        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), params_loc["router"])
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, K)
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

        ep_rank = jax.lax.axis_index(model_axes[0]) if len(model_axes) == 1 \
            else jax.lax.axis_index(model_axes)
        lo = ep_rank * E_loc
        flat_e = top_e.reshape(-1)
        flat_p = top_p.reshape(-1)
        mine = (flat_e >= lo) & (flat_e < lo + E_loc)
        loc_e = jnp.clip(flat_e - lo, 0, E_loc - 1)

        # position within local expert (among my assignments only)
        key = jnp.where(mine, loc_e, E_loc)              # E_loc = discard bin
        order = jnp.argsort(key, stable=True)
        sorted_key = key[order]
        counts = jnp.bincount(key, length=E_loc + 1)
        starts = jnp.cumsum(counts) - counts
        rank_sorted = jnp.arange(T * K) - starts[sorted_key]
        pos = jnp.zeros(T * K, jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))

        keep = mine & (pos < C)
        safe_e = jnp.where(keep, loc_e, 0)
        safe_pos = jnp.where(keep, pos, C)

        xt_rep = jnp.repeat(xt, K, axis=0) * keep[:, None].astype(x_loc.dtype)
        buf = jnp.zeros((E_loc, C + 1, D), x_loc.dtype)
        buf = buf.at[safe_e, safe_pos].add(xt_rep)[:, :C]

        g = jnp.einsum("ecd,edf->ecf", buf, params_loc["w_gate"])
        h = jnp.einsum("ecd,edf->ecf", buf, params_loc["w_in"])
        act = jax.nn.silu(g.astype(jnp.float32)).astype(x_loc.dtype) * h
        y = jnp.einsum("ecf,efd->ecd", act, params_loc["w_out"])

        tok_ids = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)
        token_of = jnp.full((E_loc, C + 1), T, jnp.int32).at[safe_e, safe_pos].set(tok_ids)
        w_of = jnp.zeros((E_loc, C + 1), jnp.float32).at[safe_e, safe_pos].set(flat_p * keep)
        weighted = y * w_of[:, :C, None].astype(y.dtype)
        out = jnp.zeros((T + 1, D), x_loc.dtype).at[token_of[:, :C]].add(weighted)
        out = out[:T]
        # the ONLY collective: combine partial expert outputs across the
        # expert(model) axis
        out = jax.lax.psum(out, model_axes)
        return out.reshape(B, S, D)

    fn = shard_map(local, mesh=mesh,
                   in_specs=(p_spec, x_spec), out_specs=x_spec,
                   check_vma=False)
    return fn(params, x)


def ep_applicable(cfg, plan) -> bool:
    if plan is None or not cfg.num_experts:
        return False
    axes = plan.tensor_axes
    if not axes:
        return False
    ep = int(np.prod([plan.mesh.shape[a] for a in axes]))
    return cfg.num_experts % ep == 0 and cfg.num_experts >= ep


def load_balance_loss(params, x, cfg):
    """Auxiliary load-balancing loss (Shazeer-style): E * sum(f_e * p_e)."""
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    xt = x.reshape(-1, D)
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    _, top_e = jax.lax.top_k(probs, K)
    f = jnp.mean(jax.nn.one_hot(top_e, E).sum(axis=1), axis=0)   # fraction routed
    p = jnp.mean(probs, axis=0)
    return E * jnp.sum(f * p)
