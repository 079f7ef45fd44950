"""Batched serving: prefill + token-by-token decode with KV/recurrent cache.

`make_serve_step` builds the jitted one-token step used by the decode dry-run
shapes (decode_32k, long_500k): ONE new token against a cache of seq_len.
`generate` drives a full sampling loop (used by examples/serve_demo.py) and
is the bit-exactness oracle for the continuous-batching engine across ALL
families (full / sliding / ssm / hybrid — per-layer state providers).
`engine_generate` routes the same request shape through the Engine in one
call for demos, benchmarks, and equality tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import parallelism as par
from repro.models import transformer as T


def make_serve_step(cfg, plan=None):
    """serve_step(params, cache, inputs, index) -> (logits (B,V), new cache)."""

    def serve_step(params, cache, inputs, index):
        ctx = par.plan_context(plan) if plan is not None else _null()
        with ctx:
            return T.decode_step(cfg, params, cache, inputs, index)

    return serve_step


def jit_serve_step(cfg, plan, params_abs, cache_abs, inputs_abs):
    step = make_serve_step(cfg, plan)
    p_sh = plan.param_shardings(params_abs)
    c_sh = plan.cache_shardings(cache_abs)
    i_sh = jax.tree.map(
        lambda l: NamedSharding(plan.mesh, plan.spec_for_batch_leaf("token", l.shape)),
        inputs_abs)
    rep = NamedSharding(plan.mesh, P())
    return jax.jit(
        step,
        in_shardings=(p_sh, c_sh, i_sh, rep),
        out_shardings=(None, c_sh),
        donate_argnums=(1,),
    )


class _null:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


# jitted-step caches keyed by the (hashable, frozen) ModelConfig so repeated
# generate() calls don't re-trace
@functools.lru_cache(maxsize=None)
def _cached_decode_step(cfg):
    def dense_decode_step(p, c, tok, i):
        return T.decode_step(cfg, p, c, {"token": tok}, i)
    return jax.jit(dense_decode_step)


@functools.lru_cache(maxsize=None)
def _cached_prefill_step(cfg):
    def dense_prefill_step(p, c, toks):
        return T.prefill_step(cfg, p, c, {"tokens": toks})
    return jax.jit(dense_prefill_step)


def sample(logits, key, temperature=1.0):
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits / temperature, axis=-1).astype(jnp.int32)


def prefill(cfg, params, prompt_tokens, max_len, *, prefill_mode="auto",
            kv_quant=None):
    """Fill a dense decode cache of ``max_len`` positions with the prompt.
    Returns (logits (B, V) of the last prompt token, cache).

    The whole prompt goes through ONE jitted call (`prefill_step`);
    `prefill_mode="loop"` runs it token by token as a reference oracle
    ("auto" falls back to it for recurrent families without a batched
    prefill). ``kv_quant`` stores the dense KV caches int8 + per-vector
    scales."""
    B, S0 = prompt_tokens.shape
    cache = T.init_decode_state(cfg, B, max_len, kv_quant=kv_quant)
    if prefill_mode not in ("auto", "batched", "loop"):
        raise ValueError(f"unknown prefill_mode {prefill_mode!r}")
    if prefill_mode == "auto":
        prefill_mode = "batched" if T.supports_batched_prefill(cfg) else "loop"
    # labeled spans so device traces separate the prefill and decode phases
    # (the engine labels its phases the same way — serving.telemetry)
    with jax.profiler.TraceAnnotation("serve/prefill"):
        if prefill_mode == "batched":
            return _cached_prefill_step(cfg)(params, cache, prompt_tokens)
        step = _cached_decode_step(cfg)     # reference: token-by-token
        logits = None
        for i in range(S0):
            logits, cache = step(params, cache, prompt_tokens[:, i],
                                 jnp.int32(i))
        return logits, cache


def generate(cfg, params, prompt_tokens, max_new, *, key=None, temperature=0.0,
             max_len=None, prefill_mode="auto", kv_quant=None):
    """Greedy/temperature generation for token-input models: `prefill`,
    then one jitted decode step per token. ``kv_quant`` stores the dense KV
    caches int8 + per-vector scales — the non-paged reference the quantized
    engine must match token-for-token."""
    key = key if key is not None else jax.random.PRNGKey(0)
    B, S0 = prompt_tokens.shape
    max_len = max_len or (S0 + max_new)
    logits, cache = prefill(cfg, params, prompt_tokens, max_len,
                            prefill_mode=prefill_mode, kv_quant=kv_quant)
    step = _cached_decode_step(cfg)
    out = []
    with jax.profiler.TraceAnnotation("serve/decode"):
        for j in range(max_new):
            key, sub = jax.random.split(key)
            tok = sample(logits, sub, temperature)
            out.append(tok)
            logits, cache = step(params, cache, tok, jnp.int32(S0 + j))
    return jnp.stack(out, axis=1)


def engine_generate(cfg, params, prompts, max_news, *, engine_cfg=None,
                    plan=None, return_engine=False):
    """Greedy generation for a batch of VARIABLE-length prompts through the
    continuous-batching Engine (any family the state providers cover: full,
    sliding, ssm, hybrid). `prompts`: list of 1-D int token arrays;
    `max_news`: per-request generation budgets. Returns a list of np arrays
    in request order — greedy outputs are bit-identical to per-request
    `generate` calls. With `return_engine=True` also returns the drained
    Engine so callers can read `engine.telemetry` (request timelines, metric
    snapshots, exporters)."""
    from repro.serving.engine import Engine, EngineConfig
    eng = Engine(cfg, params, engine_cfg or EngineConfig(), plan=plan)
    rids = [eng.add_request(p, int(m)) for p, m in zip(prompts, max_news)]
    outs = eng.drain()
    outs = [outs[r] for r in rids]
    return (outs, eng) if return_engine else outs
