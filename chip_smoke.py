#!/usr/bin/env python3
"""Start the system's main paths on a TPU and check what comes out.

  python chip_smoke.py                 # one chip: the serving engine
  python chip_smoke.py --four-chips    # four chips: the sharded trainer

One chip: phi4-mini-3.8b exactly as ``src/repro/configs/phi4_mini_3_8b.py``
gives it (32 layers, d 3072, 24/8 heads, hd 128, d_ff 8192, vocab 200064,
bf16), with random weights made from ``--seed`` inside one jit. Eight
requests (64-600 prompt tokens, two of them sharing a 256-token prefix,
32 new tokens each) go through the continuous-batching ``Engine``, whose
decode step attends through the Pallas paged kernel. The same prompts go
through ``serve.prefill`` / ``serve.generate``, the dense-cache path that
does not page. First-token logits (the engine's prefill) must agree within
``LOGIT_TOL``, and so must the logits of every engine decode step (the
paged kernel) with the dense decode step fed the engine's own tokens.

Four chips: stablelm-3b at full width, depth cut to ``TRAIN_LAYERS`` so
that one whole paper-mode replica (bf16 params, f32 SGD master, bf16
grads) fits a chip. ``launch.train`` trains it with plan dp_tp_zero1 on a
(2, 2) ("data", "model") mesh, its state built sharded from the start;
the comparison is the paper-mode data-parallel step with the explicit ring
allreduce on the same params and batches. The step-1 losses must agree
within ``LOSS_RTOL`` and the loss must fall in both.

The script exits non-zero and prints no result line when JAX finds no TPU
or any phase fails. On success the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Times it prints are smoke timings, not metrics. The persistent compile
cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, else at
``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import inspect
import json
import os
import sys
import time

import numpy as np

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

# First-token logits, engine (paged, chunked prefill) vs dense prefill.
# Both run the same bf16 weights, but the two programs tile their matmuls
# and attention differently, so each of the 32 layers may round its bf16
# output one ulp apart (2^-8 relative) and the differences add up through
# the residual stream. Logits of these random weights are O(1) (largest
# |logit| about 5); 0.25 is about 5% of the largest logit, well above that
# rounding and well below what a wrong mask, position or page would cause
# (errors of the order of the logits themselves).
LOGIT_TOL = 0.25
# Step-1 loss, sharded trainer vs paper-mode DP: the same bf16 forward pass
# partitioned two ways; the loss is an f32 mean over batch x seq tokens, so
# per-token rounding averages out. 5e-3 is below one bf16 ulp (2^-7).
LOSS_RTOL = 5e-3

PROMPT_LENS = (320, 320, 600, 64, 600, 64, 200, 200)
SHARED_PREFIX = 256          # prompts 0 and 1 share their first 256 tokens
WAVES = ((0, 2, 3, 6), (1, 4, 5, 7))   # 1 arrives after 0 is prefilled
NEW_TOKENS = 32
TRAIN_LAYERS = 12
TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ, TRAIN_LR = 4, 8, 512, 0.3


class SmokeError(RuntimeError):
    """A phase failed or no TPU was found."""


def require_tpu(count=None):
    """The device list when JAX sees a TPU (``count`` of them if given)."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SmokeError(f"no TPU: JAX sees {len(devs)} "
                         f"{devs[0].platform} device(s)")
    if count is not None and len(devs) != count:
        raise SmokeError(f"need {count} TPU chips, JAX sees {len(devs)}")
    return devs


class CompileLog:
    """Compile seconds per jitted function and persistent-cache hits, read
    from JAX's monitoring events (registered once per process)."""
    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = collections.defaultdict(float)
        self.count = collections.Counter()
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == self.BACKEND:
            name = kw.get("fun_name", "?")
            self.seconds[name] += duration
            self.count[name] += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def report(self, log):
        for name, s in sorted(self.seconds.items(), key=lambda kv: -kv[1]):
            if s >= 0.5:
                log(f"  compile {name}: {s:.1f}s over {self.count[name]} "
                    f"compile(s) (smoke timing)")
        log(f"  persistent compile cache: {self.hits} hit(s), "
            f"{self.misses} miss(es)")


def make_prompts(vocab, seed, lens=PROMPT_LENS, shared=SHARED_PREFIX):
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, vocab, shared)
    prompts = [rng.integers(0, vocab, n) for n in lens]
    prompts[0][:shared] = prefix
    prompts[1][:shared] = prefix
    return [p.astype(np.int32) for p in prompts]


def smoke_engine_config(lens=PROMPT_LENS, new_tokens=NEW_TOKENS):
    """Pool for phi4-mini on one 16 GB chip: 1024 blocks of 16 tokens,
    2.15 GB in bf16. The decode and prefill steps write it in place (the
    layer scan carries it, models/transformer.py), so params + pool must
    fit."""
    from repro.serving.engine import EngineConfig
    per_seq = -(-(max(lens) + new_tokens) // 16)
    return EngineConfig(block_size=16, num_blocks=1024,
                        max_blocks_per_seq=per_seq, max_slots=8,
                        prefill_chunk=512, prefills_per_step=2)


def _capture_prefill_logits(eng):
    """Record each engine prefill dispatch's segments and logits, so the
    smoke can read every request's first-token logits."""
    calls = []
    inner = eng._prefill

    def prefill(params, pool, tokens, tables, starts, valids, slots):
        greedy, logits, pool = inner(params, pool, tokens, tables, starts,
                                     valids, slots)
        calls.append((np.asarray(tokens), np.asarray(starts),
                      np.asarray(valids), logits))
        return greedy, logits, pool

    eng._prefill = prefill
    return calls


def _first_logits(calls, prompt):
    """Logits of the prefill segment that ended ``prompt``."""
    n = len(prompt)
    found = [logits[j] for tokens, starts, valids, logits in calls
             for j in range(len(valids))
             if valids[j] and starts[j] + valids[j] == n
             and np.array_equal(tokens[j, :valids[j]],
                                prompt[starts[j]:n])]
    if len(found) != 1:
        raise SmokeError(f"{len(found)} prefill segments end a prompt of "
                         f"{n} tokens")
    return np.asarray(found[0], np.float32)


def _capture_decode_logits(eng):
    """Record each engine decode dispatch's logits per decoding request,
    keyed by (request id, position of the token fed), with that token."""
    seen = {}
    inner = eng._decode

    def decode(params, pool, tokens, tables, seq_lens, active):
        slots = {r.slot: r.rid for r in eng.scheduler.decode_batch()}
        out = inner(params, pool, tokens, tables, seq_lens, active)
        toks, lens = np.asarray(tokens), np.asarray(seq_lens)
        logits = np.asarray(out[1], np.float32)
        for slot, rid in slots.items():
            seen[rid, int(lens[slot])] = (int(toks[slot]), logits[slot])
        return out

    eng._decode = decode
    return seen


def serve_phase(cfg, engine_cfg, *, seed=0, lens=PROMPT_LENS,
                shared=SHARED_PREFIX, waves=WAVES, new_tokens=NEW_TOKENS,
                log=print):
    """Serve ``lens``-long prompts through the Engine in two waves and
    compare with the dense-cache path. Returns the comparison's numbers;
    raises SmokeError when they fail."""
    import jax
    import jax.numpy as jnp
    from repro.models import transformer as T
    from repro.serving import serve
    from repro.serving.engine import Engine

    def init_params(key):
        return T.init_params(cfg, key)

    t0 = time.perf_counter()
    params = jax.block_until_ready(
        jax.jit(init_params)(jax.random.PRNGKey(seed)))
    n_bytes = sum(a.nbytes for a in jax.tree.leaves(params))
    log(f"params: {n_bytes / 1e9:.2f} GB in {cfg.dtype}, made from seed "
        f"{seed} in {time.perf_counter() - t0:.1f}s (smoke timing)")

    prompts = make_prompts(cfg.vocab_size, seed, lens, shared)
    t0 = time.perf_counter()
    eng = Engine(cfg, params, engine_cfg)
    log(f"engine built (prefill buckets compiled) in "
        f"{time.perf_counter() - t0:.1f}s (smoke timing)")
    # the jitted decode step, under the telemetry's recompile tracker
    decode = inspect.unwrap(eng._decode, stop=lambda f: hasattr(f, "lower"))
    calls = _capture_prefill_logits(eng)
    steps = _capture_decode_logits(eng)
    rids = {}
    t0 = time.perf_counter()
    for i in waves[0]:
        rids[i] = eng.add_request(prompts[i], new_tokens)
    for _ in range(8 * len(lens)):
        if all(eng.requests[rids[i]].got_first for i in waves[0]):
            break
        eng.step()
    else:
        raise SmokeError("the first wave never finished its prefill")
    for wave in waves[1:]:
        for i in wave:
            rids[i] = eng.add_request(prompts[i], new_tokens)
    outs = eng.drain()
    log(f"engine served {len(outs)} requests, "
        f"{sum(len(o) for o in outs.values())} tokens in "
        f"{time.perf_counter() - t0:.1f}s incl. decode compile (smoke "
        f"timing); stats {eng.stats}")
    eng_tokens = [outs[rids[i]] for i in range(len(lens))]
    eng_logits = [_first_logits(calls, p) for p in prompts]
    decode_text = decode.lower(eng.params, eng.pool_state, eng.next_tok,
                               eng.tables, eng.seq_lens,
                               eng.active).as_text()
    prefix_hits = eng.stats["prefix_hit_tokens"]
    del eng, calls, outs

    max_len = max(lens) + new_tokens
    dense_step = jax.jit(serve.make_serve_step(cfg))
    ref_tokens, ref_logits, step_errs = [], [], []
    t0 = time.perf_counter()
    for i, p in enumerate(prompts):
        logits, cache = serve.prefill(cfg, params, p[None], max_len)
        ref_logits.append(np.asarray(logits[0], np.float32))
        # teacher-forced: the dense decode step fed the engine's tokens
        # must give the logits of the engine's decode step at each position
        out = eng_tokens[i]
        for k in range(len(out) - 1):
            pos = len(p) + k
            if (rids[i], pos) not in steps:
                raise SmokeError(f"no engine decode step fed position {pos} "
                                 f"of request {i}")
            fed, eng_step = steps[rids[i], pos]
            if fed != out[k]:
                raise SmokeError(f"request {i}'s decode step at {pos} was "
                                 f"fed {fed}, not its token {out[k]}")
            logits, cache = dense_step(params, cache,
                                       {"token": jnp.asarray(out[k:k + 1])},
                                       jnp.int32(pos))
            step_errs.append(float(np.max(np.abs(
                eng_step - np.asarray(logits[0], np.float32)))))
        ref_tokens.append(np.asarray(
            serve.generate(cfg, params, p[None], new_tokens,
                           max_len=max_len)[0]))
    log(f"dense reference ran in {time.perf_counter() - t0:.1f}s incl. "
        f"compiles (smoke timing)")

    errs = [float(np.max(np.abs(a - b)))
            for a, b in zip(eng_logits, ref_logits)]
    scale = max(float(np.max(np.abs(b))) for b in ref_logits)
    same = sum(int(np.sum(a == b)) for a, b in zip(eng_tokens, ref_tokens))
    total = sum(len(b) for b in ref_tokens)
    firsts = sum(int(a[0] == b[0]) for a, b in zip(eng_tokens, ref_tokens))
    res = {
        "requests": len(eng_tokens),
        "tokens": int(sum(len(t) for t in eng_tokens)),
        "prefix_hit_tokens": int(prefix_hits),
        "decode_has_tpu_custom_call": "tpu_custom_call" in decode_text,
        "logit_max_abs_err": max(errs),
        "decode_steps_checked": len(step_errs),
        "decode_logit_max_abs_err": max(step_errs),
        "logit_max_abs": scale,
        "first_tokens_same": firsts,
        "greedy_tokens_same": same,
        "greedy_tokens_total": total,
    }
    log(f"engine vs dense first-token logits: max |diff| {max(errs):.4f} "
        f"(per request {[round(e, 4) for e in errs]}; largest |logit| "
        f"{scale:.3f}; tolerance {LOGIT_TOL})")
    log(f"engine decode steps vs dense decode fed the same tokens: "
        f"{len(step_errs)} steps, max |diff| {max(step_errs):.4f}, median "
        f"{float(np.median(step_errs)):.4f} (tolerance {LOGIT_TOL})")
    log(f"identical greedy tokens: {same}/{total} = {same / total:.3f}; "
        f"identical first tokens: {firsts}/{len(lens)}")
    if any(len(t) != new_tokens for t in eng_tokens):
        raise SmokeError("a request did not get all its tokens")
    if max(errs) > LOGIT_TOL:
        raise SmokeError(f"first-token logits differ by {max(errs):.4f} "
                         f"> {LOGIT_TOL}")
    if max(step_errs) > LOGIT_TOL:
        raise SmokeError(f"decode-step logits differ by {max(step_errs):.4f} "
                         f"> {LOGIT_TOL}")
    return res


def train_phase(cfg, *, seed=0, steps=TRAIN_STEPS, batch=TRAIN_BATCH,
                seq=TRAIN_SEQ, lr=TRAIN_LR, model_axis=2, log=print):
    """Train with plan dp_tp_zero1 on a (devices/model_axis, model_axis)
    mesh, then with paper-mode ring-allreduce DP from the same seed. Both
    take ``steps`` SGD steps on one SyntheticLM batch, so a loss that does
    not fall means the update is wrong, not that the batches differ.
    Returns both loss curves; raises SmokeError when the step-1 losses
    differ by more than LOSS_RTOL or a loss does not fall."""
    from repro.data.pipeline import SyntheticLM
    from repro.launch import train as TR
    from repro.optim import make_optimizer

    opt = make_optimizer("sgd", lr=lr, grad_clip=1.0)
    one = next(SyntheticLM(cfg.vocab_size, seq, seed=seed).batches(batch, 1))
    common = dict(batch=batch, seq=seq, seed=seed, data=[one] * steps,
                  log_every=1, log=lambda s: log("  " + s))
    log(f"sharded trainer: plan dp_tp_zero1, model axis {model_axis}")
    state, sharded = TR.train(cfg, opt, plan="dp_tp_zero1",
                              model_axis=model_axis, **common)
    del state
    log("paper mode: explicit ring allreduce DP, one replica per device")
    state, paper = TR.train(cfg, opt, paper_mode=True, algorithm="ring",
                            **common)
    del state
    rel = abs(sharded[0] - paper[0]) / abs(paper[0])
    log(f"losses: sharded {sharded}; paper mode {paper}")
    log(f"step-1 loss relative difference {rel:.2e} (tolerance {LOSS_RTOL})")
    if rel > LOSS_RTOL:
        raise SmokeError(f"step-1 losses differ by {rel:.2e} > {LOSS_RTOL}")
    for name, ls in (("sharded", sharded), ("paper", paper)):
        if not ls[-1] < ls[0]:
            raise SmokeError(f"{name} loss did not fall: {ls}")
    return {"sharded": sharded, "paper": paper, "step1_rel_diff": rel}


def _memory(devs, log):
    for d in devs:
        stats = d.memory_stats() or {}
        log(f"  {d}: peak_bytes_in_use "
            f"{stats.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB of "
            f"{stats.get('bytes_limit', 0) / 2**30:.2f} GiB")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sharded-trainer phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    def log(msg):
        print(msg, flush=True)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise SmokeError(f"no repro package under {SRC}")
    sys.path.insert(0, SRC)
    from repro.configs.base import get_config
    from repro.launch.compile_cache import enable_compile_cache

    devs = require_tpu(4 if args.four_chips else None)
    log(f"device: {devs[0].device_kind} x {len(devs)} "
        f"(platform {devs[0].platform})")
    log(f"compile cache: {enable_compile_cache()}")
    compiles = CompileLog()

    if args.four_chips:
        cfg = dataclasses.replace(get_config("stablelm-3b"),
                                  num_layers=TRAIN_LAYERS)
        log(f"config: {cfg.name} d {cfg.d_model}, heads {cfg.num_heads}/"
            f"{cfg.num_kv_heads}, hd {cfg.resolved_head_dim}, d_ff "
            f"{cfg.d_ff}, vocab {cfg.vocab_size}, {cfg.dtype}; layers cut "
            f"32 -> {cfg.num_layers}; batch {TRAIN_BATCH} x seq {TRAIN_SEQ}, "
            f"SGD lr {TRAIN_LR} (grad clip 1), {TRAIN_STEPS} steps on one "
            f"batch")
        train_phase(cfg, seed=args.seed, log=log)
    else:
        from repro.kernels import platform
        cfg = get_config("phi4-mini-3.8b")
        ecfg = smoke_engine_config()
        log(f"config: {cfg.name} layers {cfg.num_layers}, d {cfg.d_model}, "
            f"heads {cfg.num_heads}/{cfg.num_kv_heads}, hd "
            f"{cfg.resolved_head_dim}, d_ff {cfg.d_ff}, vocab "
            f"{cfg.vocab_size}, {cfg.dtype}")
        log(f"engine: {ecfg}; paged attention {platform.paged_attn_impl()}, "
            f"interpret {platform.interpret()}")
        res = serve_phase(cfg, ecfg, seed=args.seed, log=log)
        log(f"decode program contains tpu_custom_call: "
            f"{res['decode_has_tpu_custom_call']}")
        if not res["decode_has_tpu_custom_call"]:
            raise SmokeError("the decode program holds no Pallas kernel")
    compiles.report(log)
    _memory(devs, log)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
