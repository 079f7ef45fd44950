"""Serving demo: train a tiny model on the copy task until it can copy, then
serve it two ways — the legacy batched loop (`serve.generate`, now with
one-shot batched prefill) and the continuous-batching engine (paged KV cache,
chunked prefill, mixed-length requests joining and leaving the batch). A
replay wave then shows prefix caching: repeated prompts alias their cached
KV blocks and skip most of prefill, with bit-identical outputs. The engine's
telemetry is read out along the way: per-request lifecycle timelines (TTFT,
queue wait), the compiled-step-variant count, a JSONL trace export replayed
back into the same timelines, and a Prometheus-format metric snapshot. An
oversubscription wave then serves the same requests through an optimistic
engine (prompt-only admission, on-demand decode-block growth) and forces a
mid-flight preemption: the victim's prefix is registered in the cache, the
request is evicted and later resumed, and its greedy output stays
bit-identical. A speculative wave then serves the same requests with
n-gram self-drafting — the copy task is the prompt-lookahead drafter's
best case, so each verify step advances several positions at once, still
bit-identical. A final hybrid-config wave smokes the per-layer state
providers end to end: a zamba2-style mamba2+shared-attention model served
through the same engine (recurrent slabs + paged KV behind one block
table), bit-identical to `serve.generate`.

    PYTHONPATH=src python examples/serve_demo.py
"""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import parallelism as par
from repro.data.pipeline import copy_task
from repro.launch.mesh import make_mesh
from repro.models import transformer as T
from repro.optim import make_optimizer
from repro.serving import serve
from repro.serving.engine import (Engine, EngineConfig, OversubConfig,
                                  SpecConfig)
from repro.train import trainer


def main():
    cfg = ModelConfig(name="copy", family="dense", num_layers=2, d_model=128,
                      num_heads=4, num_kv_heads=4, head_dim=32, d_ff=256,
                      vocab_size=32, loss_chunk=32, attn_chunk=32, remat=False)
    plan = par.make_plan("dp", make_mesh())
    opt = make_optimizer("adam", lr=2e-3, grad_clip=1.0)
    state = trainer.init_state(cfg, opt, jax.random.PRNGKey(0))
    step = jax.jit(trainer.make_train_step(cfg, opt, plan))

    seq = 32
    for i in range(250):
        batch = copy_task(32, seq, cfg.vocab_size, seed=i)
        state, m = step(state, batch)
        if i % 50 == 0:
            print(f"step {i:3d} loss {float(m['loss']):.4f}")

    # serve: prompt = [pattern, first half of its copy]; model must finish it
    test = copy_task(4, seq, cfg.vocab_size, seed=9999)
    half = seq // 2
    keep = half // 2
    prompt = test["tokens"][:, :half + keep]
    out = serve.generate(cfg, state["params"], jnp.asarray(prompt),
                         max_new=keep, temperature=0.0)
    expect = test["tokens"][:, half + keep:half + 2 * keep]
    acc = float(np.mean(np.asarray(out) == expect))
    print(f"legacy static batch: copy accuracy over {keep} tokens x4: {acc:.2f}")

    # engine: the same requests, but MIXED lengths — each request keeps a
    # different amount of the copy, so a static batch would have to pad
    eng = Engine(cfg, state["params"],
                 EngineConfig(block_size=8, num_blocks=64, max_blocks_per_seq=8,
                              max_slots=4, prefill_chunk=16))
    keeps = [keep, keep // 2, keep - 2, 3]
    rids, expects = [], []
    for b, kp in enumerate(keeps):
        p = test["tokens"][b, :half + kp]
        rids.append(eng.add_request(p, max_new=kp))
        expects.append(test["tokens"][b, half + kp:half + 2 * kp])
        eng.step()                       # requests arrive staggered
    outs = eng.drain()
    hits = sum(int(np.sum(outs[r] == e)) for r, e in zip(rids, expects))
    total = sum(len(e) for e in expects)
    print(f"engine (mixed lengths x4): copy accuracy {hits / total:.2f} "
          f"({eng.stats['decode_steps']} decode steps, "
          f"{eng.stats['prefill_chunks']} prefill chunks, "
          f"occupancy {eng.stats['occupancy_sum'] / max(eng.stats['decode_steps'], 1):.2f})")
    assert eng.block_pool.num_free == 64, "engine leaked KV blocks"

    # prefix caching: replay the same prompts — their full prompt blocks are
    # now in the prefix index, so prefill is (almost) entirely skipped and
    # the greedy outputs are bit-identical to the first wave
    chunks_before = eng.stats["prefill_chunks"]
    rids2 = [eng.add_request(test["tokens"][b, :half + kp], max_new=kp)
             for b, kp in enumerate(keeps)]
    outs2 = eng.drain()
    for r1, r2 in zip(rids, rids2):
        np.testing.assert_array_equal(outs[r1], outs2[r2])
    print(f"engine replay with prefix caching: "
          f"{eng.stats['prefix_hit_tokens']} prompt tokens served from cache, "
          f"{eng.stats['prefill_chunks'] - chunks_before} prefill chunks "
          f"(vs {chunks_before} cold), outputs bit-identical")
    assert eng.stats["prefix_hit_tokens"] > 0, "prefix cache never hit"
    assert eng.block_pool.num_free == 64, "engine leaked KV blocks"

    # telemetry readout: lifecycle timelines, recompile tracking, exporters
    from repro.serving import telemetry as TM
    tel = eng.telemetry
    for rid in rids:
        tl = tel.request_timeline(rid)
        print(f"  request {rid}: queue wait {tl['queue_wait'] * 1e3:.2f} ms, "
              f"TTFT {tl['ttft'] * 1e3:.2f} ms, "
              f"{len(tl['decode_tokens'])} decode tokens")
    print(f"compiled step variants: {tel.recompiles.total} "
          f"{tel.recompiles.variants()} — fixed across both waves, i.e. "
          f"zero serving-time recompilation")
    fd, trace_path = tempfile.mkstemp(suffix=".jsonl")
    os.close(fd)
    try:
        n_events = tel.export_jsonl(trace_path)
        replay = TM.replay_jsonl(trace_path)
        for rid in rids:
            assert replay[rid]["ttft"] == tel.request_timeline(rid)["ttft"]
        print(f"JSONL trace: {n_events} events exported and replayed into "
              f"{len(replay)} per-request timelines (TTFTs match live)")
    finally:
        os.unlink(trace_path)
    prom = tel.prometheus_text().splitlines()
    picks = [l for l in prom if l.startswith(("engine_tokens_emitted_total",
                                              "engine_prefix_hit_tokens",
                                              "pool_registrations_total",
                                              "engine_request_ttft"))]
    print("prometheus snapshot excerpt:")
    for line in picks[:6]:
        print(f"  {line}")

    # oversubscription wave: an optimistic engine admits with only its prompt
    # blocks reserved and appends decode blocks on demand; forcing a
    # preemption mid-flight exercises the full victim rollback — prefix
    # registered in the cache, request evicted, then resumed from the cached
    # prefix with bit-identical greedy output
    ov = Engine(cfg, state["params"],
                EngineConfig(block_size=8, num_blocks=24, max_blocks_per_seq=8,
                             max_slots=4, prefill_chunk=16,
                             oversub=OversubConfig()))
    ov_rids, ov_refs = [], []
    for b, kp in enumerate(keeps):
        p = test["tokens"][b, :half + kp]
        ov_rids.append(ov.add_request(p, max_new=kp, priority=b % 2))
        ref = serve.generate(cfg, state["params"], jnp.asarray(p)[None],
                             max_new=kp, temperature=0.0)
        ov_refs.append(np.asarray(ref)[0])
    for _ in range(3):
        ov.step()
    forced = next(r for r in ov_rids if ov.preempt_request(r))
    ov_outs = ov.drain()
    for r, ref in zip(ov_rids, ov_refs):
        np.testing.assert_array_equal(ov_outs[r], ref)
    tl = ov.telemetry.request_timeline(forced)
    print(f"engine oversubscription wave x{len(ov_rids)}: "
          f"{ov.stats['block_appends']} on-demand block appends, "
          f"{ov.stats['preemptions']} preemption(s), "
          f"{ov.stats['resumes']} resume(s), outputs bit-identical")
    print(f"  request {forced} was evicted mid-flight and resumed: "
          f"{tl['preempts']} preempt/resume cycle(s), "
          f"{tl['preempted_s'] * 1e3:.2f} ms out of the batch")
    assert ov.stats["preemptions"] >= 1 and ov.stats["resumes"] >= 1
    assert ov.block_pool.num_free == 24, "oversub engine leaked KV blocks"

    # speculative wave: the copy task is the n-gram drafter's best case —
    # the continuation has literally been seen before (it IS the pattern),
    # so the prompt-lookahead drafter proposes the true tokens and each
    # verify step advances several positions at once, bit-identically
    sp = Engine(cfg, state["params"],
                EngineConfig(block_size=8, num_blocks=64, max_blocks_per_seq=8,
                             max_slots=4, prefill_chunk=16,
                             spec=SpecConfig(k=6)))
    sp_rids = [sp.add_request(test["tokens"][b, :half + kp], max_new=kp)
               for b, kp in enumerate(keeps)]
    sp_outs = sp.drain()
    for r0, r in zip(rids, sp_rids):
        np.testing.assert_array_equal(outs[r0], sp_outs[r])
    reg = sp.telemetry.registry
    drafted = reg.get("engine_draft_tokens_total").value
    accepted = reg.get("engine_accepted_tokens_total").value
    vsteps = reg.get("engine_verify_steps_total").value
    emitted = sum(len(sp_outs[r]) for r in sp_rids)
    print(f"engine speculative wave (n-gram self-drafting, k=6) x"
          f"{len(sp_rids)}: {accepted}/{drafted} drafts accepted, "
          f"{(emitted - len(sp_rids)) / max(vsteps, 1):.2f} tokens/verify "
          f"step, outputs bit-identical")
    assert accepted > 0, "speculation never accepted a draft"

    # hybrid wave: mamba2 layers carry O(1) recurrent slabs, the shared
    # attention layer pages KV — the same engine serves both behind one
    # block table, matching serve.generate token for token
    hcfg = ModelConfig(name="copy-hybrid", family="hybrid",
                       hybrid_ssm_per_attn=1, num_layers=2, d_model=64,
                       num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
                       vocab_size=32, loss_chunk=32, attn_chunk=32,
                       remat=False, dtype="float32", ssm_state_dim=8,
                       ssm_head_dim=32)
    hparams = T.init_params(hcfg, jax.random.PRNGKey(7))
    rng = np.random.default_rng(0)
    hprompts = [rng.integers(0, 32, size=int(n)).astype(np.int32)
                for n in (5, 11, 8, 3)]
    hnews = [6, 4, 9, 7]
    heng_cfg = EngineConfig(block_size=8, num_blocks=32, max_blocks_per_seq=8,
                            max_slots=4, prefill_chunk=8)
    houts = serve.engine_generate(hcfg, hparams, hprompts, hnews,
                                  engine_cfg=heng_cfg)
    for out, p, mn in zip(houts, hprompts, hnews):
        ref = serve.generate(hcfg, hparams, jnp.asarray(p)[None],
                             max_new=mn, temperature=0.0)
        np.testing.assert_array_equal(out, np.asarray(ref)[0])
    print(f"engine hybrid wave (mamba2 slabs + paged shared attention) x"
          f"{len(hprompts)}: outputs bit-identical to serve.generate")


if __name__ == "__main__":
    main()
