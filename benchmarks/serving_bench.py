"""Serving throughput: continuous-batching engine vs legacy static batch,
plus prefix caching on a shared-prefix workload.

Workload `mixed` — chat-shaped mixed lengths (short prompts, skewed
generation budgets, 3x more requests than decode slots) — the regime where
static batching collapses: every batch pads to its longest prompt AND
decodes for its longest budget while finished rows burn compute.

  * legacy — successive `serve.generate` calls over static batches of
    max_slots requests (FCFS, left-padded, max_new = batch max). This is the
    STRONG baseline: it already uses the one-shot batched prefill; the
    seed's token-by-token prefill loop is strictly slower.
  * engine — the same requests through `Engine.step()` with chunked prefill
    and continuous batching.

Workload `shared` — every request repeats a common system-prompt prefix
(chat template / few-shot header) plus a short unique suffix. The engine is
run with prefix caching ON vs OFF (cache primed by one untimed request in
both modes so the comparison is steady-state); rows report cache hit rate,
prefill tokens saved, and the on/off speedup.

Per-family mode (`--config-family full|sliding|ssm|hybrid|all`) runs a
chat-shaped workload through the engine for that model family's state
providers and reports tokens/s, per-slot sequence-state memory (the
provider's per-kind cost: paged KV for full, ring-capped KV for sliding,
O(1) slabs for ssm, the mix for hybrid), and peak block-pool utilization.

Rows: tokens/s, engine decode-batch occupancy, p50/p99 per-token latency
(wall time of the engine step that emitted each token, measured in a
separate synced pass so async dispatch can't hide compute), TTFT and
queue-wait p50/p99 per workload (derived from the engine's request-lifecycle
telemetry in the same synced pass, warmup/prime requests excluded), the
telemetry-overhead check (tokens/s with telemetry off vs on), and the
prefix-cache metrics. Packed-prefill rows: TTFT under packing vs the B=1
chunked baseline (`serving_mixed_unpacked_ttft_*`,
`serving_packed_prefill_ttft_speedup`), per-(chunk x segments) bucket
dispatch counts, and `serving_*_prefill_variants` — prefill trace keys seen
vs declared AOT buckets, where "new=0" certifies the warmup compiled every
variant steady-state serving dispatches. The per-family sweep also reports
the total number of distinct compiled step variants (recompile tracker).

Workload `oversub` — the open-loop overload study (ROADMAP item 2): Poisson
arrivals at 2x the engine's decode capacity with heavy-tailed prompt/output
lengths and a priority mix (`repro.serving.workloads.open_loop_arrivals`),
replayed through BOTH schedulers — optimistic admission + victim preemption
(`OversubConfig`) vs. conservative up-front full reservation. Rows: goodput
(completed tokens/s) for each, the goodput ratio (headline number in the
deterministic step domain — tokens per fixed-shape engine step — with the
noisier wall-clock ratio alongside), preemption/resume rates, and p99
TTFT/TPOT from a synced pass of the optimistic engine. This is the
tail-latency-under-oversubscription measurement the paper's concurrency
analysis calls for: the mean survives overload, the p99 is what collapses.

Speculation rows (`--spec` / `benchmarks/run.py --serving-spec`): the
decode-heavy `spec_workload` through every family with speculative decoding
off vs on. The on-runs draft with a ReplayDrafter fed the off-run's own
greedy outputs — a perfectly aligned draft source — so the speedup row is
the multi-query verify path's CEILING (acceptance ~1, k tokens per step);
the separate n-gram row reports the model-dependent acceptance of the
self-drafting prompt-lookahead. Greedy outputs are asserted bit-identical
on/off inside the bench, and each on-run reports its verify variant count
(must stay 1: the AOT-warmed shape).

Quantized-KV rows (`--kv-quant` / `benchmarks/run.py --serving-kv-quant`):
per KV-holding family (full / sliding / hybrid), engine tokens/s and
per-slot state memory with the paged pools stored fp32 vs int8 + per-vector
scales (`EngineConfig.kv_quant`); a kernel-isolation row timing the paged
decode kernel on identical pool contents fp32 vs int8 (the in-kernel
dequant-multiply overhead); and pool-capacity rows that hold the pool BYTE
budget fixed and report peak resident sequences on the mixed and
shared-prefix workloads — the memory win the quantization buys back as
batch capacity. Each quant run asserts its decode variant count stayed at
the single AOT-warmed shape.

`main(workload=...)` accepts "mixed" | "shared" | "oversub" | "both" (all
three); `benchmarks/run.py --serving-workload` passes it through
(`--serving-family` likewise forwards the family sweep, `--serving-seed`
the workload seed). `--trace-out PREFIX` writes each workload's synced-pass
event log to `PREFIX.<workload>.jsonl` — replayable into per-request
TTFT/decode timelines via `repro.serving.telemetry.replay_jsonl`.
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.configs.base import ModelConfig
from repro.models import state_providers as SP
from repro.models import transformer as T
from repro.serving import serve
from repro.serving import workloads as W
from repro.serving.engine import (Engine, EngineConfig, KVQuantConfig,
                                  OversubConfig, ReplayDrafter, SpecConfig)

FAMILIES = ("full", "sliding", "ssm", "hybrid")


def _cfg():
    return ModelConfig(name="serving-bench", family="dense", num_layers=2,
                       d_model=256, num_heads=4, num_kv_heads=2, head_dim=64,
                       d_ff=512, vocab_size=256, loss_chunk=64, attn_chunk=128,
                       remat=False, dtype="float32")


def _family_cfg(family):
    base = dict(num_layers=2, d_model=256, num_heads=4, num_kv_heads=2,
                head_dim=64, d_ff=512, vocab_size=256, loss_chunk=64,
                attn_chunk=128, remat=False, dtype="float32")
    if family == "full":
        return ModelConfig(name="sb-full", family="dense", **base)
    if family == "sliding":
        return ModelConfig(name="sb-sliding", family="dense",
                           attention_type="sliding", window_size=32, **base)
    if family == "ssm":
        return ModelConfig(name="sb-ssm", family="ssm", ssm_type="rwkv6",
                           ssm_head_dim=64, **base)
    if family == "hybrid":
        return ModelConfig(name="sb-hybrid", family="hybrid",
                           hybrid_ssm_per_attn=1, ssm_state_dim=16,
                           ssm_head_dim=64, **base)
    raise ValueError(f"unknown family {family!r}")


MAX_SLOTS = 8


def _fresh_engine(cfg, params, prompts, *, prefix_caching=True, prime=None,
                  telemetry=True, packed_prefill=True):
    eng = Engine(cfg, params, EngineConfig(
        block_size=16, num_blocks=256, max_blocks_per_seq=8,
        max_slots=MAX_SLOTS, prefill_chunk=32, prefills_per_step=4,
        prefix_caching=prefix_caching, telemetry=telemetry,
        packed_prefill=packed_prefill))
    # warmup: compile decode once on a throwaway request (every prefill
    # bucket is already AOT-compiled at engine construction)
    skip = {eng.add_request(prompts[0][:4], 2)}
    eng.drain()
    if prime is not None:
        # populate the prefix index (no-op with caching off; run in both
        # modes so the timed region does identical request work)
        skip.add(eng.add_request(prime, 1))
        eng.drain()
    return eng, skip


@dataclasses.dataclass
class EngineRun:
    """One engine measurement pass. `latencies` is None for throughput
    runs (free-running steps) and a per-token wall-time array for synced
    runs (`collect_latency=True`)."""
    tokens: int
    wall: float
    occupancy: float
    prefix_hits: int
    latencies: object
    engine: object
    skip: set


def _run_engine(cfg, params, prompts, max_news, *, prefix_caching=True,
                prime=None, telemetry=True, packed_prefill=True,
                collect_latency=False) -> EngineRun:
    """One driver for both measurement modes. Throughput pass
    (`collect_latency=False`): free-running steps, one sync at the end, so
    the host-ahead pipeline is measured. Latency pass: each step's emitted
    tokens are read back to the host before the next step, so per-step wall
    time — and the engine's TTFT, which closes at a request's first
    ``deliver`` — are delivery times, not async dispatch. Warmup and
    cache-priming tokens/steps are excluded from every reported number."""
    eng, skip = _fresh_engine(cfg, params, prompts,
                              prefix_caching=prefix_caching, prime=prime,
                              telemetry=telemetry,
                              packed_prefill=packed_prefill)
    warm = dict(eng.stats)
    for p, mn in zip(prompts, max_news):
        eng.add_request(p, mn)
    lat = [] if collect_latency else None
    t0 = time.perf_counter()
    if collect_latency:
        while eng.scheduler.has_work:
            s = time.perf_counter()
            emitted = eng.step()
            for rid in dict.fromkeys(emitted):
                eng.output(rid)                    # blocks: values on host
            lat.extend([time.perf_counter() - s] * len(emitted))
    outs = eng.drain()                             # materializes every token
    wall = time.perf_counter() - t0
    total = sum(o.shape[0] for rid, o in outs.items() if rid not in skip)
    occ = ((eng.stats["occupancy_sum"] - warm["occupancy_sum"])
           / max(eng.stats["decode_steps"] - warm["decode_steps"], 1))
    hits = eng.stats["prefix_hit_tokens"] - warm["prefix_hit_tokens"]
    return EngineRun(total, wall, occ, hits,
                     None if lat is None else np.asarray(lat), eng, skip)


def _lifecycle_percentiles(eng, skip):
    """Per-request TTFT and queue-wait arrays from the engine's telemetry,
    excluding warmup/prime requests."""
    ttfts, waits = [], []
    for rid in eng.requests:
        if rid in skip:
            continue
        tl = eng.telemetry.request_timeline(rid)
        if tl["ttft"] is not None:
            ttfts.append(tl["ttft"])
        if tl["queue_wait"] is not None:
            waits.append(tl["queue_wait"])
    return np.asarray(ttfts), np.asarray(waits)


def _emit_lifecycle(tag, eng, skip, trace_out=None):
    ttfts, waits = _lifecycle_percentiles(eng, skip)
    for name, arr in ((f"serving_{tag}_ttft", ttfts),
                      (f"serving_{tag}_queue_wait", waits)):
        for q in (50, 99):
            emit(f"{name}_p{q}", float(np.percentile(arr, q)) * 1e6)
    if trace_out:
        path = f"{trace_out}.{tag}.jsonl"
        n = eng.telemetry.export_jsonl(path)
        emit(f"serving_{tag}_trace_events", None, f"{n}@{path}")


def _emit_prefill_variants(tag, eng):
    """Prefill trace keys seen vs. declared buckets (new must be 0 — the
    AOT warmup contract) plus per-bucket dispatch counts."""
    declared = len(eng.prefill_grid)
    seen = eng.telemetry.recompiles.unique("prefill")
    emit(f"serving_{tag}_prefill_variants", None,
         f"{seen}/{declared} declared (new={seen - declared})")
    for (c, g), n in sorted(eng.bucket_dispatches().items()):
        if n:
            emit(f"serving_{tag}_prefill_bucket_c{c}g{g}_dispatches", None,
                 str(n))


def _legacy_once(cfg, params, prompts, max_news):
    done = 0
    for i in range(0, len(prompts), MAX_SLOTS):
        bp, bn = prompts[i:i + MAX_SLOTS], max_news[i:i + MAX_SLOTS]
        S = max(p.shape[0] for p in bp)
        batch = np.zeros((len(bp), S), np.int32)
        for j, p in enumerate(bp):
            batch[j, S - p.shape[0]:] = p          # left-pad: keep tail intact
        jax.block_until_ready(serve.generate(
            cfg, params, jnp.asarray(batch), max_new=max(bn), temperature=0.0))
        done += sum(bn)                             # tokens anyone asked for
    return done


def _run_legacy(cfg, params, prompts, max_news):
    _legacy_once(cfg, params, prompts, max_news)    # warmup
    t0 = time.perf_counter()
    useful = _legacy_once(cfg, params, prompts, max_news)
    wall = time.perf_counter() - t0
    return useful, wall


def _run_legacy_loop(cfg, params, prompts, max_news):
    """The seed's serving loop: token-by-token sequential prefill (kept as
    `prefill_mode='loop'`), one static batch at a time."""
    def once():
        done = 0
        for i in range(0, len(prompts), MAX_SLOTS):
            bp, bn = prompts[i:i + MAX_SLOTS], max_news[i:i + MAX_SLOTS]
            S = max(p.shape[0] for p in bp)
            batch = np.zeros((len(bp), S), np.int32)
            for j, p in enumerate(bp):
                batch[j, S - p.shape[0]:] = p
            jax.block_until_ready(serve.generate(
                cfg, params, jnp.asarray(batch), max_new=max(bn),
                temperature=0.0, prefill_mode="loop"))
            done += sum(bn)
        return done
    once()                                           # warmup
    t0 = time.perf_counter()
    useful = once()
    wall = time.perf_counter() - t0
    return useful, wall


def _main_mixed(cfg, params, trace_out=None, seed=0):
    prompts, max_news = W.mixed_workload(seed=seed)

    thr = _run_engine(cfg, params, prompts, max_news)
    total, wall, occ = thr.tokens, thr.wall, thr.occupancy
    tps_engine = total / wall
    off = _run_engine(cfg, params, prompts, max_news, telemetry=False)
    total_o, wall_o = off.tokens, off.wall
    tps_off = total_o / wall_o
    useful, wall_legacy = _run_legacy(cfg, params, prompts, max_news)
    tps_legacy = useful / wall_legacy
    useful_l, wall_loop = _run_legacy_loop(cfg, params, prompts, max_news)
    tps_loop = useful_l / wall_loop
    sync = _run_engine(cfg, params, prompts, max_news, collect_latency=True)
    lat, eng_lat, skip = sync.latencies, sync.engine, sync.skip

    emit("serving_engine_tokens_per_s", wall / total * 1e6, f"{tps_engine:.1f}")
    emit("serving_telemetry_off_tokens_per_s", wall_o / total_o * 1e6,
         f"{tps_off:.1f}")
    emit("serving_telemetry_overhead", None,
         f"{wall / total / (wall_o / total_o):.3f}x")
    emit("serving_legacy_batched_tokens_per_s", wall_legacy / useful * 1e6,
         f"{tps_legacy:.1f}")
    emit("serving_legacy_loop_tokens_per_s", wall_loop / useful_l * 1e6,
         f"{tps_loop:.1f}")
    emit("serving_engine_occupancy", None, f"{occ:.3f}")
    emit("serving_engine_p50_token_latency", float(np.percentile(lat, 50)) * 1e6)
    emit("serving_engine_p99_token_latency", float(np.percentile(lat, 99)) * 1e6)
    _emit_lifecycle("mixed", eng_lat, skip, trace_out)
    _emit_prefill_variants("mixed", eng_lat)
    # packed-prefill TTFT vs. the B=1 chunked baseline (same synced-pass
    # methodology, packing off => one G=1 bucket-padded call per chunk)
    unp = _run_engine(cfg, params, prompts, max_news, packed_prefill=False,
                      collect_latency=True)
    eng_unp, skip_u = unp.engine, unp.skip
    ttft_p, _w = _lifecycle_percentiles(eng_lat, skip)
    ttft_u, _w = _lifecycle_percentiles(eng_unp, skip_u)
    for q in (50, 99):
        emit(f"serving_mixed_unpacked_ttft_p{q}",
             float(np.percentile(ttft_u, q)) * 1e6)
    emit("serving_packed_prefill_ttft_speedup", None,
         f"{np.percentile(ttft_u, 50) / np.percentile(ttft_p, 50):.2f}x")
    emit("serving_speedup_vs_legacy_batched", None,
         f"{tps_engine / tps_legacy:.2f}x")
    emit("serving_speedup_vs_legacy_loop", None, f"{tps_engine / tps_loop:.2f}x")


def _main_shared(cfg, params, trace_out=None, seed=0):
    prompts, max_news, prefix = W.shared_prefix_workload(seed=seed)
    prompt_tokens = sum(p.shape[0] for p in prompts)

    cache = _run_engine(cfg, params, prompts, max_news, prefix_caching=True,
                        prime=prefix)
    total_c, wall_c, hits = cache.tokens, cache.wall, cache.prefix_hits
    nocache = _run_engine(cfg, params, prompts, max_news, prefix_caching=False,
                          prime=prefix)
    total_n, wall_n = nocache.tokens, nocache.wall
    tps_cache, tps_nocache = total_c / wall_c, total_n / wall_n
    sync = _run_engine(cfg, params, prompts, max_news, prefix_caching=True,
                       prime=prefix, collect_latency=True)
    eng_lat, skip = sync.engine, sync.skip

    emit("serving_prefix_cache_tokens_per_s", wall_c / total_c * 1e6,
         f"{tps_cache:.1f}")
    emit("serving_prefix_nocache_tokens_per_s", wall_n / total_n * 1e6,
         f"{tps_nocache:.1f}")
    emit("serving_prefix_cache_hit_rate", None,
         f"{hits / prompt_tokens:.3f}")
    emit("serving_prefill_tokens_saved", None, str(int(hits)))
    emit("serving_prefix_cache_speedup", None,
         f"{tps_cache / tps_nocache:.2f}x")
    _emit_lifecycle("shared", eng_lat, skip, trace_out)
    _emit_prefill_variants("shared", eng_lat)


def _main_family(family, seed=0):
    """One model family through the engine: tokens/s, per-slot state memory
    (from the family's providers), and peak block-pool utilization."""
    cfg = _family_cfg(family)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    ecfg = EngineConfig(block_size=8, num_blocks=128, max_blocks_per_seq=16,
                        max_slots=MAX_SLOTS, prefill_chunk=16,
                        prefills_per_step=2)
    prompts, max_news = W.mixed_workload(n=16, seed=seed + 4)

    def run():
        eng = Engine(cfg, params, ecfg)
        for p, mn in zip(prompts, max_news):
            eng.add_request(p, mn)
        peak = 0.0
        t0 = time.perf_counter()
        while eng.scheduler.has_work:
            eng.step()
            peak = max(peak, eng.block_pool.utilization)
        outs = eng.drain()
        wall = time.perf_counter() - t0
        return eng, sum(o.shape[0] for o in outs.values()), wall, peak

    run()                                          # warmup / compile
    eng, total, wall, peak = run()

    # per-slot state budget at the workload's worst-case context length
    worst = max(p.shape[0] + m for p, m in zip(prompts, max_news))
    mem = SP.state_memory_per_slot(cfg, eng.providers, worst)
    emit(f"serving_family_{family}_tokens_per_s", wall / total * 1e6,
         f"{total / wall:.1f}")
    emit(f"serving_family_{family}_state_kb_per_slot", None,
         f"{mem / 1024:.1f}")
    emit(f"serving_family_{family}_peak_pool_utilization", None,
         f"{peak:.3f}")
    # distinct compiled step variants the run dispatched — a fixed set
    # (decode + the declared AOT prefill buckets [+ reset_slot for
    # recurrent kinds]); growth here is serving-time recompilation
    emit(f"serving_family_{family}_compiled_step_variants", None,
         str(eng.telemetry.recompiles.total))
    _emit_prefill_variants(f"family_{family}", eng)


OV_BLOCKS = 24       # tight pool: 384 KV tokens for up to 8 x 256-token seqs


def _ov_cfg():
    """The overload study runs a larger model than the closed-loop rows:
    the goodput gap between schedulers is a decode-occupancy gap, visible in
    wall time only when the per-step model compute dominates the per-token
    host bookkeeping both engines share."""
    return ModelConfig(name="serving-ov", family="dense", num_layers=4,
                       d_model=512, num_heads=8, num_kv_heads=4, head_dim=64,
                       d_ff=1024, vocab_size=256, loss_chunk=64,
                       attn_chunk=128, remat=False, dtype="float32")


def _ov_ecfg(oversub):
    """Engine config for the overload study. The pool is deliberately small
    relative to worst-case demand (8 slots x 16 blocks = 128 >> 24 blocks)
    and to the mean full reservation (~5 blocks x 8 slots), so up-front
    reservation is pool-bound at ~4-5 concurrent requests while optimistic
    admission keeps all 8 slots decoding and preempts on actual exhaustion."""
    return EngineConfig(block_size=16, num_blocks=OV_BLOCKS,
                        max_blocks_per_seq=16, max_slots=MAX_SLOTS,
                        prefill_chunk=32, prefills_per_step=4,
                        oversub=oversub)


def _run_open_loop(cfg, params, arrivals, ecfg, *, synced=False):
    """Replay an open-loop arrival trace: admit every arrival whose step has
    come, step the engine, repeat. Arrivals never wait for completions —
    under overload the waiting queue grows and the scheduler must cope.
    Returns (tokens, wall, steps, engine, skip)."""
    eng = Engine(cfg, params, ecfg)
    skip = {eng.add_request(arrivals[0].prompt[:4], 2)}   # decode warmup
    eng.drain()
    i, step = 0, 0
    t0 = time.perf_counter()
    while i < len(arrivals) or eng.scheduler.has_work:
        while i < len(arrivals) and arrivals[i].step <= step:
            a = arrivals[i]
            eng.add_request(a.prompt, a.max_new, priority=a.priority)
            i += 1
        if eng.scheduler.has_work:
            emitted = eng.step()
            if synced:                  # values on the host: TTFT closes
                for rid in dict.fromkeys(emitted):
                    eng.output(rid)
            step += 1
        else:
            step = arrivals[i].step                        # idle: fast-forward
    outs = eng.drain()
    wall = time.perf_counter() - t0
    tokens = sum(o.shape[0] for rid, o in outs.items() if rid not in skip)
    return tokens, wall, step, eng, skip


def _main_oversub(trace_out=None, seed=0):
    cfg = _ov_cfg()
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    arrivals = W.open_loop_arrivals(
        48, seed=seed, overload=2.0, max_slots=MAX_SLOTS, prompt_mean=12.0,
        prompt_max=32, out_mean=64.0, out_max=224)
    n = len(arrivals)

    tok_o, wall_o, steps_o, eng_o, _s = _run_open_loop(
        cfg, params, arrivals, _ov_ecfg(OversubConfig()))
    tok_f, wall_f, steps_f, eng_f, _s = _run_open_loop(
        cfg, params, arrivals, _ov_ecfg(None))
    gp_o, gp_f = tok_o / wall_o, tok_f / wall_f

    emit("serving_oversub_goodput_tokens_per_s", wall_o / tok_o * 1e6,
         f"{gp_o:.1f}")
    emit("serving_fullres_goodput_tokens_per_s", wall_f / tok_f * 1e6,
         f"{gp_f:.1f}")
    # same trace, same total work — the gap is pure scheduling, so the
    # headline ratio is measured in the step domain (engine steps have fixed
    # shapes and near-constant cost, and the count is deterministic given
    # (seed, params)); the wall-clock view rides along in the derived text
    emit("serving_oversub_goodput_ratio", None,
         f"{(tok_o / steps_o) / (tok_f / steps_f):.2f}x "
         f"(steps; wall {gp_o / gp_f:.2f}x)")
    emit("serving_oversub_tokens_per_step", None, f"{tok_o / steps_o:.2f}")
    emit("serving_fullres_tokens_per_step", None, f"{tok_f / steps_f:.2f}")
    st = eng_o.stats
    emit("serving_oversub_preempts_per_request", None,
         f"{st['preemptions'] / n:.3f}")
    emit("serving_oversub_resumes", None, str(st["resumes"]))
    emit("serving_oversub_block_appends", None, str(st["block_appends"]))

    # tail latencies from a synced pass of the optimistic engine: reading
    # each step's tokens makes TTFT a delivery time; TPOT below still reads
    # the dispatch events of a loop that blocks every step
    _t, _w, _n, eng_s, skip_s = _run_open_loop(
        cfg, params, arrivals, _ov_ecfg(OversubConfig()), synced=True)
    _emit_lifecycle("oversub", eng_s, skip_s, trace_out)
    tpots = []
    for rid in eng_s.requests:
        if rid in skip_s:
            continue
        tl = eng_s.telemetry.request_timeline(rid)
        if tl["first_token"] is not None and tl["decode_tokens"]:
            toks = [tl["first_token"]] + tl["decode_tokens"]
            tpots.append((toks[-1] - toks[0]) / (len(toks) - 1))
    for q in (50, 99):
        emit(f"serving_oversub_tpot_p{q}",
             float(np.percentile(tpots, q)) * 1e6)


SPEC_K = 8           # verify width for the speculation rows


def _spec_ecfg(spec):
    return EngineConfig(block_size=16, num_blocks=256, max_blocks_per_seq=8,
                        max_slots=MAX_SLOTS, prefill_chunk=32,
                        prefills_per_step=4, spec=spec)


def _run_spec(cfg, params, prompts, max_news, spec, streams=None):
    """One measured pass (second of two; the first warms the compile
    caches). With ``streams`` (one expected prompt++output stream per
    request, submit order) the spec config's ReplayDrafter is fed the true
    continuations — the high-acceptance limit. Returns (outputs by submit
    order, wall seconds, engine)."""
    def once():
        eng = Engine(cfg, params, _spec_ecfg(spec))
        rids = [eng.add_request(p, mn) for p, mn in zip(prompts, max_news)]
        if streams is not None:
            for rid, s in zip(rids, streams):
                eng.drafter.remember(rid, s)
        t0 = time.perf_counter()
        outs = eng.drain()
        wall = time.perf_counter() - t0
        return [outs[r] for r in rids], wall, eng
    once()
    return once()


def _main_spec(trace_out=None, seed=0):
    """Speculative decoding rows: per family, wall tokens/s with speculation
    off vs on (ReplayDrafter — a perfectly aligned draft source, so the row
    measures the verify path's ceiling), acceptance rate, and tokens per
    verify step; plus the self-drafting n-gram row on the full-attention
    family (model-dependent acceptance). Greedy outputs are bit-identical
    on/off — asserted here, not just claimed."""
    prompts, max_news = W.spec_workload(seed=seed)
    for fam in FAMILIES:
        cfg = _family_cfg(fam)
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        outs_off, wall_off, _e = _run_spec(cfg, params, prompts, max_news,
                                           None)
        # the off run's greedy outputs ARE the true continuations (greedy is
        # bit-identical on/off): replay them as drafts to measure the
        # high-acceptance limit of the verify path
        streams = [np.concatenate([p, o]) for p, o in zip(prompts, outs_off)]
        spec = SpecConfig(k=SPEC_K, drafter=ReplayDrafter())
        outs_on, wall_on, eng = _run_spec(cfg, params, prompts, max_news,
                                          spec, streams=streams)
        for a, b in zip(outs_off, outs_on):
            np.testing.assert_array_equal(a, b)
        total = sum(o.shape[0] for o in outs_on)
        snap = eng.telemetry.registry.snapshot()
        drafted = snap["engine_draft_tokens_total"]
        accepted = snap["engine_accepted_tokens_total"]
        vsteps = snap["engine_verify_steps_total"]
        emit(f"serving_spec_{fam}_off_tokens_per_s", wall_off / total * 1e6,
             f"{total / wall_off:.1f}")
        emit(f"serving_spec_{fam}_on_tokens_per_s", wall_on / total * 1e6,
             f"{total / wall_on:.1f}")
        emit(f"serving_spec_{fam}_speedup", None,
             f"{wall_off / wall_on:.2f}x")
        emit(f"serving_spec_{fam}_acceptance", None,
             f"{accepted / max(drafted, 1):.3f}")
        emit(f"serving_spec_{fam}_tokens_per_verify_step", None,
             f"{total / max(vsteps, 1):.2f}")
        emit(f"serving_spec_{fam}_verify_variants", None,
             str(eng.telemetry.recompiles.unique("verify")))

    # self-drafting n-gram lookahead on the full-attention family: no
    # oracle, acceptance is whatever the model's own stream offers
    cfg = _family_cfg("full")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    outs_off, wall_off, _e = _run_spec(cfg, params, prompts, max_news, None)
    outs_on, wall_on, eng = _run_spec(cfg, params, prompts, max_news,
                                      SpecConfig(k=4, drafter="ngram"))
    for a, b in zip(outs_off, outs_on):
        np.testing.assert_array_equal(a, b)
    snap = eng.telemetry.registry.snapshot()
    rate = (snap["engine_accepted_tokens_total"]
            / max(snap["engine_draft_tokens_total"], 1))
    emit("serving_spec_ngram_speedup", None, f"{wall_off / wall_on:.2f}x")
    emit("serving_spec_ngram_acceptance", None, f"{rate:.3f}")


KVQ_FAMILIES = ("full", "sliding", "hybrid")   # ssm holds no KV to quantize
KVQ_CAP_BLOCKS = 32  # fixed pool byte budget for the capacity rows (fp32)


def _kvq_ecfg(kv_quant, *, num_blocks=128, max_slots=MAX_SLOTS):
    return EngineConfig(block_size=8, num_blocks=num_blocks,
                        max_blocks_per_seq=16, max_slots=max_slots,
                        prefill_chunk=16, prefills_per_step=2,
                        kv_quant=kv_quant)


def _run_kvq(cfg, params, prompts, max_news, ecfg):
    """Two passes (first warms the compile caches); returns the measured
    pass's (engine, tokens, wall, peak resident sequences)."""
    def once():
        eng = Engine(cfg, params, ecfg)
        for p, mn in zip(prompts, max_news):
            eng.add_request(p, mn)
        peak = 0
        t0 = time.perf_counter()
        while eng.scheduler.has_work:
            eng.step()
            peak = max(peak, len(eng.scheduler.running))
        outs = eng.drain()
        wall = time.perf_counter() - t0
        return eng, sum(o.shape[0] for o in outs.values()), wall, peak
    once()
    return once()


def _kvq_kernel_overhead(mode, kvq_bits=8, iters=20):
    """Direct kernel timing: the paged decode kernel on the same pool
    contents, fp32 vs int8+scales — the dequant-multiply overhead in
    isolation (full mode for the dense family, ring mode for sliding)."""
    from repro.kernels.paged_attention import ops as PA
    from repro.kernels.quantize import quantize_kv
    B, Hq, Hkv, hd, bs, N, P = 8, 4, 2, 64, 16, 64, 8
    key = jax.random.PRNGKey(0)
    kk, kv_, kq = jax.random.split(key, 3)
    k_pool = jax.random.normal(kk, (N, bs, Hkv, hd), jnp.float32)
    v_pool = jax.random.normal(kv_, (N, bs, Hkv, hd), jnp.float32)
    q = jax.random.normal(kq, (B, Hq, hd), jnp.float32)
    tables = jnp.arange(B * P, dtype=jnp.int32).reshape(B, P) % N
    lens = jnp.full((B,), P * bs, jnp.int32)
    kw = {}
    if mode == "ring":
        kw = dict(window=bs * (P - 1), positions=lens - 1, ring_pages=P)
    qk, sk = quantize_kv(k_pool)
    qv, sv = quantize_kv(v_pool)

    def time_call(fn):
        jax.block_until_ready(fn())                 # compile
        t0 = time.perf_counter()
        for _ in range(iters):
            jax.block_until_ready(fn())
        return (time.perf_counter() - t0) / iters

    t_f32 = time_call(lambda: PA.paged_attention(q, k_pool, v_pool, tables,
                                                 lens, **kw))
    t_int8 = time_call(lambda: PA.paged_attention(q, qk, qv, tables, lens,
                                                  k_scale=sk, v_scale=sv,
                                                  **kw))
    return t_f32, t_int8


def _main_kv_quant(seed=0):
    """Quantized paged KV rows (ROADMAP item 4): per family, engine tokens/s
    and per-slot state memory with the pools fp32 vs int8+per-vector scales;
    the dequant-overhead row times the paged kernel alone on identical pool
    contents; the capacity rows hold the pool BYTE budget fixed (the fp32
    row's pool, ~`KVQ_CAP_BLOCKS` blocks) and report peak resident
    sequences on the mixed and shared-prefix workloads — the number int8
    must lift >=1.8x. Decode variant counts are asserted flat (==1): quant
    changes the traced pool pytree, so the warmup must have compiled it."""
    kvq = KVQuantConfig()
    prompts, max_news = W.mixed_workload(n=16, seed=seed + 4)
    worst = max(p.shape[0] + m for p, m in zip(prompts, max_news))
    for fam in KVQ_FAMILIES:
        cfg = _family_cfg(fam)
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        tps, kb = {}, {}
        for tag, q in (("fp32", None), ("int8", kvq)):
            eng, total, wall, _peak = _run_kvq(cfg, params, prompts,
                                               max_news, _kvq_ecfg(q))
            tps[tag] = total / wall
            kb[tag] = SP.state_memory_per_slot(cfg, eng.providers, worst)
            if q is not None:
                dv = eng.telemetry.recompiles.unique("decode")
                assert dv == 1, f"{fam}: {dv} decode variants with quant on"
                snap = eng.telemetry.registry.snapshot()
                emit(f"serving_kv_quant_{fam}_bytes_saved", None,
                     str(int(snap["kv_quant_bytes_saved_total"])))
                emit(f"serving_kv_quant_{fam}_decode_variants", None,
                     str(dv))
        emit(f"serving_kv_quant_{fam}_fp32_tokens_per_s", None,
             f"{tps['fp32']:.1f}")
        emit(f"serving_kv_quant_{fam}_int8_tokens_per_s",
             1.0 / tps["int8"] * 1e6, f"{tps['int8']:.1f}")
        emit(f"serving_kv_quant_{fam}_tokens_per_s_ratio", None,
             f"{tps['int8'] / tps['fp32']:.2f}x")
        emit(f"serving_kv_quant_{fam}_state_kb_per_slot", None,
             f"{kb['int8'] / 1024:.1f} (fp32 {kb['fp32'] / 1024:.1f}, "
             f"{kb['int8'] / kb['fp32']:.2f}x)")

    # dequant overhead in isolation: kernel wall time on identical contents
    for fam, mode in (("full", "full"), ("sliding", "ring")):
        t_f32, t_int8 = _kvq_kernel_overhead(mode)
        emit(f"serving_kv_quant_{fam}_kernel_overhead", None,
             f"{t_int8 / t_f32:.2f}x ({t_int8 * 1e6:.0f}us vs "
             f"{t_f32 * 1e6:.0f}us)")

    # pool capacity at a fixed byte budget: the fp32 pool's bytes buy
    # ~3.76x as many int8 blocks (2*hkv*hd*4 -> 2*hkv*(hd+4) per token)
    cfg = _family_cfg("full")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    hkv, hd = cfg.num_kv_heads, cfg.head_dim
    blocks_int8 = KVQ_CAP_BLOCKS * (2 * hkv * hd * 4) // (2 * hkv * (hd + 4))
    for wname, (wp, wm) in (
            ("mixed", W.mixed_workload(seed=seed)),
            ("shared", W.shared_prefix_workload(seed=seed)[:2])):
        res = {}
        for tag, q, nb in (("fp32", None, KVQ_CAP_BLOCKS),
                           ("int8", kvq, blocks_int8)):
            _e, _t, _w, peak = _run_kvq(
                cfg, params, wp, wm,
                _kvq_ecfg(q, num_blocks=nb, max_slots=16))
            res[tag] = peak
        emit(f"serving_kv_quant_{wname}_max_resident_fp32", None,
             f"{res['fp32']} ({KVQ_CAP_BLOCKS} blocks)")
        emit(f"serving_kv_quant_{wname}_max_resident_int8", None,
             f"{res['int8']} ({blocks_int8} blocks)")
        emit(f"serving_kv_quant_{wname}_capacity_ratio", None,
             f"{res['int8'] / max(res['fp32'], 1):.2f}x")


def main(workload: str = "both", config_family: str = None, trace_out=None,
         seed: int = 0, spec: bool = False, kv_quant: bool = False):
    if workload not in ("mixed", "shared", "oversub", "both", "none"):
        raise ValueError(f"unknown workload {workload!r}")
    if workload != "none":
        cfg = _cfg()
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        if workload in ("mixed", "both"):
            _main_mixed(cfg, params, trace_out, seed)
        if workload in ("shared", "both"):
            _main_shared(cfg, params, trace_out, seed)
        if workload in ("oversub", "both"):
            _main_oversub(trace_out, seed)
    if spec:
        _main_spec(trace_out, seed)
    if kv_quant:
        _main_kv_quant(seed)
    if config_family:
        fams = FAMILIES if config_family == "all" else (config_family,)
        for fam in fams:
            _main_family(fam, seed)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload",
                    choices=("mixed", "shared", "oversub", "both", "none"),
                    default="both")
    ap.add_argument("--config-family",
                    choices=FAMILIES + ("all",), default=None,
                    help="also run the per-family state-provider sweep")
    ap.add_argument("--spec", action="store_true",
                    help="also run the speculative-decoding rows (per-family "
                         "spec on/off, acceptance, tokens per verify step)")
    ap.add_argument("--kv-quant", action="store_true",
                    help="also run the quantized-KV rows (per-family tokens/s"
                         " and state-KB/slot fp32 vs int8, kernel dequant "
                         "overhead, pool capacity at a fixed byte budget)")
    ap.add_argument("--trace-out", default=None, metavar="PREFIX",
                    help="write each workload's synced-pass event log to "
                         "PREFIX.<workload>.jsonl (replay via "
                         "repro.serving.telemetry.replay_jsonl)")
    ap.add_argument("--seed", type=int, default=0,
                    help="workload-generator seed (arrival trace, lengths)")
    args = ap.parse_args()
    main(args.workload, args.config_family, args.trace_out, args.seed,
         args.spec, args.kv_quant)
