"""The serving cells' common path: the engine built from seeded weights, a
client that streams every token to the host, the measured window, and the
comparison with the plain reference.

A traffic source (``perfbench/drivers/open_loop.py``, ``backlog.py``) says
when each request is due; everything else is here. Each request is timed
from when it was due. Each token is timed when its value is on the host: the
client reads every step's new tokens back, as a streaming server must, so
the times are delivery times and not dispatch times.
"""
from __future__ import annotations

import gc
import time
from collections import deque
from statistics import NormalDist

import numpy as np

from perfbench import bench, model

DRAIN_CAP_S = 60.0          # how long past the close a due request may take


# ------------------------------------------------------------------ traffic
def quantile_lengths(n: int, dist: dict) -> np.ndarray:
    """``n`` lengths that stand for a lognormal of ``dist['median']`` and
    ``dist['sigma']`` clipped to ``[min, max]``: its quantiles at the
    midpoints ``(i + 0.5) / n``. Every seed gets this same multiset."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.round(dist["median"] * np.exp(dist.get("sigma", 0.0) * z))
    return np.clip(x, dist["min"], dist["max"]).astype(np.int64)


def quantile_gaps(n: int, rate: float) -> np.ndarray:
    """``n`` gaps that stand for a Poisson process of ``rate`` per second:
    the exponential's quantiles at the midpoints."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


class Item:
    """One request of the traffic: due time, prompt, budget and, once
    added, the engine's id and every token with the host time it arrived."""
    __slots__ = ("due", "prompt", "max_new", "counted", "rid", "added",
                 "tokens", "times")

    def __init__(self, due, prompt, max_new, counted):
        self.due, self.prompt, self.max_new = due, prompt, int(max_new)
        self.counted = counted
        self.rid = self.added = None
        self.tokens, self.times = [], []

    @property
    def done(self):
        return len(self.tokens) >= self.max_new


class Draws:
    """The two random streams of a serving run. ``order`` orders the mix's
    lengths and gaps and is seeded by the traffic file's ``order_seed``:
    the order of the work changes the tail of TTFT by a third from one
    order to another (PERF.md), so every run gets the same order.
    ``tokens`` is seeded by the run's seed and draws the prompts' ids."""

    def __init__(self, seed, traffic):
        seed = int(seed)
        self.order = np.random.default_rng(traffic["order_seed"])
        self.tokens = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32])


def make_items(draws, traffic, n, vocab, counted):
    """``n`` requests with the mix's lengths in the order ``draws.order``
    gives (prompt and answer lengths permuted apart) and token ids from
    ``draws.tokens``; due times unset."""
    plens = draws.order.permutation(quantile_lengths(n, traffic["prompt"]))
    olens = draws.order.permutation(quantile_lengths(n, traffic["output"]))
    return [Item(None, draws.tokens.integers(0, vocab, int(p)).astype(
        np.int32), o, counted) for p, o in zip(plens, olens)]


# ------------------------------------------------------------------- client
class Client:
    """Feeds the engine the requests that are due and reads back every
    token as soon as its step is done."""

    def __init__(self, engine, clock=time.perf_counter):
        import jax
        self.eng = engine
        self.clock = clock
        self.queue = deque()          # not yet added, by due time
        self.items = {}               # rid -> Item
        self.lateness = []            # added - due, seconds
        self.steps = 0
        # host spans on the profiler's clock, to name the device's idle gaps
        self.annotate = jax.profiler.TraceAnnotation

    def add(self, item):
        with self.annotate("bench/add_request"):
            item.rid = self.eng.add_request(item.prompt, item.max_new)
        item.added = self.clock()
        self.items[item.rid] = item
        self.lateness.append(item.added - item.due)

    def in_flight(self):
        s = self.eng.scheduler
        return len(s.waiting) + len(s.running)

    def pump(self, until, done=lambda: False, refill=None):
        """Step until ``until`` (host clock) or ``done()``. ``refill(now)``,
        where given, returns items to add at once (a backlog)."""
        eng = self.eng
        while True:
            now = self.clock()
            if now >= until or done():
                return
            if refill is not None:
                for it in refill(now):
                    it.due = now
                    self.add(it)
            while self.queue and self.queue[0].due <= now:
                self.add(self.queue.popleft())
            if not eng.scheduler.has_work:
                nxt = self.queue[0].due if self.queue else until
                with self.annotate("bench/wait_for_arrival"):
                    time.sleep(max(0.0, min(nxt, until) - self.clock()))
                continue
            emitted = eng.step()
            self.steps += 1
            with self.annotate("bench/read_tokens"):
                self._deliver(emitted)

    def _deliver(self, emitted):
        memo = {}
        for rid in dict.fromkeys(emitted):
            it = self.items[rid]
            toks = self.eng.requests[rid].out_tokens
            for i in range(len(it.tokens), len(toks)):
                t = toks[i]
                if isinstance(t, tuple):            # (step vector, index)
                    vec, j = t
                    if id(vec) not in memo:
                        memo[id(vec)] = (np.asarray(vec), self.clock())
                    host, when = memo[id(vec)]
                    val = int(host[j])
                    toks[i] = val                   # drop the device ref
                else:
                    val, when = int(t), self.clock()
                it.tokens.append(val)
                it.times.append(when)


# ------------------------------------------------------------------ set-up
def build(cell, seed):
    """Weights from the seed, then the engine with its prefill buckets
    compiled. Returns (engine, sizes, timings)."""
    import jax
    from repro.serving.engine import Engine, EngineConfig
    spec = cell["spec"]
    arch = model.arch(spec)
    s, cfg = arch.sizes(spec), arch.model_config(spec)
    t0 = time.perf_counter()
    params = jax.block_until_ready(model.program_params(s, seed))
    t1 = time.perf_counter()
    eng = Engine(cfg, params, EngineConfig(**cell["traffic"]["engine"]))
    jax.block_until_ready(eng.pool_state)
    t2 = time.perf_counter()
    del params
    return eng, s, {"init_s": t1 - t0, "engine_s": t2 - t1}


def warm_paths(eng, vocab, rng):
    """Short requests through admission, prefill in each of the engine's
    segment counts, decode and finish, so that every small program those
    paths use is compiled before the traffic starts."""
    for g in eng.segment_buckets:
        for _ in range(g):
            eng.add_request(rng.integers(0, vocab, 8).astype(np.int32), 3)
        eng.drain()


def free_device():
    """Delete every array still on the device (the engine's weights, pool
    and slot state), so that the reference runs on an empty chip."""
    import jax
    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    gc.collect()


# ------------------------------------------------------------------ metrics
def latency_metrics(items, t_close):
    """TTFT per request due in the window and every gap between its
    consecutive tokens, in ms. A request with no first token by the end of
    the wait counts with the wait it had so far, and as failed."""
    ttft, gaps, failed = [], [], 0
    counted = [it for it in items if it.counted]
    for it in counted:
        if not it.done:
            failed += 1
        if it.times:
            ttft.append(1e3 * (it.times[0] - it.due))
        else:
            ttft.append(1e3 * (t_close + DRAIN_CAP_S - it.due))
        gaps.extend(1e3 * np.diff(it.times))
    return {"ttft_ms": ttft, "itl_ms": gaps, "attempted": len(counted),
            "failed": failed}


# -------------------------------------------------------------- correctness
def pick_sample(items, seed, check):
    """Requests the window finished, drawn from the seed: the longest (in
    prompt and served tokens) first, then others until ``check['tokens']``
    served tokens or ``check['requests']`` requests."""
    done = [it for it in items if it.counted and it.done]
    if not done:
        return []
    done.sort(key=lambda it: it.rid)
    longest = max(done, key=lambda it: len(it.prompt) + len(it.tokens))
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 7])
    rest = [done[i] for i in rng.permutation(len(done))
            if done[i] is not longest]
    out, n = [longest], len(longest.tokens)
    for it in rest:
        if n >= check["tokens"] or len(out) >= check["requests"]:
            break
        out.append(it)
        n += len(it.tokens)
    return out


def check(cell, seed, sample, *, control=False):
    """The widest gap by which a served token's logit lies below the plain
    float32 reference's best, over ``sample``; with ``control`` also the
    same reading for the token the reference in fp8 puts first."""
    ref = bench.load_module("reference", cell["spec"]["reference"])
    t0 = time.perf_counter()
    gaps = ref.served_gaps(cell["spec"], seed,
                           [it.prompt for it in sample],
                           [np.asarray(it.tokens, np.int32) for it in sample],
                           control=control)
    out = {"reference_s": time.perf_counter() - t0,
           "tokens_checked": int(sum(len(g) for g in gaps["served"])),
           "requests_checked": len(sample),
           "widest_logit_gap": float(max(np.max(g) for g in gaps["served"]))}
    if control:
        out["control_widest_logit_gap"] = float(
            max(np.max(g) for g in gaps["control"]))
    return out


# ------------------------------------------------------------- the window
class record_work:
    """While active, note what each decode and prefill dispatch computes:
    the live lengths of every decode step's sequences, and each prefill
    segment's (start, valid)."""

    def __init__(self, eng):
        self.eng = eng
        self.decode_lens, self.prefill = [], []

    def __enter__(self):
        eng = self.eng
        self._dec, self._pre = eng._decode, eng._prefill
        dec, pre = self._dec, self._pre

        def decode(params, pool, tokens, tables, seq_lens, active):
            lens, act = np.asarray(seq_lens), np.asarray(active)
            self.decode_lens.append([int(n) + 1 for n in lens[act]])
            return dec(params, pool, tokens, tables, seq_lens, active)

        def prefill(params, pool, tokens, tables, starts, valids, slots):
            for st, v in zip(np.asarray(starts), np.asarray(valids)):
                if v:
                    self.prefill.append((int(st), int(v)))
            return pre(params, pool, tokens, tables, starts, valids, slots)

        eng._decode, eng._prefill = decode, prefill
        return self

    def __exit__(self, *exc):
        self.eng._decode, self.eng._prefill = self._dec, self._pre
        return False


TRACED_S = 8.0              # seconds of a run under the profiler


def traced_stretch(ctx, client, refill=None):
    """With ``ctx.trace``, ``TRACED_S`` seconds of the loop under the
    profiler. Returns the capture and the work recorded under it, or
    (None, None); the trace is read once the run's timing is over."""
    from perfbench import trace as TR
    if not ctx.trace:
        return None, None
    with record_work(client.eng) as work, TR.capture(ctx.keep_trace) as cap:
        client.pump(client.clock() + TRACED_S, refill=refill)
    return cap, work


def queue_waits_ms(eng, items):
    """arrive -> admit per request, from the engine's own lifecycle tracer
    (both are host decisions, so the engine's clock is sound for them)."""
    tr = eng.telemetry.tracer
    out = []
    for it in items:
        a, b = tr.first(it.rid, "arrive"), tr.first(it.rid, "admit")
        if a is not None and b is not None:
            out.append(1e3 * (b - a))
    return out


def finish(ctx, client, s, timings, items, cap, work, e2e):
    """Close a serving run: read the trace, peak memory, free the chip, run
    the reference on the sample, and assemble what ``run.py`` prints."""
    eng = client.eng
    summary = cap.reduce() if cap is not None else None
    limit = ctx.cell["traffic"]["check"]["max_logit_gap"]
    obs = {"sizes": s, "trace": summary,
           "kind": ctx.devs[0].device_kind,
           "queue_wait_ms": queue_waits_ms(eng, [i for i in items
                                                  if i.counted]),
           "decode_lens": work.decode_lens if work else None,
           "prefill": work.prefill if work else None}
    device = bench.device_info(ctx.devs, summary)
    sample = pick_sample(items, ctx.seed, ctx.cell["traffic"]["check"])
    del eng, client
    free_device()
    control = getattr(ctx, "control", False)
    if sample:
        res = check(ctx.cell, ctx.seed, sample, control=control)
        gap = res["widest_logit_gap"]
    else:
        res, gap = {"requests_checked": 0}, float("inf")
    ctx.log(f"correctness: {res}")
    return {"correct": bool(gap <= limit), "e2e": e2e, "obs": obs,
            "device": device, "timings": timings, "reference": res,
            "checks": {"widest_logit_gap": {"value": gap, "limit": limit}}}
