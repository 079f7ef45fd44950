"""Serving telemetry: metrics registry (streaming quantiles vs np.percentile),
request-lifecycle event ordering, recompile tracking (unique trace keys),
the engine's profiler spans (every step covered by its children) and the
named scopes of the compiled programs, exporters (JSONL replay + Prometheus
text), and the disabled-mode guarantees (no events, bit-identical greedy
outputs).
All CPU (`-m telemetry`, subset of `-m serving`)."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.models import transformer as T
from repro.serving import serve
from repro.serving import telemetry as TM
from repro.serving.engine import Engine, EngineConfig

pytestmark = [pytest.mark.serving, pytest.mark.telemetry]


# ------------------------------------------------------------------ registry
class TestRegistry:
    def test_counter_and_gauge(self):
        reg = TM.MetricsRegistry()
        c = reg.counter("c_total", "help text")
        c.inc()
        c.inc(4)
        assert c.value == 5
        with pytest.raises(TM.TelemetryError):
            c.inc(-1)
        g = reg.gauge("g")
        g.set(7)
        g.add(-3)
        assert g.value == 4
        assert reg.counter("c_total") is c          # get-or-create

    def test_kind_conflict_raises(self):
        reg = TM.MetricsRegistry()
        reg.counter("x")
        with pytest.raises(TM.TelemetryError):
            reg.gauge("x")

    def test_histogram_exact_below_compaction(self):
        """Until the buffer first compacts, quantiles are identical to
        np.percentile (linear interpolation) on the raw data."""
        rng = np.random.default_rng(0)
        data = rng.lognormal(mean=0.0, sigma=1.5, size=1000)
        h = TM.Histogram("h", cap=4096)
        for x in data:
            h.observe(x)
        for q in (0, 1, 10, 50, 90, 99, 100):
            np.testing.assert_allclose(h.quantile(q), np.percentile(data, q),
                                       rtol=1e-12)
        assert h.count == 1000
        np.testing.assert_allclose(h.sum, data.sum())
        assert h.min == data.min() and h.max == data.max()

    def test_histogram_streaming_accuracy(self):
        """Past the cap the sketch compacts; rank error must stay within 2%
        of the requested quantile on 20k heavy-tailed samples at cap=256."""
        rng = np.random.default_rng(7)
        data = rng.lognormal(mean=0.0, sigma=2.0, size=20_000)
        h = TM.Histogram("h", cap=256)
        for x in data:
            h.observe(x)
        assert len(h._v) <= 2 * 256                 # memory actually bounded
        for q in (10, 50, 90, 99):
            est = h.quantile(q)
            emp_rank = np.mean(data <= est)
            assert abs(emp_rank - q / 100.0) < 0.02, \
                f"p{q}: est {est} sits at rank {emp_rank}"
        assert h.count == 20_000
        np.testing.assert_allclose(h.sum, data.sum(), rtol=1e-9)
        assert h.min == data.min() and h.max == data.max()

    def test_histogram_edge_cases(self):
        h = TM.Histogram("h")
        assert math.isnan(h.quantile(50))
        h.observe(3.0)
        assert h.quantile(0) == h.quantile(100) == 3.0
        with pytest.raises(TM.TelemetryError):
            h.quantile(101)

    def test_snapshot_and_prometheus_text(self):
        reg = TM.MetricsRegistry()
        reg.counter("reqs_total", "requests").inc(3)
        reg.gauge("depth").set(2)
        h = reg.histogram("lat_seconds", "latency")
        for x in (0.1, 0.2, 0.3):
            h.observe(x)
        snap = reg.snapshot()
        assert snap["reqs_total"] == 3 and snap["depth"] == 2
        assert snap["lat_seconds"]["count"] == 3
        np.testing.assert_allclose(snap["lat_seconds"]["p50"], 0.2)
        text = reg.prometheus_text()
        assert "# HELP reqs_total requests" in text
        assert "# TYPE reqs_total counter" in text
        assert "reqs_total 3" in text
        assert "# TYPE depth gauge" in text
        assert "# TYPE lat_seconds summary" in text
        assert 'lat_seconds{quantile="0.5"} 0.2' in text
        assert "lat_seconds_count 3" in text


# ---------------------------------------------------------- tracer invariants
class TestTracerValidation:
    def test_validate_order_accepts_canonical_stream(self):
        tr = TM.RequestTracer()
        for name in ("arrive", "admit", "prefix_hit", "prefill_chunk",
                     "prefill_chunk", "first_token", "decode_token",
                     "decode_token", "finish"):
            tr.record(0, name)
        TM.validate_order(tr.request_events(0))

    @pytest.mark.parametrize("names,msg", [
        (("admit", "finish"), "arrive"),
        (("arrive", "first_token", "admit"), "order"),
        (("arrive", "admit", "arrive"), "duplicate"),
        (("arrive", "finish", "decode_token"), "finish"),
        (("arrive", "admit", "prefill_chunk", "deliver"), "first_token"),
        (("arrive", "admit", "first_token", "preempt", "deliver"),
         "evicted"),
    ])
    def test_validate_order_rejects(self, names, msg):
        tr = TM.RequestTracer()
        for name in names:
            tr.record(0, name)
        with pytest.raises(TM.TelemetryError, match=msg):
            TM.validate_order(tr.request_events(0))

    def test_deliver_may_follow_finish(self):
        tr = TM.RequestTracer()
        tr.step = 4
        for name in ("arrive", "admit", "prefill_chunk", "first_token",
                     "deliver", "decode_token", "finish", "deliver"):
            tr.record(0, name, **({"tokens": 1} if name == "deliver"
                                  else {}))
        evs = tr.request_events(0)
        TM.validate_order(evs)
        assert {e.step for e in evs} == {4}
        tl = TM.derive_timeline(evs)
        assert tl["ttft"] == tl["deliver"] - tl["arrive"]
        assert len(tl["delivered"]) == 2

    def test_timestamp_regression_rejected(self):
        evs = [TM.Event(2.0, 0, "arrive", None),
               TM.Event(1.0, 0, "admit", None)]
        with pytest.raises(TM.TelemetryError, match="regress"):
            TM.validate_order(evs)


# ------------------------------------------------------------------- fixtures
@pytest.fixture(scope="module")
def cfg():
    return ModelConfig(name="tel-t", family="dense", num_layers=2, d_model=64,
                       num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
                       vocab_size=50, loss_chunk=16, attn_chunk=16,
                       remat=False, dtype="float32")


@pytest.fixture(scope="module")
def params(cfg):
    return T.init_params(cfg, jax.random.PRNGKey(0))


def _engine(cfg, params, **kw):
    base = dict(block_size=4, num_blocks=64, max_blocks_per_seq=8,
                max_slots=4, prefill_chunk=8)
    base.update(kw)
    return Engine(cfg, params, EngineConfig(**base))


def _requests(n=6, vocab=50, seed=21):
    rng = np.random.default_rng(seed)
    lens = rng.integers(2, 18, size=n)
    news = rng.integers(1, 8, size=n)
    return ([rng.integers(0, vocab, size=int(L)).astype(np.int32)
             for L in lens], [int(m) for m in news])


# ------------------------------------------------------------ engine lifecycle
class TestEngineLifecycle:
    def test_event_ordering_and_derived_metrics(self, cfg, params):
        prompts, news = _requests()
        eng = _engine(cfg, params)
        rids = []
        for p, mn in zip(prompts, news):
            rids.append(eng.add_request(p, mn))
            eng.step()                              # staggered arrivals
        outs = eng.drain()
        for rid, mn in zip(rids, news):
            evs = eng.telemetry.tracer.request_events(rid)
            TM.validate_order(evs)                  # arrive≤admit≤first≤finish
            tl = eng.telemetry.request_timeline(rid)
            assert tl["arrive"] <= tl["admit"] <= tl["first_token"] \
                <= tl["finish"]
            assert tl["queue_wait"] >= 0 and tl["ttft"] >= tl["queue_wait"]
            # TTFT ends when the first value reaches the host (here: in
            # drain, after finish), never before its dispatch
            assert tl["first_token"] <= tl["deliver"]
            assert tl["ttft"] == tl["deliver"] - tl["arrive"]
            assert tl["e2e"] >= tl["first_token"] - tl["arrive"]
            assert len(tl["delivered"]) == outs[rid].shape[0]
            # token #1 comes from the final prefill chunk's logits; every
            # later token is a decode step
            assert len(tl["decode_tokens"]) == outs[rid].shape[0] - 1 == mn - 1
            assert all(tl["first_token"] <= t <= tl["finish"]
                       for t in tl["decode_tokens"])
            # prefill chunks all land inside [admit, first_token]
            chunk_ts = [e.t for e in evs if e.name == "prefill_chunk"]
            assert len(chunk_ts) == -(-len(prompts[rids.index(rid)]) // 8)
            assert all(tl["admit"] <= t <= tl["first_token"]
                       for t in chunk_ts)

    def test_lifecycle_histograms_count_requests(self, cfg, params):
        prompts, news = _requests(seed=3)
        eng = _engine(cfg, params)
        for p, mn in zip(prompts, news):
            eng.add_request(p, mn)
        eng.drain()
        reg = eng.telemetry.registry
        for name in ("engine_request_queue_wait_seconds",
                     "engine_request_ttft_seconds",
                     "engine_request_e2e_seconds"):
            h = reg.get(name)
            assert h.count == len(prompts)
            assert h.min >= 0
        assert reg.get("engine_tokens_emitted_total").value == sum(news)

    def test_prefix_hit_and_evict_events(self, cfg, params):
        """A replayed prompt records a prefix_hit event whose token count
        matches the engine counter; cache pressure records evict events."""
        rng = np.random.default_rng(11)
        prompt = rng.integers(0, 50, size=12).astype(np.int32)
        eng = _engine(cfg, params, num_blocks=16)
        eng.add_request(prompt, 2)
        eng.drain()
        r2 = eng.add_request(prompt, 2)             # identical prompt: hit
        eng.drain()
        hits = [e for e in eng.telemetry.tracer.request_events(r2)
                if e.name == "prefix_hit"]
        assert len(hits) == 1
        assert hits[0].data["tokens"] == eng.stats["prefix_hit_tokens"] > 0
        # churn through fresh prompts until the tiny pool must evict
        for i in range(6):
            p = rng.integers(0, 50, size=14).astype(np.int32)
            eng.add_request(p, 2)
            eng.drain()
        evicts = [e for e in eng.telemetry.tracer.events
                  if e.name == "evict"]
        assert len(evicts) == eng.block_pool.stats["evictions"] > 0

    def test_defrag_event_and_counter(self, cfg, params):
        prompts, news = _requests(seed=5)
        eng = _engine(cfg, params)
        for p, mn in zip(prompts[:3], news[:3]):
            eng.add_request(p, mn)
        eng.step()
        eng.step()
        eng.defragment()
        eng.drain()
        assert eng.telemetry.registry.get("engine_defrags_total").value == 1
        assert any(e.name == "defrag" and e.rid is None
                   for e in eng.telemetry.tracer.events)


# ----------------------------------------------------------- recompile tracker
class TestRecompileTracker:
    def test_unit_unique_trace_keys(self):
        tracker = TM.RecompileTracker()
        calls = []
        fn = tracker.wrap("f", lambda *a: calls.append(a))
        fn(jnp.zeros((2, 3)), 1)
        fn(jnp.ones((2, 3)), 2)                     # same shapes: same key
        assert tracker.unique("f") == 1
        fn(jnp.zeros((4, 3)), 1)                    # new shape
        fn(jnp.zeros((2, 3), jnp.int32), 1)         # new dtype
        fn({"a": jnp.zeros((2, 3))})                # new structure
        assert tracker.unique("f") == 4
        assert tracker.total == 4
        assert len(calls) == 5                      # every call goes through

    def test_engine_counts_exactly_one_variant_per_step_fn(self, cfg, params):
        """Fixed-shape decode/prefill must each compile exactly once no
        matter how many requests and steps run."""
        prompts, news = _requests(seed=9)
        eng = _engine(cfg, params)
        for p, mn in zip(prompts, news):
            eng.add_request(p, mn)
        eng.drain()
        v = eng.telemetry.recompiles.variants()
        assert v["decode"] == 1 and v["prefill"] == 1
        assert v["copy_block"] == 0 and v["reset_slot"] == 0
        assert eng.telemetry.recompiles.total == 2
        # replaying a prompt is fully cached -> the copy-on-write block copy
        # dispatches for the first time; a second replay adds nothing
        eng.add_request(prompts[0], 3)
        eng.drain()
        assert eng.telemetry.recompiles.variants()["copy_block"] == 1
        assert eng.telemetry.recompiles.total == 3
        eng.add_request(prompts[0], 3)
        eng.drain()
        assert eng.telemetry.recompiles.total == 3

    def test_hybrid_run_reports_exact_variant_count(self):
        """Acceptance: a hybrid-config run dispatches exactly three compiled
        step variants — decode, prefill, and the recurrent slot reset."""
        hcfg = ModelConfig(name="tel-hy", family="hybrid",
                           hybrid_ssm_per_attn=1, num_layers=2, d_model=64,
                           num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
                           vocab_size=50, loss_chunk=16, attn_chunk=16,
                           remat=False, dtype="float32", ssm_state_dim=8,
                           ssm_head_dim=16)
        hparams = T.init_params(hcfg, jax.random.PRNGKey(3))
        prompts, news = _requests(n=4, seed=13)
        eng = _engine(hcfg, hparams)
        for p, mn in zip(prompts, news):
            eng.add_request(p, mn)
        eng.drain()
        assert eng.telemetry.recompiles.variants() == {
            "decode": 1, "prefill": 1, "copy_block": 0, "reset_slot": 1}
        assert eng.telemetry.recompiles.total == 3


# ------------------------------------------------------------ profiler spans
STEP_CHILDREN = {"engine/schedule", "engine/prefill", "engine/decode",
                 "engine/verify", "engine/copy_block", "engine/reset_slot",
                 "engine/emit"}


def _engine_spans(logdir):
    """(start, end, name, stats) of every ``engine/*`` host annotation in
    the profile written under ``logdir``, by start."""
    import glob
    from jax.profiler import ProfileData
    path, = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine/"):
                    out.append((ev.start_ns, ev.end_ns, ev.name,
                                dict(ev.stats)))
    return sorted(out, key=lambda s: (s[0], -s[1]))


class TestProfilerSpans:
    def test_step_spans_cover_each_step(self, cfg, params, tmp_path):
        """Under the profiler every engine step is one ``engine/step`` span
        carrying the engine's step number, whose direct children are the
        named phases, never overlap, and leave little of it uncovered; each
        lifecycle event carries the step it was recorded in."""
        prompts, news = _requests(seed=31)
        eng = _engine(cfg, params)
        eng.add_request(prompts[0], 2)
        eng.drain()                                 # compiles off the trace
        first = eng.step_count
        jax.profiler.start_trace(str(tmp_path))
        try:
            rids = []
            for i, (p, mn) in enumerate(zip(prompts, news)):
                rids.append(eng.add_request(
                    p, mn, stop_token=7 if i % 2 else None))
                eng.step()
            outs = eng.drain()
        finally:
            jax.profiler.stop_trace()
        spans = _engine_spans(tmp_path)
        steps = [s for s in spans if s[2] == "engine/step"]
        assert [int(s[3]["step"]) for s in steps] == list(
            range(first, eng.step_count))
        total = uncovered = 0
        for a, b, _, _ in steps:
            inner = [s for s in spans if s[2] != "engine/step"
                     and a <= s[0] and s[1] <= b]
            kids = [k for k in inner if not any(
                o is not k and o[0] <= k[0] and k[1] <= o[1] for o in inner)]
            names = {k[2] for k in kids}
            assert names <= STEP_CHILDREN
            assert {"engine/schedule", "engine/emit"} <= names
            for x, y in zip(kids, kids[1:]):
                assert x[1] <= y[0]                 # children never overlap
            self_ns = (b - a) - sum(k[1] - k[0] for k in kids)
            assert self_ns >= 0
            assert sum(k[1] - k[0] for k in kids) + self_ns == b - a
            total += b - a
            uncovered += self_ns
        # what no child covers is the spans' own entry and exit, no more
        assert uncovered < 0.25 * total
        names = [s[2] for s in spans]
        assert names.count("engine/add_request") == len(prompts)
        assert "engine/sync" in names               # stop tokens, drain
        traced = set(range(first, eng.step_count))
        for rid in rids:
            evs = eng.telemetry.tracer.request_events(rid)
            TM.validate_order(evs)
            assert {e.step for e in evs if e.name not in (
                "arrive", "deliver")} <= traced
            tl = TM.derive_timeline(evs)
            assert len(tl["delivered"]) == outs[rid].shape[0]

    def test_ttft_histogram_counts_deliveries(self, cfg, params):
        """``engine_request_ttft_seconds`` closes at the first token's
        delivery: not at dispatch, and once per request."""
        prompts, news = _requests(n=3, seed=37)
        eng = _engine(cfg, params)
        rids = [eng.add_request(p, mn) for p, mn in zip(prompts, news)]
        while eng.scheduler.has_work:
            eng.step()
        h = eng.telemetry.registry.get("engine_request_ttft_seconds")
        assert h.count == 0                         # dispatched, not read
        eng.output(rids[0])
        eng.output(rids[0])                         # read once, counted once
        assert h.count == 1
        eng.drain()
        assert h.count == len(rids)
        for rid in rids:
            tl = eng.telemetry.request_timeline(rid)
            assert tl["ttft"] == tl["deliver"] - tl["arrive"]
            assert tl["deliver"] > tl["first_token"]


# ----------------------------------------------------- disabled mode + equality
class TestDisabledMode:
    def test_disabled_records_nothing_and_outputs_identical(self, cfg, params):
        """Acceptance: greedy outputs are bit-identical to serve.generate
        with telemetry on and off."""
        prompts, news = _requests(seed=19)
        outs = {}
        for mode, kw in (("on", {}), ("off", {"telemetry": False})):
            eng = _engine(cfg, params, **kw)
            rids = [eng.add_request(p, mn) for p, mn in zip(prompts, news)]
            res = eng.drain()
            outs[mode] = [res[r] for r in rids]
            if mode == "off":
                assert eng.telemetry.tracer.events == []
                assert eng.telemetry.recompiles.total == 0
                # back-compat stats stay live with telemetry off
                assert eng.stats["decode_steps"] > 0
                assert eng.stats["emitted"] == sum(news)
        for p, mn, a, b in zip(prompts, news, outs["on"], outs["off"]):
            ref = np.asarray(serve.generate(
                cfg, params, jnp.asarray(p)[None], max_new=mn,
                temperature=0.0))[0]
            np.testing.assert_array_equal(a, ref)
            np.testing.assert_array_equal(b, ref)

    def test_pool_stats_backcompat_standalone(self):
        from repro.serving.engine import BlockPool
        pool = BlockPool(8, 4)
        assert pool.stats == {"lookups": 0, "hit_blocks": 0, "evictions": 0,
                              "registrations": 0}
        pool.note_prefix_lookup(3)
        assert pool.stats["lookups"] == 1 and pool.stats["hit_blocks"] == 3


# ---------------------------------------------------------------- exporters
class TestExporters:
    def test_jsonl_roundtrip_replays_timelines(self, cfg, params, tmp_path):
        """Acceptance: a JSONL trace replays into per-request TTFT/decode
        timelines identical to the live telemetry's."""
        prompts, news = _requests(seed=23)
        eng = _engine(cfg, params)
        rids = []
        for p, mn in zip(prompts, news):
            rids.append(eng.add_request(p, mn))
            eng.step()
        eng.drain()
        path = tmp_path / "trace.jsonl"
        n = eng.telemetry.export_jsonl(path)
        assert n == len(eng.telemetry.tracer.events) > 0
        replay = TM.replay_jsonl(path)
        assert sorted(replay) == sorted(rids)
        for rid in rids:
            live = eng.telemetry.request_timeline(rid)
            got = replay[rid]
            assert got["ttft"] == live["ttft"]
            assert got["queue_wait"] == live["queue_wait"]
            assert got["e2e"] == live["e2e"]
            assert got["decode_tokens"] == live["decode_tokens"]
            assert got["prefix_hit_tokens"] == live["prefix_hit_tokens"]

    def test_engine_prometheus_snapshot_covers_pool_and_engine(self, cfg,
                                                               params):
        prompts, news = _requests(n=3, seed=29)
        eng = _engine(cfg, params)
        for p, mn in zip(prompts, news):
            eng.add_request(p, mn)
        eng.drain()
        text = eng.telemetry.prometheus_text()
        assert f"engine_tokens_emitted_total {sum(news)}" in text
        assert "# TYPE pool_evictions_total counter" in text
        assert "# TYPE engine_request_ttft_seconds summary" in text
        assert f"engine_request_ttft_seconds_count {len(prompts)}" in text
