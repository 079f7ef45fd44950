"""Speculative decoding: multi-query verify kernel vs pure-JAX reference
(interpret mode), verify-vs-sequential-decode logits oracle, the greedy
acceptance rule, recurrent rollback via checkpoint selection, drafter units,
and end-to-end engine bit-identity per model family — including under forced
preemption and with zero verify variants compiled past warmup.

The load-bearing guarantee: greedy outputs with ``EngineConfig.spec`` set are
bit-identical to ``serve.generate``; drafting quality only moves the
acceptance rate, never the tokens. All CPU (`pytest -m spec_decode`, subset
of `-m serving`)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.kernels.paged_attention import (paged_attention_ref,
                                           paged_attention_verify,
                                           paged_attention_verify_ref)
from repro.models import state_providers as SP
from repro.models import transformer as T
from repro.serving import serve
from repro.serving.engine import (Drafter, Engine, EngineConfig,
                                  KVQuantConfig, NgramDrafter, OversubConfig,
                                  ReplayDrafter, SpecConfig)
from repro.serving.engine import spec as SPEC
from repro.serving.engine.scheduler import DECODING
from repro.serving.telemetry import derive_timeline, validate_order

pytestmark = [pytest.mark.serving, pytest.mark.spec_decode]

K = 4


# ------------------------------------------------------- kernel vs reference
def _verify_case(seed, B, H, Hkv, hd, N, bs, P, dtype, lens, k=K):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, k, H, hd)), dtype)
    kp = jnp.asarray(rng.standard_normal((N, bs, Hkv, hd)), dtype)
    vp = jnp.asarray(rng.standard_normal((N, bs, Hkv, hd)), dtype)
    perm = rng.permutation(N)[:B * P].reshape(B, P)
    return q, kp, vp, jnp.asarray(perm, jnp.int32), jnp.asarray(lens, jnp.int32)


class TestVerifyKernel:
    # lens INCLUDE the K draft tokens; 0 = inactive; 16 = exact page boundary
    FULL_LENS = (K, 7, 13, 0, 16, 29)

    @pytest.mark.parametrize("H,Hkv,hd", [(4, 4, 32), (4, 2, 64), (8, 1, 32)])
    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                           (jnp.bfloat16, 0.08)])
    def test_full_matches_ref(self, H, Hkv, hd, dtype, tol):
        q, kp, vp, tables, lens = _verify_case(
            0, len(self.FULL_LENS), H, Hkv, hd, 64, 4, 8, dtype, self.FULL_LENS)
        out = paged_attention_verify(q, kp, vp, tables, lens)
        ref = paged_attention_verify_ref(q, kp, vp, tables, lens)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32), atol=tol)
        np.testing.assert_array_equal(np.asarray(out)[3], 0.0)  # inactive row
        np.testing.assert_array_equal(np.asarray(ref)[3], 0.0)

    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                           (jnp.bfloat16, 0.08)])
    def test_ring_matches_ref(self, dtype, tol):
        window, bs = 8, 4
        rp = SP.ring_pages(window, bs, draft=K - 1)
        lens = (K, 9, 17, 0, 40)              # 17/40 wrap the ring modulus
        q, kp, vp, tables, lens = _verify_case(
            1, 5, 4, 2, 32, 32, bs, rp, dtype, lens)
        pos = jnp.maximum(lens - 1, 0)
        out = paged_attention_verify(q, kp, vp, tables, lens, window=window,
                                     positions=pos, ring_pages=rp)
        ref = paged_attention_verify_ref(q, kp, vp, tables, lens,
                                         window=window, positions=pos,
                                         ring_pages=rp)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32), atol=tol)
        np.testing.assert_array_equal(np.asarray(out)[3], 0.0)

    def test_verify_rows_equal_single_query_decode(self):
        """Semantic anchor: verify row j IS a one-token decode at position
        lens - K + j (attending lens - K + 1 + j keys) — per row, the
        multi-query sweep must reproduce the single-query path exactly."""
        q, kp, vp, tables, lens = _verify_case(
            2, len(self.FULL_LENS), 4, 2, 32, 64, 4, 8, jnp.float32,
            self.FULL_LENS)
        ref = paged_attention_verify_ref(q, kp, vp, tables, lens)
        for j in range(K):
            lens_j = jnp.where(lens > 0, lens - K + 1 + j, 0)
            dec = paged_attention_ref(q[:, j], kp, vp, tables, lens_j)
            np.testing.assert_allclose(np.asarray(ref[:, j]), np.asarray(dec),
                                       atol=1e-6, err_msg=f"row {j}")

    def test_verify_rows_equal_single_query_decode_ring(self):
        window, bs = 8, 4
        rp = SP.ring_pages(window, bs, draft=K - 1)
        lens = (K, 9, 17, 0, 40)
        q, kp, vp, tables, lens = _verify_case(
            3, 5, 4, 2, 32, 32, bs, rp, jnp.float32, lens)
        pos = jnp.maximum(lens - 1, 0)
        ref = paged_attention_verify_ref(q, kp, vp, tables, lens,
                                         window=window, positions=pos,
                                         ring_pages=rp)
        for j in range(K):
            lens_j = jnp.where(lens > 0, lens - K + 1 + j, 0)
            dec = paged_attention_ref(q[:, j], kp, vp, tables, lens_j,
                                      window=window,
                                      positions=jnp.maximum(lens_j - 1, 0),
                                      ring_pages=rp)
            np.testing.assert_allclose(np.asarray(ref[:, j]), np.asarray(dec),
                                       atol=1e-6, err_msg=f"ring row {j}")

    def test_garbage_beyond_lens_is_masked(self):
        """Stale-KV canonicality: pool contents past each slot's valid length
        (rejected-draft leftovers, freed blocks) must not leak into the
        output — poisoning them changes nothing."""
        B, bs, P, N = len(self.FULL_LENS), 4, 8, 64
        q, kp, vp, tables, lens = _verify_case(
            4, B, 4, 2, 32, N, bs, P, jnp.float32, self.FULL_LENS)
        clean = paged_attention_verify(q, kp, vp, tables, lens)
        kp2, vp2 = np.array(kp), np.array(vp)
        perm, lens_np = np.asarray(tables), np.asarray(lens)
        referenced = set()
        for b in range(B):
            for t in range(int(lens_np[b])):
                referenced.add((int(perm[b, t // bs]), t % bs))
        for blk in range(N):
            for off in range(bs):
                if (blk, off) not in referenced:
                    kp2[blk, off] = 1e4
                    vp2[blk, off] = 1e4
        dirty = paged_attention_verify(q, jnp.asarray(kp2), jnp.asarray(vp2),
                                       tables, lens)
        np.testing.assert_allclose(np.asarray(dirty), np.asarray(clean),
                                   atol=1e-6)

    def test_ring_pages_draft_slack(self):
        assert SP.ring_pages(8, 4) == 3
        assert SP.ring_pages(8, 4, draft=3) == 4       # ceil(11/4) + 1
        assert SP.ring_pages(4, 4, draft=3) == 3
        for d in range(4):
            assert SP.ring_pages(8, 4, draft=d + 1) >= SP.ring_pages(8, 4, draft=d)


# ------------------------------------------------- verify step + acceptance
@pytest.fixture(scope="module")
def cfg():
    return ModelConfig(name="spec-t", family="dense", num_layers=2, d_model=64,
                       num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
                       vocab_size=50, loss_chunk=16, attn_chunk=16,
                       remat=False, dtype="float32")


@pytest.fixture(scope="module")
def params(cfg):
    return T.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def prefilled(cfg, params):
    """Slot 0 prefilled with a 6-token prompt; returns (pool, tables, base,
    first greedy token)."""
    pool = T.init_paged_state(cfg, 32, 4, max_slots=2)
    tables = jnp.arange(16, dtype=jnp.int32).reshape(2, 8)
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, cfg.vocab_size, size=6).astype(np.int32)
    toks = jnp.zeros((1, 8), jnp.int32).at[0, :6].set(jnp.asarray(prompt))
    lg, pool = T.paged_prefill_step(cfg, params, pool, toks, tables[0], 0, 6, 0)
    return pool, tables, 6, int(jnp.argmax(lg[0]))


def _sequential(cfg, params, prefilled, k=3):
    """k one-token decode steps from the prefilled state: returns the fed
    tokens [t0, g0, .., g_{k-2}] and the per-step logits rows."""
    pool, tables, base, t0 = prefilled
    cur, fed, rows = t0, [t0], []
    for j in range(k):
        lg, pool = T.paged_decode_step(
            cfg, params, pool, {"token": jnp.asarray([cur, 0], jnp.int32)},
            tables, jnp.asarray([base + j, 0], jnp.int32),
            jnp.asarray([base + j + 1, 0], jnp.int32))
        rows.append(np.asarray(lg[0]))
        cur = int(jnp.argmax(lg[0]))
        if j < k - 1:
            fed.append(cur)
    return fed, np.stack(rows)


class TestVerifyStep:
    def test_logits_match_sequential_decode(self, cfg, params, prefilled):
        """The verify sweep's K logits rows equal K sequential one-token
        decode steps — the equivalence the acceptance rule stands on."""
        pool, tables, base, _ = prefilled
        fed, rows = _sequential(cfg, params, prefilled, k=3)
        tokens = jnp.zeros((2, 3), jnp.int32).at[0].set(jnp.asarray(fed))
        lg, _ = T.paged_verify_step(cfg, params, pool, tokens, tables,
                                    jnp.asarray([base, 0], jnp.int32),
                                    jnp.asarray([3, 0], jnp.int32))
        np.testing.assert_allclose(np.asarray(lg[0]), rows, rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_array_equal(np.argmax(np.asarray(lg[0]), -1),
                                      np.argmax(rows, -1))

    @pytest.mark.parametrize("wrong_at,qlim,want", [(None, 3, 3), (2, 3, 2),
                                                    (1, 3, 1), (None, 1, 1)])
    def test_acceptance_rule(self, cfg, params, prefilled, wrong_at, qlim, want):
        """accepts = 1 + longest verified draft prefix, capped at qlims."""
        pool, tables, base, _ = prefilled
        fed, rows = _sequential(cfg, params, prefilled, k=3)
        greedy_true = np.argmax(rows, -1)
        drafts = list(fed)
        if wrong_at is not None:   # corrupt draft at position wrong_at
            drafts[wrong_at] = int(greedy_true[wrong_at - 1] + 1) % cfg.vocab_size
        tokens = jnp.zeros((2, 3), jnp.int32).at[0].set(jnp.asarray(drafts))
        greedy, accepts, _, new_lens, new_pool = SPEC.verify_step(
            cfg, params, pool, tokens, tables,
            jnp.asarray([base, 0], jnp.int32), jnp.asarray([True, False]),
            jnp.asarray([qlim, 0], jnp.int32))
        assert int(accepts[0]) == want and int(accepts[1]) == 0
        assert int(new_lens[0]) == base + want and int(new_lens[1]) == 0
        # emitted tokens (the accepted run) match the sequential greedy
        np.testing.assert_array_equal(np.asarray(greedy[0, :want]),
                                      greedy_true[:want])
        assert set(new_pool) == set(pool)

    def test_all_inactive_round_trips_pool(self, cfg, params, prefilled):
        """The engine's warmup call: every slot inactive, qlims 0 — the pool
        must come back bit-identical (this is what makes warmup free)."""
        pool, tables, _, _ = prefilled
        z = jnp.zeros((2,), jnp.int32)
        _, accepts, _, new_lens, new_pool = SPEC.verify_step(
            cfg, params, pool, jnp.zeros((2, 3), jnp.int32), tables, z,
            jnp.zeros((2,), bool), z)
        assert np.asarray(accepts).tolist() == [0, 0]
        assert np.asarray(new_lens).tolist() == [0, 0]
        for a, b in zip(jax.tree.leaves(pool), jax.tree.leaves(new_pool)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_select_checkpoint_picks_accepted_and_keeps_old(self):
        cps = jnp.arange(1 * 3 * 2 * 4, dtype=jnp.float32).reshape(1, 3, 2, 4)
        old = -jnp.ones((1, 2, 4), jnp.float32)
        out = SP.select_checkpoint(cps, jnp.asarray([2, 0], jnp.int32), old)
        np.testing.assert_array_equal(np.asarray(out[0, 0]),
                                      np.asarray(cps[0, 1, 0]))
        np.testing.assert_array_equal(np.asarray(out[0, 1]),
                                      np.asarray(old[0, 1]))


# ------------------------------------------------------------------ drafters
class _ConstantDrafter:
    """Deliberately terrible drafter (protocol via duck typing): wrong
    guesses must only cost acceptance, never correctness."""

    def __init__(self, tok=0):
        self.tok = tok

    def propose(self, rid, context, n):
        return np.full((n,), self.tok, np.int32)

    def forget(self, rid):
        pass


class TestDrafters:
    def test_ngram_proposes_seen_continuation(self):
        d = NgramDrafter(3)
        out = d.propose(1, np.asarray([1, 2, 3, 4, 1, 2, 3]), 2)
        np.testing.assert_array_equal(out, [4, 1])
        # accepted run extends the stream; the cursor keeps streaming
        out = d.propose(1, np.asarray([1, 2, 3, 4, 1, 2, 3, 4, 1]), 2)
        np.testing.assert_array_equal(out, [2, 3])

    def test_ngram_fallback_repeats_last_token(self):
        d = NgramDrafter(3)
        np.testing.assert_array_equal(d.propose(1, np.asarray([7]), 3),
                                      [7, 7, 7])

    def test_ngram_forget_then_repropose(self):
        d = NgramDrafter(2)
        ctx = np.asarray([5, 6, 5, 6, 5, 6])
        first = d.propose(9, ctx, 2)
        d.forget(9)
        np.testing.assert_array_equal(d.propose(9, ctx, 2), first)

    def test_replay_drafter_streams_the_remembered_future(self):
        d = ReplayDrafter()
        stream = np.arange(1, 11, dtype=np.int32)
        d.remember(3, stream)
        np.testing.assert_array_equal(d.propose(3, stream[:4], 3), [5, 6, 7])
        np.testing.assert_array_equal(d.propose(3, stream[:9], 3), [10, 9, 9])
        d.forget(3)                      # no-op: streams survive preemption
        np.testing.assert_array_equal(d.propose(3, stream[:4], 3), [5, 6, 7])
        np.testing.assert_array_equal(d.propose(4, stream[:4], 2), [4, 4])

    def test_protocol_duck_typing(self):
        assert isinstance(NgramDrafter(), Drafter)
        assert isinstance(ReplayDrafter(), Drafter)
        assert isinstance(_ConstantDrafter(), Drafter)
        assert not isinstance(object(), Drafter)

    def test_spec_config_validation(self):
        for bad in (1, 33, 0):
            with pytest.raises(ValueError):
                SpecConfig(k=bad)
        with pytest.raises(ValueError):
            SpecConfig(drafter="beam")
        with pytest.raises(TypeError):
            SpecConfig(drafter=42)
        with pytest.raises(ValueError):
            SpecConfig(ngram=0)
        assert isinstance(SpecConfig().build_drafter(), NgramDrafter)
        inst = _ConstantDrafter()
        assert SpecConfig(drafter=inst).build_drafter() is inst


# ------------------------------------------------------------ engine, e2e
def _model_cfg(family):
    base = dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                head_dim=16, d_ff=128, vocab_size=50, loss_chunk=16,
                attn_chunk=16, remat=False, dtype="float32")
    if family == "full":
        return ModelConfig(name="sd-full", family="dense", **base)
    if family == "sliding":
        return ModelConfig(name="sd-sliding", family="dense",
                           attention_type="sliding", window_size=4, **base)
    if family == "ssm":
        return ModelConfig(name="sd-ssm", family="ssm", ssm_type="rwkv6",
                           ssm_head_dim=16, **base)
    if family == "hybrid":
        return ModelConfig(name="sd-hybrid", family="hybrid",
                           hybrid_ssm_per_attn=1, ssm_state_dim=8,
                           ssm_head_dim=16, **base)
    raise ValueError(family)


@pytest.fixture(scope="module", params=["full", "sliding", "ssm", "hybrid"])
def fam_setup(request):
    cfg = _model_cfg(request.param)
    return request.param, cfg, T.init_params(cfg, jax.random.PRNGKey(0))


def _engine(cfg, params, **kw):
    base = dict(block_size=4, num_blocks=64, max_blocks_per_seq=8,
                max_slots=4, prefill_chunk=8, spec=SpecConfig(k=K))
    base.update(kw)
    return Engine(cfg, params, EngineConfig(**base))


def _ref(cfg, params, prompt, max_new, kv_quant=None):
    return np.asarray(serve.generate(cfg, params, jnp.asarray(prompt)[None],
                                     max_new=max_new, temperature=0.0,
                                     kv_quant=kv_quant))[0]


def _prompts(n, seed=0, lo=3, hi=14):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 50, size=int(s)).astype(np.int32)
            for s in rng.integers(lo, hi, size=n)]


class TestSpecEngine:
    def test_family_bit_identical_to_serve(self, fam_setup):
        """Acceptance: greedy outputs with speculation on are bit-identical
        to serve.generate across every state-provider family (sliding runs
        window=4, so the draft-enlarged ring wraps mid-decode)."""
        family, cfg, params = fam_setup
        eng = _engine(cfg, params)
        prompts, max_new = _prompts(5, seed=2), 10
        rids = []
        for p in prompts:
            rids.append(eng.add_request(p, max_new))
            eng.step()                              # staggered arrivals
        outs = eng.drain()
        for rid, p in zip(rids, prompts):
            np.testing.assert_array_equal(
                outs[rid], _ref(cfg, params, p, max_new),
                err_msg=f"family={family} rid={rid}")
        assert eng.block_pool.num_free == eng.ecfg.num_blocks

    def test_forced_preemption_soak_bit_identical(self, fam_setup):
        """Every request is evicted at a distinct decode depth while
        speculation runs; resume re-prefills over canonical KV (positions
        beyond seq_lens are rejected-draft leftovers the causal bound masks)
        and the drained outputs still match serve.generate bit-for-bit."""
        family, cfg, params = fam_setup
        eng = _engine(cfg, params, oversub=OversubConfig())
        prompts, max_new = _prompts(4, seed=1), 10
        rids = [eng.add_request(p, max_new) for p in prompts]
        pending, steps = list(rids), 0
        while pending and steps < 200:
            eng.step()
            steps += 1
            for rid in list(pending):
                req = eng.requests[rid]
                if req.state == DECODING and len(req.out_tokens) >= rids.index(rid) + 1:
                    assert eng.preempt_request(rid)
                    pending.remove(rid)
        assert not pending, "not every request reached its eviction point"
        outs = eng.drain()
        for rid, p in zip(rids, prompts):
            np.testing.assert_array_equal(
                outs[rid], _ref(cfg, params, p, max_new),
                err_msg=f"family={family} rid={rid}")
        assert eng.stats["preemptions"] >= len(rids)
        assert eng.telemetry.recompiles.variants().get("verify") == 1
        for rid in rids:
            validate_order(eng.telemetry.tracer.request_events(rid))
        assert eng.block_pool.num_free == eng.ecfg.num_blocks
        eng.block_pool.check()

    @pytest.mark.kv_quant
    def test_quantized_kv_spec_soak_bit_identical(self, fam_setup):
        """Speculation over int8 paged KV, with every request force-evicted
        mid-decode: the verify kernel dequantizes in-register, rejected
        drafts roll back by seq_lens alone (their quantized writes beyond the
        bound are masked), and greedy outputs still match the quantized
        dense reference bit-for-bit with zero verify variants past warmup."""
        family, cfg, params = fam_setup
        kvq = KVQuantConfig()
        eng = _engine(cfg, params, oversub=OversubConfig(), kv_quant=kvq)
        prompts, max_new = _prompts(4, seed=7), 10
        rids = [eng.add_request(p, max_new) for p in prompts]
        pending, steps = list(rids), 0
        while pending and steps < 200:
            eng.step()
            steps += 1
            for rid in list(pending):
                req = eng.requests[rid]
                if (req.state == DECODING
                        and len(req.out_tokens) >= rids.index(rid) + 1):
                    assert eng.preempt_request(rid)
                    pending.remove(rid)
        assert not pending, "not every request reached its eviction point"
        outs = eng.drain()
        for rid, p in zip(rids, prompts):
            np.testing.assert_array_equal(
                outs[rid], _ref(cfg, params, p, max_new, kv_quant=kvq),
                err_msg=f"family={family} rid={rid}")
        assert eng.stats["preemptions"] >= len(rids)
        assert eng.telemetry.recompiles.variants().get("verify") == 1
        assert eng.block_pool.num_free == eng.ecfg.num_blocks
        eng.block_pool.check()

    @pytest.mark.parametrize("family", ["full", "sliding"])
    def test_kernel_impl_bit_identical(self, family):
        """The Pallas verify kernel (interpret mode off-TPU) drives the same
        greedy streams as the reference attention."""
        cfg = _model_cfg(family)
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        eng = _engine(cfg, params, attn_impl="kernel", max_slots=2)
        prompts, max_new = _prompts(2, seed=3), 8
        rids = [eng.add_request(p, max_new) for p in prompts]
        outs = eng.drain()
        for rid, p in zip(rids, prompts):
            np.testing.assert_array_equal(outs[rid],
                                          _ref(cfg, params, p, max_new),
                                          err_msg=f"family={family}")

    def test_wrong_drafts_only_cost_acceptance(self, cfg, params):
        """An adversarially bad drafter (constant token) still yields
        bit-identical output — acceptance degrades to ~1 token/step."""
        eng = _engine(cfg, params, spec=SpecConfig(k=K, drafter=_ConstantDrafter(0)))
        prompts, max_new = _prompts(3, seed=4), 8
        rids = [eng.add_request(p, max_new) for p in prompts]
        outs = eng.drain()
        for rid, p in zip(rids, prompts):
            np.testing.assert_array_equal(outs[rid],
                                          _ref(cfg, params, p, max_new))
        reg = eng.telemetry.registry
        drafted = reg.get("engine_draft_tokens_total").value
        accepted = reg.get("engine_accepted_tokens_total").value
        assert drafted > 0 and 0 <= accepted <= drafted

    def test_replay_drafter_reaches_full_acceptance(self, cfg, params):
        """ReplayDrafter fed the true continuation is the acceptance=1
        ceiling: every non-final verify step advances by min(k, budget)."""
        prompt, max_new = _prompts(1, seed=6, lo=6, hi=7)[0], 9
        ref = _ref(cfg, params, prompt, max_new)
        d = ReplayDrafter()
        eng = _engine(cfg, params, spec=SpecConfig(k=K, drafter=d))
        rid = eng.add_request(prompt, max_new)
        d.remember(rid, np.concatenate([prompt, ref]))   # prompt ++ output
        outs = eng.drain()
        np.testing.assert_array_equal(outs[rid], ref)
        reg = eng.telemetry.registry
        assert (reg.get("engine_accepted_tokens_total").value
                == reg.get("engine_draft_tokens_total").value > 0)

    def test_stop_token_truncates_identically(self, cfg, params):
        """The device may verify past the stop token; the host truncates the
        accepted run exactly where the non-speculative engine stops."""
        prompt, max_new = _prompts(1, seed=8, lo=5, hi=6)[0], 12
        ref = _ref(cfg, params, prompt, max_new)
        stop = int(ref[3])                 # the 4th generated token
        outs = {}
        for name, spec in (("off", None), ("on", SpecConfig(k=K))):
            eng = _engine(cfg, params, spec=spec)
            rid = eng.add_request(prompt, max_new, stop_token=stop)
            outs[name] = eng.drain()[rid]
        np.testing.assert_array_equal(outs["on"], outs["off"])
        assert int(outs["on"][-1]) == stop
        assert len(outs["on"]) <= max_new

    def test_temperature_requests_run_unspeculated(self, cfg, params):
        """temperature > 0 runs with qlims == 1 (host samples the one
        guaranteed token); the request still completes its full budget."""
        prompt, max_new = _prompts(1, seed=9, lo=5, hi=6)[0], 8
        eng = _engine(cfg, params)
        rid = eng.add_request(prompt, max_new, temperature=0.8,
                              key=jax.random.PRNGKey(3))
        out = np.asarray(eng.drain()[rid])
        assert len(out) == max_new
        assert ((0 <= out) & (out < cfg.vocab_size)).all()

    def test_no_new_verify_variants_at_steady_state(self, cfg, params):
        """The verify shape is AOT-warmed at construction; a mixed staggered
        workload must add ZERO compiled variants of any step function."""
        eng = _engine(cfg, params)
        v0 = dict(eng.telemetry.recompiles.variants())
        assert v0.get("verify") == 1
        prompts, news = _prompts(6, seed=5), [3, 8, 5, 10, 2, 7]
        for p, mn in zip(prompts, news):
            eng.add_request(p, mn)
            eng.step()
        eng.drain()
        assert dict(eng.telemetry.recompiles.variants()) == v0

    def test_telemetry_counts_accepted_tokens_not_steps(self, cfg, params):
        """Satellite (b): verify events carry drafted/accepted, decode_token
        carries the accepted run length, and the derived timeline counts
        TOKENS — len(decode_tokens) equals generated-1 even though the
        engine stepped far fewer times."""
        eng = _engine(cfg, params)
        prompts, max_new = _prompts(3, seed=10), 9
        rids = [eng.add_request(p, max_new) for p in prompts]
        outs = eng.drain()
        reg = eng.telemetry.registry
        assert reg.get("engine_draft_tokens_total").value > 0
        assert reg.get("engine_spec_acceptance_rate").count > 0
        for rid, p in zip(rids, prompts):
            evs = eng.telemetry.tracer.request_events(rid)
            validate_order(evs)
            n_verify = sum(ev.name == "verify" for ev in evs)
            assert n_verify > 0
            gen = len(outs[rid])               # drain returns generated only
            tl = derive_timeline(evs)
            assert len(tl["decode_tokens"]) == gen - 1
            assert tl["accepted_tokens"] == gen - 1 - n_verify
            assert tl["draft_tokens"] >= tl["accepted_tokens"]
