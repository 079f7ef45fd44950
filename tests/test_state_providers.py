"""Per-layer sequence-state providers: ring-buffer paged pool for sliding
windows, O(1) recurrent slabs for rwkv6/mamba2, and the engine serving ALL
families (full / sliding / local_global / ssm / hybrid) bit-identically to
`serve.generate`.

All CPU. Select with `pytest -m state_providers` (subset of `-m serving`).
"""
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.kernels.paged_attention import paged_attention, paged_attention_ref
from repro.models import state_providers as SP
from repro.models import transformer as T
from repro.serving import serve
from repro.serving.engine import (BlockPool, Engine, EngineConfig,
                                  KVQuantConfig)

pytestmark = [pytest.mark.serving, pytest.mark.state_providers]

NEG_INF = -1e30

_COMMON = dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
               head_dim=16, d_ff=128, vocab_size=50, loss_chunk=16,
               attn_chunk=16, remat=False, dtype="float32")

FAMILIES = ("full", "sliding", "local_global", "ssm", "hybrid")


def family_cfg(family: str) -> ModelConfig:
    if family == "full":
        return ModelConfig(name="sp-full", family="dense", **_COMMON)
    if family == "sliding":
        return ModelConfig(name="sp-sliding", family="dense",
                           attention_type="sliding", window_size=8, **_COMMON)
    if family == "local_global":
        return ModelConfig(name="sp-lg", family="dense",
                           attention_type="local_global", local_global_ratio=1,
                           window_size=8, **_COMMON)
    if family == "ssm":
        return ModelConfig(name="sp-ssm", family="ssm", ssm_type="rwkv6",
                           ssm_head_dim=32, **_COMMON)
    if family == "hybrid":
        return ModelConfig(name="sp-hybrid", family="hybrid",
                           hybrid_ssm_per_attn=1, ssm_state_dim=8,
                           ssm_head_dim=32, **_COMMON)
    raise ValueError(family)


@pytest.fixture(scope="module")
def fam_params():
    cache = {}

    def get(family):
        if family not in cache:
            cfg = family_cfg(family)
            cache[family] = (cfg, T.init_params(cfg, jax.random.PRNGKey(0)))
        return cache[family]

    return get


def _engine(cfg, params, **kw):
    base = dict(block_size=4, num_blocks=64, max_blocks_per_seq=16,
                max_slots=4, prefill_chunk=8)
    base.update(kw)
    return Engine(cfg, params, EngineConfig(**base))


def _ref_out(cfg, params, prompt, max_new):
    return np.asarray(serve.generate(
        cfg, params, jnp.asarray(prompt)[None], max_new=max_new,
        temperature=0.0))[0]


# ----------------------------------------------------------- provider units
class TestProviderAccounting:
    def test_kinds_per_family(self):
        assert SP.state_kinds(family_cfg("full")) == ["full"]
        assert SP.state_kinds(family_cfg("sliding")) == ["ring"]
        assert SP.state_kinds(family_cfg("local_global")) == ["ring", "full"]
        assert SP.state_kinds(family_cfg("ssm")) == ["rwkv"]
        assert SP.state_kinds(family_cfg("hybrid")) == ["mamba", "full"]

    def test_ring_pages_formula(self):
        assert SP.ring_pages(8, 4) == 3       # 2 intact pages + 1 wrap page
        assert SP.ring_pages(7, 4) == 3
        assert SP.ring_pages(9, 4) == 4
        assert SP.ring_pages(4, 4) == 2

    def test_blocks_needed_per_kind(self):
        def provs(fam):
            return SP.providers_for(family_cfg(fam), num_blocks=64,
                                    block_size=4, max_slots=4)
        # full: O(S) blocks
        assert SP.seq_blocks_needed(provs("full"), 30) == 8
        # ring: capped at ring_pages regardless of length
        assert SP.seq_blocks_needed(provs("sliding"), 30) == 3
        assert SP.seq_blocks_needed(provs("sliding"), 5) == 2
        # recurrent: zero blocks
        assert SP.seq_blocks_needed(provs("ssm"), 10_000) == 0
        # mixed: the full-attention layer dominates (shared block table)
        assert SP.seq_blocks_needed(provs("local_global"), 30) == 8
        assert SP.seq_blocks_needed(provs("hybrid"), 30) == 8

    def test_prefix_caching_soundness_gate(self):
        def provs(fam):
            return SP.providers_for(family_cfg(fam), num_blocks=64,
                                    block_size=4, max_slots=4)
        assert all(p.supports_prefix_caching for p in provs("full"))
        for fam in ("sliding", "local_global", "ssm", "hybrid"):
            assert not all(p.supports_prefix_caching for p in provs(fam))

    def test_state_bytes_per_slot(self):
        provs = SP.providers_for(family_cfg("ssm"), num_blocks=64,
                                 block_size=4, max_slots=4)
        # rwkv6 @ d=64, hd=32: S (2,32,32) f32 + prev/prev_cm (1,64) f32 each
        assert provs[0].state_bytes_per_slot(1000) == 2 * 32 * 32 * 4 + 2 * 64 * 4
        mem = SP.state_memory_per_slot(family_cfg("ssm"), provs, 1000)
        assert mem == 2 * provs[0].state_bytes_per_slot(1000)  # 2 superblocks


# ------------------------------------------------- ring pool property harness
class _RingShadow:
    """Host-side model of ONE ring sequence: absolute positions -> expected
    fingerprints, mapped through the shared BlockPool table modulo the ring."""

    def __init__(self, rid, table, total, window, block_size, ring):
        self.rid, self.table, self.total = rid, list(table), total
        self.window, self.bs, self.ring = window, block_size, ring
        self.pos = 0                     # next position to write

    def slot_of(self, p):
        return self.table[(p // self.bs) % self.ring], p % self.bs

    def fingerprint(self, p):
        return self.rid * 10_000 + p


class TestRingPoolProperties:
    """Seeded episodes over alloc / write / wrap / free / defrag, mirroring
    tests/test_prefix_cache.py's BlockPool harness. A numpy fingerprint
    array stands in for the device pool (defrag applies the SAME
    permutation the engine applies with jnp.take)."""

    N_EPISODES = 60
    STEPS = 120

    def _check_window_readable(self, seq, store):
        """Every position in the window (pos - window, pos) must be intact."""
        lo = max(0, seq.pos - seq.window)
        for p in range(lo, seq.pos):
            blk, off = seq.slot_of(p)
            assert store[blk, off] == seq.fingerprint(p), \
                f"seq {seq.rid} pos {p}: clobbered ring entry"

    def test_seeded_episodes(self):
        for ep in range(self.N_EPISODES):
            self._episode(random.Random(1234 + ep))

    def _episode(self, rng):
        N, bs = 24, 4
        window = rng.choice([5, 8, 12])
        ring = SP.ring_pages(window, bs)
        pool = BlockPool(N, bs)
        store = np.full((N, bs), -1, np.int64)   # stand-in device pool
        live, next_rid = {}, 0

        for _ in range(self.STEPS):
            op = rng.random()
            if op < 0.3 and len(live) < 5:
                total = rng.randrange(1, 60)
                need = min(pool.blocks_for(total), ring)
                if pool.can_alloc(need):
                    rid = next_rid
                    next_rid += 1
                    table = pool.alloc(rid, need)
                    assert len(table) <= ring
                    live[rid] = _RingShadow(rid, table, total, window, bs, ring)
            elif op < 0.75 and live:
                seq = live[rng.choice(sorted(live))]
                for _ in range(rng.randrange(1, 2 * window)):
                    if seq.pos >= seq.total:
                        break
                    blk, off = seq.slot_of(seq.pos)
                    assert blk in pool.table(seq.rid)
                    store[blk, off] = seq.fingerprint(seq.pos)
                    seq.pos += 1
                self._check_window_readable(seq, store)
            elif op < 0.9 and live:
                rid = rng.choice(sorted(live))
                pool.free_seq(rid)
                del live[rid]
            else:
                src = pool.defragment()
                store = store[src]               # new[i] = old[src[i]]
                for seq in live.values():
                    seq.table = pool.table(seq.rid)
            pool.check()
            for seq in live.values():
                self._check_window_readable(seq, store)

        for rid in sorted(live):
            pool.free_seq(rid)
        assert pool.num_free == N


# -------------------------------------------------- ring attention vs oracle
def _build_ring_case(key, B, Hkv, H, hd, bs, window, positions):
    """Simulate the engine's write order: every position 0..pos scattered
    through the ring in sequence (later laps overwrite earlier ones)."""
    R = SP.ring_pages(window, bs)
    N = B * R + 2
    maxp = max(positions) + 1
    k1, k2, k3 = jax.random.split(key, 3)
    k_all = jax.random.normal(k1, (B, maxp, Hkv, hd), jnp.float32)
    v_all = jax.random.normal(k2, (B, maxp, Hkv, hd), jnp.float32)
    q = jax.random.normal(k3, (B, H, hd), jnp.float32)
    kp = np.zeros((N, bs, Hkv, hd), np.float32)
    vp = np.zeros((N, bs, Hkv, hd), np.float32)
    tables = np.zeros((B, R), np.int32)
    for b in range(B):
        tables[b] = 2 + b * R + np.arange(R)
        for p in range(positions[b] + 1):
            blk = tables[b][(p // bs) % R]
            kp[blk, p % bs] = np.asarray(k_all)[b, p]
            vp[blk, p % bs] = np.asarray(v_all)[b, p]
    return q, k_all, v_all, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tables)


class TestRingAttention:
    def test_ref_and_kernel_match_dense_window_oracle(self):
        B, Hkv, H, hd, bs, window = 3, 2, 4, 32, 4, 6
        R = SP.ring_pages(window, bs)
        positions = [0, 7, 23]                  # fresh, 2nd page, deep wrap
        q, k_all, v_all, kp, vp, tables = _build_ring_case(
            jax.random.PRNGKey(0), B, Hkv, H, hd, bs, window, positions)
        pos = jnp.asarray(positions, jnp.int32)
        lens = pos + 1
        out_ref = paged_attention_ref(q, kp, vp, tables, lens, window=window,
                                      positions=pos, ring_pages=R)
        out_ker = paged_attention(q, kp, vp, tables, lens, window=window,
                                  positions=pos, ring_pages=R)

        # dense oracle: softmax over exactly the last `window` positions
        g = H // Hkv
        for b in range(B):
            lo = max(0, positions[b] - window + 1)
            ks = jnp.repeat(k_all[b, lo:positions[b] + 1], g, axis=1)
            vs = jnp.repeat(v_all[b, lo:positions[b] + 1], g, axis=1)
            s = jnp.einsum("hd,khd->hk", q[b], ks) * hd ** -0.5
            p = jax.nn.softmax(s, axis=-1)
            want = np.asarray(jnp.einsum("hk,khd->hd", p, vs))
            np.testing.assert_allclose(np.asarray(out_ref[b]), want, atol=2e-5)
            np.testing.assert_allclose(np.asarray(out_ker[b]), want, atol=2e-5)

    def test_inactive_slot_and_stale_lap_masked(self):
        B, Hkv, H, hd, bs, window = 2, 2, 4, 32, 4, 6
        R = SP.ring_pages(window, bs)
        q, k_all, v_all, kp, vp, tables = _build_ring_case(
            jax.random.PRNGKey(1), B, Hkv, H, hd, bs, window, [9, 9])
        pos = jnp.asarray([9, 0], jnp.int32)
        lens = jnp.asarray([10, 0], jnp.int32)  # slot 1 inactive
        # poison every entry outside slot 0's window — including the stale
        # previous-lap offsets of its current page — output must not move
        out1 = paged_attention_ref(q, kp, vp, tables, lens, window=window,
                                   positions=pos, ring_pages=R)
        live = set()
        for p in range(9 - window + 1, 10):
            live.add((int(tables[0][(p // bs) % R]), p % bs))
        kp2, vp2 = np.asarray(kp).copy(), np.asarray(vp).copy()
        for blk in range(kp2.shape[0]):
            for off in range(bs):
                if (blk, off) not in live:
                    kp2[blk, off] = 1e4
                    vp2[blk, off] = 1e4
        for fn in (paged_attention_ref, paged_attention):
            out2 = fn(q, jnp.asarray(kp2), jnp.asarray(vp2), tables, lens,
                      window=window, positions=pos, ring_pages=R)
            np.testing.assert_allclose(np.asarray(out2[0]),
                                       np.asarray(out1[0]), atol=1e-5)
            assert bool(jnp.all(out2[1] == 0))


# ------------------------------------------------------- engine end-to-end
class TestEngineAllFamilies:
    @pytest.mark.parametrize("family", FAMILIES)
    def test_engine_matches_generate(self, family, fam_params):
        """Acceptance: staggered mixed-length requests through the engine are
        bit-identical to serve.generate for every family. Generation budgets
        exceed the ring capacity so sliding-window paths wrap."""
        cfg, params = fam_params(family)
        rng = np.random.default_rng(7)
        prompts = [rng.integers(0, 50, size=L).astype(np.int32)
                   for L in (3, 11, 6)]
        news = [24, 6, 17]                      # 24 > ring capacity 3*4 = 12
        eng = _engine(cfg, params)
        rids = []
        for p, mn in zip(prompts, news):
            rids.append(eng.add_request(p, mn))
            eng.step()                          # staggered arrivals
        outs = eng.drain()
        for rid, p, mn in zip(rids, prompts, news):
            np.testing.assert_array_equal(outs[rid], _ref_out(cfg, params, p, mn))
        assert eng.block_pool.num_free == eng.ecfg.num_blocks

    def test_sliding_blocks_bounded_under_long_generation(self, fam_params):
        """A sliding-window sequence allocates at most ceil(window/bs)+1
        blocks no matter how long it decodes (acceptance criterion)."""
        cfg, params = fam_params("sliding")
        ring = SP.ring_pages(cfg.window_size, 4)
        eng = _engine(cfg, params, num_blocks=16, max_blocks_per_seq=6)
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, 50, size=L).astype(np.int32)
                   for L in (3, 11)]
        news = [40, 50]                         # totals 43 / 61 tokens
        rids = [eng.add_request(p, mn) for p, mn in zip(prompts, news)]
        max_blocks = 0
        while eng.scheduler.has_work:
            eng.step()
            for r in eng.scheduler.running.values():
                max_blocks = max(max_blocks, len(eng.block_pool.table(r.rid)))
        assert max_blocks == ring == 3
        for rid, p, mn in zip(rids, prompts, news):
            np.testing.assert_array_equal(
                eng.output(rid), _ref_out(cfg, params, p, mn))

    def test_prefill_chunk_spanning_full_ring_lap(self, fam_params):
        """A prefill chunk LONGER than the ring capacity (C > R*bs = 12) maps
        several chunk positions to the same (block, offset); only the newest
        lap may land — duplicate-index scatter order is undefined. Long
        prompts prefilled through such chunks must still match the oracle."""
        cfg, params = fam_params("sliding")
        rng = np.random.default_rng(23)
        prompts = [rng.integers(0, 50, size=L).astype(np.int32)
                   for L in (29, 17)]
        news = [8, 21]
        eng = _engine(cfg, params, prefill_chunk=16, max_blocks_per_seq=8)
        rids = [eng.add_request(p, mn) for p, mn in zip(prompts, news)]
        outs = eng.drain()
        for rid, p, mn in zip(rids, prompts, news):
            np.testing.assert_array_equal(outs[rid], _ref_out(cfg, params, p, mn))

    def test_sliding_kernel_impl_matches_ref_impl(self, fam_params):
        cfg, params = fam_params("sliding")
        rng = np.random.default_rng(5)
        prompts = [rng.integers(0, 50, size=L).astype(np.int32)
                   for L in (3, 9)]
        news = [18, 7]
        outs = {}
        for impl in ("ref", "kernel"):
            eng = _engine(cfg, params, attn_impl=impl, max_slots=2)
            rids = [eng.add_request(p, mn) for p, mn in zip(prompts, news)]
            res = eng.drain()
            outs[impl] = [res[r] for r in rids]
        for a, b in zip(outs["ref"], outs["kernel"]):
            np.testing.assert_array_equal(a, b)

    def test_hybrid_defrag_mid_flight(self, fam_params):
        """Defrag permutes paged pools and rewrites tables while leaving the
        recurrent slabs alone — outputs stay bit-identical."""
        cfg, params = fam_params("hybrid")
        rng = np.random.default_rng(11)
        prompts = [rng.integers(0, 50, size=L).astype(np.int32)
                   for L in (5, 9, 4)]
        news = [8, 6, 10]
        eng = _engine(cfg, params)
        rids = [eng.add_request(p, mn) for p, mn in zip(prompts, news)]
        for _ in range(3):
            eng.step()
        eng.defragment()
        for _ in range(2):
            eng.step()
        eng.defragment()
        outs = eng.drain()
        for rid, p, mn in zip(rids, prompts, news):
            np.testing.assert_array_equal(outs[rid], _ref_out(cfg, params, p, mn))

    def test_ssm_admits_on_slots_alone(self, fam_params):
        """Recurrent sequences reserve zero blocks: a tiny pool still admits
        max_slots ssm requests at once."""
        cfg, params = fam_params("ssm")
        eng = _engine(cfg, params, num_blocks=1, max_slots=3)
        rng = np.random.default_rng(13)
        prompts = [rng.integers(0, 50, size=6).astype(np.int32)
                   for _ in range(3)]
        rids = [eng.add_request(p, 5) for p in prompts]
        eng.step()
        assert len(eng.scheduler.running) == 3  # all admitted despite 1 block
        outs = eng.drain()
        for rid, p in zip(rids, prompts):
            np.testing.assert_array_equal(outs[rid], _ref_out(cfg, params, p, 5))

    def test_engine_generate_convenience(self, fam_params):
        cfg, params = fam_params("hybrid")
        rng = np.random.default_rng(17)
        prompts = [rng.integers(0, 50, size=L).astype(np.int32)
                   for L in (4, 7)]
        outs = serve.engine_generate(
            cfg, params, prompts, [6, 4],
            engine_cfg=EngineConfig(block_size=4, num_blocks=64,
                                    max_blocks_per_seq=16, max_slots=4,
                                    prefill_chunk=8))
        for out, p, mn in zip(outs, prompts, (6, 4)):
            np.testing.assert_array_equal(out, _ref_out(cfg, params, p, mn))


# ------------------------------------------------------- request validation
# ------------------------------------------- steps write only live tokens
class TestStepsWriteOnlyLiveTokens:
    """The paged steps carry the stacked pool through their layer scan and
    write it in place. After a decode, a packed prefill and a verify step,
    every pool entry that no live token wrote is bit-identical to before,
    and the written entries changed in every superblock (so no layer's
    write lands at another's index). Recurrent slab rows of slots a step
    does not serve are untouched, and those it serves changed in every
    superblock."""

    BS, N, P, B, K = 4, 16, 4, 4, 3

    def _setup(self, family, quant, fam_params):
        cfg, params = fam_params(family)
        pool = T.init_paged_state(cfg, self.N, self.BS, max_slots=self.B,
                                  kv_quant=KVQuantConfig() if quant else None)
        keys = iter(jax.random.split(jax.random.PRNGKey(1), 64))

        def noise(a):       # every entry distinct from what a step writes
            if a.dtype == jnp.int8:
                return jax.random.randint(next(keys), a.shape, -127, 128,
                                          jnp.int32).astype(jnp.int8)
            return jax.random.uniform(next(keys), a.shape, a.dtype, 0.5, 2.0)

        pool = {n: jax.tree.map(noise, st) for n, st in pool.items()}
        tables = jnp.arange(self.B * self.P, dtype=jnp.int32).reshape(
            self.B, self.P)
        return cfg, params, pool, tables

    def _written(self, cfg, skind, tables, writes):
        """(N, bs) mask of the entries the (slot, position) pairs write."""
        mask = np.zeros((self.N, self.BS), bool)
        R = (SP.ring_pages(cfg.window_size, self.BS, draft=self.K - 1)
             if skind == "ring" else None)
        for b, pos in writes:
            page = pos // self.BS if R is None else (pos // self.BS) % R
            mask[int(tables[b, page]), pos % self.BS] = True
        return mask

    def _check(self, cfg, before, after, tables, writes, served):
        skinds = {f"l{j}": k for j, k in enumerate(SP.state_kinds(cfg))}
        for name in before:
            skind = skinds[name]
            for leaf, old, new in zip(before[name], before[name].values(),
                                      after[name].values()):
                old, new = np.asarray(old), np.asarray(new)
                if skind in ("full", "ring"):
                    mask = self._written(cfg, skind, tables, writes)
                    np.testing.assert_array_equal(new[:, ~mask], old[:, ~mask],
                                                  err_msg=f"{name}/{leaf}")
                    for i in range(old.shape[0]):
                        assert not np.array_equal(new[i][mask], old[i][mask]), (
                            f"{name}/{leaf}: superblock {i} not written")
                else:
                    idle = [b for b in range(self.B) if b not in served]
                    np.testing.assert_array_equal(new[:, idle], old[:, idle],
                                                  err_msg=f"{name}/{leaf}")
                    for i in range(old.shape[0]):
                        for b in served:
                            assert not np.array_equal(new[i, b], old[i, b]), (
                                f"{name}/{leaf}: superblock {i} slot {b}")

    @pytest.mark.parametrize("family,quant", [(f, False) for f in FAMILIES]
                             + [("full", True), ("sliding", True)])
    def test_decode_prefill_verify(self, family, quant, fam_params):
        cfg, params, pool, tables = self._setup(family, quant, fam_params)
        copy = lambda tree: jax.tree.map(jnp.array, tree)

        # decode: slot 1 inactive
        pos = jnp.asarray([5, 0, 9, 3], jnp.int32)
        active = np.array([True, False, True, True])
        lens = jnp.where(active, pos + 1, 0)
        _, after = T.paged_decode_step(
            cfg, params, copy(pool), {"token": jnp.arange(self.B) % 50},
            tables, pos, lens, draft=self.K - 1)
        self._check(cfg, pool, after, tables,
                    [(b, int(pos[b])) for b in range(self.B) if active[b]],
                    served={0, 2, 3})

        # packed prefill: slot 2 from position 4, 6 real tokens; a padded
        # segment (slot == max_slots, nothing valid)
        starts = jnp.asarray([4, 0], jnp.int32)
        valids = jnp.asarray([6, 0], jnp.int32)
        slots = jnp.asarray([2, self.B], jnp.int32)
        toks = jnp.arange(16, dtype=jnp.int32).reshape(2, 8) % 50
        _, after = T.paged_prefill_packed(
            cfg, params, copy(pool), toks, tables, starts, valids, slots,
            draft=self.K - 1)
        self._check(cfg, pool, after, tables,
                    [(2, p) for p in range(4, 10)], served={2})

        # verify: K drafts per slot, qlims caps the writes; slot 1 inactive
        base = jnp.asarray([5, 0, 9, 3], jnp.int32)
        qlims = jnp.asarray([3, 0, 2, 1], jnp.int32)
        toks = (jnp.arange(self.B * self.K, dtype=jnp.int32).reshape(
            self.B, self.K) % 50)
        _, after = T.paged_verify_step(cfg, params, copy(pool), toks, tables,
                                       base, qlims)
        paged = {f"l{j}": after[f"l{j}"]
                 for j, k in enumerate(SP.state_kinds(cfg))
                 if k in ("full", "ring")}
        self._check(cfg, {n: pool[n] for n in paged}, paged, tables,
                    [(b, int(base[b]) + j) for b in range(self.B)
                     for j in range(int(qlims[b]))], served=set())


class TestAddRequestValidation:
    def test_oversized_total_raises_with_numbers(self, fam_params):
        cfg, params = fam_params("full")
        eng = _engine(cfg, params)              # 16 blocks * 4 = 64 tokens
        with pytest.raises(ValueError, match=r"60.*max_new 10.*70.*18 blocks"):
            eng.add_request(np.zeros(60, np.int32), 10)

    def test_pool_budget_raises_with_numbers(self, fam_params):
        cfg, params = fam_params("full")
        eng = _engine(cfg, params, num_blocks=8, max_blocks_per_seq=32)
        with pytest.raises(ValueError, match=r"pool budget num_blocks 8"):
            eng.add_request(np.zeros(40, np.int32), 10)

    def test_ring_and_ssm_exempt_from_table_width(self, fam_params):
        """Unbounded-context kinds admit totals far beyond the table width."""
        for fam in ("sliding", "ssm"):
            cfg, params = fam_params(fam)
            eng = _engine(cfg, params, max_blocks_per_seq=4)
            rid = eng.add_request(np.zeros(8, np.int32), 60)    # 68 tokens
            outs = eng.drain()
            assert outs[rid].shape == (60,)

    def test_ring_wider_than_table_rejected_at_construction(self, fam_params):
        cfg, params = fam_params("sliding")     # window 8, bs 4 -> ring 3
        with pytest.raises(ValueError, match=r"ring needs 3 blocks"):
            _engine(cfg, params, max_blocks_per_seq=2)
