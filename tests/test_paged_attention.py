"""Paged-decode attention: Pallas kernel (interpret mode) vs pure-jnp oracle,
and the oracle vs a contiguous masked-attention reference."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attention import (paged_attention,
                                           paged_attention_ref,
                                           paged_attention_verify,
                                           paged_attention_verify_ref)
from repro.kernels.quantize import dequantize_kv, quantize_kv
from repro.models import state_providers as SP

pytestmark = pytest.mark.serving

NEG_INF = -1e30


def _stack(pool, layer, n_layers=4):
    """``pool`` as layer ``layer`` of an (n_layers, ...) stack whose other
    layers are poison: NaN for float leaves, 127 for int8 values (whose NaN
    scales poison them). A read outside the indexed layer turns the output
    NaN."""
    fill = 127 if pool.dtype == jnp.int8 else jnp.nan
    stack = jnp.full((n_layers,) + pool.shape, fill, pool.dtype)
    return stack.at[layer].set(pool)


def _random_case(key, B, H, Hkv, hd, N, bs, P, dtype, lens):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    q = jax.random.normal(k1, (B, H, hd), jnp.float32).astype(dtype)
    kp = jax.random.normal(k2, (N, bs, Hkv, hd), jnp.float32).astype(dtype)
    vp = jax.random.normal(k3, (N, bs, Hkv, hd), jnp.float32).astype(dtype)
    # distinct random blocks per sequence (no aliasing between sequences)
    perm = jax.random.permutation(k4, N)[:B * P]
    tables = perm.reshape(B, P).astype(jnp.int32)
    return q, kp, vp, tables, jnp.asarray(lens, jnp.int32)


class TestPagedAttentionSweep:
    @pytest.mark.parametrize("layer", [None, 2], ids=["pool", "stack"])
    @pytest.mark.parametrize("H,Hkv,hd", [(4, 4, 32), (4, 2, 64), (8, 1, 32)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_kernel_vs_ref(self, H, Hkv, hd, dtype, layer):
        """One layer's pool, or the layers' stack read at ``layer``."""
        B, N, bs, P = 3, 24, 8, 4
        # lengths cross page boundaries, fill exactly, and include a mid-page
        lens = [1, bs * P, bs + 3]
        q, kp, vp, tables, lens = _random_case(
            jax.random.PRNGKey(H * 100 + hd), B, H, Hkv, hd, N, bs, P,
            dtype, lens)
        if layer is not None:
            kp, vp = _stack(kp, layer), _stack(vp, layer)
        out = paged_attention(q, kp, vp, tables, lens, layer=layer)
        ref = paged_attention_ref(q, kp, vp, tables, lens, layer=layer)
        tol = 2e-5 if dtype == jnp.float32 else 0.08
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32), atol=tol)

    def test_inactive_slot_outputs_zero(self):
        B, H, Hkv, hd, N, bs, P = 2, 4, 2, 32, 8, 4, 2
        q, kp, vp, tables, lens = _random_case(
            jax.random.PRNGKey(0), B, H, Hkv, hd, N, bs, P, jnp.float32,
            [5, 0])
        for out in (paged_attention(q, kp, vp, tables, lens),
                    paged_attention_ref(q, kp, vp, tables, lens)):
            assert bool(jnp.all(out[1] == 0))
            assert bool(jnp.all(jnp.isfinite(out)))

    def test_garbage_beyond_seq_len_is_masked(self):
        """Blocks past seq_len may contain stale data from freed sequences."""
        B, H, Hkv, hd, N, bs, P = 1, 2, 2, 32, 6, 4, 3
        key = jax.random.PRNGKey(7)
        q, kp, vp, tables, lens = _random_case(
            key, B, H, Hkv, hd, N, bs, P, jnp.float32, [6])
        out1 = paged_attention(q, kp, vp, tables, lens)
        # poison everything at/after position 6 in this sequence's pages
        kp2 = kp.at[tables[0, 1], 2:].set(1e4).at[tables[0, 2]].set(1e4)
        vp2 = vp.at[tables[0, 1], 2:].set(1e4).at[tables[0, 2]].set(1e4)
        out2 = paged_attention(q, kp2, vp2, tables, lens)
        np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-6)

    def test_ref_matches_contiguous_attention(self):
        """Scatter a contiguous sequence into pages -> paged ref equals plain
        masked decode attention over the contiguous K/V."""
        B, H, Hkv, hd, bs, P = 2, 4, 2, 16, 4, 4
        N = B * P
        L = [11, 7]
        key = jax.random.PRNGKey(3)
        k1, k2, k3 = jax.random.split(key, 3)
        q = jax.random.normal(k1, (B, H, hd))
        k_ctg = jax.random.normal(k2, (B, P * bs, Hkv, hd))
        v_ctg = jax.random.normal(k3, (B, P * bs, Hkv, hd))
        tables = jnp.arange(N, dtype=jnp.int32).reshape(B, P)
        kp = k_ctg.reshape(B * P, bs, Hkv, hd)
        vp = v_ctg.reshape(B * P, bs, Hkv, hd)
        lens = jnp.asarray(L, jnp.int32)
        out = paged_attention_ref(q, kp, vp, tables, lens)

        # contiguous oracle
        g = H // Hkv
        kk = jnp.repeat(k_ctg, g, axis=2)
        vv = jnp.repeat(v_ctg, g, axis=2)
        s = jnp.einsum("bhd,bkhd->bhk", q, kk) * hd ** -0.5
        valid = jnp.arange(P * bs)[None] < lens[:, None]
        s = jnp.where(valid[:, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        ref = jnp.einsum("bhk,bkhd->bhd", p, vv)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# ------------------------------------------------- int8 pools + scales
def _quantize_pools(kp, vp):
    qk, sk = quantize_kv(kp)
    qv, sv = quantize_kv(vp)
    return qk, qv, dict(k_scale=sk, v_scale=sv)


@pytest.mark.kv_quant
class TestQuantizedPagedAttention:
    """int8 pools + per-(token, head) scales, dequantized inside the kernel:
    every mode (full / ring / verify / ring-verify) must match the quantized
    reference, and the reference with scales must equal the reference run on
    an explicitly dequantized fp32 pool bit-for-bit (the scales are pure
    layout, not new math)."""

    def _full_case(self, k=None):
        B, H, Hkv, hd, N, bs, P = 3, 4, 2, 64, 24, 8, 4
        lens = [1, bs * P, bs + 3]
        if k is None:
            return _random_case(jax.random.PRNGKey(0), B, H, Hkv, hd, N, bs,
                                P, jnp.float32, lens)
        q, kp, vp, tables, lens = _random_case(
            jax.random.PRNGKey(0), B, H, Hkv, hd, N, bs, P, jnp.float32,
            [max(l, k) for l in lens])
        q = jax.random.normal(jax.random.PRNGKey(1), (B, k, H, hd))
        return q, kp, vp, tables, lens

    def _ring_case(self, k=None):
        B, H, Hkv, hd, bs, window = 3, 4, 2, 32, 4, 6
        K = 1 if k is None else k
        R = SP.ring_pages(window, bs, draft=K - 1)
        N = B * R + 2
        lens = [K, 2 * bs + 1, 6 * bs]          # fresh / 2nd page / deep wrap
        q, kp, vp, tables, lens = _random_case(
            jax.random.PRNGKey(2), B, H, Hkv, hd, N, bs, R, jnp.float32,
            lens)
        if k is not None:
            q = jax.random.normal(jax.random.PRNGKey(3), (B, k, H, hd))
        pos = jnp.maximum(lens - 1, 0)
        return q, kp, vp, tables, lens, dict(window=window, positions=pos,
                                             ring_pages=R)

    @pytest.mark.parametrize("mode", ["full", "ring", "verify",
                                      "ring_verify"])
    def test_quant_kernel_vs_quant_ref(self, mode):
        if mode == "full":
            q, kp, vp, tables, lens = self._full_case()
            kw, op, rf = {}, paged_attention, paged_attention_ref
        elif mode == "ring":
            q, kp, vp, tables, lens, kw = self._ring_case()
            op, rf = paged_attention, paged_attention_ref
        elif mode == "verify":
            q, kp, vp, tables, lens = self._full_case(k=4)
            kw, op, rf = {}, paged_attention_verify, paged_attention_verify_ref
        else:
            q, kp, vp, tables, lens, kw = self._ring_case(k=4)
            op, rf = paged_attention_verify, paged_attention_verify_ref
        qk, qv, scales = _quantize_pools(kp, vp)
        out = op(q, qk, qv, tables, lens, **scales, **kw)
        ref = rf(q, qk, qv, tables, lens, **scales, **kw)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    @pytest.mark.parametrize("mode", ["full", "ring"])
    def test_ref_scales_equals_dequantized_pool(self, mode):
        if mode == "full":
            q, kp, vp, tables, lens = self._full_case()
            kw = {}
        else:
            q, kp, vp, tables, lens, kw = self._ring_case()
        qk, qv, scales = _quantize_pools(kp, vp)
        with_scales = paged_attention_ref(q, qk, qv, tables, lens, **scales,
                                          **kw)
        on_dequant = paged_attention_ref(
            q, dequantize_kv(qk, scales["k_scale"]),
            dequantize_kv(qv, scales["v_scale"]), tables, lens, **kw)
        np.testing.assert_array_equal(np.asarray(with_scales),
                                      np.asarray(on_dequant))

    def test_garbage_blocks_and_scales_masked(self):
        """Stale blocks past seq_len may hold garbage VALUES AND SCALES from
        freed sequences — both must be masked out."""
        B, H, Hkv, hd, N, bs, P = 1, 2, 2, 32, 6, 4, 3
        q, kp, vp, tables, lens = _random_case(
            jax.random.PRNGKey(7), B, H, Hkv, hd, N, bs, P, jnp.float32, [6])
        qk, qv, scales = _quantize_pools(kp, vp)
        out1 = paged_attention(q, qk, qv, tables, lens, **scales)
        qk2 = qk.at[tables[0, 1], 2:].set(127).at[tables[0, 2]].set(127)
        qv2 = qv.at[tables[0, 1], 2:].set(127).at[tables[0, 2]].set(127)
        poisoned = {
            n: s.at[tables[0, 1], 2:].set(1e6).at[tables[0, 2]].set(1e6)
            for n, s in scales.items()}
        for fn in (paged_attention, paged_attention_ref):
            out2 = fn(q, qk2, qv2, tables, lens, **poisoned)
            np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                                       atol=1e-6)

    def test_inactive_slot_outputs_zero_quant(self):
        B, H, Hkv, hd, N, bs, P = 2, 4, 2, 32, 8, 4, 2
        q, kp, vp, tables, lens = _random_case(
            jax.random.PRNGKey(4), B, H, Hkv, hd, N, bs, P, jnp.float32,
            [5, 0])
        qk, qv, scales = _quantize_pools(kp, vp)
        for out in (paged_attention(q, qk, qv, tables, lens, **scales),
                    paged_attention_ref(q, qk, qv, tables, lens, **scales)):
            assert bool(jnp.all(out[1] == 0))
            assert bool(jnp.all(jnp.isfinite(out)))


# ----------------------------------------------- the layers' stacked pool
@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("mode", ["full", "ring", "verify", "ring_verify"])
def test_stacked_pool_kernel_vs_ref(mode, quant):
    """The layer scan hands the kernel every layer's pool stacked and the
    layer's index (a traced scalar). Read at layer 2 of a stack whose other
    layers are NaN, kernel and reference must match the reference on that
    layer's pool alone, and the reference bit for bit."""
    cases = TestQuantizedPagedAttention()
    k = 4 if mode.endswith("verify") else None
    if mode.startswith("ring"):
        q, kp, vp, tables, lens, kw = cases._ring_case(k)
    else:
        q, kp, vp, tables, lens = cases._full_case(k)
        kw = {}
    op, rf = ((paged_attention_verify, paged_attention_verify_ref)
              if k else (paged_attention, paged_attention_ref))
    if quant:
        kp, vp, scales = _quantize_pools(kp, vp)
    else:
        q, kp, vp = (a.astype(jnp.bfloat16) for a in (q, kp, vp))
        scales = {}
    layer = jnp.int32(2)
    stacked = dict(layer=layer, **{n: _stack(a, 2) for n, a in scales.items()})
    ks, vs = _stack(kp, 2), _stack(vp, 2)
    alone = rf(q, kp, vp, tables, lens, **scales, **kw)
    ref = rf(q, ks, vs, tables, lens, **stacked, **kw)
    out = op(q, ks, vs, tables, lens, **stacked, **kw)
    np.testing.assert_array_equal(np.asarray(ref, np.float32),
                                  np.asarray(alone, np.float32))
    assert bool(jnp.all(jnp.isfinite(out)))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(alone, np.float32),
                               atol=2e-5 if quant else 0.08)


@pytest.mark.parametrize("mode", ["full", "ring", "verify", "ring_verify"])
def test_pages_span_grid_steps(mode):
    """Tables longer than one grid step's pages (PAGES_PER_STEP) and not a
    multiple of it: lengths end inside the first step, exactly at its end,
    one token into the second, and at the table's end (the last step's
    pages past the table repeat its last entry and are skipped)."""
    from repro.kernels.paged_attention.kernel import PAGES_PER_STEP
    B, H, Hkv, hd, bs = 4, 4, 2, 32, 4
    P = PAGES_PER_STEP + 3
    verify, ring = mode.endswith("verify"), mode.startswith("ring")
    K = 3 if verify else 1
    lens = [K + 2, PAGES_PER_STEP * bs, PAGES_PER_STEP * bs + 1, P * bs]
    q, kp, vp, tables, lens = _random_case(
        jax.random.PRNGKey(11), B, H, Hkv, hd, B * P + 2, bs, P,
        jnp.float32, lens)
    kw = {}
    if ring:
        # a window whose ring is the whole table, entered past its first lap
        window = (P - 1) * bs - (K - 1)
        assert SP.ring_pages(window, bs, draft=K - 1) == P
        lens = lens + 2 * P * bs
        kw = dict(window=window, positions=lens - 1, ring_pages=P)
    if verify:
        q = jax.random.normal(jax.random.PRNGKey(12), (B, K, H, hd))
        op, rf = paged_attention_verify, paged_attention_verify_ref
    else:
        op, rf = paged_attention, paged_attention_ref
    ks, vs = _stack(kp, 1, 3), _stack(vp, 1, 3)
    out = op(q, ks, vs, tables, lens, layer=jnp.int32(1), **kw)
    ref = rf(q, kp, vp, tables, lens, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
