"""The operation and byte counts against hand counts, and the peak table."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench import flops, model, peaks  # noqa: E402
from perfbench.arch import decoder  # noqa: E402

S = {"arch": "decoder", "layers": 2, "d": 8, "heads": 4, "kv_heads": 2,
     "head_dim": 2, "ff": 16, "vocab": 10}


def test_layer_matmuls():
    # q 8x8, k 8x4, v 8x4, o 8x8, mlp 3 x 8x16
    assert decoder._layer_matmul(S) == 64 + 32 + 32 + 64 + 384


def test_token_and_attention():
    # q.k and p.v: 2 ops a multiply-add, 2 products, 4 heads x 2 dims
    assert flops.attention_flops(4, 2, 5) == 4 * 4 * 2 * 5
    assert decoder._attention(S, 5) == 2 * flops.attention_flops(4, 2, 5)
    assert decoder.token_flops(S, 5) == 2 * 2 * 576 + 320 + 2 * 8 * 10
    assert decoder.token_flops(S, 5, logits=False) == 2 * 2 * 576 + 320


def test_prefill_counts_causal_keys():
    # tokens at positions 3, 4, 5 attend 4 + 5 + 6 = 15 keys
    assert flops.causal_keys(3, 3) == 15
    want = 3 * 2 * 2 * 576 + decoder._attention(S, 15) + 2 * 8 * 10
    assert decoder.prefill_flops(S, 3, 3) == want


@pytest.mark.parametrize("start,valid,window", [
    (0, 1, 4), (0, 4, 4), (0, 9, 4), (2, 5, 4), (3, 1, 4), (7, 6, 4),
    (0, 40, 16), (15, 3, 16), (100, 7, 16), (5, 5, None)])
def test_causal_keys_under_a_window(start, valid, window):
    """The closed form against a token-by-token count."""
    want = sum(min(p + 1, window or p + 1)
               for p in range(start, start + valid))
    assert flops.causal_keys(start, valid, window) == want


def test_train_per_token():
    f = decoder.train_flops_per_token(S, 4)
    assert f == 3 * (2 * 2 * 576 + 2 * 8 * 10 + 4 * 2 * 4 * 2 * 2.5)


def test_paged_attention_work():
    ops, b = decoder.paged_attention_work(S, [3, 5])
    assert ops == decoder._attention(S, 8)
    assert b == 2 * (2 * 8 * 2 * 2 * 2 + 2 * 2 * 4 * 2 * 2)
    assert flops.decode_attention_work(4, 2, 2, [3, 5]) == (ops // 2, b // 2)


def test_model_counts_match_published_sizes():
    spec = model.load_spec("phi4mini")
    s = model.arch(spec).sizes(spec)
    per_layer = decoder._layer_matmul(s)
    # phi-4-mini: 3.84 B parameters with the tied head
    total = s["layers"] * (per_layer + 2 * s["d"]) + s["vocab"] * s["d"] \
        + s["d"]
    assert total == pytest.approx(3.836e9, rel=2e-3)


def test_peaks_by_device_kind():
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
