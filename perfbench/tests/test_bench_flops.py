"""The operation and byte counts against hand counts, and the peak table."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench import flops, model, peaks  # noqa: E402

S = {"layers": 2, "d": 8, "heads": 4, "kv_heads": 2, "head_dim": 2,
     "ff": 16, "vocab": 10}


def test_layer_matmuls():
    # q 8x8, k 8x4, v 8x4, o 8x8, mlp 3 x 8x16
    assert flops._layer_matmul(S) == 64 + 32 + 32 + 64 + 384


def test_token_and_attention():
    # q.k and p.v: 2 ops a multiply-add, 2 products, 4 heads x 2 dims
    assert flops.attention_flops(S, 5) == 4 * 2 * 4 * 2 * 5
    assert flops.token_flops(S, 5) == 2 * 2 * 576 + 320 + 2 * 8 * 10
    assert flops.token_flops(S, 5, logits=False) == 2 * 2 * 576 + 320


def test_prefill_counts_causal_keys():
    # tokens at positions 3, 4, 5 attend 4 + 5 + 6 = 15 keys
    want = 3 * 2 * 2 * 576 + flops.attention_flops(S, 15) + 2 * 8 * 10
    assert flops.prefill_flops(S, 3, 3) == want


def test_train_per_token():
    f = flops.train_flops_per_token(S, 4)
    assert f == 3 * (2 * 2 * 576 + 2 * 8 * 10 + 4 * 2 * 4 * 2 * 2.5)


def test_paged_attention_work():
    ops, b = flops.paged_attention_work(S, [3, 5])
    assert ops == flops.attention_flops(S, 8)
    assert b == 2 * (2 * 8 * 2 * 2 * 2 + 2 * 2 * 4 * 2 * 2)


def test_model_counts_match_published_sizes():
    s = model.sizes(model.load_spec("phi4mini"))
    per_layer = flops._layer_matmul(s)
    # phi-4-mini: 3.84 B parameters with the tied head
    total = s["layers"] * (per_layer + 2 * s["d"]) + s["vocab"] * s["d"] \
        + s["d"]
    assert total == pytest.approx(3.836e9, rel=2e-3)


def test_peaks_by_device_kind():
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
