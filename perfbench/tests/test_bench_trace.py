"""The trace reduction on a small recorded trace: busy union, idle share,
per-op and per-program device time, and idle gaps named by host spans."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench import bench, trace  # noqa: E402

# A recorded window of 1.0 s on one chip (seconds): two decode programs with
# their ops, a prefill, and the host's annotations around them.
OPS = {"/device:TPU:0": [
    (0.10, 0.20, "fusion.1"), (0.15, 0.25, "%paged_attention.9"),   # overlap
    (0.40, 0.50, "fusion.1"), (0.50, 0.55, "%paged_attention.9"),
    (0.70, 0.90, "fusion.2"),
    (0.95, 1.20, "fusion.2"),                                 # past the end
]}
MODULES = {"/device:TPU:0": [
    (0.10, 0.25, "jit_decode_fn(1)"), (0.40, 0.55, "jit_decode_fn(1)"),
    (0.70, 0.90, "jit_prefill_fn(2)"), (0.95, 1.20, "jit_prefill_fn(2)"),
]}
HOST = [(0.0, 0.12, "engine/decode"), (0.25, 0.40, "bench/read_tokens"),
        (0.26, 0.30, "engine/decode"), (0.55, 0.70, "bench/add_request")]
WINDOW = (0.0, 1.0)


def test_op_names_drop_the_hlo_text():
    assert trace.op_name("%paged_attention.9 = bf16[16,24,128]{2,1,0} "
                         "custom-call(s32[16,288] %copy-done)") == \
        "%paged_attention.9"
    assert trace.op_name("fusion.1") == "fusion.1"


def test_union_and_gaps():
    assert trace.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert trace.union_length([]) == 0
    assert trace.gaps([(1, 2), (1.5, 3)], 0, 4) == [(0, 1), (3, 4)]
    assert trace.gaps([], 0, 1) == [(0, 1)]


def test_summarize_recorded_window():
    s = trace.summarize(OPS, MODULES, HOST, WINDOW)
    # busy: [0.10, 0.25] + [0.40, 0.55] + [0.70, 0.90] + [0.95, 1.0]
    assert s["busy_s"] == pytest.approx(0.55)
    assert s["window_s"] == pytest.approx(1.0)
    assert s["op_s"]["%paged_attention.9"] == pytest.approx(0.15)
    assert s["module_s"]["jit_decode_fn(1)"] == pytest.approx(0.30)
    assert s["module_n"]["jit_decode_fn(1)"] == 2
    # the prefill that runs past the window counts only inside it
    assert s["module_s"]["jit_prefill_fn(2)"] == pytest.approx(0.25)
    gaps = s["breakdown"]["idle_gaps"]
    # longest first: [0.25, 0.40] under read_tokens (0.265 .. 0.30 is a
    # shorter decode span, but the middle 0.325 is outside it), then
    # [0.55, 0.70] under add_request, [0.0, 0.10] under decode
    assert [g[0] for g in gaps] == ["bench/read_tokens", "bench/add_request",
                                    "engine/decode", "none"]
    assert [round(g[1], 6) for g in gaps] == [0.15, 0.15, 0.1, 0.05]
    ops = dict(s["breakdown"]["device_ops"])
    assert ops["fusion.2"] == pytest.approx(0.25)


def test_idle_share_reader():
    s = trace.summarize(OPS, MODULES, HOST, WINDOW)
    idle = bench.load_module("metrics", "device_idle_pct.online")
    assert idle.read({"trace": s}, "device_idle_pct.online") == \
        pytest.approx(45.0)
    assert idle.read({"trace": None}, "x") is None
    dec = bench.load_module("metrics", "decode_step_ms.online")
    assert dec.read({"trace": s}, "x") == pytest.approx(150.0)


def test_kernel_roofline_reader():
    s = trace.summarize(OPS, MODULES, HOST, WINDOW)
    roof = bench.load_module("metrics", "paged_attn_roofline")
    sizes = {"arch": "decoder", "layers": 2, "heads": 4, "kv_heads": 2,
             "head_dim": 8}
    obs = {"trace": s, "kind": "TPU v5 lite", "sizes": sizes,
           "decode_lens": [[10, 20], [30]]}
    # bytes: 2 layers x (K and V of 60 tokens: 2*60*2*8*2 + q and out of
    # 3 sequences: 3*2*4*8*2) = 2 x (3840 + 384)
    want = 2 * (3840 + 384) / 819e9 / 0.15 * 100
    assert roof.read(obs, "paged_attn_roofline") == pytest.approx(want)
    assert roof.read(dict(obs, decode_lens=[]), "x") is None
