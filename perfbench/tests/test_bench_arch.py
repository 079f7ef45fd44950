"""Architectures are modules that a configuration file names.

The dense decoder's weights, layout and work counts are the ones the
harness had before they moved to ``perfbench/arch/decoder.py``: every
expected value below was read from the harness of the commit before that
move. A second architecture (``moe_toy.py``, substituted here for
``load_module("arch", ...)``) gets its engine, its train step and its
step MFU with no harness file knowing it.
"""
import json
import os
import sys
import zlib

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, HERE)

import moe_toy  # noqa: E402
import tiny  # noqa: E402
from perfbench import bench, model, peaks, serving  # noqa: E402
from perfbench.drivers import train_steps  # noqa: E402
from perfbench.metrics import step_mfu  # noqa: E402

SEED = 2 ** 33 + 5


def _crc(tree):
    import jax
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    crc = 0
    for _, a in sorted(flat, key=lambda t: jax.tree_util.keystr(t[0])):
        crc = zlib.crc32(np.asarray(a).tobytes(), crc)
    return crc


# ------------------------------------------------------------ nothing moved
@pytest.mark.parametrize("tied,want", [(True, 1316370890),
                                       (False, 371046549)])
def test_toy_weights_equal_the_parents_bit_for_bit(tied, want):
    """CRC-32 over every leaf's bytes, in path order; the values are the
    parent commit's for the same spec and seed."""
    spec = dict(tiny.SPEC, tie_word_embeddings=tied)
    s = model.arch(spec).sizes(spec)
    assert _crc(model.program_params(s, SEED)) == want


_ATTN = ("attn/wk", "attn/wo", "attn/wq", "attn/wv", "ln1/scale",
         "ln2/scale", "mlp/w_gate", "mlp/w_in", "mlp/w_out")
PARENT_SHAPES = {      # (layers, d, kv, q, ff, vocab, tied), parent commit
    "phi4mini": (32, 3072, 1024, 3072, 8192, 200064, True),
    "stablelm3b": (4, 2560, 2560, 2560, 6912, 50304, False),
}


@pytest.mark.parametrize("name", sorted(PARENT_SHAPES))
def test_param_shapes_equal_the_parents(name):
    import jax
    L, d, kv, q, ff, vocab, tied = PARENT_SHAPES[name]
    bf, f32 = "bfloat16", "float32"
    want = dict(zip([f"['blocks']['l0']['{a}']['{b}']" for a, b in
                     (p.split("/") for p in _ATTN)],
                    [((L, d, kv), bf), ((L, q, d), bf), ((L, d, q), bf),
                     ((L, d, kv), bf), ((L, d), f32), ((L, d), f32),
                     ((L, d, ff), bf), ((L, d, ff), bf), ((L, ff, d), bf)]))
    want["['embed']['table']"] = ((vocab, d), bf)
    want["['final_norm']['scale']"] = ((d,), f32)
    if not tied:
        want["['lm_head']['w']"] = ((d, vocab), bf)
    spec = model.load_spec(name)
    s = model.arch(spec).sizes(spec)
    got = jax.eval_shape(lambda: model.program_params(s, 0))
    assert {jax.tree_util.keystr(k): (tuple(v.shape), str(v.dtype))
            for k, v in jax.tree_util.tree_flatten_with_path(got)[0]} == want


TOKENS = (1, 17, 1000, 4096)
CHUNKS = ((0, 1), (0, 512), (512, 512), (3000, 77))
SEQS = (1, 64, 4096)
LENS = ([1], [3, 5], [300, 900, 4096])
PARENT_COUNTS = {      # the parent commit's flops.py on the same sizes
    "phi4mini": {
        "token": [7672037376, 7678328832, 8064860160, 9282256896],
        "token_no_head_300": 6560415744,
        "prefill": [7672037376, 3351404347392, 3454483562496, 589311639552],
        "train": [23016112128.0, 23053271040.0, 25431441408.0],
        "paged": [(393216, 524288), (3145728, 1835008),
                  (2082471936, 695336960)],
        "paged_int8_300_900": (471859200, 79036416)},
    "stablelm3b": {
        "token": [891985920, 892641280, 932904960, 1059717120],
        "token_no_head_300": 646676480,
        "prefill": [891985920, 330443653120, 341181071360, 58690232320],
        "train": [2675957760.0, 2679828480.0, 2927554560.0],
        "paged": [(40960, 81920), (327680, 409600), (216924160, 217047040)],
        "paged_int8_300_900": (49152000, 24616960)},
}


@pytest.mark.parametrize("name", sorted(PARENT_COUNTS))
def test_work_counts_equal_the_parents(name):
    spec = model.load_spec(name)
    arch = model.arch(spec)
    s = arch.sizes(spec)
    got = {
        "token": [arch.token_flops(s, n) for n in TOKENS],
        "token_no_head_300": arch.token_flops(s, 300, logits=False),
        "prefill": [arch.prefill_flops(s, a, b) for a, b in CHUNKS],
        "train": [arch.train_flops_per_token(s, n) for n in SEQS],
        "paged": [arch.paged_attention_work(s, ln) for ln in LENS],
        "paged_int8_300_900": arch.paged_attention_work(s, [300, 900],
                                                        kv_bytes=1)}
    assert got == PARENT_COUNTS[name]


# ------------------------------------------------- the file names its arch
@pytest.mark.parametrize("arch,says", [(None, "names no 'arch'"),
                                       ("nosuch", "no perfbench/arch/")])
def test_config_without_a_known_arch_fails_at_load_cell(
        monkeypatch, tmp_path, arch, says):
    spec = dict(tiny.SPEC)
    spec.pop("arch")
    if arch:
        spec["arch"] = arch
    for sub, name, body in (("configs", "toyconf", spec),
                            ("traffic", "toymix", {"driver": "backlog"})):
        (tmp_path / sub).mkdir()
        (tmp_path / sub / f"{name}.json").write_text(json.dumps(body))
    man = {"run_seconds": 5, "end_to_end": [], "per_layer": [],
           "workloads": [{"name": "toy.cell", "config": "toyconf",
                          "traffic": "toymix", "chips": 1, "why": "a test"}]}
    monkeypatch.setattr(bench, "HERE", str(tmp_path))
    monkeypatch.setattr(bench, "manifest", lambda: man)
    with pytest.raises(bench.BenchError) as e:
        bench.load_cell("toy.cell")
    assert "perfbench/configs/toyconf.json" in str(e.value)
    assert says in str(e.value)


# --------------------------------------------- a second architecture, CPU
@pytest.fixture
def moe(monkeypatch):
    """``arch: moe_toy`` resolves to the test's module, as a committed
    ``perfbench/arch/moe_toy.py`` would."""
    load = bench.load_module
    monkeypatch.setattr(bench, "load_module", lambda kind, name: moe_toy if (
        kind, name) == ("arch", "moe_toy") else load(kind, name))
    return moe_toy.SPEC


def test_second_arch_lays_out_the_programs_moe_params(moe):
    import jax
    from repro.models import transformer as T
    arch = model.arch(moe)
    s, cfg = arch.sizes(moe), arch.model_config(moe)
    ours = jax.eval_shape(lambda: model.program_params(s, 0))
    theirs = jax.eval_shape(lambda k: T.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    assert set(ours["blocks"]["l0"]["moe"]) == {"router", "w_gate", "w_in",
                                                "w_out"}


def test_second_arch_serves_and_step_mfu_reads_its_counts(moe):
    cell = tiny.cell("open_loop", spec=moe)
    eng, s, _ = serving.build(cell, SEED)
    assert s["arch"] == "moe_toy"
    rng = np.random.default_rng(0)
    lens = (20, 45, 9)                  # 45 > the 32-token chunk: two chunks
    with serving.record_work(eng) as work:
        for n in lens:
            eng.add_request(rng.integers(0, s["vocab"], n).astype(np.int32), 5)
        out = eng.drain()
    assert sorted(len(v) for v in out.values()) == [5, 5, 5]
    assert all(0 <= t < s["vocab"] for v in out.values() for t in v)
    assert work.decode_lens and (0, 32) in work.prefill
    obs = {"trace": {"window_s": 1.0}, "kind": "TPU v5 lite", "sizes": s,
           "decode_lens": work.decode_lens, "prefill": work.prefill}
    ops = sum(moe_toy.token_flops(s, n) for ln in work.decode_lens
              for n in ln) + sum(moe_toy.prefill_flops(s, a, b)
                                 for a, b in work.prefill)
    peak = peaks.peaks("TPU v5 lite")["bf16_flops"]
    assert step_mfu.read(obs, "step_mfu.offline") == 100.0 * ops / peak
    # the router and the experts a token uses are in its count
    dense = dict(s, ff=moe_toy.SPEC["moe_intermediate_size"])
    from perfbench.arch import decoder
    assert moe_toy.token_flops(s, 10) - decoder.token_flops(dense, 10) == \
        2 * s["layers"] * (s["d"] * s["experts"]
                           + (s["top_k"] - 1) * 3 * s["d"] * s["expert_ff"])


def test_second_arch_takes_a_train_step(moe):
    import jax
    cell = tiny.cell("train_steps", spec=moe)
    traffic = cell["traffic"]
    arch = model.arch(moe)
    s, cfg = arch.sizes(moe), arch.model_config(moe)
    init, step, _, _ = train_steps.build(cfg, s, traffic, jax.devices()[:1])
    key = model.seed_key(SEED)
    state = init(key)
    state, m = step(state, train_steps.make_batch(s, traffic)(key, 0))
    assert np.isfinite(float(m["loss"]))
    grad, delta = train_steps.reader(s, traffic["optimizer"]["beta1"])(
        state, key)
    for leaf in ("router", "w_gate", "w_in", "w_out"):
        k = f"['blocks']['l0']['moe']['{leaf}']"
        assert np.all(grad[k] > 0) and np.all(delta[k] > 0)
