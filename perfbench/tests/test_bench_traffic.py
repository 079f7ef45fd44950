"""Traffic and weights repeat from a seed; every seed offers the same work."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench import model, serving  # noqa: E402
from perfbench.drivers import open_loop  # noqa: E402

CHAT = {"rate_rps": 2.0, "order_seed": 0,
        "prompt": {"median": 1024, "sigma": 1.0, "min": 64, "max": 4096},
        "output": {"median": 128, "sigma": 0.8, "min": 16, "max": 512}}
BIG = 2 ** 33 + 12345                       # more than 32 bits


def _sched(seed, order_seed=0):
    draws = serving.Draws(seed, dict(CHAT, order_seed=order_seed))
    return open_loop.schedule(draws, CHAT, 100.0, 30.0, 200064, True)


def test_quantile_lengths_stand_for_the_distribution():
    x = serving.quantile_lengths(1001, CHAT["prompt"])
    assert x[500] == 1024                   # the median itself
    assert x.min() >= 64 and x.max() <= 4096
    assert np.all(np.diff(x) >= 0)
    g = serving.quantile_gaps(1000, 2.0)
    assert g.mean() == pytest.approx(0.5, rel=0.01)


def test_schedule_repeats_from_a_seed():
    a, b = _sched(BIG), _sched(BIG)
    assert [i.due for i in a] == [i.due for i in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [i.max_new for i in a] == [i.max_new for i in b]


def test_every_seed_offers_the_same_work_in_the_same_order():
    a, b = _sched(BIG), _sched(7)
    assert len(a) == len(b) == 60
    assert [i.due for i in a] == [i.due for i in b]
    assert [len(i.prompt) for i in a] == [len(i.prompt) for i in b]
    assert [i.max_new for i in a] == [i.max_new for i in b]
    assert not np.array_equal(a[0].prompt, b[0].prompt)


def test_another_order_seed_offers_the_same_work_in_another_order():
    a, b = _sched(BIG), _sched(BIG, order_seed=1)
    assert sorted(len(i.prompt) for i in a) == sorted(len(i.prompt) for i in b)
    assert sorted(i.max_new for i in a) == sorted(i.max_new for i in b)
    assert [len(i.prompt) for i in a] != [len(i.prompt) for i in b]
    gaps = [np.diff([100.0] + [i.due for i in s]) for s in (a, b)]
    assert sorted(gaps[0]) == pytest.approx(sorted(gaps[1]))
    for g in gaps:
        assert np.all(g > 0) and g.sum() == pytest.approx(30.0)


def test_weights_one_layer_alone_equal_the_stacked_ones():
    s = {"arch": "decoder", "layers": 3, "d": 16, "heads": 2, "kv_heads": 1,
         "head_dim": 8, "ff": 32, "vocab": 50, "eps": 1e-5, "theta": 1e4,
         "tied": False, "dtype": "bfloat16"}
    p = model.program_params(s, BIG)
    key = model.seed_key(BIG)
    for i in range(3):
        one = model.layer_weights(s, key, i)
        assert np.array_equal(np.asarray(one["mlp/w_in"]),
                              np.asarray(p["blocks"]["l0"]["mlp"]["w_in"][i]))
        assert np.array_equal(np.asarray(one["attn/wq"]),
                              np.asarray(p["blocks"]["l0"]["attn"]["wq"][i]))
    assert str(p["embed"]["table"].dtype) == "bfloat16"
    q = model.program_params(s, BIG + 1)
    assert not np.array_equal(np.asarray(p["lm_head"]["w"]),
                              np.asarray(q["lm_head"]["w"]))


def test_weights_follow_the_programs_layout():
    import jax
    from repro.models import transformer as T
    spec = dict(model.load_spec("phi4mini"), num_hidden_layers=2,
                hidden_size=64, intermediate_size=128, num_attention_heads=4,
                num_key_value_heads=2, vocab_size=256)
    arch = model.arch(spec)
    s, cfg = arch.sizes(spec), arch.model_config(spec)
    ours = jax.eval_shape(lambda: model.program_params(s, 0))
    theirs = jax.eval_shape(lambda k: T.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
