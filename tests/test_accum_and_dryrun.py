"""Gradient-accumulation equivalence + end-to-end dry-run smoke."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import parallelism as par
from repro.launch.mesh import make_mesh
from repro.optim import make_optimizer
from repro.train import trainer
from conftest import run_multidev


def tiny():
    return ModelConfig(name="t", family="dense", num_layers=2, d_model=64,
                       num_heads=4, num_kv_heads=4, head_dim=16, d_ff=128,
                       vocab_size=64, loss_chunk=32, attn_chunk=32, remat=False)


class TestGradAccumulation:
    def test_accum_matches_full_batch(self):
        """accum_steps=4 must produce the same update as one full batch
        (same mean gradient, modulo f32 accumulation order)."""
        cfg = tiny()
        opt = make_optimizer("sgd", lr=1e-2)
        plan = par.make_plan("dp", make_mesh())
        key = jax.random.PRNGKey(0)
        batch = {
            "tokens": jax.random.randint(key, (8, 64), 0, cfg.vocab_size),
            "labels": jax.random.randint(key, (8, 64), 0, cfg.vocab_size),
        }
        s0 = trainer.init_state(cfg, opt, key)
        full = jax.jit(trainer.make_train_step(cfg, opt, plan, accum_steps=1))
        acc = jax.jit(trainer.make_train_step(cfg, opt, plan, accum_steps=4))
        s1, m1 = full(s0, batch)
        s2, m2 = acc(s0, batch)
        assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-3)
        for a, b in zip(jax.tree_util.tree_leaves(s1["params"]),
                        jax.tree_util.tree_leaves(s2["params"])):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=2e-3, rtol=2e-2)


@pytest.mark.slow
class TestDryRunEndToEnd:
    def test_dryrun_lowers_and_compiles_on_production_mesh(self):
        """Deliverable (e) in miniature: one full-config decode combo lowers
        + compiles under 512 placeholder devices inside the test suite."""
        run_multidev("""
            from repro.launch.dryrun import run
            rec = run('rwkv6-7b', 'decode_32k', 'single', 'dp_tp', quiet=True)
            assert rec['status'] == 'ok', rec
            assert rec['chips'] == 256
            assert rec['fits_hbm'] is True
            assert rec['roofline']['memory_s'] > 0
            rec2 = run('phi4-mini-3.8b', 'long_500k', 'single', 'dp_tp',
                       quiet=True)
            assert rec2['status'] == 'skipped'
            print('PASS')
        """, devices=512, timeout=900)
