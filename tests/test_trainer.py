"""Integration: training decreases loss; checkpoint roundtrip; paper-mode
(explicit collectives + compression) matches pjit mode."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import parallelism as par
from repro.data.pipeline import SyntheticLM, copy_task
from repro.launch.mesh import make_mesh
from repro.models import transformer as T
from repro.optim import make_optimizer
from repro.train import checkpoint as ckpt
from repro.train import trainer
from conftest import run_multidev


def tiny():
    return ModelConfig(name="tiny", family="dense", num_layers=2, d_model=64,
                       num_heads=4, num_kv_heads=4, head_dim=16, d_ff=128,
                       vocab_size=64, loss_chunk=32, attn_chunk=32, remat=False)


class TestTrainingConverges:
    def test_loss_decreases_synthetic_lm(self):
        cfg = tiny()
        opt = make_optimizer("adam", lr=3e-3)
        state = trainer.init_state(cfg, opt, jax.random.PRNGKey(0))
        plan = par.make_plan("dp", make_mesh())
        step = jax.jit(trainer.make_train_step(cfg, opt, plan))
        data = SyntheticLM(cfg.vocab_size, 64, noise=0.05)
        losses = []
        for batch in data.batches(16, 60):
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
        first, last = np.mean(losses[:5]), np.mean(losses[-5:])
        assert last < first - 0.35, (first, last)
        assert min(losses) == min(losses[-30:])   # still improving late


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        cfg = tiny()
        opt = make_optimizer("adam")
        state = trainer.init_state(cfg, opt, jax.random.PRNGKey(0))
        path = str(tmp_path / "ck.npz")
        ckpt.save(path, state, step=7)
        restored, step = ckpt.restore(path, jax.eval_shape(lambda: state))
        assert step == 7
        for a, b in zip(jax.tree_util.tree_leaves(state),
                        jax.tree_util.tree_leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_restore_rejects_shape_mismatch(self, tmp_path):
        cfg = tiny()
        opt = make_optimizer("sgd")
        state = trainer.init_state(cfg, opt, jax.random.PRNGKey(0))
        path = str(tmp_path / "ck.npz")
        ckpt.save(path, state)
        import dataclasses
        cfg2 = dataclasses.replace(cfg, d_model=128, head_dim=32)
        bad = jax.eval_shape(lambda: trainer.init_state(
            cfg2, opt, jax.random.PRNGKey(0)))
        with pytest.raises((ValueError, KeyError)):
            ckpt.restore(path, bad)

    def test_launcher_resume_saves_global_step(self, tmp_path):
        """launch.train saves the step count since the first run, not the
        steps of the last one, so numbering carries across resumes."""
        from repro.launch.train import train
        opt = make_optimizer("sgd", lr=0.1)
        first, second = str(tmp_path / "a.npz"), str(tmp_path / "b.npz")
        kw = dict(batch=2, seq=16, log=lambda s: None)
        state, _ = train(tiny(), opt, steps=2, checkpoint=first, **kw)
        logs = []
        train(tiny(), opt, steps=3, resume=first, checkpoint=second,
              **{**kw, "log": logs.append})
        assert ckpt.restore(first, state)[1] == 2
        assert ckpt.restore(second, state)[1] == 5
        assert logs[0] == f"resumed from {first} at step 2"
        assert logs[1].startswith("step 3: loss=")


@pytest.mark.slow
class TestPaperMode:
    def test_explicit_dp_matches_pjit_mode(self):
        """shard_map DP with our ring allreduce reproduces pjit-mode losses."""
        run_multidev("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.configs.base import ModelConfig
            from repro.core import parallelism as par
            from repro.data.pipeline import SyntheticLM
            from repro.launch.mesh import make_mesh
            from repro.optim import make_optimizer
            from repro.train import trainer
            cfg = ModelConfig(name='t', family='dense', num_layers=1,
                              d_model=32, num_heads=2, num_kv_heads=2,
                              head_dim=16, d_ff=64, vocab_size=32,
                              loss_chunk=32, attn_chunk=32, remat=False)
            mesh = make_mesh((4,), ('data',))
            opt = make_optimizer('sgd', lr=1e-2)
            data = SyntheticLM(cfg.vocab_size, 32, noise=0.05)
            batches = list(data.batches(8, 5))

            plan = par.make_plan('dp', mesh)
            s1 = trainer.init_state(cfg, opt, jax.random.PRNGKey(0))
            f1 = jax.jit(trainer.make_train_step(cfg, opt, plan))
            l1 = []
            for b in batches:
                s1, m = f1(s1, b)
                l1.append(float(m['loss']))

            s2 = trainer.init_state(cfg, opt, jax.random.PRNGKey(0))
            f2 = jax.jit(trainer.make_paper_train_step(
                cfg, opt, mesh, algorithm='ring'))
            res = {'_': jnp.zeros((1,), jnp.float32)}
            l2 = []
            for b in batches:
                s2, m, res = f2(s2, b, res)
                l2.append(float(m['loss']))
            np.testing.assert_allclose(l1, l2, rtol=2e-2)
            print('PASS')
        """, devices=4)
