"""Expert-parallel MoE fast path: equivalence vs the auto-sharded reference
on a 4-device mesh (subprocess)."""
import pytest

from conftest import run_multidev


@pytest.mark.slow
class TestExpertParallel:
    def test_ep_matches_reference(self):
        run_multidev("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.configs.base import ModelConfig
            from repro.launch.mesh import make_mesh
            from repro.core import parallelism as par
            from repro.models import moe as M
            mesh = make_mesh((2, 2), ('data', 'model'))
            plan = par.make_plan('dp_tp', mesh)
            cfg = ModelConfig(name='t', family='moe', d_model=32, num_heads=2,
                              num_kv_heads=2, d_ff=64, vocab_size=17,
                              num_experts=4, experts_per_token=2,
                              capacity_factor=8.0)
            assert M.ep_applicable(cfg, plan)
            p = M.init_moe(jax.random.PRNGKey(0), cfg)
            x = (jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))
                 ).astype(jnp.bfloat16)
            ref = M.moe_apply(p, x, cfg)               # single-logical-device
            out = jax.jit(lambda p_, x_: M.moe_apply_ep(p_, x_, cfg, plan))(p, x)
            np.testing.assert_allclose(np.asarray(out, np.float32),
                                       np.asarray(ref, np.float32),
                                       atol=0.35, rtol=0.15)
            print('PASS')
        """, devices=4)

    def test_ep_inside_train_step(self):
        """EP path engages through the plan context in a jitted train step."""
        run_multidev("""
            import jax, jax.numpy as jnp
            from repro.configs.base import ModelConfig
            from repro.launch.mesh import make_mesh
            from repro.core import parallelism as par
            from repro.optim import make_optimizer
            from repro.train import trainer
            mesh = make_mesh((2, 2), ('data', 'model'))
            plan = par.make_plan('dp_tp', mesh)
            cfg = ModelConfig(name='t', family='moe', num_layers=2, d_model=32,
                              num_heads=2, num_kv_heads=2, head_dim=16,
                              d_ff=64, vocab_size=64, num_experts=4,
                              experts_per_token=2, loss_chunk=16,
                              attn_chunk=16, remat=True)
            opt = make_optimizer('adam', lr=1e-3)
            state = trainer.init_state(cfg, opt, jax.random.PRNGKey(0))
            batch = {'tokens': jnp.ones((4, 32), jnp.int32),
                     'labels': jnp.ones((4, 32), jnp.int32)}
            step = jax.jit(trainer.make_train_step(cfg, opt, plan))
            new_state, m = step(state, batch)
            loss = float(m['loss'])
            assert 0 < loss < 20 and loss == loss, loss
            print('PASS')
        """, devices=4)
