"""Per-architecture smoke tests (deliverable (f)): REDUCED variant of each
family — one forward/train step on CPU, asserting shapes + no NaNs — plus a
serve step for decode-capable archs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SHAPES, get_config, list_configs, reduced
from repro.models import transformer as T
from repro.optim import make_optimizer
from repro.train import trainer

ARCHS = list_configs()
B, S = 2, 64


def make_batch(cfg, key):
    batch = {}
    if cfg.frontend != "none":
        batch["embeds"] = jax.random.normal(key, (B, S, cfg.d_model))
        if cfg.rope_mode == "mrope":
            batch["positions"] = jnp.broadcast_to(
                jnp.arange(S)[None, None], (3, B, S))
    else:
        batch["tokens"] = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    batch["labels"] = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
class TestArchSmoke:
    def test_reduced_is_actually_reduced(self, arch):
        cfg = reduced(get_config(arch))
        assert cfg.num_layers <= 2
        assert cfg.d_model <= 512
        assert cfg.num_experts <= 4

    def test_forward_shapes_no_nan(self, arch):
        cfg = reduced(get_config(arch))
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        batch = make_batch(cfg, jax.random.PRNGKey(1))
        hidden, aux = T.forward(cfg, params, batch)
        assert hidden.shape == (B, S, cfg.d_model)
        assert bool(jnp.all(jnp.isfinite(hidden.astype(jnp.float32))))
        assert bool(jnp.isfinite(aux))

    def test_train_step_loss_finite(self, arch):
        cfg = reduced(get_config(arch))
        opt = make_optimizer("adam", lr=1e-3)
        state = trainer.init_state(cfg, opt, jax.random.PRNGKey(0))
        batch = make_batch(cfg, jax.random.PRNGKey(1))
        from repro.launch.mesh import make_mesh
        from repro.core import parallelism as par
        plan = par.make_plan("dp", make_mesh())
        step = jax.jit(trainer.make_train_step(cfg, opt, plan))
        new_state, metrics = step(state, batch)
        loss = float(metrics["loss"])
        assert np.isfinite(loss)
        assert 0.0 < loss < 3.0 * np.log(cfg.vocab_size)
        # params actually changed
        before = jax.tree_util.tree_leaves(state["params"])[1]
        after = jax.tree_util.tree_leaves(new_state["params"])[1]
        assert not bool(jnp.all(before == after))

    def test_serve_step(self, arch):
        cfg = reduced(get_config(arch))
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        cache = T.init_decode_state(cfg, B, 32)
        inputs = ({"token": jnp.ones((B,), jnp.int32)}
                  if cfg.frontend == "none"
                  else {"embed": jax.random.normal(jax.random.PRNGKey(2),
                                                   (B, cfg.d_model))})
        lg, cache2 = T.decode_step(cfg, params, cache, inputs, jnp.int32(3))
        assert lg.shape == (B, cfg.vocab_size)
        assert bool(jnp.all(jnp.isfinite(lg.astype(jnp.float32))))
        changed = any(
            not bool(jnp.all(a == b))
            for a, b in zip(jax.tree_util.tree_leaves(cache),
                            jax.tree_util.tree_leaves(cache2)))
        assert changed


class TestFullConfigsConsistent:
    @pytest.mark.parametrize("arch", ARCHS)
    def test_config_metadata(self, arch):
        cfg = get_config(arch)
        assert cfg.source
        n = cfg.param_count()
        # sanity: parameter count within 3x of the name-plate size
        plate = {"gemma3-12b": 12e9, "phi4-mini-3.8b": 3.8e9, "qwen2-vl-2b": 2e9,
                 "mixtral-8x7b": 47e9, "stablelm-3b": 3e9, "rwkv6-7b": 7e9,
                 "yi-9b": 9e9, "qwen3-moe-30b-a3b": 30e9, "zamba2-2.7b": 2.7e9,
                 "musicgen-medium": 1.5e9}[arch]
        assert plate / 3 < n < plate * 3, f"{arch}: {n:.2e} vs {plate:.2e}"

    def test_long_context_applicability(self):
        from repro.launch.specs import shape_applicable
        runs = {a for a in ARCHS
                if shape_applicable(get_config(a), SHAPES["long_500k"])[0]}
        assert runs == {"gemma3-12b", "mixtral-8x7b", "rwkv6-7b", "zamba2-2.7b"}
