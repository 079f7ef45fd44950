"""Loop-aware HLO analyzer tests: known FLOPs, trip counts, collectives."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hlo_analysis as ha
from conftest import run_multidev


def compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


class TestFlopCounting:
    def test_plain_matmul(self):
        a = jnp.zeros((128, 256), jnp.float32)
        b = jnp.zeros((256, 64), jnp.float32)
        txt = compiled_text(lambda x, y: x @ y, a, b)
        res = ha.analyze_hlo_text(txt)
        assert res["flops"] == pytest.approx(2 * 128 * 256 * 64, rel=0.01)

    def test_scan_multiplies_by_trip_count(self):
        """The whole reason this module exists: XLA's cost_analysis counts a
        while body once; ours multiplies by the trip count."""
        a = jnp.zeros((64, 64), jnp.float32)

        def loop(x):
            def body(c, _):
                return c @ a, None
            out, _ = jax.lax.scan(body, x, None, length=10)
            return out

        txt = compiled_text(loop, a)
        res = ha.analyze_hlo_text(txt)
        one = 2 * 64 ** 3
        assert res["flops"] == pytest.approx(10 * one, rel=0.05)

    def test_nested_scan(self):
        a = jnp.zeros((32, 32), jnp.float32)

        def inner(x):
            def body(c, _):
                return c @ a, None
            return jax.lax.scan(body, x, None, length=4)[0]

        def outer(x):
            def body(c, _):
                return inner(c), None
            return jax.lax.scan(body, x, None, length=3)[0]

        txt = compiled_text(outer, a)
        res = ha.analyze_hlo_text(txt)
        assert res["flops"] == pytest.approx(12 * 2 * 32 ** 3, rel=0.05)

    def test_matches_xla_when_no_loops(self):
        a = jnp.zeros((128, 128), jnp.float32)
        low = jax.jit(lambda x: (x @ x) @ x).lower(a)
        comp = low.compile()
        ours = ha.analyze_hlo_text(comp.as_text())["flops"]
        xla = ha.cost_analysis_dict(comp).get("flops", 0)
        assert ours == pytest.approx(xla, rel=0.05)


class TestEndToEndFlops:
    def test_model_grad_step_close_to_6nd(self):
        """Integration: analyzer FLOPs ≈ 6·N·D for a tiny decoder grad."""
        from repro.configs.base import ModelConfig
        from repro.models import transformer as T
        cfg = ModelConfig(name="t", family="dense", num_layers=4, d_model=128,
                          num_heads=4, num_kv_heads=4, head_dim=32, d_ff=256,
                          vocab_size=512, loss_chunk=64, attn_chunk=64,
                          remat=False)
        params = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
        batch = {"tokens": jax.ShapeDtypeStruct((4, 128), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((4, 128), jnp.int32)}
        txt = jax.jit(jax.grad(lambda p, b: T.loss_fn(cfg, p, b))) \
            .lower(params, batch).compile().as_text()
        res = ha.analyze_hlo_text(txt)
        model = 6 * cfg.param_count() * 4 * 128
        assert 0.5 * model < res["flops"] < 2.5 * model


@pytest.mark.slow
class TestCollectiveBytes:
    def test_psum_bytes(self):
        run_multidev("""
            import jax, jax.numpy as jnp
            from jax.sharding import PartitionSpec as P
            from jax import shard_map
            from repro.launch.mesh import make_mesh
            from repro.core import hlo_analysis as ha
            mesh = make_mesh((8,), ('x',))
            x = jnp.zeros((8, 1024), jnp.float32)
            f = shard_map(lambda v: jax.lax.psum(v, 'x'), mesh=mesh,
                          in_specs=P('x'), out_specs=P(), check_vma=False)
            txt = jax.jit(f).lower(x).compile().as_text()
            res = ha.analyze_hlo_text(txt)
            total = res['total_collective_bytes']
            # one all-reduce of (1,1024) f32 per device = 4096 bytes result
            assert 4000 <= total <= 16384, total
            print('PASS')
        """)
