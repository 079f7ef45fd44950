"""§5.3 pipeline tests: GPipe schedule correctness + bubble model."""
import pytest

from repro.core import costmodel as cm
from repro.core.pipeline import num_pipeline_rounds
from conftest import run_multidev


class TestBubbleModel:
    def test_rounds(self):
        assert num_pipeline_rounds(4, 8) == 11

    def test_bubble_matches_rounds(self):
        """bubble = idle work / total work = (S−1)/(S−1+M)."""
        S, M = 4, 8
        rounds = num_pipeline_rounds(S, M)
        busy = M  # each stage works M of the rounds
        assert cm.pipeline_bubble_fraction(S, M) == pytest.approx(
            (rounds - busy) / rounds)


@pytest.mark.slow
class TestPipelineCorrectness:
    def test_matches_sequential(self):
        run_multidev("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.core.pipeline import pipeline_forward
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((4,), ('stage',))
            key = jax.random.PRNGKey(0)
            W = jax.random.normal(key, (4, 16, 16)) * 0.3
            b = jax.random.normal(jax.random.PRNGKey(1), (4, 16)) * 0.1
            params = {'w': W, 'b': b}
            def stage_fn(p, x):
                return jnp.tanh(x @ p['w'][0] + p['b'][0]) \
                    if p['w'].ndim == 3 else jnp.tanh(x @ p['w'] + p['b'])
            M, mb = 8, 4
            x = jax.random.normal(jax.random.PRNGKey(2), (M, mb, 16))
            out = pipeline_forward(stage_fn, params, x, mesh)
            ref = x
            for s in range(4):
                ref = jnp.tanh(ref @ W[s] + b[s])
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=2e-4, atol=2e-5)
            print('PASS')
        """, devices=4)
