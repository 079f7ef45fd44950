"""Quickstart: train a small decoder on synthetic data with the public API.

    PYTHONPATH=src python examples/quickstart.py

Builds a reduced yi-9b-family config, trains 100 steps of minibatch SGD with
Adam (survey Algorithm 2 + Table 3), prints the loss curve, saves and
restores a checkpoint, and greedily decodes a few tokens.
"""
import jax
import jax.numpy as jnp

from repro.configs.base import get_config, reduced
from repro.core import parallelism as par
from repro.data.pipeline import SyntheticLM, shard_batch
from repro.launch.mesh import make_mesh
from repro.models import transformer as T
from repro.optim import make_optimizer
from repro.serving import serve
from repro.train import checkpoint as ckpt
from repro.train import trainer


def main():
    cfg = reduced(get_config("yi-9b"))
    print(f"arch={cfg.name} params={cfg.param_count():,}")

    mesh = make_mesh()
    plan = par.make_plan("dp", mesh)
    opt = make_optimizer("adam", lr=3e-3, grad_clip=1.0)
    state = trainer.init_state(cfg, opt, jax.random.PRNGKey(0))
    step = jax.jit(trainer.make_train_step(cfg, opt, plan))

    data = SyntheticLM(cfg.vocab_size, seq_len=64, noise=0.05)
    for i, batch in enumerate(data.batches(batch_size=16, steps=100)):
        state, metrics = step(state, shard_batch(batch, plan))
        if i % 10 == 0:
            print(f"step {i:3d}  loss {float(metrics['loss']):.4f}")

    path = ckpt.save("/tmp/quickstart_ckpt.npz", state, step=100)
    restored, at = ckpt.restore(path, jax.eval_shape(lambda: state))
    print(f"checkpoint roundtrip ok (step {at})")

    prompt = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    out = serve.generate(cfg, restored["params"], prompt, max_new=8,
                         temperature=0.0)
    print("greedy continuation:", out[0].tolist())


if __name__ == "__main__":
    main()
