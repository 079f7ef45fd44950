"""Paper Fig 7: minibatch-size effect — fixed token budget, varying B.
Small B → poor hardware efficiency (us/token high); very large B (few
updates) → worse final loss. derived = final loss + us/token."""
import time

import jax

from benchmarks.common import emit
from repro.configs.base import ModelConfig
from repro.core import parallelism as par
from repro.data.pipeline import SyntheticLM
from repro.launch.mesh import make_mesh
from repro.optim import make_optimizer
from repro.train import trainer


def main():
    cfg = ModelConfig(name="bench", family="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=4, head_dim=16, d_ff=128,
                      vocab_size=64, loss_chunk=32, attn_chunk=32, remat=False)
    token_budget = 64 * 64 * 16          # fixed across batch sizes
    seq = 64
    plan = par.make_plan("dp", make_mesh())
    for B in (4, 16, 64):
        steps = token_budget // (B * seq)
        opt = make_optimizer("adam", lr=3e-3)
        state = trainer.init_state(cfg, opt, jax.random.PRNGKey(0))
        step = jax.jit(trainer.make_train_step(cfg, opt, plan))
        data = SyntheticLM(cfg.vocab_size, seq, noise=0.05)
        t0 = time.perf_counter()
        loss = None
        for batch in data.batches(B, steps):
            state, m = step(state, batch)
            loss = float(m["loss"])
        dt = time.perf_counter() - t0
        emit(f"fig7/B={B}", dt / token_budget * 1e6,
             f"steps={steps} final_loss={loss:.3f}")


if __name__ == "__main__":
    main()
