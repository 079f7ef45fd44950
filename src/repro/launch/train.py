"""Training launcher — the end-to-end driver (deliverable (b)).

  PYTHONPATH=src python -m repro.launch.train --arch yi-9b --preset reduced \
      --steps 50 --batch 8 --seq 128 --plan dp --optimizer adam --lr 3e-4

On a CPU machine use --preset reduced; on a TPU host drop --preset to train
the full config. Devices form a ("data", "model") mesh of shape
(devices // --model-axis, --model-axis); the plan decides what shards over
each axis. The state is built sharded from the start (one jitted init with
the plan's output shardings), never whole on one device, and the step is
jitted with the same shardings. Supports checkpoint save/restore and the
paper-mode explicit-collective runtime (--paper-mode --algorithm ring
--compress topk), which keeps a whole replica on every device of a 1-D
("data",) mesh.
"""
from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


def train(cfg, optimizer, *, plan="dp", model_axis=1, steps=100, batch=8,
          seq=128, seed=0, paper_mode=False, algorithm="ring",
          compress="none", resume=None, checkpoint=None, data=None,
          log_every=10, log=print):
    """Run one step per batch of ``data`` (default: ``steps`` batches of
    SyntheticLM, each ``batch`` x ``seq``); returns (state, per-step
    losses). With ``checkpoint`` the final state is saved there under its
    global step, the resumed-from step plus the steps taken."""
    from repro.core import parallelism as par
    from repro.core.compression import make_compressor
    from repro.data.pipeline import SyntheticLM, shard_batch
    from repro.launch.mesh import make_mesh
    from repro.train import checkpoint as ckpt
    from repro.train import trainer

    n_dev = len(jax.devices())
    state_abs = trainer.abstract_state(cfg, optimizer)
    if paper_mode:
        # paper mode keeps a whole replica on every device of a data mesh
        mesh = make_mesh((n_dev,), ("data",))
        plan_obj = par.make_plan("dp", mesh)
        rep = NamedSharding(mesh, P())
        state_sh = jax.tree.map(lambda _: rep, state_abs)
    else:
        if n_dev % model_axis:
            raise ValueError(f"model axis {model_axis} does not divide "
                             f"{n_dev} devices")
        mesh = make_mesh((n_dev // model_axis, model_axis), ("data", "model"))
        plan_obj = par.make_plan(plan, mesh)
        state_sh = trainer.state_shardings(state_abs, plan_obj)
    # made inside one jit whose outputs carry the shardings: no device ever
    # holds more than its shard, not even during init
    state = jax.jit(functools.partial(trainer.init_state, cfg, optimizer),
                    out_shardings=state_sh)(jax.random.PRNGKey(seed))

    start_step = 0
    if resume:
        state, start_step = ckpt.restore(resume, state, state_sh)
        log(f"resumed from {resume} at step {start_step}")

    if paper_mode:
        compressor = None if compress == "none" else make_compressor(compress)
        jitted = jax.jit(trainer.make_paper_train_step(
            cfg, optimizer, mesh, algorithm=algorithm,
            compression=compressor), donate_argnums=(0,))
        residual = (trainer.zero_residual(state["params"]) if compressor
                    else {"_": jnp.zeros((1,), jnp.float32)})

        def run_step(state, b):
            nonlocal residual
            state, metrics, residual = jitted(state, b, residual)
            return state, metrics
    else:
        b_abs = {k: jax.ShapeDtypeStruct((batch, seq), jnp.int32)
                 for k in ("tokens", "labels")}
        run_step = trainer.jit_train_step(cfg, optimizer, plan_obj,
                                          state_abs, b_abs)

    if data is None:
        data = SyntheticLM(cfg.vocab_size, seq, seed=seed).batches(batch,
                                                                   steps)
    losses = []
    t0 = time.time()
    for i, b in enumerate(data):
        state, metrics = run_step(state, shard_batch(b, plan_obj))
        losses.append(metrics["loss"])
        if (i + 1) % log_every == 0 or i == 0:
            log(f"step {start_step+i+1}: loss={float(metrics['loss']):.4f} "
                f"({(time.time()-t0)/(i+1):.2f}s/step)")
    if checkpoint:
        ckpt.save(checkpoint, state, start_step + len(losses))
        log(f"saved {checkpoint} at step {start_step + len(losses)}")
    return state, [float(x) for x in losses]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--preset", default="full", choices=("full", "reduced"))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--plan", default="dp")
    ap.add_argument("--model-axis", type=int, default=1,
                    help="size of the mesh's 'model' axis")
    ap.add_argument("--optimizer", default="adam")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--grad-clip", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--resume", default=None)
    ap.add_argument("--devices", type=int, default=0,
                    help="run on N virtual CPU devices")
    ap.add_argument("--paper-mode", action="store_true",
                    help="explicit shard_map DP with chosen collective")
    ap.add_argument("--algorithm", default="ring")
    ap.add_argument("--compress", default="none")
    args = ap.parse_args(argv)

    if args.devices:
        from repro.launch.mesh import use_cpu_devices
        use_cpu_devices(args.devices)

    from repro.configs.base import get_config, reduced
    from repro.launch.compile_cache import enable_compile_cache
    from repro.optim import make_optimizer

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.preset == "reduced":
        cfg = reduced(cfg)
    optimizer = make_optimizer(args.optimizer, lr=args.lr,
                               grad_clip=args.grad_clip)
    _, losses = train(
        cfg, optimizer, plan=args.plan, model_axis=args.model_axis,
        steps=args.steps, batch=args.batch, seq=args.seq, seed=args.seed,
        paper_mode=args.paper_mode, algorithm=args.algorithm,
        compress=args.compress, resume=args.resume, checkpoint=args.checkpoint,
        log_every=args.log_every, log=lambda s: print(s, flush=True))
    return losses


if __name__ == "__main__":
    main()
