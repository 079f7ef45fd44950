"""Training step builders.

Two runtimes (DESIGN.md §3):

* **pjit mode** — `make_train_step`: XLA-partitioned via the ShardingPlan's
  in/out shardings; collectives are implicit. Used by the launcher and all
  dry-runs.
* **paper mode** — `make_paper_train_step`: data-parallel `shard_map` where
  the gradient allreduce is *explicit* — our own ring/tree/butterfly/
  Rabenseifner schedule (survey §2.5) with optional gradient compression +
  error feedback (survey §6.3). This is the survey's distributed-SGD
  pipeline made concrete.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import parallelism as par
from repro.models import transformer as T


# ------------------------------------------------------------------ state
def init_state(cfg, optimizer, key):
    params = T.init_params(cfg, key)
    return {"params": params, "opt": optimizer.init(params)}


def abstract_state(cfg, optimizer):
    return jax.eval_shape(lambda k: init_state(cfg, optimizer, k),
                          jax.random.PRNGKey(0))


def state_shardings(state, plan):
    """NamedShardings for a TrainState pytree (params + optimizer)."""
    params = state["params"]
    p_specs = plan.param_specs(params)
    o_specs = plan.opt_specs(params)
    params_treedef = jax.tree_util.tree_structure(params)

    def opt_entry(v):
        if jax.tree_util.tree_structure(v) == params_treedef:
            return o_specs
        return jax.tree.map(lambda _: P(), v)

    opt = state["opt"]
    opt_specs = {k: opt_entry(v) for k, v in opt.items()}
    specs = {"params": p_specs, "opt": opt_specs}
    return jax.tree.map(lambda s: NamedSharding(plan.mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


# -------------------------------------------------------------- pjit mode
def make_train_step(cfg, optimizer, plan, *, donate=True, accum_steps=1):
    """train_step(state, batch). With accum_steps > 1 the global batch is
    split into microbatches scanned sequentially with f32 gradient
    accumulation — activation live-range shrinks ~accum_steps× at the cost
    of accum_steps× more (smaller) collectives (§Perf: the lever that fits
    gemma3-12b train_4k into v5e HBM)."""

    def grads_of(params, batch):
        return jax.value_and_grad(
            lambda p: T.loss_fn(cfg, p, batch))(params)

    def train_step(state, batch):
        with par.plan_context(plan):
            if accum_steps == 1:
                loss, grads = grads_of(state["params"], batch)
            else:
                def split(a):
                    return a.reshape((accum_steps, a.shape[0] // accum_steps)
                                     + a.shape[1:])

                micro = {k: split(v) for k, v in batch.items()
                         if k != "positions"}
                if "positions" in batch:   # mrope (3, B, S): split on axis 1
                    p = batch["positions"]
                    micro["positions"] = p.reshape(
                        (3, accum_steps, p.shape[1] // accum_steps) + p.shape[2:]
                    ).swapaxes(0, 1)

                def micro_step(acc, mb):
                    loss_i, g_i = grads_of(state["params"], mb)
                    acc_loss, acc_g = acc
                    acc_g = jax.tree.map(
                        lambda a, g: a + g.astype(jnp.float32), acc_g, g_i)
                    return (acc_loss + loss_i, acc_g), None

                zero = (jnp.float32(0.0),
                        jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                     state["params"]))
                (loss, grads), _ = jax.lax.scan(micro_step, zero, micro)
                loss = loss / accum_steps
                grads = jax.tree.map(lambda g: g / accum_steps, grads)
        with jax.named_scope("optimizer"):
            new_params, new_opt = optimizer.update(grads, state["opt"],
                                                   state["params"])
        metrics = {"loss": loss}
        return {"params": new_params, "opt": new_opt}, metrics

    return train_step


def jit_train_step(cfg, optimizer, plan, state_abs, batch_abs):
    """jit with explicit in/out shardings (the production entry point)."""
    step = make_train_step(cfg, optimizer, plan)
    st_sh = state_shardings(state_abs, plan)
    b_sh = plan.batch_shardings(batch_abs)
    rep = NamedSharding(plan.mesh, P())
    return jax.jit(
        step,
        in_shardings=(st_sh, b_sh),
        out_shardings=(st_sh, {"loss": rep}),
        donate_argnums=(0,),
    )


# ------------------------------------------------------------- paper mode
def make_paper_train_step(cfg, optimizer, mesh, *, axis="data",
                          algorithm="ring", compression=None):
    """Explicit data-parallel SGD over `axis` via shard_map (survey §5.1+§6.3).

    Per-shard gradients are reduced with `core.collectives` (algorithm =
    ring|tree|butterfly|rabenseifner|psum), optionally compressed with error
    feedback (`compression` = a core.compression.Compressor). The error-
    feedback residual is carried in the state (survey: "local gradient
    accumulation", Seide et al. / Lin et al.).
    """
    from jax import shard_map
    from repro.core import collectives as coll

    def local_grads(params, batch):
        return jax.value_and_grad(lambda p: T.loss_fn(cfg, p, batch))(params)

    def step(state, batch, residual):
        loss, grads = local_grads(state["params"], batch)

        if compression is not None:
            grads, residual = compression.compress_with_feedback(grads, residual)

        grads = jax.tree.map(
            lambda g: coll.allreduce_mean(g, axis, algorithm=algorithm), grads)
        loss = coll.allreduce_mean(loss, axis, algorithm="psum")

        with jax.named_scope("optimizer"):
            new_params, new_opt = optimizer.update(grads, state["opt"],
                                                   state["params"])
        return {"params": new_params, "opt": new_opt}, {"loss": loss}, residual

    pspec_state = jax.tree.map(lambda _: P(), {"dummy": 0})  # built below

    def wrapped(state, batch, residual):
        in_specs = (
            jax.tree.map(lambda _: P(), state),
            jax.tree.map(lambda _: P(axis), batch),
            jax.tree.map(lambda _: P(), residual),
        )
        out_specs = (
            jax.tree.map(lambda _: P(), state),
            {"loss": P()},
            jax.tree.map(lambda _: P(), residual),
        )
        f = shard_map(step, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                      check_vma=False)
        return f(state, batch, residual)

    return wrapped


def zero_residual(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
