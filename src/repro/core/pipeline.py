"""Layer pipelining (survey §5.3) — GPipe-style microbatch schedule over a
named mesh axis, built from `shard_map` + `lax.ppermute`.

Each of the S stages holds its own contiguous slice of layers; M microbatches
flow through; activations hop stage→stage with ppermute. The bubble fraction
(S−1)/(S−1+M) matches `costmodel.pipeline_bubble_fraction` — the survey's
"latency proportional to the number of processors" disadvantage — and is
validated structurally in tests (number of ppermute rounds = M + S − 1).

This is the composable runner used by examples/pipeline_training.py; the 40
dry-runs use DP+TP plans instead (DESIGN.md §3).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P


def pipeline_forward(stage_fn, params_stacked, x_microbatches, mesh, axis="stage"):
    """Run M microbatches through S pipeline stages.

    stage_fn(stage_params, x) -> x          (one stage's computation)
    params_stacked: pytree with leading dim S (sharded over `axis`)
    x_microbatches: (M, mb, ...) input microbatches (replicated)
    Returns (M, mb, ...) outputs (replicated).

    Schedule: M + S − 1 rounds; in round r, stage s processes microbatch
    r − s (if valid); activations ppermute to s+1 after each round.
    """
    S = mesh.shape[axis]
    M = x_microbatches.shape[0]

    def per_stage(params, xs):
        params = jax.tree.map(lambda p: p[0], params)      # local stage slice
        xs = xs                                            # (M, mb, ...) replicated
        sid = lax.axis_index(axis)
        mb_shape = xs.shape[1:]
        buf = jnp.zeros(mb_shape, xs.dtype)                # activation in flight
        outs = jnp.zeros((M,) + mb_shape, xs.dtype)

        def round_fn(r, carry):
            buf, outs = carry
            # stage 0 injects microbatch r; others use the incoming buffer
            inject = lax.dynamic_index_in_dim(xs, jnp.clip(r, 0, M - 1), 0,
                                              keepdims=False)
            cur = jnp.where(sid == 0, inject, buf)
            mb_id = r - sid                                # which microbatch
            valid = (mb_id >= 0) & (mb_id < M)
            y = stage_fn(params, cur)
            y = jnp.where(valid, y, cur)
            # last stage records finished microbatch
            outs = lax.cond(
                valid & (sid == S - 1),
                lambda o: lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(mb_id, 0, M - 1), 0),
                lambda o: o, outs)
            # hop to next stage
            buf = lax.ppermute(y, axis, [(i, (i + 1) % S) for i in range(S)])
            return buf, outs

        buf, outs = lax.fori_loop(0, M + S - 1, round_fn, (buf, outs))
        # gather outputs from the last stage to everyone
        outs = lax.psum(jnp.where(sid == S - 1, outs, jnp.zeros_like(outs)), axis)
        return outs[None]

    fn = shard_map(
        per_stage, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis), params_stacked), P()),
        out_specs=P(axis), check_vma=False)
    out = fn(params_stacked, x_microbatches)   # (S, M, ...) — identical copies
    return out[0]


def num_pipeline_rounds(stages: int, microbatches: int) -> int:
    return microbatches + stages - 1
