"""Compile a cell's device programs for a described TPU v5e, with no chip.

  JAX_PLATFORMS=cpu PYTHONPATH=src python perfbench/rehearse.py phi4mini.chat

Serving cells: the engine's decode step and each packed-prefill bucket at
the cell's pool and slot counts. Training cells: the state init and the
train step at the cell's batch. Prints each program's ``memory_analysis``
bytes (arguments, temporaries, outputs not aliased to an argument) against
one chip's 16 GiB. Nothing runs; a pass is not a chip run.
"""
from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

import jax                                             # noqa: E402
import jax.numpy as jnp                                # noqa: E402
from jax.sharding import SingleDeviceSharding          # noqa: E402

from perfbench import bench, model                     # noqa: E402

GiB = 2 ** 30


def _report(name, compiled):
    m = compiled.memory_analysis()
    held = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)
    print(json.dumps({"program": name,
                      "argument_bytes": m.argument_size_in_bytes,
                      "temp_bytes": m.temp_size_in_bytes,
                      "output_bytes": m.output_size_in_bytes,
                      "alias_bytes": m.alias_size_in_bytes,
                      "held_GiB": round(held / GiB, 3),
                      "tpu_custom_call": "tpu_custom_call" in
                      compiled.as_text()}), flush=True)


def main(argv=None):
    name = (argv or sys.argv[1:])[0]
    from jax.experimental import topologies
    from repro.kernels import platform
    platform.on_tpu = lambda: True           # the paths a TPU would take
    jax.config.update("jax_enable_compilation_cache", False)
    chip = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0]
    dev = SingleDeviceSharding(chip)
    cell = bench.load_cell(name)
    spec, traffic = cell["spec"], cell["traffic"]
    arch = model.arch(spec)
    s, cfg = arch.sizes(spec), arch.model_config(spec)
    place = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=dev), tree)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=dev)
    params = place(jax.eval_shape(lambda: model.program_params(s, 0)))
    if traffic["driver"] == "train_steps":
        from perfbench.drivers import train_steps
        init, step, state_abs, batch_abs = train_steps.build(cfg, s, traffic,
                                                             [chip])
        key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=dev)
        _report("init", init.lower(key).compile())
        _report("train_step", step.lower(place(state_abs),
                                         place(batch_abs)).compile())
        return
    from repro.models import transformer as T
    from repro.serving.engine.engine import EngineConfig, _build_step_fns
    from repro.serving.engine.scheduler import (chunk_buckets_for,
                                                segment_buckets_for)
    e = EngineConfig(**traffic["engine"])
    decode, prefill = _build_step_fns(cfg, e, None)[:2]
    pool = place(jax.eval_shape(lambda: T.init_paged_state(
        cfg, e.num_blocks, e.block_size, max_slots=e.max_slots)))
    B, P = e.max_slots, e.max_blocks_per_seq
    _report("decode", decode.lower(
        params, pool, i32(B), i32(B, P), i32(B),
        jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=dev)).compile())
    for c in chunk_buckets_for(e.prefill_chunk, e.prefill_buckets):
        for g in segment_buckets_for(e.prefills_per_step, e.packed_prefill):
            _report(f"prefill_c{c}_g{g}", prefill.lower(
                params, pool, i32(g, c), i32(B, P), i32(g), i32(g),
                i32(g)).compile())


if __name__ == "__main__":
    main()
