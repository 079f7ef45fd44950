"""Compile the main path for a described TPU v5e, with no chip attached.

The paged-attention kernel in its four modes (full / ring / verify /
ring-verify, bf16 and int8-scale pools) at phi4-mini's widths, the
engine's whole phi4-mini decode step at chip_smoke.py's pool size and at
the offline benchmark cell's, the engine's decode and packed prefill
writing that pool in place (no instruction copies or slices a layer of
it), and chip_smoke.py's four-chip trainer (stablelm-3b at full width, its depth
cut as the smoke cuts it) on a 2x2 mesh, sharded and in paper mode. Mosaic
refuses here what interpret mode accepts (unaligned slices, too much VMEM),
and XLA refuses a program that does not fit the chip. Nothing runs.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler's library. The decode step
picks its attention path from the platform, which is the CPU here, so the
tests steer ``repro.kernels.platform.on_tpu``.
"""
import dataclasses
import functools
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from conftest import REPO
from repro.configs.base import get_config
from repro.kernels import platform

H, HKV, HD, BS, B = 24, 8, 128, 16, 8        # phi4-mini widths, smoke batch
N, P, K, WINDOW = 1024, 40, 4, 512           # pool, table, drafts, ring
HBM = 16 * 2**30                             # one v5e chip
GiB = 2**30
# the phi4mini.offline benchmark cell's engine (perfbench/traffic/offline.json)
OFFLINE = dict(block_size=16, num_blocks=1536, max_slots=32,
               max_blocks_per_seq=128, prefill_chunk=512, prefills_per_step=2)
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* ([\w-]+)\(",
                         re.M)


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def tpu_compile_env(monkeypatch):
    """The kernel path as on a TPU, and no persistent compile cache: an
    entry compiled for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _chip_smoke():
    sys.path.insert(0, REPO)
    import chip_smoke
    return chip_smoke


def _per_device_bytes(compiled):
    """Arguments, temporaries and the outputs that do not reuse an
    argument's buffer: what one device holds while the program runs."""
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("mode", ["full", "ring", "verify", "ring_verify"])
def test_paged_kernel_compiles(one_chip, mode, quant):
    from repro.kernels.paged_attention import (paged_attention,
                                               paged_attention_verify)
    from repro.models.state_providers import ring_pages
    verify = mode.endswith("verify")
    ring = mode.startswith("ring")
    s = lambda shape, dt: _spec(shape, dt, one_chip)
    q = s((B, K, H, HD) if verify else (B, H, HD), jnp.bfloat16)
    pool = s((N, BS, HKV, HD), jnp.int8 if quant else jnp.bfloat16)
    args = [q, pool, pool, s((B, P), jnp.int32), s((B,), jnp.int32)]
    kw = {}
    if ring:
        kw = dict(window=WINDOW,
                  ring_pages=ring_pages(WINDOW, BS, draft=K - 1 if verify
                                        else 0))
    fn = paged_attention_verify if verify else paged_attention

    def call(q, kp, vp, tables, lens, *extra):
        more = dict(kw)
        if ring:
            more["positions"] = lens - 1
        if quant:
            more["k_scale"], more["v_scale"] = extra
        return fn(q, kp, vp, tables, lens, **more)

    if quant:
        args += [s((N, BS, HKV), jnp.float32)] * 2
    text = jax.jit(call).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _engine_config(shapes):
    from repro.serving.engine import EngineConfig
    if shapes == "smoke":
        return _chip_smoke().smoke_engine_config()
    return EngineConfig(**OFFLINE)


@pytest.fixture(scope="module")
def engine_program(one_chip):
    """``get(shapes, name)``: the engine's phi4-mini ``decode`` or packed
    ``prefill`` (2 segments of a full chunk) compiled for one chip, at
    chip_smoke.py's engine shapes ("smoke") or the offline cell's
    ("offline"); each compiled once per module."""
    from repro.models import transformer as T
    from repro.serving.engine.engine import _build_step_fns
    cfg = get_config("phi4-mini-3.8b")
    place = lambda tree: jax.tree.map(
        lambda a: _spec(a.shape, a.dtype, one_chip), tree)
    i32 = lambda *shape: _spec(shape, jnp.int32, one_chip)
    done = {}

    def get(shapes, name):
        if (shapes, name) in done:
            return done[shapes, name]
        e = _engine_config(shapes)
        decode, prefill = _build_step_fns(cfg, e, None)[:2]
        params = place(jax.eval_shape(lambda k: T.init_params(cfg, k),
                                      jax.random.PRNGKey(0)))
        pool = place(jax.eval_shape(lambda: T.init_paged_state(
            cfg, e.num_blocks, e.block_size, max_slots=e.max_slots)))
        B, P = e.max_slots, e.max_blocks_per_seq
        if name == "decode":
            low = decode.lower(params, pool, i32(B), i32(B, P), i32(B),
                               _spec((B,), jnp.bool_, one_chip))
        else:
            g, c = e.prefills_per_step, e.prefill_chunk
            low = prefill.lower(params, pool, i32(g, c), i32(B, P), i32(g),
                                i32(g), i32(g))
        done[shapes, name] = low.compile()
        return done[shapes, name]

    return get


@pytest.mark.parametrize("shapes", ["smoke", "offline"])
def test_phi4_decode_step_fits_one_chip(engine_program, shapes):
    compiled = engine_program(shapes, "decode")
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < HBM, f"{used / 2**30:.2f} GiB does not fit one chip"


def _pool_movers(hlo_text, layer_elems):
    """Copies, slices and slice updates, fused ones included, that move a
    whole layer of a K/V pool leaf or more: a shape ending in (Hkv, hd)
    with at least one layer's elements. Scatters of the new tokens and
    gathers of a table's blocks are not among them."""
    found = []
    for shape, op in INSTRUCTION.findall(hlo_text):
        dims = [int(d) for d in shape.split(",") if d]
        if (op in ("copy", "dynamic-slice", "dynamic-update-slice")
                and dims[-2:] == [HKV, HD]
                and functools.reduce(lambda a, b: a * b, dims) >= layer_elems):
            found.append(f"{op} {dims}")
    return found


@pytest.mark.parametrize("name,temp_gib", [("decode", 0.5), ("prefill", 1.07)])
def test_engine_writes_pool_in_place(engine_program, name, temp_gib):
    """The layer scan carries the pool and writes only the new tokens: no
    instruction copies, slices or rewrites a layer of it, and the
    temporaries hold no second pool (3.00 GiB decode, 3.57 GiB prefill
    when the pool was a scanned input). Offline shapes: 1536 blocks."""
    compiled = engine_program("offline", name)
    layer_elems = OFFLINE["num_blocks"] * OFFLINE["block_size"] * HKV * HD
    assert not _pool_movers(compiled.as_text(), layer_elems)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < temp_gib * GiB, f"{name} temp {temp / GiB:.2f} GiB"


@pytest.mark.parametrize("mode", ["sharded", "paper"])
def test_stablelm_trainer_fits_four_chips(topo, mode):
    """The states and steps launch/train.py builds for chip_smoke.py
    --four-chips: plan dp_tp_zero1 on a (2, 2) ("data", "model") mesh, and
    paper-mode ring-allreduce DP with a whole replica on each of 4 chips.
    Init and step must each fit one chip's HBM."""
    chip_smoke = _chip_smoke()
    from repro.core import parallelism as par
    from repro.launch.mesh import make_mesh
    from repro.optim import make_optimizer
    from repro.train import trainer
    cfg = dataclasses.replace(get_config("stablelm-3b"),
                              num_layers=chip_smoke.TRAIN_LAYERS)
    opt = make_optimizer("sgd", lr=chip_smoke.TRAIN_LR, grad_clip=1.0)
    state_abs = trainer.abstract_state(cfg, opt)
    batch = (chip_smoke.TRAIN_BATCH, chip_smoke.TRAIN_SEQ)
    if mode == "sharded":
        mesh = make_mesh((2, 2), ("data", "model"), devices=topo.devices)
        plan = par.make_plan("dp_tp_zero1", mesh)
        state_sh = trainer.state_shardings(state_abs, plan)
        b_abs = {k: jax.ShapeDtypeStruct(batch, jnp.int32)
                 for k in ("tokens", "labels")}
        step = trainer.jit_train_step(cfg, opt, plan, state_abs, b_abs)
        step_args = (state_abs, b_abs)
    else:
        mesh = make_mesh((4,), ("data",), devices=topo.devices)
        rep = NamedSharding(mesh, PartitionSpec())
        state_sh = jax.tree.map(lambda _: rep, state_abs)
        step = jax.jit(trainer.make_paper_train_step(cfg, opt, mesh,
                                                     algorithm="ring"),
                       donate_argnums=(0,))
        step_args = (
            jax.tree.map(lambda a: _spec(a.shape, a.dtype, rep), state_abs),
            {k: _spec(batch, jnp.int32, NamedSharding(mesh, PartitionSpec("data")))
             for k in ("tokens", "labels")},
            {"_": _spec((1,), jnp.float32, rep)})
    init = jax.jit(functools.partial(trainer.init_state, cfg, opt),
                   out_shardings=state_sh)
    key = _spec((2,), jnp.uint32, NamedSharding(mesh, PartitionSpec()))
    for name, compiled in (("init", init.lower(key).compile()),
                           ("step", step.lower(*step_args).compile())):
        used = _per_device_bytes(compiled)
        assert used < HBM, (f"{mode} {name}: {used / 2**30:.2f} GiB per "
                            f"device does not fit one chip")
