"""Named scopes in the compiled programs: decode, packed prefill and the
train step name their parts (``attn``, ``kv_write``, ``mlp``, ``head``,
``optimizer``) in every instruction's ``op_name``, which is how a device
trace's ops are attributed to layers. Scopes are metadata only: the same
programs lowered with ``jax.named_scope`` made a no-op compile to as many
instructions and as many bytes."""
import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ModelConfig
from repro.core import parallelism as par
from repro.launch.mesh import make_mesh
from repro.models import transformer as T
from repro.optim import make_optimizer
from repro.serving.engine import EngineConfig
from repro.serving.engine.engine import _build_step_fns
from repro.train import trainer

SCOPE_PART = re.compile(r"^(?:[\w.\-]*\()*(attn|kv_write|mlp|head|optimizer)"
                        r"\)*$")
INSTRUCTION = re.compile(r"^\s*(ROOT )?%\S+ = ", re.M)


@pytest.fixture(scope="module")
def cfg():
    return ModelConfig(name="scope-t", family="dense", num_layers=2,
                       d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
                       d_ff=128, vocab_size=50, loss_chunk=16, attn_chunk=16,
                       remat=True, dtype="float32")


@pytest.fixture(scope="module")
def params(cfg):
    return T.init_params(cfg, jax.random.PRNGKey(0))


def scopes(hlo_text):
    """Model scopes named among the components of the op_names."""
    found = set()
    for name in re.findall(r'op_name="([^"]+)"', hlo_text):
        for part in name.split("/"):
            m = SCOPE_PART.match(part)
            if m:
                found.add(m.group(1))
    return found


def engine_programs(cfg, params):
    """Compiled decode and packed prefill (2 segments) as the engine jits
    them, built afresh so that each call traces again."""
    e = EngineConfig(block_size=4, num_blocks=32, max_blocks_per_seq=8,
                     max_slots=4, prefill_chunk=8)
    decode, prefill = _build_step_fns(cfg, e, None)[:2]
    pool = T.init_paged_state(cfg, e.num_blocks, e.block_size,
                              max_slots=e.max_slots)
    tables = jnp.zeros((e.max_slots, e.max_blocks_per_seq), jnp.int32)
    slot_i = jnp.zeros((e.max_slots,), jnp.int32)
    seg = jnp.zeros((2,), jnp.int32)
    return {
        "decode": decode.lower(params, pool, slot_i, tables, slot_i,
                               jnp.ones((e.max_slots,), bool)).compile(),
        "prefill": prefill.lower(params, pool, jnp.zeros((2, 8), jnp.int32),
                                 tables, seg, seg, seg).compile(),
    }


def train_program(cfg, params):
    opt = make_optimizer("adam", lr=1e-3, grad_clip=1.0)
    plan = par.make_plan("dp", make_mesh((1, 1), ("data", "model")))
    step = jax.jit(trainer.make_train_step(cfg, opt, plan))
    state = {"params": params, "opt": opt.init(params)}
    batch = {k: jnp.zeros((2, 32), jnp.int32) for k in ("tokens", "labels")}
    return step.lower(state, batch).compile()


def test_programs_name_their_parts(cfg, params):
    progs = engine_programs(cfg, params)
    for name in ("decode", "prefill"):
        assert scopes(progs[name].as_text()) >= {
            "attn", "kv_write", "mlp", "head"}, name
    assert scopes(train_program(cfg, params).as_text()) >= {
        "attn", "mlp", "head", "optimizer"}


def _shape(compiled):
    m = compiled.memory_analysis()
    return (len(INSTRUCTION.findall(compiled.as_text())),
            m.argument_size_in_bytes, m.output_size_in_bytes,
            m.temp_size_in_bytes, m.alias_size_in_bytes)


def test_scopes_are_metadata_only(cfg, params, monkeypatch):
    scoped = dict(engine_programs(cfg, params), train=train_program(
        cfg, params))
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = dict(engine_programs(cfg, params), train=train_program(
        cfg, params))
    assert not scopes(plain["decode"].as_text())
    for name in scoped:
        assert _shape(scoped[name]) == _shape(plain[name]), name
