"""Model configurations and weights, made from a configuration file and a seed.

A configuration file (``perfbench/configs/<name>.json``) holds the published
``config.json`` keys as they are run, plus ``arch`` (the module under
``perfbench/arch/`` that reads its sizes, lays out its weights and counts
its work), ``registry`` (the program's registry entry it is built on),
``reference`` (the module under ``perfbench/reference/`` that computes it
plainly) and ``assumed``.

An architecture module gives ``sizes(spec)`` (a dict that carries
``"arch"`` and ``"vocab"``), ``model_config(spec)``, ``layer_leaves(s,
layer)`` and ``top_leaves(s)`` (path -> ``(shape, std)`` or ``(shape, std,
dtype)``; std None is ones), ``params_from_key(s, key)`` (the program's
layout) and the work counts ``token_flops``, ``prefill_flops``,
``train_flops_per_token`` and ``paged_attention_work``.

Weights are made by this module, never by the program: every leaf is a
seeded normal draw keyed by the leaf's path and, for a layer's leaf, by the
layer index. So the plain reference can make one layer's weights alone and
get exactly the values the program was given, and the program gets them in
one jitted call, on the device, in the type they are served in.
"""
from __future__ import annotations

import json
import os
import zlib

import jax
import jax.numpy as jnp

from perfbench import bench

HERE = os.path.dirname(os.path.abspath(__file__))


def load_spec(name: str) -> dict:
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def arch(spec_or_sizes: dict):
    """The architecture module that a configuration file or its sizes name."""
    name = spec_or_sizes.get("arch")
    if not name:
        raise bench.BenchError("the configuration names no 'arch'")
    return bench.load_module("arch", name)


def seed_key(seed: int):
    """A key from any whole number, also one past 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _leaf(key, path, shape, std, dtype, layer=None):
    if std is None:                      # norm scales are f32 ones
        return jnp.ones(shape, jnp.float32)
    k = jax.random.fold_in(key, zlib.crc32(path.encode()))
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)


def draw(leaves: dict, key, dtype, layer=None):
    """Each leaf of ``leaves`` drawn from ``key`` (and ``layer``, which may
    be traced), flat by path, in ``dtype`` unless the leaf names its own."""
    return {p: _leaf(key, p, leaf[0], leaf[1],
                     leaf[2] if len(leaf) > 2 else dtype, layer)
            for p, leaf in leaves.items()}


def layer_weights(s: dict, key, layer, dtype=None):
    """Layer ``layer``'s leaves, flat by path, in ``dtype`` (default the
    served type)."""
    return draw(arch(s).layer_leaves(s, layer), key, dtype or s["dtype"],
                layer)


def top_weights(s: dict, key, dtype=None):
    return draw(arch(s).top_leaves(s), key, dtype or s["dtype"])


def _nest(flat: dict) -> dict:
    out = {}
    for path, v in flat.items():
        node = out
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = v
    return out


def program_params(s: dict, seed: int):
    """All weights in the program's layout, made on the device in one
    jitted call."""
    return jax.jit(lambda k: arch(s).params_from_key(s, k))(seed_key(seed))
