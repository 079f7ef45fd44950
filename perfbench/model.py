"""Model configurations and weights, made from a configuration file and a seed.

A configuration file (``perfbench/configs/<name>.json``) holds the published
``config.json`` keys as they are run, plus ``registry`` (the program's
registry entry it is built on), ``reference`` (the module under
``perfbench/reference/`` that computes it plainly) and ``assumed``.

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

HERE = os.path.dirname(os.path.abspath(__file__))


def load_spec(name: str) -> dict:
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def sizes(spec: dict) -> dict:
    """The sizes a decoder needs, read from the published keys."""
    d, h = spec["hidden_size"], spec["num_attention_heads"]
    return {
        "layers": spec["num_hidden_layers"], "d": d, "heads": h,
        "kv_heads": spec["num_key_value_heads"],
        "head_dim": spec.get("head_dim", d // h),
        "ff": spec["intermediate_size"], "vocab": spec["vocab_size"],
        "eps": spec.get("rms_norm_eps", spec.get("layer_norm_eps")),
        "theta": float(spec["rope_theta"]),
        "tied": bool(spec["tie_word_embeddings"]),
        "dtype": spec["torch_dtype"],
    }


def model_config(spec: dict):
    """The program's ModelConfig for this file: its registry entry with
    every size replaced by the file's."""
    import dataclasses
    from repro.configs.base import get_config
    s = sizes(spec)
    return dataclasses.replace(
        get_config(spec["registry"]), num_layers=s["layers"], d_model=s["d"],
        num_heads=s["heads"], num_kv_heads=s["kv_heads"],
        head_dim=s["head_dim"], d_ff=s["ff"], vocab_size=s["vocab"],
        norm_eps=s["eps"], rope_theta=s["theta"], tie_embeddings=s["tied"],
        dtype=s["dtype"])


# ------------------------------------------------------------------ weights
def layer_leaves(s: dict) -> dict:
    """One decoder layer's leaves: path -> (shape, std); std None = ones."""
    d, hd, ff = s["d"], s["head_dim"], s["ff"]
    q, kv = s["heads"] * hd, s["kv_heads"] * hd
    return {
        "ln1/scale": ((d,), None),
        "attn/wq": ((d, q), d ** -0.5), "attn/wk": ((d, kv), d ** -0.5),
        "attn/wv": ((d, kv), d ** -0.5), "attn/wo": ((q, d), q ** -0.5),
        "ln2/scale": ((d,), None),
        "mlp/w_gate": ((d, ff), d ** -0.5), "mlp/w_in": ((d, ff), d ** -0.5),
        "mlp/w_out": ((ff, d), ff ** -0.5),
    }


def top_leaves(s: dict) -> dict:
    """The program multiplies token embeddings by sqrt(d) before the first
    layer; the table is drawn at 0.02 / sqrt(d) so that the stream the
    first layer sees has the published initializer's 0.02. (Drawn at 0.02,
    the scaled embedding outweighs all 32 layers' outputs, and a tied head
    then echoes the input token whatever the layers compute.)"""
    out = {"embed/table": ((s["vocab"], s["d"]), 0.02 / s["d"] ** 0.5),
           "final_norm/scale": ((s["d"],), None)}
    if not s["tied"]:
        out["lm_head/w"] = ((s["d"], s["vocab"]), s["d"] ** -0.5)
    return out


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


def layer_weights(s: dict, key, layer, dtype=None):
    """Layer ``layer``'s leaves, flat by path, in ``dtype`` (default the
    served type)."""
    dtype = dtype or s["dtype"]
    return {p: _leaf(key, p, shape, std, dtype, layer)
            for p, (shape, std) in layer_leaves(s).items()}


def top_weights(s: dict, key, dtype=None):
    dtype = dtype or s["dtype"]
    return {p: _leaf(key, p, shape, std, dtype)
            for p, (shape, std) in top_leaves(s).items()}


def _nest(flat: dict) -> dict:
    out = {}
    for path, v in flat.items():
        node = out
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = v
    return out


def params_from_key(s: dict, key):
    """All weights in the program's layout (``blocks/l0/...`` stacked over
    layers); traceable, so a caller can make them inside its own jit."""
    blocks = jax.vmap(lambda i: layer_weights(s, key, i))(
        jnp.arange(s["layers"]))
    return {**_nest(top_weights(s, key)), "blocks": {"l0": _nest(blocks)}}


def program_params(s: dict, seed: int):
    """All weights in the program's layout, made on the device in one
    jitted call."""
    return jax.jit(lambda k: params_from_key(s, k))(seed_key(seed))

