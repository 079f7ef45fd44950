"""The dense decoder: every layer attention over the whole context and a
SwiGLU MLP of ``intermediate_size``, as the program's ``dense`` family runs
it (``models/transformer.py``, one layer a superblock, stacked under
``blocks/l0``).

Its work counts follow ``perfbench/flops.py``: the model's arithmetic,
never what an implementation happens to do.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from perfbench import flops, model


def sizes(spec: dict) -> dict:
    """The sizes a decoder needs, read from the published keys."""
    d, h = spec["hidden_size"], spec["num_attention_heads"]
    return {
        "arch": "decoder",
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
    from repro.configs.base import get_config
    s = sizes(spec)
    return dataclasses.replace(
        get_config(spec["registry"]), num_layers=s["layers"], d_model=s["d"],
        num_heads=s["heads"], num_kv_heads=s["kv_heads"],
        head_dim=s["head_dim"], d_ff=s["ff"], vocab_size=s["vocab"],
        norm_eps=s["eps"], rope_theta=s["theta"], tie_embeddings=s["tied"],
        dtype=s["dtype"])


# ------------------------------------------------------------------ weights
def attn_leaves(s: dict) -> dict:
    """The attention half of a layer: its norm and q, k, v, o."""
    d, hd = s["d"], s["head_dim"]
    q, kv = s["heads"] * hd, s["kv_heads"] * hd
    return {
        "ln1/scale": ((d,), None),
        "attn/wq": ((d, q), d ** -0.5), "attn/wk": ((d, kv), d ** -0.5),
        "attn/wv": ((d, kv), d ** -0.5), "attn/wo": ((q, d), q ** -0.5),
        "ln2/scale": ((d,), None),
    }


def layer_leaves(s: dict, layer) -> dict:
    """One decoder layer's leaves: path -> (shape, std); std None = ones.
    Every layer is alike."""
    d, ff = s["d"], s["ff"]
    return {
        **attn_leaves(s),
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


def params_from_key(s: dict, key):
    """All weights in the program's layout (``blocks/l0/...`` stacked over
    layers); traceable, so a caller can make them inside its own jit."""
    blocks = jax.vmap(lambda i: model.layer_weights(s, key, i))(
        jnp.arange(s["layers"]))
    return {**model._nest(model.top_weights(s, key)),
            "blocks": {"l0": model._nest(blocks)}}


# --------------------------------------------------------------- work counts
def attn_matmul(s) -> int:
    """Weights one token multiplies in one layer's q, k, v and o."""
    hd = s["head_dim"]
    return s["d"] * (s["heads"] + 2 * s["kv_heads"]) * hd \
        + s["heads"] * hd * s["d"]


def _layer_matmul(s) -> int:
    """Weights one token multiplies in one layer."""
    return attn_matmul(s) + 3 * s["d"] * s["ff"]


def _attention(s, keys):
    """Every layer's attention over ``keys`` keys in all."""
    return s["layers"] * flops.attention_flops(s["heads"], s["head_dim"],
                                               keys)


def token_flops(s, context: int, logits: bool = True) -> int:
    """Forward operations of one token that attends ``context`` keys; with
    ``logits`` it also goes through the output head."""
    f = 2 * s["layers"] * _layer_matmul(s) + _attention(s, context)
    if logits:
        f += 2 * s["d"] * s["vocab"]
    return f


def prefill_flops(s, start: int, valid: int) -> int:
    """A chunk of ``valid`` prompt tokens from position ``start``: every
    token through the layers, causally, and the last through the head."""
    return (valid * 2 * s["layers"] * _layer_matmul(s)
            + _attention(s, flops.causal_keys(start, valid))
            + 2 * s["d"] * s["vocab"])


def train_flops_per_token(s, seq: int) -> float:
    """Forward and backward (3x forward) of one token of a causal sequence
    of ``seq``: the matrices, the head, and attention over the
    ``(seq + 1) / 2`` keys a token sees on average."""
    return 3 * (2 * s["layers"] * _layer_matmul(s) + 2 * s["d"] * s["vocab"]
                + _attention(s, flops.causal_keys(0, seq) / seq))


def paged_attention_work(s, lens, kv_bytes: int = 2):
    """(operations, bytes) of one decode step of the paged kernel over all
    layers, for live sequences of ``lens`` tokens."""
    ops, bytes_ = flops.decode_attention_work(
        s["heads"], s["kv_heads"], s["head_dim"], lens, kv_bytes)
    return s["layers"] * ops, s["layers"] * bytes_
