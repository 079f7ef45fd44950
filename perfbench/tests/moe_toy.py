"""A second architecture for the CPU tests: a sparse-expert decoder on the
program's ``moe`` family (every layer attention and a top-k routed SwiGLU
over ``num_experts`` experts, leaves ``moe/router|w_gate|w_in|w_out``).

It is found as ``arch: moe_toy`` only where a test substitutes it for
``bench.load_module("arch", "moe_toy")``: no harness file knows it. It
shows that an architecture whose layers are not the dense decoder's gets
its weights, its engine, its train step and its work counts from a module
of its own.
"""
from __future__ import annotations

import dataclasses

from perfbench import flops
from perfbench.arch import decoder

SPEC = {
    "name": "moe_toy", "arch": "moe_toy", "registry": "qwen3-moe-30b-a3b",
    "hidden_size": 64, "moe_intermediate_size": 32, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_experts": 4, "num_experts_per_tok": 2, "vocab_size": 256,
    "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
}


def sizes(spec: dict) -> dict:
    return {
        "arch": "moe_toy",
        "layers": spec["num_hidden_layers"], "d": spec["hidden_size"],
        "heads": spec["num_attention_heads"],
        "kv_heads": spec["num_key_value_heads"], "head_dim": spec["head_dim"],
        "expert_ff": spec["moe_intermediate_size"],
        "experts": spec["num_experts"], "top_k": spec["num_experts_per_tok"],
        "vocab": spec["vocab_size"], "eps": spec["rms_norm_eps"],
        "theta": float(spec["rope_theta"]),
        "tied": bool(spec["tie_word_embeddings"]),
        "dtype": spec["torch_dtype"],
    }


def model_config(spec: dict):
    from repro.configs.base import get_config
    s = sizes(spec)
    return dataclasses.replace(
        get_config(spec["registry"]), num_layers=s["layers"], d_model=s["d"],
        num_heads=s["heads"], num_kv_heads=s["kv_heads"],
        head_dim=s["head_dim"], d_ff=s["expert_ff"], vocab_size=s["vocab"],
        num_experts=s["experts"], experts_per_token=s["top_k"],
        norm_eps=s["eps"], rope_theta=s["theta"], tie_embeddings=s["tied"],
        dtype=s["dtype"])


def layer_leaves(s: dict, layer) -> dict:
    d, f, e = s["d"], s["expert_ff"], s["experts"]
    return {
        **decoder.attn_leaves(s),
        "moe/router": ((d, e), d ** -0.5, "float32"),
        "moe/w_gate": ((e, d, f), d ** -0.5),
        "moe/w_in": ((e, d, f), d ** -0.5),
        "moe/w_out": ((e, f, d), f ** -0.5),
    }


top_leaves = decoder.top_leaves
params_from_key = decoder.params_from_key


def _layer_matmul(s) -> int:
    """Weights one token multiplies in one layer: attention, the router and
    its ``top_k`` experts."""
    return decoder.attn_matmul(s) + s["d"] * s["experts"] \
        + s["top_k"] * 3 * s["d"] * s["expert_ff"]


def _attention(s, keys):
    return s["layers"] * flops.attention_flops(s["heads"], s["head_dim"],
                                               keys)


def token_flops(s, context: int, logits: bool = True) -> int:
    f = 2 * s["layers"] * _layer_matmul(s) + _attention(s, context)
    return f + 2 * s["d"] * s["vocab"] if logits else f


def prefill_flops(s, start: int, valid: int) -> int:
    return (valid * 2 * s["layers"] * _layer_matmul(s)
            + _attention(s, flops.causal_keys(start, valid))
            + 2 * s["d"] * s["vocab"])


def train_flops_per_token(s, seq: int) -> float:
    return 3 * (2 * s["layers"] * _layer_matmul(s) + 2 * s["d"] * s["vocab"]
                + _attention(s, flops.causal_keys(0, seq) / seq))


def paged_attention_work(s, lens, kv_bytes: int = 2):
    ops, bytes_ = flops.decode_attention_work(
        s["heads"], s["kv_heads"], s["head_dim"], lens, kv_bytes)
    return s["layers"] * ops, s["layers"] * bytes_
