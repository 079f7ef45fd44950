"""Operations and bytes of the work, counted from shapes and live lengths.

Counts are of the model's arithmetic (a multiply-add is 2 operations), never
of what an implementation happens to do: a padded slot, a masked key or a
recomputed layer adds nothing. ``s`` is ``perfbench.model.sizes``.
"""
from __future__ import annotations


def _layer_matmul(s) -> int:
    """Weights one token multiplies in one layer."""
    d, hd, ff = s["d"], s["head_dim"], s["ff"]
    return d * (s["heads"] + 2 * s["kv_heads"]) * hd + s["heads"] * hd * d \
        + 3 * d * ff


def attention_flops(s, context: int) -> int:
    """One query attending ``context`` keys in every layer: q.k and p.v."""
    return 4 * s["layers"] * s["heads"] * s["head_dim"] * context


def token_flops(s, context: int, logits: bool = True) -> int:
    """Forward operations of one token that attends ``context`` keys; with
    ``logits`` it also goes through the output head."""
    f = 2 * s["layers"] * _layer_matmul(s) + attention_flops(s, context)
    if logits:
        f += 2 * s["d"] * s["vocab"]
    return f


def prefill_flops(s, start: int, valid: int) -> int:
    """A chunk of ``valid`` prompt tokens from position ``start``: every
    token through the layers, causally, and the last through the head."""
    keys = valid * start + valid * (valid + 1) // 2
    return (valid * 2 * s["layers"] * _layer_matmul(s)
            + attention_flops(s, keys) + 2 * s["d"] * s["vocab"])


def train_flops_per_token(s, seq: int) -> float:
    """Forward and backward (3x forward) of one token of a causal sequence
    of ``seq``: the matrices, the head, and attention over the
    ``(seq + 1) / 2`` keys a token sees on average."""
    return 3 * (2 * s["layers"] * _layer_matmul(s) + 2 * s["d"] * s["vocab"]
                + attention_flops(s, (seq + 1) / 2))


def paged_attention_work(s, lens, kv_bytes: int = 2):
    """(operations, bytes) of one decode step of the paged kernel over all
    layers: each live sequence of ``lens`` tokens reads its K and V, one
    query and one output per head."""
    ops = sum(attention_flops(s, n) for n in lens)
    hd = s["head_dim"]
    kv = sum(2 * n * s["kv_heads"] * hd * kv_bytes for n in lens)
    qo = len(lens) * 2 * s["heads"] * hd * kv_bytes
    return ops, s["layers"] * (kv + qo)
