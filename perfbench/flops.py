"""Operations and bytes of the work, counted from shapes and live lengths.

Counts are of the model's arithmetic (a multiply-add is 2 operations), never
of what an implementation happens to do: a padded slot, a masked key or a
recomputed layer adds nothing. These are the pieces of one layer; an
architecture module (``perfbench/arch/``) sums them over its own layers.
"""
from __future__ import annotations


def attention_flops(heads: int, head_dim: int, keys) -> int:
    """Queries attending ``keys`` keys in all, in one layer: q.k and p.v."""
    return 4 * heads * head_dim * keys


def causal_keys(start: int, valid: int, window: int | None = None) -> int:
    """Keys that ``valid`` tokens from position ``start`` attend causally,
    each at most ``window`` of them where a window is given."""
    lo, hi = start + 1, start + valid          # a token at p sees p + 1 keys
    if window is None or hi <= window:
        return (lo + hi) * valid // 2
    below = max(0, window - lo)                # tokens that see fewer
    return (lo + window - 1) * below // 2 + window * (valid - below)


def decode_attention_work(heads: int, kv_heads: int, head_dim: int, lens,
                          kv_bytes: int = 2):
    """(operations, bytes) of one layer of a decode step: each live sequence
    of ``lens`` keys reads its K and V, one query and one output per head."""
    ops = sum(attention_flops(heads, head_dim, n) for n in lens)
    kv = sum(2 * n * kv_heads * head_dim * kv_bytes for n in lens)
    qo = len(lens) * 2 * heads * head_dim * kv_bytes
    return ops, kv + qo
