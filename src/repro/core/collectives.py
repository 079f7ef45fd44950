"""Allreduce algorithms from survey §2.5, as shard_map-composable schedules.

The survey's four algorithms are re-implemented with `lax.ppermute` /
`lax.all_gather` so their *structure* (number of communication steps, bytes
per step) is visible in HLO and checkable against the α-β cost model
(`core.costmodel`):

  tree          reduce-to-root then broadcast:      T = 2·log2(P)(L + γmG)
  butterfly     recursive doubling:                 T = log2(P)(L + γmG)
  ring          bandwidth-optimal pipeline:         T = 2(P−1)(L + γ(m/P)G)
                (reduce-scatter ring + allgather ring)
  rabenseifner  reduce-scatter (halving) + allgather(doubling):
                                                    T = 2L·log2(P) + 2γmG(P−1)/P
  psum          XLA's native allreduce (the production default)

All run inside `shard_map` over a named mesh axis. For non-power-of-two axis
sizes, tree/butterfly/rabenseifner fall back to psum (the survey analyzes
them for P = 2^k).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

ALGORITHMS = ("psum", "ring", "tree", "butterfly", "rabenseifner")


def _is_pow2(n):
    return n & (n - 1) == 0


# -------------------------------------------------------------------- helpers
def _perm(axis_size, shift):
    return [(i, (i + shift) % axis_size) for i in range(axis_size)]


def allreduce_sum(x, axis, algorithm="psum"):
    """Allreduce-sum of `x` over mesh axis `axis` (inside shard_map)."""
    if algorithm == "psum":
        return lax.psum(x, axis)
    P = lax.axis_size(axis)
    if P == 1:
        return x
    if algorithm == "ring":
        return _ring_allreduce(x, axis, P)
    if not _is_pow2(P):
        return lax.psum(x, axis)
    if algorithm == "tree":
        return _tree_allreduce(x, axis, P)
    if algorithm == "butterfly":
        return _butterfly_allreduce(x, axis, P)
    if algorithm == "rabenseifner":
        return _rabenseifner_allreduce(x, axis, P)
    raise ValueError(f"unknown algorithm {algorithm!r}")


def allreduce_mean(x, axis, algorithm="psum"):
    return allreduce_sum(x, axis, algorithm) / lax.axis_size(axis)


# ------------------------------------------------------------------ butterfly
def _butterfly_allreduce(x, axis, P):
    """Recursive doubling: log2(P) steps, full message per step."""
    idx = lax.axis_index(axis)
    for k in range(int(math.log2(P))):
        shift = 1 << k
        # pair-wise exchange with partner idx ^ shift: ppermute both ways,
        # each rank picks the direction its partner lives in.
        fwd = lax.ppermute(x, axis, _perm(P, shift))        # from idx − shift
        bwd = lax.ppermute(x, axis, _perm(P, P - shift))    # from idx + shift
        partner_above = (idx // shift) % 2 == 0
        x = jax.tree.map(lambda a, u, v: a + jnp.where(partner_above, u, v),
                         x, bwd, fwd)
    return x


def _select(pred, a, b):
    return jax.tree.map(lambda u, v: jnp.where(pred, u, v), a, b)


# ----------------------------------------------------------------------- tree
def _tree_allreduce(x, axis, P):
    """Binomial-tree reduce to rank 0, then broadcast: 2·log2(P) steps.

    Structurally faithful (2 log P dependent steps with full-size messages);
    implemented with masked ppermute exchanges.
    """
    idx = lax.axis_index(axis)
    # reduce phase: at step k, ranks with idx % 2^(k+1) == 2^k send to idx−2^k
    for k in range(int(math.log2(P))):
        shift = 1 << k
        moved = lax.ppermute(x, axis, _perm(P, P - shift))  # from idx+shift
        is_recv = (idx % (2 * shift)) == 0
        x = jax.tree.map(lambda a, m: a + jnp.where(is_recv, m, 0.0).astype(a.dtype),
                         x, moved)
    # broadcast phase: root sends down the tree (log2(P) masked steps)
    for k in reversed(range(int(math.log2(P)))):
        shift = 1 << k
        moved = lax.ppermute(x, axis, _perm(P, shift))      # from idx−shift
        use = (idx % (2 * shift)) == shift
        x = jax.tree.map(lambda a, m: jnp.where(use, m, a), x, moved)
    return x


# ----------------------------------------------------------------------- ring
def _ring_allreduce(x, axis, P):
    """Bandwidth-optimal ring: reduce-scatter (P−1 steps of m/P) then
    allgather (P−1 steps of m/P) — the survey's `T_pipe` pipeline."""
    flat, treedef = jax.tree_util.tree_flatten(x)
    sizes = [f.size for f in flat]
    shapes = [f.shape for f in flat]
    v = jnp.concatenate([f.reshape(-1) for f in flat]) if len(flat) > 1 else flat[0].reshape(-1)
    n = v.size
    pad = (-n) % P
    v = jnp.pad(v, (0, pad))
    m = (n + pad) // P

    idx = lax.axis_index(axis)
    perm_next = _perm(P, 1)  # send to rank+1

    # chunks are sliced from the flat vector: indexing rows of a (P, m)
    # reshape instead makes the TPU compiler's time grow with m (minutes
    # for an embedding-sized gradient)
    def chunk(i):
        return lax.dynamic_slice_in_dim(v, (i % P) * m, m)

    # reduce-scatter ring: after P−1 steps rank r owns the full sum of chunk r
    buf = chunk(idx - 1)
    for k in range(1, P - 1):
        buf = lax.ppermute(buf, axis, perm_next)
        buf = buf + chunk(idx - k - 1)
    owned = lax.ppermute(buf, axis, perm_next) + chunk(idx)

    # allgather ring: circulate owned chunks P−1 steps
    cur = owned
    out = lax.dynamic_update_slice_in_dim(jnp.zeros_like(v), owned, idx * m, 0)
    for k in range(1, P):
        cur = lax.ppermute(cur, axis, perm_next)
        out = lax.dynamic_update_slice_in_dim(out, cur, ((idx - k) % P) * m, 0)
    res = out[:n]
    if len(flat) == 1:
        return res.reshape(shapes[0])
    outs = []
    off = 0
    for s, shp in zip(sizes, shapes):
        outs.append(res[off:off + s].reshape(shp))
        off += s
    return jax.tree_util.tree_unflatten(treedef, outs)


# --------------------------------------------------------------- rabenseifner
def _rabenseifner_allreduce(x, axis, P):
    """Reduce-scatter via recursive *halving* + allgather via recursive
    *doubling*: 2·log2(P) latency steps, 2γm(P−1)/P bandwidth — achieves the
    survey's allreduce lower bound. Message size halves (then doubles) each
    step, visible in the lowered HLO as shrinking/growing ppermute operands.
    """
    flat, treedef = jax.tree_util.tree_flatten(x)
    shapes = [f.shape for f in flat]
    sizes = [f.size for f in flat]
    v = jnp.concatenate([f.reshape(-1) for f in flat]) if len(flat) > 1 else flat[0].reshape(-1)
    n = v.size
    pad = (-n) % P
    v = jnp.pad(v, (0, pad))
    m = v.size
    idx = lax.axis_index(axis)
    logp = int(math.log2(P))

    # ---- reduce-scatter (recursive halving), partner distance P/2 → 1
    off = jnp.int32(0)
    seg = m
    d = P // 2
    for _ in range(logp):
        half = seg // 2
        bit = (idx // d) % 2                       # 0: keep lower, partner above
        keep_off = off + bit * half
        send_off = off + (1 - bit) * half
        send = lax.dynamic_slice(v, (send_off,), (half,))
        fwd = lax.ppermute(send, axis, _perm(P, d))        # from idx − d
        bwd = lax.ppermute(send, axis, _perm(P, P - d))    # from idx + d
        recv = _select(bit == 0, bwd, fwd)
        keep = lax.dynamic_slice(v, (keep_off,), (half,)) + recv
        v = lax.dynamic_update_slice(v, keep, (keep_off,))
        off, seg, d = keep_off, half, d // 2

    # ---- allgather (recursive doubling), partner distance 1 → P/2
    d = 1
    for _ in range(logp):
        bit = (idx // d) % 2
        send = lax.dynamic_slice(v, (off,), (seg,))
        fwd = lax.ppermute(send, axis, _perm(P, d))
        bwd = lax.ppermute(send, axis, _perm(P, P - d))
        recv = _select(bit == 0, bwd, fwd)
        partner_off = off + (1 - 2 * bit) * seg
        v = lax.dynamic_update_slice(v, recv, (jnp.maximum(partner_off, 0),))
        off = off - bit * seg
        seg, d = seg * 2, d * 2

    return _unflatten(v[:n], treedef, shapes, sizes)


def _unflatten(res, treedef, shapes, sizes):
    if len(shapes) == 1:
        return jax.tree_util.tree_unflatten(treedef, [res.reshape(shapes[0])])
    outs = []
    off = 0
    for s, shp in zip(sizes, shapes):
        outs.append(res[off:off + s].reshape(shp))
        off += s
    return jax.tree_util.tree_unflatten(treedef, outs)


# ------------------------------------------------------------- step counters
def schedule_steps(algorithm: str, P: int) -> int:
    """Number of dependent communication steps (for structural tests)."""
    if P == 1:
        return 0
    if algorithm == "tree":
        return 2 * int(math.log2(P))
    if algorithm == "butterfly":
        return int(math.log2(P))
    if algorithm == "ring":
        return 2 * (P - 1)
    if algorithm == "rabenseifner":
        return 2 * int(math.log2(P))
    return 1
