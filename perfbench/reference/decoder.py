"""Plain float32 reference of the decoder the program runs.

Written from the published description in ``jax.numpy``, with nothing of
the program imported: RMSNorm, rotary position on the whole head (the
program's departure from the published partial rotary), grouped-query
causal attention, a SwiGLU MLP, and a tied or separate output head. Token
embeddings are scaled by ``sqrt(hidden_size)``, as the program does. Every
matrix product runs at ``HIGHEST`` precision, so a TPU does not round its
inputs to bfloat16. The weights come from ``perfbench.model``'s generator:
the served bf16 values, widened to float32.

It runs layer by layer: one layer's weights exist at a time, and each
sequence's activations stay on the device between layers. Attention works
through blocks of queries, so no (heads, S, S) array is ever formed.

The control (``quant=True``) is the same computation in fp8, the precision
below the served bf16: every weight matrix rounded to fp8 (e4m3) with a
scale per output channel, and every matrix product's input activations
rounded to fp8 with a scale per token.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import model

BLOCK_Q = 512
HI = jax.lax.Precision.HIGHEST


def _round8(x, axis):
    """Rounding to fp8 (e4m3) with one scale per slice along ``axis`` that
    maps the slice's largest magnitude to fp8's largest, 448; its gradient
    passes straight through, as in quantized training."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def _mm(x, w, quant):
    """(..., in) @ (in, out)."""
    if quant:
        x, w = _round8(x, -1), _round8(w, 0)
    return jnp.einsum("...i,io->...o", x, w, precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x: (S, H, hd); rotate the two halves of each head by position."""
    hd = x.shape[-1]
    freqs = jnp.asarray(1.0 / (theta ** (np.arange(0, hd, 2) / hd)),
                        jnp.float32)
    ang = (pos.astype(jnp.float32)[:, None] * freqs)[:, None, :]
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _attend(q, k, v):
    """Causal attention, one block of queries at a time. q: (S, H, hd);
    k, v: (S, Hkv, hd) with head h reading kv head h // (H // Hkv)."""
    S, H, hd = q.shape
    rep = H // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    kpos = jnp.arange(S)
    bq = min(BLOCK_Q, S)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * bq, bq, 0)
        sc = jnp.einsum("qhd,khd->hqk", qi, k, precision=HI) / np.sqrt(hd)
        qpos = i * bq + jnp.arange(bq)
        sc = jnp.where((kpos[None, :] <= qpos[:, None])[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    out = jax.lax.map(block, jnp.arange(S // bq))
    return out.reshape(S, H * hd)


def layer(s, w, x, quant):
    """One decoder layer on x: (S, d), positions 0..S-1."""
    S = x.shape[0]
    hd = s["head_dim"]
    pos = jnp.arange(S)
    h = _rms(x, w["ln1/scale"], s["eps"])
    q = _rope(_mm(h, w["attn/wq"], quant).reshape(S, s["heads"], hd), pos,
              s["theta"])
    k = _rope(_mm(h, w["attn/wk"], quant).reshape(S, s["kv_heads"], hd), pos,
              s["theta"])
    v = _mm(h, w["attn/wv"], quant).reshape(S, s["kv_heads"], hd)
    x = x + _mm(_attend(q, k, v), w["attn/wo"], quant)
    h = _rms(x, w["ln2/scale"], s["eps"])
    a = jax.nn.silu(_mm(h, w["mlp/w_gate"], quant)) * _mm(h, w["mlp/w_in"],
                                                          quant)
    return x + _mm(a, w["mlp/w_out"], quant)


def head_matrix(s, top):
    """(d, vocab): the tied embedding's transpose, or the separate head."""
    return top["embed/table"].T if s["tied"] else top["lm_head/w"]


def _bucket(n, least):
    b = least
    while b < n:
        b *= 2
    return b


@functools.lru_cache(maxsize=None)
def _programs(key_tuple):
    s = dict(key_tuple)

    @jax.jit
    def weights(key, i):
        w = model.layer_weights(s, key, i)
        return jax.tree.map(lambda a: a.astype(jnp.float32), w)

    @jax.jit
    def top(key):
        w = model.top_weights(s, key)
        return jax.tree.map(lambda a: a.astype(jnp.float32), w)

    @jax.jit
    def embed(t, tokens):
        return t["embed/table"][tokens] * np.float32(np.sqrt(s["d"]))

    run_layer = jax.jit(functools.partial(layer, s), static_argnums=(2,))

    @functools.partial(jax.jit, static_argnums=(3,))
    def logits(t, x, idx, quant):
        h = _rms(x[idx], t["final_norm/scale"], s["eps"])
        return _mm(h, head_matrix(s, t), quant)

    return weights, top, embed, run_layer, logits


def served_gaps(spec, seed, prompts, served, *, control=False):
    """For each request, at each position where the program served a token:
    the reference's best logit minus its logit of the served token (0 where
    they agree). With ``control``, also the same gap for the token the fp8
    control (fp8) puts first there. Returns {"served": [...], "control": [...]},
    one float32 array per request."""
    s = model.arch(spec).sizes(spec)
    weights, top, embed, run_layer, logits = _programs(
        tuple(sorted(s.items())))
    key = model.seed_key(seed)
    t = top(key)
    seqs, sel = [], []
    for p, out in zip(prompts, served):
        toks = np.concatenate([p, out[:-1]]).astype(np.int32)
        pad = np.zeros(_bucket(len(toks), BLOCK_Q), np.int32)
        pad[:len(toks)] = toks
        seqs.append(embed(t, jnp.asarray(pad)))
        idx = np.full(_bucket(len(out), 64), len(toks) - 1, np.int32)
        idx[:len(out)] = len(p) - 1 + np.arange(len(out))
        sel.append(idx)
    variants = (False, True) if control else (False,)
    xs = {q: list(seqs) for q in variants}
    for i in range(s["layers"]):
        w = weights(key, i)
        for q in variants:
            xs[q] = [run_layer(w, x, q) for x in xs[q]]
        del w
    res = {"served": [], "control": []}
    for r, out in enumerate(served):
        n = len(out)
        ref = np.asarray(logits(t, xs[False][r], jnp.asarray(sel[r]),
                                False))[:n]
        best = ref.max(axis=-1)
        res["served"].append(best - ref[np.arange(n), out])
        if control:
            ctl = np.asarray(logits(t, xs[True][r], jnp.asarray(sel[r]),
                                    True))[:n]
            res["control"].append(best - ref[np.arange(n),
                                             ctl.argmax(axis=-1)])
    return res


# ----------------------------------------------------------------- training
def _keystr(path, layer):
    """The program's leaf name for a weights path, as
    ``jax.tree_util.keystr`` writes it."""
    parts = path.split("/")
    if layer:
        parts = ["blocks", "l0"] + parts
    return "".join(f"[{p!r}]" for p in parts)


def _loss(s, quant, params, tokens, labels):
    """Mean next-token cross-entropy over a batch, one row at a time and
    each layer recomputed in the backward pass, so one row's activations
    of one layer exist at a time."""
    top, layers = params["top"], params["layers"]
    step = jax.checkpoint(lambda w, x: layer(s, w, x, quant))

    @jax.checkpoint
    def row(tok, lab):
        x = top["embed/table"][tok] * np.float32(np.sqrt(s["d"]))
        for w in layers:
            x = step(w, x)
        h = _rms(x, top["final_norm/scale"], s["eps"])
        lg = _mm(h, head_matrix(s, top), quant)
        gold = jnp.take_along_axis(lg, lab[:, None], axis=-1)[:, 0]
        return jnp.mean(jax.nn.logsumexp(lg, axis=-1) - gold)

    def body(tot, tl):
        return tot + row(*tl), None
    tot, _ = jax.lax.scan(body, jnp.float32(0.0), (tokens, labels))
    return tot / tokens.shape[0]


@functools.lru_cache(maxsize=None)
def _train_programs(key_tuple, quant, opt_items):
    s, o = dict(key_tuple), dict(opt_items)
    b1, b2, eps, lr, clip = (o["beta1"], o["beta2"], o["eps"], o["lr"],
                             o["grad_clip"])

    @jax.jit
    def init(key):
        f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
        return {"top": f32(model.top_weights(s, key)),
                "layers": [f32(model.layer_weights(s, key, i))
                           for i in range(s["layers"])]}

    grad = jax.jit(jax.value_and_grad(functools.partial(_loss, s, quant)))

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def adam(w, m, v, g, t):
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(a)) for a in jax.tree.leaves(g)))
        g = jax.tree.map(lambda a: a * jnp.minimum(1.0, clip / (gn + 1e-12)),
                         g)
        m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
        v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
        bc1, bc2 = 1.0 - b1 ** t, 1.0 - b2 ** t
        w = jax.tree.map(lambda a, mm, vv: a - lr * (mm / bc1)
                         / (jnp.sqrt(vv / bc2) + eps), w, m, v)
        return w, m, v, g

    @jax.jit
    def norms(tree, other=None):
        if other is not None:
            tree = jax.tree.map(lambda a, b: a - b, tree, other)
        n = lambda a: jnp.sqrt(jnp.sum(jnp.square(a)))
        return ({p: n(a) for p, a in tree["top"].items()},
                [{p: n(a) for p, a in w.items()} for w in tree["layers"]])

    return init, grad, adam, norms


def train_readings(spec, seed, traffic, steps, batch, *, quant=False):
    """The reference's readings of ``steps`` Adam steps on the batches
    ``batch(i)`` gives: each step's loss, the per-leaf norms of the first
    (clipped) gradient, and of the weights' change after the last step.
    Leaf names and per-layer arrays follow the program's layout."""
    s = model.arch(spec).sizes(spec)
    init, grad, adam, norms = _train_programs(
        tuple(sorted(s.items())), quant,
        tuple(sorted(traffic["optimizer"].items())))
    key = model.seed_key(seed)
    w = init(key)
    m, v = jax.tree.map(jnp.zeros_like, w), jax.tree.map(jnp.zeros_like, w)
    losses, first = [], None
    for i in range(steps):
        b = batch(i)
        loss, g = grad(w, b["tokens"], b["labels"])
        losses.append(float(loss))
        w, m, v, g = adam(w, m, v, g, jnp.float32(i + 1))
        if i == 0:
            first = norms(g)
        del g
    delta = norms(w, init(key))

    def flat(pair):
        top, layers = pair
        out = {_keystr(p, False): np.asarray(a) for p, a in top.items()}
        for p in layers[0]:
            out[_keystr(p, True)] = np.asarray([float(ly[p]) for ly in layers])
        return out
    return {"loss": losses, "grad_norm": flat(first),
            "delta_norm": flat(delta)}
