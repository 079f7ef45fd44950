"""Train steps: the trainer's whole jitted step, back to back, on batches of
seeded random tokens.

Set-up builds one object, the step with its state, as ``launch/train.py``
builds it: a ("data", "model") mesh over the cell's chips, the traffic
file's plan, the state made inside one jit with the plan's shardings (the
weights by the configuration's architecture module from the seed, Adam's
f32 master and moments beside them) and ``trainer.jit_train_step``. The first
``CHECKED`` steps run through that same object and feed and are read for
the comparison; the window then goes on with the same object.

End to end: ``train_tok_s``, the tokens of every step dispatched from the
window's opening until ``seconds`` later, over the time from the opening
until the last of them has completed. The window keeps ``AHEAD_S`` seconds
of steps (a tenth of the window at most) in flight beyond the one it waits
for, so that a pause of the host does not leave the chip idle; it reads
no loss. When its time is up it dispatches nothing more, waits for every
step it dispatched, and reads the clock after that wait.
"""
from __future__ import annotations

import collections
import math
import time

import numpy as np

from perfbench import bench, model, serving

CHECKED = 3
AHEAD_S = 5.0


def optimizer(traffic):
    from repro.optim import make_optimizer
    o = traffic["optimizer"]
    return make_optimizer(o["name"], lr=o["lr"], grad_clip=o["grad_clip"])


def build(cfg, s, traffic, devices):
    """(init, step, abstract state, abstract batch) for ``devices``."""
    import jax
    import jax.numpy as jnp
    from repro.core import parallelism as par
    from repro.launch.mesh import make_mesh
    from repro.train import trainer
    opt = optimizer(traffic)
    n = len(devices)
    mesh = make_mesh((n // traffic["model_axis"], traffic["model_axis"]),
                     ("data", "model"), devices=devices)
    plan = par.make_plan(traffic["plan"], mesh)

    def init_state(key):
        params = model.arch(s).params_from_key(s, key)
        return {"params": params, "opt": opt.init(params)}

    state_abs = jax.eval_shape(init_state, jax.random.PRNGKey(0))
    init = jax.jit(init_state,
                   out_shardings=trainer.state_shardings(state_abs, plan))
    batch_abs = {k: jax.ShapeDtypeStruct((traffic["batch"], traffic["seq"]),
                                         jnp.int32)
                 for k in ("tokens", "labels")}
    step = trainer.jit_train_step(cfg, opt, plan, state_abs, batch_abs)
    return init, step, state_abs, batch_abs


def make_batch(s, traffic):
    """Step ``i``'s batch from the seed's key: random tokens, each row's
    labels its next tokens. Rows differ within and across steps."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def batch(key, i):
        t = jax.random.randint(jax.random.fold_in(key, i),
                               (traffic["batch"], traffic["seq"] + 1), 0,
                               s["vocab"], jnp.int32)
        return {"tokens": t[:, :-1], "labels": t[:, 1:]}
    return batch


def leaf_norms(tree):
    """{path: float32 L2 norm} of a params-shaped tree, on the device."""
    import jax
    import jax.numpy as jnp
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32)), axis=tuple(range(1, a.ndim)) if
        p[0].key == "blocks" else None)) for p, a in flat}


def reader(s, beta1):
    """A jitted ``read(state, key)``: norms per leaf (per layer for the
    stacked layers), on the host, of the first gradient as Adam holds it
    (m / (1 - beta1) after one step) and of the master weights' change
    since the seed's weights."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(state, key):
        init = model.arch(s).params_from_key(s, key)
        grad = jax.tree.map(lambda m: m / (1.0 - beta1), state["opt"]["m"])
        delta = jax.tree.map(lambda w, p: w - p.astype(jnp.float32),
                             state["opt"]["master"], init)
        return leaf_norms(grad), leaf_norms(delta)

    def read(state, key):
        g, d = norms(state, key)
        return ({k: np.asarray(v) for k, v in g.items()},
                {k: np.asarray(v) for k, v in d.items()})
    return read


def run(ctx):
    import jax
    traffic = ctx.cell["traffic"]
    spec = ctx.cell["spec"]
    arch = model.arch(spec)
    s, cfg = arch.sizes(spec), arch.model_config(spec)
    key = model.seed_key(ctx.seed)
    t0 = time.perf_counter()
    init, step, _, _ = build(cfg, s, traffic, ctx.devs)
    batch = make_batch(s, traffic)
    state = jax.block_until_ready(init(key))
    t1 = time.perf_counter()
    read = reader(s, traffic["optimizer"]["beta1"])
    losses, grad_norms = [], None
    for i in range(CHECKED):
        t_step = time.perf_counter()
        state, m = step(state, batch(key, i))
        losses.append(float(m["loss"]))
        step_s = time.perf_counter() - t_step
        if i == 0:
            grad_norms, _ = read(state, key)
    _, delta_norms = read(state, key)
    ahead = max(1, math.ceil(min(AHEAD_S, ctx.seconds / 10) / step_s))
    t2 = time.perf_counter()
    i = CHECKED
    t_open = ctx.open_window()
    deadline = t_open + ctx.seconds
    steps = 0

    def loop(until):
        """Dispatch steps until ``until`` with ``ahead`` of them in flight
        beyond the one waited for, then wait for all of them."""
        nonlocal state, i, steps
        flight = collections.deque()
        while time.perf_counter() < until:
            state, m = step(state, batch(key, i))
            flight.append(m["loss"])
            i += 1
            steps += 1
            if len(flight) > ahead:
                jax.block_until_ready(flight.popleft())
        jax.block_until_ready(list(flight))

    cap = work = None
    if ctx.trace:
        from perfbench import trace as TR
        length = min(serving.TRACED_S, ctx.seconds / 3)   # mid-window
        loop(t_open + (ctx.seconds - length) / 2)
        before = steps
        with TR.capture(ctx.keep_trace) as cap:
            loop(time.perf_counter() + length)
        work = steps - before
    loop(deadline)
    t_end = time.perf_counter()
    ctx.close_window()
    summary = cap.reduce() if cap is not None else None
    tokens = steps * traffic["batch"] * traffic["seq"]
    ctx.log(f"train: {steps} steps, {tokens} tokens in {t_end - t_open:.3f} "
            f"s, {ahead} in flight; losses of the checked steps {losses}")
    e2e = {"train_tok_s": tokens / (t_end - t_open)}
    obs = {"sizes": s, "trace": summary, "kind": ctx.devs[0].device_kind,
           "train_tokens": None if work is None else
           work * traffic["batch"] * traffic["seq"],
           "seq": traffic["seq"]}
    device = bench.device_info(ctx.devs, summary)
    del state
    serving.free_device()
    ref = bench.load_module("reference", spec["reference"])
    t3 = time.perf_counter()
    got = {"loss": losses, "grad_norm": grad_norms, "delta_norm": delta_norms}
    key = model.seed_key(ctx.seed)            # the old one was freed too
    want = ref.train_readings(spec, ctx.seed, traffic, CHECKED,
                              lambda j: batch(key, j))
    checks, info = compare(got, want, traffic["check"])
    ctx.log(f"reference ran in {time.perf_counter() - t3:.1f} s; losses "
            f"{want['loss']}; read, not compared: {info}")
    values = {**{k: c["value"] for k, c in checks.items()}, **info}
    if getattr(ctx, "control", False):
        # the reference in the program's place: in fp8, and in f32 with
        # half of each batch left out (the mean over the other half)
        half = traffic["batch"] // 2
        for name, kw, feed in (
                ("control", {"quant": True}, lambda j: batch(key, j)),
                ("half_batch", {}, lambda j: {k: v[:half] for k, v in
                                              batch(key, j).items()})):
            serving.free_device()
            key = model.seed_key(ctx.seed)
            other = ref.train_readings(spec, ctx.seed, traffic, CHECKED,
                                       feed, **kw)
            checks_o, info_o = compare(other, want, traffic["check"])
            values.update({f"{name}_{k}": v for k, v in
                           [(k, c["value"]) for k, c in checks_o.items()]
                           + list(info_o.items())})
    return {"correct": all(c["value"] <= c["limit"]
                           for c in checks.values()),
            "attempted": steps, "failed": 0, "e2e": e2e, "obs": obs,
            "device": device, "checks": checks, "reference": values,
            "timings": {"init_s": t1 - t0, "checked_steps_s": t2 - t1}}


def norm_gaps(got, want, moved):
    """Per leaf (and layer): |program's norm - reference's| over the larger
    of the reference's norm and the median leaf's, for the leaves ``moved``
    selects. Returns {leaf: gap}."""
    flat_w = np.concatenate([np.ravel(v) for v in want.values()])
    med = float(np.median(flat_w))
    out = {}
    for k in want:
        w, g = np.ravel(want[k]), np.ravel(got[k])
        keep = moved[k] if moved is not None else np.ones(w.shape, bool)
        if keep.any():
            gap = np.abs(g - w)[keep] / np.maximum(w[keep], med)
            out[k] = float(gap.max())
    return out


def compare(got, want, limits):
    """The numbers compared, each beside its limit: the worst leaf's gap of
    the first gradient's norm, and the worst leaf's gap of the weights'
    change after the checked steps, over the leaves the reference's
    gradient moves (norm at least a thousandth of the median leaf's).
    Also the worst step's loss gap relative to the reference's, which is
    read but not compared: no control or fault separates it (PERF.md)."""
    loss = max(abs(a - b) / abs(b) for a, b in zip(got["loss"], want["loss"]))
    gflat = np.concatenate([np.ravel(v) for v in want["grad_norm"].values()])
    floor = 1e-3 * float(np.median(gflat))
    moved = {k: np.ravel(v) >= floor for k, v in want["grad_norm"].items()}
    grad = norm_gaps(got["grad_norm"], want["grad_norm"], None)
    delta = norm_gaps(got["delta_norm"], want["delta_norm"], moved)
    return {
        "grad_norm_gap": {"value": max(grad.values()),
                          "limit": limits["grad_norm_gap"]},
        "delta_norm_gap": {"value": max(delta.values()),
                           "limit": limits["delta_norm_gap"]},
    }, {"loss_rel_gap": loss}
